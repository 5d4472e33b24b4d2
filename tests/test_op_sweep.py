"""Registry-driven operator sweep.

VERDICT r1 weak-spot 2: the op surface (306 ops) had ~1 test per 12
ops.  This sweep is generated FROM the registry: every op must appear
in exactly one tier below, and ``test_registry_fully_covered`` fails
when a newly registered op has no test.

Tiers (reference model: tests/python/unittest/test_operator.py — the
~7k-line dtype/shape/attr matrix):

- UNARY / BINARY / SCALAR / REDUCE — forward vs numpy at float32 AND
  float16, numeric gradient (smooth ops) via jax.grad vs central
  differences, plus eager/staged/sharded 3-way consistency
  (test_utils.check_op_consistency) on a sample.
- EXPLICIT — per-op cases with handmade inputs; ref=None means the op
  is validated by shape/finiteness + consistency (its exact semantics
  are covered by a dedicated test elsewhere).
- ELSEWHERE — ops with dedicated deep tests; each entry names the file
  so coverage claims stay auditable.
"""

import math

import numpy as np

try:
    import scipy.special  # noqa: F401
    _HAVE_SCIPY = True
except ImportError:
    _HAVE_SCIPY = False


def _digamma_ref(x, eps=1e-5):
    # central difference of lgamma: accurate to ~1e-6 for x in [0.5, 3]
    return (math.lgamma(x + eps) - math.lgamma(x - eps)) / (2 * eps)
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import registry
from mxnet_tpu.ops.registry import apply_op
from mxnet_tpu.test_utils import check_op_consistency

RS = np.random.RandomState


def _erf(x):
    from math import erf

    return np.vectorize(erf)(x)


def _erfinv(y):
    from scipy_free_erfinv import erfinv  # pragma: no cover

    return erfinv(y)


# --------------------------------------------------------------------------
# tier tables
# --------------------------------------------------------------------------
# name -> (numpy_fn, low, high, smooth_for_grad)
UNARY = {
    "abs": (np.abs, -2, 2, False),
    "arccos": (np.arccos, -0.9, 0.9, True),
    "arccosh": (np.arccosh, 1.1, 3, True),
    "arcsin": (np.arcsin, -0.9, 0.9, True),
    "arcsinh": (np.arcsinh, -2, 2, True),
    "arctan": (np.arctan, -2, 2, True),
    "arctanh": (np.arctanh, -0.9, 0.9, True),
    "cbrt": (np.cbrt, 0.1, 3, True),
    "ceil": (np.ceil, -2, 2, False),
    "cos": (np.cos, -2, 2, True),
    "cosh": (np.cosh, -2, 2, True),
    "degrees": (np.degrees, -2, 2, True),
    "erf": (_erf, -2, 2, True),
    "exp": (np.exp, -2, 2, True),
    "expm1": (np.expm1, -2, 2, True),
    "fix": (np.trunc, -2, 2, False),
    "floor": (np.floor, -2, 2, False),
    "gamma": (lambda x: np.vectorize(__import__("math").gamma)(x), 0.5, 3,
              True),
    "gammaln": (lambda x: np.vectorize(__import__("math").lgamma)(x), 0.5, 3,
                True),
    "digamma": (lambda x: __import__("scipy.special", fromlist=["digamma"])
                .digamma(x) if _HAVE_SCIPY
                else np.vectorize(_digamma_ref)(x), 0.5, 3, True),
    "log": (np.log, 0.1, 3, True),
    "log10": (np.log10, 0.1, 3, True),
    "log1p": (np.log1p, -0.5, 3, True),
    "log2": (np.log2, 0.1, 3, True),
    "logical_not": (lambda x: (x == 0).astype(x.dtype), -1, 1, False),
    "negative": (np.negative, -2, 2, True),
    "radians": (np.radians, -2, 2, True),
    "rcbrt": (lambda x: 1.0 / np.cbrt(x), 0.2, 3, True),
    "reciprocal": (np.reciprocal, 0.2, 3, True),
    "relu": (lambda x: np.maximum(x, 0), -2, 2, False),
    "rint": (np.rint, -2, 2, False),
    "round": (lambda x: np.floor(x + 0.5), -2, 2, False),
    "rsqrt": (lambda x: 1.0 / np.sqrt(x), 0.2, 3, True),
    "sigmoid": (lambda x: 1 / (1 + np.exp(-x)), -2, 2, True),
    "sign": (np.sign, -2, 2, False),
    "sin": (np.sin, -2, 2, True),
    "sinh": (np.sinh, -2, 2, True),
    "softrelu": (lambda x: np.log1p(np.exp(x)), -2, 2, True),
    "softsign": (lambda x: x / (1 + np.abs(x)), -2, 2, True),
    "sqrt": (np.sqrt, 0.1, 3, True),
    "square": (np.square, -2, 2, True),
    "tan": (np.tan, -1, 1, True),
    "tanh": (np.tanh, -2, 2, True),
    "trunc": (np.trunc, -2, 2, False),
    "isfinite": (lambda x: np.isfinite(x).astype(x.dtype), -2, 2, False),
    "isinf": (lambda x: np.isinf(x).astype(x.dtype), -2, 2, False),
    "isnan": (lambda x: np.isnan(x).astype(x.dtype), -2, 2, False),
    "hard_sigmoid": (lambda x: np.clip(0.2 * x + 0.5, 0, 1), -4, 4, False),
    "erfinv": (None, -0.9, 0.9, True),  # checked via erf(erfinv(x)) == x
    "_copy": (lambda x: x, -2, 2, True),
    "BlockGrad": (lambda x: x, -2, 2, False),
    "make_loss": (lambda x: x, -2, 2, False),
    "zeros_like": (np.zeros_like, -2, 2, False),
    "ones_like": (np.ones_like, -2, 2, False),
    "shape_array": (lambda x: np.array(x.shape, np.int64), -2, 2, False),
    "size_array": (lambda x: np.array([x.size], np.int64), -2, 2, False),
}

# name -> (numpy_fn, low, high) — both operands from [low, high]
_cmp = {
    "equal": lambda a, b: (a == b), "not_equal": lambda a, b: (a != b),
    "greater": lambda a, b: (a > b), "greater_equal": lambda a, b: (a >= b),
    "lesser": lambda a, b: (a < b), "lesser_equal": lambda a, b: (a <= b),
    "logical_and": lambda a, b: (a != 0) & (b != 0),
    "logical_or": lambda a, b: (a != 0) | (b != 0),
    "logical_xor": lambda a, b: (a != 0) ^ (b != 0),
}
BINARY_CORE = {
    "add": (np.add, -2, 2), "sub": (np.subtract, -2, 2),
    "mul": (np.multiply, -2, 2), "div": (np.divide, 0.5, 3),
    "mod": (np.mod, 0.5, 3), "power": (np.power, 0.5, 2),
    "maximum": (np.maximum, -2, 2), "minimum": (np.minimum, -2, 2),
    "hypot": (np.hypot, -2, 2),
}
BINARY = {}
for _n, (_f, _lo, _hi) in BINARY_CORE.items():
    BINARY["elemwise_" + _n] = (_f, _lo, _hi)
    BINARY["broadcast_" + _n] = (_f, _lo, _hi)
for _n, _f in _cmp.items():
    _wrapped = (lambda f: lambda a, b: f(a, b).astype(a.dtype))(_f)
    BINARY["elemwise_" + _n] = (_wrapped, -1, 1)
    BINARY["broadcast_" + _n] = (_wrapped, -1, 1)

# name -> (numpy_fn(x, s), low, high, scalar)
SCALAR = {
    "_plus_scalar": (lambda x, s: x + s, -2, 2, 0.7),
    "_minus_scalar": (lambda x, s: x - s, -2, 2, 0.7),
    "_rminus_scalar": (lambda x, s: s - x, -2, 2, 0.7),
    "_mul_scalar": (lambda x, s: x * s, -2, 2, 0.7),
    "_div_scalar": (lambda x, s: x / s, -2, 2, 0.7),
    "_rdiv_scalar": (lambda x, s: s / x, 0.5, 3, 0.7),
    "_mod_scalar": (lambda x, s: np.mod(x, s), 0.1, 3, 0.7),
    "_rmod_scalar": (lambda x, s: np.mod(s, x), 0.5, 3, 0.7),
    "_power_scalar": (lambda x, s: np.power(x, s), 0.5, 2, 0.7),
    "_rpower_scalar": (lambda x, s: np.power(s, x), -1, 1, 0.7),
    "_maximum_scalar": (lambda x, s: np.maximum(x, s), -2, 2, 0.3),
    "_minimum_scalar": (lambda x, s: np.minimum(x, s), -2, 2, 0.3),
    "_hypot_scalar": (lambda x, s: np.hypot(x, s), -2, 2, 0.7),
    "_equal_scalar": (lambda x, s: (x == s).astype(x.dtype), 0, 2, 1.0),
    "_not_equal_scalar": (lambda x, s: (x != s).astype(x.dtype), 0, 2, 1.0),
    "_greater_scalar": (lambda x, s: (x > s).astype(x.dtype), -2, 2, 0.3),
    "_greater_equal_scalar": (lambda x, s: (x >= s).astype(x.dtype), -2, 2, 0.3),
    "_lesser_scalar": (lambda x, s: (x < s).astype(x.dtype), -2, 2, 0.3),
    "_lesser_equal_scalar": (lambda x, s: (x <= s).astype(x.dtype), -2, 2, 0.3),
    "_logical_and_scalar": (lambda x, s: ((x != 0) & (s != 0)).astype(x.dtype), -1, 1, 1.0),
    "_logical_or_scalar": (lambda x, s: ((x != 0) | (s != 0)).astype(x.dtype), -1, 1, 0.0),
    "_logical_xor_scalar": (lambda x, s: ((x != 0) ^ (s != 0)).astype(x.dtype), -1, 1, 1.0),
    "smooth_l1": (lambda x, s: np.where(np.abs(x) < 1 / s**2,
                                        0.5 * s**2 * x * x,
                                        np.abs(x) - 0.5 / s**2), -2, 2, 1.0),
}

# name -> (numpy_fn(x, axis_kwarg), attrs_variants)
REDUCE = {
    "sum": (np.sum, [{}, {"axis": 1}, {"axis": (0, 2), "keepdims": True}]),
    "mean": (np.mean, [{}, {"axis": 1}, {"axis": 2, "keepdims": True}]),
    "max": (np.max, [{}, {"axis": 1}]),
    "min": (np.min, [{}, {"axis": 1}]),
    "prod": (np.prod, [{}, {"axis": 1}]),
    "nansum": (np.nansum, [{}, {"axis": 1}]),
    "nanprod": (np.nanprod, [{}, {"axis": 1}]),
    "argmax": (lambda x, **k: np.argmax(x, **k).astype(np.float32),
               [{"axis": 1}, {"axis": 2}]),
    "argmin": (lambda x, **k: np.argmin(x, **k).astype(np.float32),
               [{"axis": 1}]),
}


def _case(inputs, attrs=None, ref=None, rtol=2e-4, atol=2e-4,
          consistency=True):
    return {"inputs": inputs, "attrs": attrs or {}, "ref": ref,
            "rtol": rtol, "atol": atol, "consistency": consistency}


def _f32(*shape, seed=0, lo=-1.0, hi=1.0):
    return (RS(seed).uniform(lo, hi, shape)).astype(np.float32)


def _idx(*shape, seed=0, n=4):
    return RS(seed).randint(0, n, shape).astype(np.int32)


def _posdef(n, seed=0):
    a = RS(seed).randn(n, n).astype(np.float32)
    return a @ a.T + n * np.eye(n, dtype=np.float32)


# ops with handmade inputs; ref=None -> run + consistency only
EXPLICIT = {
    # ---- shape / indexing / layout ----
    "Reshape": [_case([_f32(2, 6)], {"shape": (3, 4)},
                      lambda x: x.reshape(3, 4))],
    "reshape_like": [_case([_f32(2, 6), _f32(3, 4)], {},
                           lambda x, y: x.reshape(3, 4))],
    "Flatten": [_case([_f32(2, 3, 4)], {}, lambda x: x.reshape(2, 12))],
    "expand_dims": [_case([_f32(2, 3)], {"axis": 1},
                          lambda x: x[:, None, :])],
    "squeeze": [_case([_f32(2, 1, 3)], {"axis": 1},
                      lambda x: x.squeeze(1))],
    "transpose": [_case([_f32(2, 3, 4)], {"axes": (2, 0, 1)},
                        lambda x: x.transpose(2, 0, 1))],
    "SwapAxis": [_case([_f32(2, 3, 4)], {"dim1": 0, "dim2": 2},
                       lambda x: x.swapaxes(0, 2))],
    "slice": [_case([_f32(4, 6)], {"begin": (1, 2), "end": (3, 5)},
                    lambda x: x[1:3, 2:5])],
    "slice_axis": [_case([_f32(4, 6)], {"axis": 1, "begin": 1, "end": 4},
                         lambda x: x[:, 1:4])],
    "slice_like": [_case([_f32(4, 6), _f32(2, 3)], {},
                         lambda x, y: x[:2, :3])],
    "Crop": [_case([_f32(1, 2, 6, 6), _f32(1, 2, 4, 4)], {"num_args": 2},
                   lambda x, y: x[:, :, :4, :4])],
    "clip": [_case([_f32(3, 4, lo=-2, hi=2)], {"a_min": -0.5, "a_max": 0.5},
                   lambda x: np.clip(x, -0.5, 0.5))],
    "tile": [_case([_f32(2, 3)], {"reps": (2, 2)},
                   lambda x: np.tile(x, (2, 2)))],
    "repeat": [_case([_f32(2, 3)], {"repeats": 2, "axis": 1},
                     lambda x: np.repeat(x, 2, 1))],
    "reverse": [_case([_f32(3, 4)], {"axis": 0}, lambda x: x[::-1])],
    "pick": [_case([_f32(3, 5), _idx(3, n=5)], {"axis": 1},
                   lambda x, i: x[np.arange(3), i])],
    "batch_take": [_case([_f32(3, 5), _idx(3, n=5)], {"axis": 1},
                         lambda x, i: x[np.arange(3), i])],
    "take": [_case([_f32(5, 4), _idx(3, n=5)], {"axis": 0},
                   lambda x, i: x[i])],
    "one_hot": [_case([_idx(4, n=5)], {"depth": 5},
                      lambda i: np.eye(5, dtype=np.float32)[i])],
    "where": [_case([(_f32(3, 4) > 0).astype(np.float32), _f32(3, 4, seed=1),
                     _f32(3, 4, seed=2)], {},
                    lambda c, x, y: np.where(c != 0, x, y))],

    "gather_nd": [_case([_f32(4, 5), _idx(2, 3, n=4).astype(np.int32)], {},
                        lambda x, i: x[i[0], i[1]])],
    "_backward_gather_nd": [_case(
        [_f32(3), _idx(2, 3, n=4)], {"shape": (4, 5)}, None,
        consistency=False)],
    "scatter_nd": [_case([_f32(3), _idx(2, 3, n=4)], {"shape": (4, 5)},
                         None, consistency=False)],
    "index_copy": [_case([_f32(5, 3), np.array([1, 3], np.int32),
                          _f32(2, 3, seed=1)], {}, None)],
    "index_add": [_case([_f32(5, 3), np.array([1, 3], np.int32),
                         _f32(2, 3, seed=1)], {}, None)],
    "boolean_mask": [_case([_f32(4, 3),
                            np.array([1, 0, 1, 1], np.float32)], {}, None,
                           consistency=False)],
    "Concat": [_case([_f32(2, 3), _f32(2, 4, seed=1)], {"dim": 1,
                                                        "num_args": 2},
                     lambda a, b: np.concatenate([a, b], 1))],
    "stack": [_case([_f32(2, 3), _f32(2, 3, seed=1)], {"axis": 0,
                                                       "num_args": 2},
                    lambda a, b: np.stack([a, b]))],
    "SliceChannel": [_case([_f32(2, 6)], {"num_outputs": 2},
                           lambda x: (x[:, :3], x[:, 3:]))],
    "split_v2": [_case([_f32(2, 6)], {"axis": 1, "sections": 3},
                       lambda x: (x[:, :2], x[:, 2:4], x[:, 4:]))],
    "broadcast_to": [_case([_f32(1, 3)], {"shape": (4, 3)},
                           lambda x: np.broadcast_to(x, (4, 3)).copy())],
    "broadcast_axis": [_case([_f32(1, 3)], {"axis": 0, "size": 4},
                             lambda x: np.broadcast_to(x, (4, 3)).copy())],
    "broadcast_like": [_case([_f32(1, 3), _f32(4, 3)], {},
                             lambda x, y: np.broadcast_to(x, (4, 3)).copy())],
    "Pad": [_case([_f32(1, 2, 3, 3)],
                  {"mode": "constant", "pad_width": (0, 0, 0, 0, 1, 1, 1, 1)},
                  lambda x: np.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1))))],
    "cumsum": [_case([_f32(3, 4)], {"axis": 1},
                     lambda x: np.cumsum(x, 1))],
    "diag": [_case([_f32(4, 4)], {}, lambda x: np.diag(x).copy())],
    "depth_to_space": [_case([_f32(1, 8, 2, 2)], {"block_size": 2}, None)],
    "space_to_depth": [_case([_f32(1, 2, 4, 4)], {"block_size": 2}, None)],
    "ravel_multi_index": [_case(
        [np.array([[1, 2], [2, 3]], np.float32)], {"shape": (4, 5)},
        lambda x: np.array([1 * 5 + 2, 2 * 5 + 3], np.float32),
        consistency=False)],
    "unravel_index": [_case(
        [np.array([7, 13], np.float32)], {"shape": (4, 5)},
        lambda x: np.stack(np.unravel_index([7, 13], (4, 5))).astype(
            np.float32), consistency=False)],
    # ---- ordering ----
    "sort": [_case([_f32(3, 5)], {"axis": 1}, lambda x: np.sort(x, 1))],
    "argsort": [_case([_f32(3, 5)], {"axis": 1},
                      lambda x: np.argsort(x, 1).astype(np.float32))],
    "topk": [_case([_f32(3, 5)], {"k": 2, "axis": 1, "ret_typ": "value"},
                   lambda x: -np.sort(-x, 1)[:, :2])],
    # ---- linear algebra ----
    "dot": [_case([_f32(3, 4), _f32(4, 5, seed=1)], {},
                  lambda a, b: a @ b)],
    "batch_dot": [_case([_f32(2, 3, 4), _f32(2, 4, 5, seed=1)], {},
                        lambda a, b: np.einsum("bij,bjk->bik", a, b))],
    "linalg_gemm": [_case([_f32(3, 4), _f32(4, 5, seed=1),
                           _f32(3, 5, seed=2)], {},
                          lambda a, b, c: a @ b + c)],
    "linalg_gemm2": [_case([_f32(3, 4), _f32(4, 5, seed=1)], {},
                           lambda a, b: a @ b)],
    "linalg_potrf": [_case([_posdef(4)], {},
                           lambda a: np.linalg.cholesky(a), rtol=1e-3,
                           atol=1e-3)],
    "linalg_potri": [_case([np.linalg.cholesky(_posdef(4)).astype(
        np.float32)], {}, None, rtol=1e-2)],
    "linalg_trmm": [_case([np.tril(_f32(3, 3)) + 2 * np.eye(3, dtype=np.float32),
                           _f32(3, 4, seed=1)], {}, None)],
    "linalg_trsm": [_case([np.tril(_f32(3, 3)) + 2 * np.eye(3, dtype=np.float32),
                           _f32(3, 4, seed=1)], {}, None)],
    "linalg_syrk": [_case([_f32(3, 4)], {},
                          lambda a: a @ a.T, rtol=1e-3)],
    "linalg_sumlogdiag": [_case([_posdef(4)], {},
                                lambda a: np.array(
                                    np.sum(np.log(np.diag(a))),
                                    np.float32))],
    "linalg_extractdiag": [_case([_f32(4, 4)], {},
                                 lambda a: np.diag(a).copy())],
    "linalg_makediag": [_case([_f32(4)], {}, lambda a: np.diag(a))],
    "linalg_gelqf": [_case([_f32(3, 5)], {}, None, consistency=False)],
    "linalg_syevd": [_case([_posdef(4)], {}, None, consistency=False)],
    "khatri_rao": [_case([_f32(2, 3), _f32(4, 3, seed=1)], {},
                         lambda a, b: np.stack(
                             [np.kron(a[:, j], b[:, j]) for j in range(3)],
                             axis=1))],
    "trace_op": [_case([_f32(4, 4)], {},
                       lambda x: np.array(np.trace(x), np.float32))],
    "norm": [_case([_f32(3, 4)], {},
                   lambda x: np.array(np.linalg.norm(x), np.float32))],
    # ---- neural net ----
    "Activation": [
        _case([_f32(3, 4)], {"act_type": t},
              {"relu": lambda x: np.maximum(x, 0),
               "sigmoid": lambda x: 1 / (1 + np.exp(-x)),
               "tanh": np.tanh,
               "softrelu": lambda x: np.log1p(np.exp(x)),
               "softsign": lambda x: x / (1 + np.abs(x))}[t])
        for t in ("relu", "sigmoid", "tanh", "softrelu", "softsign")],
    "FullyConnected": [
        _case([_f32(3, 4), _f32(5, 4, seed=1), _f32(5, seed=2)],
              {"num_hidden": 5}, lambda x, w, b: x @ w.T + b),
        _case([_f32(3, 2, 2), _f32(5, 4, seed=1), _f32(5, seed=2)],
              {"num_hidden": 5}, lambda x, w, b: x.reshape(3, 4) @ w.T + b),
        _case([_f32(3, 2, 4), _f32(5, 4, seed=1), _f32(5, seed=2)],
              {"num_hidden": 5, "flatten": False},
              lambda x, w, b: x @ w.T + b)],
    "softmax": [_case([_f32(3, 5)], {"axis": -1},
                      lambda x: np.exp(x) / np.exp(x).sum(-1,
                                                          keepdims=True))],
    "softmin": [_case([_f32(3, 5)], {"axis": -1},
                      lambda x: np.exp(-x) / np.exp(-x).sum(
                          -1, keepdims=True))],
    "log_softmax": [_case([_f32(3, 5)], {"axis": -1},
                          lambda x: x - x.max(-1, keepdims=True) - np.log(
                              np.exp(x - x.max(-1, keepdims=True)).sum(
                                  -1, keepdims=True)))],
    "SoftmaxActivation": [_case([_f32(3, 5)], {},
                                lambda x: np.exp(x) / np.exp(x).sum(
                                    -1, keepdims=True))],
    "argmax_channel": [_case([_f32(3, 5)], {},
                             lambda x: np.argmax(x, 1).astype(np.float32))],
    "softmax_cross_entropy": [_case(
        [_f32(3, 5), np.array([1, 0, 4], np.float32)], {}, None)],
    # symbol autogen grows a gamma variable for prelu, so the generic
    # staged-consistency leg does not apply
    "LeakyReLU": [
        _case([_f32(3, 4)], {"act_type": "leaky", "slope": 0.1},
              lambda x: np.where(x > 0, x, 0.1 * x), consistency=False),
        _case([_f32(3, 4)], {"act_type": "elu", "slope": 0.3},
              lambda x: np.where(x > 0, x, 0.3 * np.expm1(x)),
              consistency=False)],
    "L2Normalization": [_case(
        [_f32(3, 4)], {},
        lambda x: x / np.sqrt((x * x).sum(1, keepdims=True) + 1e-10))],
    "quadratic": [_case([_f32(3, 4)], {"a": 2.0, "b": 1.0, "c": 0.5},
                        lambda x: 2 * x * x + x + 0.5)],
    # conv/pool attr matrices live in test_conv_attr_matrix below
    "Convolution": [_case(
        [_f32(1, 2, 5, 5), _f32(3, 2, 3, 3, seed=1), _f32(3, seed=2)],
        {"kernel": (3, 3), "num_filter": 3}, None)],
    "Deconvolution": [_case(
        [_f32(1, 3, 4, 4), _f32(3, 2, 2, 2, seed=1)],
        {"kernel": (2, 2), "num_filter": 2, "no_bias": True}, None)],
    "Pooling": [_case([_f32(1, 2, 6, 6)],
                      {"kernel": (2, 2), "stride": (2, 2),
                       "pool_type": "max"}, None)],
    # train/eval stats semantics differ by path; deep test in
    # test_operator.py — forward-run only here
    "BatchNorm": [_case(
        [_f32(2, 3, 4, 4), np.ones(3, np.float32), np.zeros(3, np.float32),
         np.zeros(3, np.float32), np.ones(3, np.float32)], {}, None,
        consistency=False)],
    "LayerNorm": [_case(
        [_f32(3, 6), np.ones(6, np.float32), np.zeros(6, np.float32)], {},
        lambda x, g, b: (x - x.mean(-1, keepdims=True)) /
        np.sqrt(x.var(-1, keepdims=True) + 1e-5), rtol=1e-3, atol=1e-3)],
    "InstanceNorm": [_case(
        [_f32(2, 3, 5), np.ones(3, np.float32), np.zeros(3, np.float32)],
        {}, None)],
    "LRN": [_case([_f32(1, 4, 3, 3)], {"nsize": 3}, None)],
    "Embedding": [_case([_idx(3, 2, n=6), _f32(6, 4, seed=1)],
                        {"input_dim": 6, "output_dim": 4},
                        lambda i, w: w[i])],
    "Dropout": [_case([_f32(3, 4)], {"p": 0.5}, lambda x: x,
                      consistency=False)],  # eval mode = identity
    "UpSampling": [_case([_f32(1, 2, 3, 3)],
                         {"scale": 2, "sample_type": "nearest"},
                         lambda x: x.repeat(2, 2).repeat(2, 3))],
    "BilinearResize2D": [_case([_f32(1, 2, 3, 3)],
                               {"height": 6, "width": 6}, None)],
    "AdaptiveAvgPooling2D": [_case([_f32(1, 2, 6, 6)],
                                   {"output_size": 3}, None)],
    "GridGenerator": [_case([_f32(1, 6)],
                            {"transform_type": "affine",
                             "target_shape": (4, 4)}, None,
                            consistency=False)],
    "SequenceMask": [_case(
        [_f32(4, 3, 2), np.array([2, 4, 1], np.float32)],
        {"use_sequence_length": True}, None)],
    "SequenceLast": [_case(
        [_f32(4, 3, 2), np.array([2, 4, 1], np.float32)],
        {"use_sequence_length": True}, None)],
    "SequenceReverse": [_case(
        [_f32(4, 3, 2), np.array([2, 4, 1], np.float32)],
        {"use_sequence_length": True}, None)],
    "SVMOutput": [_case([_f32(3, 5), np.array([1, 0, 4], np.float32)], {},
                        None)],
    "LinearRegressionOutput": [_case(
        [_f32(3, 4), _f32(3, 4, seed=1)], {}, lambda x, y: x)],
    "MAERegressionOutput": [_case(
        [_f32(3, 4), _f32(3, 4, seed=1)], {}, lambda x, y: x)],
    "LogisticRegressionOutput": [_case(
        [_f32(3, 4), _f32(3, 4, seed=1)], {},
        lambda x, y: 1 / (1 + np.exp(-x)))],
    "SoftmaxOutput": [_case(
        [_f32(3, 5), np.array([1, 0, 4], np.float32)], {},
        lambda x, y: np.exp(x) / np.exp(x).sum(-1, keepdims=True))],
    # ---- misc data ops ----
    "histogram": [_case([_f32(20)], {"bin_cnt": 5, "range": (-1, 1)}, None,
                        consistency=False)],
    "getnnz": [_case([np.array([[1, 0], [0, 2]], np.float32)], {},
                     lambda x: np.array(2, np.int64), consistency=False)],
    "cast_storage_op": [_case([_f32(3, 4)], {"stype": "default"},
                              lambda x: x)],
    "sparse_retain": [_case([_f32(4, 3), np.array([0, 2], np.float32)], {},
                            None, consistency=False)],
    "Cast": [_case([_f32(3, 4)], {"dtype": "float16"},
                   lambda x: x.astype(np.float16))],
    "image_to_tensor": [_case([_f32(4, 4, 3, lo=0, hi=255)], {},
                              lambda x: x.transpose(2, 0, 1) / 255.0)],
    "image_normalize": [_case([_f32(3, 4, 4, lo=0, hi=1)],
                              {"mean": (0.5,), "std": (0.5,)},
                              lambda x: (x - 0.5) / 0.5)],
    "image_resize": [_case([_f32(4, 4, 3, lo=0, hi=1)], {"size": (8, 8)},
                           None, consistency=False)],
    "_contrib_div_sqrt_dim": [_case([_f32(3, 16)], {},
                                    lambda x: x / 4.0)],
    "_contrib_fft": [_case([_f32(2, 8)], {}, None, consistency=False)],
    "_contrib_ifft": [_case([_f32(2, 16)], {}, None, consistency=False)],
    "_contrib_count_sketch": [_case(
        [_f32(2, 6), np.array([0, 1, 2, 0, 1, 2], np.float32),
         np.array([1, -1, 1, -1, 1, -1], np.float32)], {"out_dim": 3},
        None, consistency=False)],
    "_scatter_elemwise_div": [_case([_f32(3, 4), _f32(3, 4, lo=1, hi=2)],
                                    {}, lambda a, b: a / b)],
    "_shuffle": [_case([_f32(6, 3)], {}, None, consistency=False)],
    "arange_like": [_case([_f32(2, 3)], {},
                          lambda x: np.arange(6, dtype=np.float32).reshape(
                              2, 3), consistency=False)],
    "add_n": [_case([_f32(3, 4), _f32(3, 4, seed=1), _f32(3, 4, seed=2)],
                    {}, lambda a, b, c: a + b + c)],
}

# zero-tensor-input ops: (attrs, ref)
CREATION = {
    "_zeros": ({"shape": (2, 3)}, lambda: np.zeros((2, 3), np.float32)),
    "_ones": ({"shape": (2, 3)}, lambda: np.ones((2, 3), np.float32)),
    "_full": ({"shape": (2, 3), "value": 1.5},
              lambda: np.full((2, 3), 1.5, np.float32)),
    "_eye": ({"N": 4}, lambda: np.eye(4, dtype=np.float32)),
    "_arange": ({"start": 1, "stop": 7, "step": 2},
                lambda: np.arange(1, 7, 2).astype(np.float32)),
    "_linspace": ({"start": 0, "stop": 1, "num": 5},
                  lambda: np.linspace(0, 1, 5).astype(np.float32)),
}

# ops whose deep coverage lives in a dedicated file (auditable pointers);
# the sweep still asserts the name is registered
ELSEWHERE = {
    "RNN": ("tests/test_rnn.py", "FusedRNNCell"),
    "choose_element_0index": ("tests/test_operator.py",
                              "test_choose_and_fill_element_0index"),
    "fill_element_0index": ("tests/test_operator.py",
                            "test_choose_and_fill_element_0index"),
    "gradientmultiplier": ("tests/test_extended_ops.py",
                           "gradientmultiplier"),
    "IdentityAttachKLSparseReg": ("tests/test_extended_ops.py",
                                  "IdentityAttachKLSparseReg"),
    "_square_sum": ("tests/test_extended_ops.py", "square_sum"),
    "_sparse_adagrad_update": ("tests/test_extended_ops.py",
                               "sparse_adagrad_update"),
    "_sample_exponential": ("tests/test_extended_ops.py",
                            "sample_distribution_families"),
    "_sample_poisson": ("tests/test_extended_ops.py",
                        "sample_distribution_families"),
    "_sample_negative_binomial": ("tests/test_extended_ops.py",
                                  "sample_distribution_families"),
    "_sample_generalized_negative_binomial": (
        "tests/test_extended_ops.py", "sample_distribution_families"),
    "_basic_index": ("tests/test_ndarray.py", "_basic_index"),
    "_subgraph_exec": ("tests/test_subgraph.py", "_subgraph_exec"),
    "Custom": ("tests/test_review_fixes.py", "Custom"),
    "CTCLoss": ("tests/test_operator.py", "CTCLoss"),
    "MultiBoxPrior": ("tests/test_contrib.py", "MultiBoxPrior"),
    "MultiBoxTarget": ("tests/test_review_fixes.py", "MultiBoxTarget"),
    "MultiBoxDetection": ("tests/test_contrib.py", "MultiBoxDetection"),
    "box_iou": ("tests/test_contrib.py", "box_iou"),
    "box_nms": ("tests/test_contrib.py", "box_nms"),
    "ROIAlign": ("tests/test_review_fixes.py", "ROIAlign"),
    "ROIPooling": ("tests/test_extended_ops.py", "ROIPooling"),
    "_contrib_bipartite_matching": ("tests/test_extended_ops.py",
                                    "bipartite_matching"),
    "_contrib_Proposal": ("tests/test_extended_ops.py", "Proposal"),
    "_contrib_PSROIPooling": ("tests/test_extended_ops.py", "PSROIPooling"),
    "_contrib_DeformableConvolution": ("tests/test_extended_ops.py",
                                       "Deformable"),
    "_contrib_SyncBatchNorm": ("tests/test_sync_bn.py", "SyncBatchNorm"),
    "Correlation": ("tests/test_extended_ops.py", "Correlation"),
    "_contrib_rms_norm": ("tests/test_llm_ops.py", "llm.rms_norm"),
    "_contrib_rope": ("tests/test_llm_ops.py", "llm.rope"),
    "_contrib_gated_silu": ("tests/test_llm_ops.py", "llm.gated_silu"),
    "_contrib_mla_qkv": ("tests/test_mla_moe.py", "mla_qkv"),
    "_contrib_mla_out": ("tests/test_mla_moe.py", "mla_out"),
    "_contrib_gqa_qkv": ("tests/test_llm_ops.py", "llm.gqa_qkv"),
    "_contrib_gqa_out": ("tests/test_llm_ops.py", "llm.gqa_out"),
    "_contrib_head_gate": ("tests/test_laguna_layers.py", "llm.head_gate"),
    "_contrib_gated_short_conv": ("tests/test_llm_ops.py",
                                  "llm.gated_short_conv"),
    "_contrib_moe_route": ("tests/test_llm_ops.py", "llm.moe_route"),
    "_contrib_moe_experts": ("tests/test_llm_ops.py", "llm.moe_experts"),
    "_contrib_linear_cross_entropy": ("tests/test_llm_ops.py",
                                      "llm.linear_cross_entropy"),
    "_contrib_hc_coefficients": ("tests/test_hyper_connections.py",
                                 "llm.hc_coefficients"),
    "_contrib_hc_pre_mix": ("tests/test_hyper_connections.py",
                            "llm.hc_pre_mix"),
    "_contrib_hc_post_mix": ("tests/test_hyper_connections.py",
                             "llm.hc_post_mix"),
    "_contrib_flash_attention": ("tests/test_attention.py",
                                 "flash_attention"),
    "_contrib_interleaved_matmul_selfatt_qk": (
        "tests/test_attention.py", "interleaved_matmul_selfatt_qk"),
    "_contrib_interleaved_matmul_selfatt_valatt": (
        "tests/test_attention.py", "interleaved_matmul_selfatt_valatt"),
    "_contrib_quantize": ("tests/test_quantization.py",
                          '"_contrib_quantize"'),
    "_contrib_quantize_v2": ("tests/test_quantization.py", "quantize_v2"),
    "_contrib_dequantize": ("tests/test_quantization.py", "dequantize"),
    "_contrib_requantize": ("tests/test_quantization.py", "requantize"),
    "_contrib_quantized_conv": ("tests/test_quantization.py",
                                "quantized_conv"),
    "_contrib_quantized_fully_connected": (
        "tests/test_quantization.py", "quantized_fully_connected"),
    # optimizer kernels dispatch through the optimizer registry: the
    # no-recompile test drives every listed optimizer end-to-end, so
    # the evidence is the optimizer NAME in its parameterization
    "sgd_update": ("tests/test_optimizer_no_recompile.py", '"sgd"'),
    "sgd_mom_update": ("tests/test_optimizer_no_recompile.py", '"sgd"'),
    "nag_mom_update": ("tests/test_optimizer_no_recompile.py", '"nag"'),
    "adam_update": ("tests/test_optimizer_no_recompile.py", '"adam"'),
    "adamax_update": ("tests/test_optimizer_no_recompile.py", '"adamax"'),
    "nadam_update": ("tests/test_optimizer_no_recompile.py", '"nadam"'),
    "ftml_update": ("tests/test_optimizer_no_recompile.py", '"ftml"'),
    "ftrl_update": ("tests/test_optimizer_no_recompile.py", '"ftrl"'),
    "rmsprop_update": ("tests/test_optimizer_no_recompile.py",
                       '"rmsprop"'),
    "signum_update": ("tests/test_optimizer_no_recompile.py", '"signum"'),

    # lazy sparse kernels dispatch via lazy_update=True + rsp grads
    "_sparse_sgd_update": ("tests/test_sparse.py", "lazy_update=True"),
    "_sparse_adam_update": ("tests/test_sparse.py", "lazy_adam"),
}

# --------------------------------------------------------------------------
# generic executors
# --------------------------------------------------------------------------
def _run(op_name, arrays, attrs):
    """Dispatch through the imperative path (handles PRNG-keyed ops and
    aux-state plumbing exactly like user code)."""
    from mxnet_tpu.ndarray import array
    from mxnet_tpu.ndarray.ndarray import imperative_invoke

    outs = imperative_invoke(op_name, [array(a) for a in arrays],
                             dict(attrs))
    return tuple(o.asnumpy() for o in outs)


def _check_ref(op_name, arrays, attrs, ref, rtol, atol):
    got = _run(op_name, arrays, attrs)
    want = ref(*arrays) if callable(ref) else ref
    want = want if isinstance(want, tuple) else (want,)
    assert len(got) >= len(want), op_name
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=rtol, atol=atol, err_msg=op_name)


def _numeric_grad_check(op_name, x, attrs, eps=1e-3, rtol=0.02, atol=1e-3):
    """jax.grad of sum(op(x)) vs central differences, float32."""
    import jax
    import jax.numpy as jnp

    op = registry.get(op_name)
    fn = op.bind_attrs(op.canonicalize_attrs(attrs))

    def loss(v):
        out = fn(v)
        out = out if isinstance(out, tuple) else (out,)
        return sum(jnp.sum(o) for o in out)

    analytic = np.asarray(jax.grad(loss)(x))
    numeric = np.zeros_like(x)
    flat = x.reshape(-1)
    for i in range(flat.size):
        bump = np.zeros_like(flat)
        bump[i] = eps
        hi = float(loss((flat + bump).reshape(x.shape)))
        lo = float(loss((flat - bump).reshape(x.shape)))
        numeric.reshape(-1)[i] = (hi - lo) / (2 * eps)
    np.testing.assert_allclose(analytic, numeric, rtol=rtol, atol=atol,
                               err_msg=op_name)


# --------------------------------------------------------------------------
# tests
# --------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(UNARY), ids=str)
@pytest.mark.parametrize("dtype", [np.float32, np.float16], ids=["f32", "f16"])
def test_unary_forward(name, dtype):
    fn, lo, hi, _ = UNARY[name]
    x = RS(0).uniform(lo, hi, (3, 4)).astype(dtype)
    if name == "erfinv":  # inverse pair identity instead of a numpy ref
        y = np.asarray(_run("erfinv", [x.astype(np.float32)], {})[0])
        np.testing.assert_allclose(_erf(y), x.astype(np.float32),
                                   rtol=2e-3, atol=2e-3)
        return
    got = np.asarray(_run(name, [x], {})[0])
    want = fn(x.astype(np.float64))
    tol = 2e-2 if dtype == np.float16 else 2e-5
    np.testing.assert_allclose(got.astype(np.float64), want, rtol=tol,
                               atol=tol, err_msg=name)


@pytest.mark.parametrize("name", sorted(n for n, s in UNARY.items()
                                        if s[3]), ids=str)
def test_unary_gradient(name):
    if name == "erfinv":
        pytest.skip("covered by the inverse-pair identity")
    _, lo, hi, _ = UNARY[name]
    x = RS(1).uniform(lo, hi, (2, 3)).astype(np.float32)
    _numeric_grad_check(name, x, {})


@pytest.mark.parametrize("name", sorted(BINARY), ids=str)
@pytest.mark.parametrize("dtype", [np.float32, np.float16], ids=["f32", "f16"])
def test_binary_forward(name, dtype):
    fn, lo, hi = BINARY[name]
    a = RS(0).uniform(lo, hi, (3, 4)).astype(dtype)
    shape_b = (3, 4) if name.startswith("elemwise") else (1, 4)
    b = RS(1).uniform(lo, hi, shape_b).astype(dtype)
    got = np.asarray(_run(name, [a, b], {})[0])
    want = fn(a.astype(np.float64), b.astype(np.float64))
    tol = 5e-2 if dtype == np.float16 else 1e-5
    np.testing.assert_allclose(got.astype(np.float64), want, rtol=tol,
                               atol=tol, err_msg=name)


@pytest.mark.parametrize("name", ["elemwise_add", "elemwise_mul",
                                  "broadcast_add", "broadcast_mul",
                                  "elemwise_sub", "broadcast_div"], ids=str)
def test_binary_consistency(name):
    a = _f32(8, 4)
    b = _f32(8, 4, seed=1, lo=0.5, hi=2) if name.startswith("elemwise") \
        else _f32(1, 4, seed=1, lo=0.5, hi=2)
    check_op_consistency(name, [a, b])


@pytest.mark.parametrize("name", sorted(SCALAR), ids=str)
@pytest.mark.parametrize("dtype", [np.float32, np.float16], ids=["f32", "f16"])
def test_scalar_forward(name, dtype):
    fn, lo, hi, s = SCALAR[name]
    x = RS(0).uniform(lo, hi, (3, 4)).astype(dtype)
    got = np.asarray(_run(name, [x], {"scalar": s})[0])
    want = fn(x.astype(np.float64), s)
    tol = 5e-2 if dtype == np.float16 else 1e-5
    np.testing.assert_allclose(got.astype(np.float64), want, rtol=tol,
                               atol=tol, err_msg=name)


@pytest.mark.parametrize("name", sorted(REDUCE), ids=str)
@pytest.mark.parametrize("dtype", [np.float32, np.float16], ids=["f32", "f16"])
def test_reduce_forward(name, dtype):
    fn, variants = REDUCE[name]
    x = RS(0).uniform(0.5, 1.5, (2, 3, 4)).astype(dtype)
    for attrs in variants:
        got = np.asarray(_run(name, [x], attrs)[0])
        kw = {}
        if "axis" in attrs:
            ax = attrs["axis"]
            kw["axis"] = tuple(ax) if isinstance(ax, (tuple, list)) else ax
        if attrs.get("keepdims"):
            kw["keepdims"] = True
        want = fn(x.astype(np.float64), **kw)
        tol = 5e-2 if dtype == np.float16 else 1e-4
        np.testing.assert_allclose(np.squeeze(got.astype(np.float64)),
                                   np.squeeze(want), rtol=tol, atol=tol,
                                   err_msg="%s %r" % (name, attrs))


@pytest.mark.parametrize("name", ["sum", "mean", "max"], ids=str)
def test_reduce_consistency(name):
    check_op_consistency(name, [_f32(8, 3, 4)], {"axis": 1})


@pytest.mark.parametrize("name", sorted(EXPLICIT), ids=str)
def test_explicit_forward(name):
    for case in EXPLICIT[name]:
        arrays, attrs, ref = case["inputs"], case["attrs"], case["ref"]
        if ref is not None:
            _check_ref(name, arrays, attrs, ref, case["rtol"], case["atol"])
        else:
            outs = _run(name, arrays, attrs)
            for o in outs:
                assert np.all(np.isfinite(np.asarray(o, dtype=np.float64))), \
                    name
        if case["consistency"] and name not in ("Dropout",):
            check_op_consistency(name, arrays, attrs,
                                 rtol=max(case["rtol"], 1e-3),
                                 atol=max(case["atol"], 1e-3))


@pytest.mark.parametrize("name", sorted(CREATION), ids=str)
def test_creation_ops(name):
    attrs, ref = CREATION[name]
    got = np.asarray(_run(name, [], attrs)[0])
    np.testing.assert_allclose(got, ref(), err_msg=name)


# nn attr matrix: the stride/pad/dilate x shape grid the reference's
# test_operator.py covers for convolution (vs a direct lax reference is
# circular, so check against torch-free explicit im2col)
def _conv2d_ref(x, w, b, stride, pad, dilate):
    import itertools

    n, cin, hh, ww = x.shape
    cout, _, kh, kw = w.shape
    dh, dw = dilate
    eff_kh, eff_kw = (kh - 1) * dh + 1, (kw - 1) * dw + 1
    xp = np.pad(x, ((0, 0), (0, 0), (pad[0], pad[0]), (pad[1], pad[1])))
    oh = (hh + 2 * pad[0] - eff_kh) // stride[0] + 1
    ow = (ww + 2 * pad[1] - eff_kw) // stride[1] + 1
    out = np.zeros((n, cout, oh, ow), np.float64)
    for i, j in itertools.product(range(oh), range(ow)):
        patch = xp[:, :, i * stride[0]:i * stride[0] + eff_kh:dh,
                   j * stride[1]:j * stride[1] + eff_kw:dw]
        out[:, :, i, j] = np.einsum("nchw,ochw->no", patch, w)
    return out + b.reshape(1, -1, 1, 1)


@pytest.mark.parametrize("stride", [(1, 1), (2, 2), (2, 1)])
@pytest.mark.parametrize("pad", [(0, 0), (1, 1)])
@pytest.mark.parametrize("dilate", [(1, 1), (2, 2)])
def test_conv_attr_matrix(stride, pad, dilate):
    x = _f32(2, 3, 7, 7)
    w = _f32(4, 3, 3, 3, seed=1)
    b = _f32(4, seed=2)
    got = np.asarray(_run("Convolution", [x, w, b],
                          {"kernel": (3, 3), "num_filter": 4,
                           "stride": stride, "pad": pad,
                           "dilate": dilate})[0])
    want = _conv2d_ref(x.astype(np.float64), w.astype(np.float64),
                       b.astype(np.float64), stride, pad, dilate)
    np.testing.assert_allclose(got.astype(np.float64), want, rtol=1e-3,
                               atol=1e-3)


@pytest.mark.parametrize("pool_type", ["max", "avg"])
@pytest.mark.parametrize("stride", [(1, 1), (2, 2)])
@pytest.mark.parametrize("pad", [(0, 0), (1, 1)])
def test_pool_attr_matrix(pool_type, stride, pad):
    x = _f32(2, 3, 6, 6)
    got = np.asarray(_run("Pooling", [x],
                          {"kernel": (3, 3), "pool_type": pool_type,
                           "stride": stride, "pad": pad})[0])
    # reference via explicit window walk
    xp = np.pad(x, ((0, 0), (0, 0), (pad[0], pad[0]), (pad[1], pad[1])),
                constant_values=-np.inf if pool_type == "max" else 0)
    hh = xp.shape[2]
    oh = (hh - 3) // stride[0] + 1
    want = np.zeros((2, 3, oh, oh), np.float64)
    counts = np.zeros_like(want)
    for i in range(oh):
        for j in range(oh):
            win = xp[:, :, i * stride[0]:i * stride[0] + 3,
                     j * stride[1]:j * stride[1] + 3]
            if pool_type == "max":
                want[:, :, i, j] = win.max((2, 3))
            else:
                # count_include_pad=True matches the reference default
                want[:, :, i, j] = win.sum((2, 3)) / 9.0
    np.testing.assert_allclose(got.astype(np.float64), want, rtol=1e-4,
                               atol=1e-4)


def test_conv_consistency_sharded():
    x = _f32(8, 3, 6, 6)
    w = _f32(4, 3, 3, 3, seed=1)
    b = _f32(4, seed=2)
    check_op_consistency("Convolution", [x, w, b],
                         {"kernel": (3, 3), "num_filter": 4, "pad": (1, 1)},
                         rtol=1e-3, atol=1e-3)


def test_fc_consistency_sharded():
    check_op_consistency("FullyConnected",
                         [_f32(8, 5), _f32(6, 5, seed=1), _f32(6, seed=2)],
                         {"num_hidden": 6}, rtol=1e-3, atol=1e-3)


# ------------------------------------------------------------- random tier --
# op -> (attrs, check(out)) — PRNG-keyed ops get statistical sanity
# checks through the imperative path (which threads the key)
RANDOM = {
    "_random_uniform": ({"low": 2.0, "high": 5.0, "shape": (4000,)},
                        lambda o: (2.0 <= o).all() and (o < 5.0).all()
                        and abs(o.mean() - 3.5) < 0.2),
    "_random_normal": ({"loc": 1.0, "scale": 2.0, "shape": (4000,)},
                       lambda o: abs(o.mean() - 1.0) < 0.25
                       and abs(o.std() - 2.0) < 0.25),
    "_random_gamma": ({"alpha": 3.0, "beta": 2.0, "shape": (4000,)},
                      lambda o: (o > 0).all()
                      and abs(o.mean() - 6.0) < 0.8),
    "_random_exponential": ({"lam": 2.0, "shape": (4000,)},
                            lambda o: (o >= 0).all()
                            and abs(o.mean() - 0.5) < 0.1),
    "_random_poisson": ({"lam": 4.0, "shape": (4000,)},
                        lambda o: (o >= 0).all()
                        and abs(o.mean() - 4.0) < 0.5),
    "_random_negative_binomial": ({"k": 5, "p": 0.5, "shape": (4000,)},
                                  lambda o: (o >= 0).all()
                                  and abs(o.mean() - 5.0) < 1.0),
    "_random_generalized_negative_binomial": (
        {"mu": 3.0, "alpha": 0.2, "shape": (4000,)},
        lambda o: (o >= 0).all() and abs(o.mean() - 3.0) < 0.8),
    "_random_randint": ({"low": 3, "high": 9, "shape": (4000,)},
                        lambda o: (o >= 3).all() and (o < 9).all()),
}


@pytest.mark.parametrize("name", sorted(RANDOM), ids=str)
def test_random_ops_statistics(name):
    attrs, check = RANDOM[name]
    out = np.asarray(_run(name, [], attrs)[0], dtype=np.float64)
    assert check(out), "%s: statistics off (mean %.3f)" % (name, out.mean())
    # two invocations draw different streams
    out2 = np.asarray(_run(name, [], attrs)[0], dtype=np.float64)
    assert not np.array_equal(out, out2)


def test_sample_ops():
    """Per-row parameterized samplers (reference: random/sample_op.cc)."""
    low = np.array([0.0, 10.0], np.float32)
    high = np.array([1.0, 20.0], np.float32)
    out = np.asarray(_run("_sample_uniform", [low, high],
                          {"shape": (500,)})[0])
    assert out.shape == (2, 500)
    assert (out[0] >= 0).all() and (out[0] < 1).all()
    assert (out[1] >= 10).all() and (out[1] < 20).all()

    mu = np.array([0.0, 50.0], np.float32)
    sd = np.array([1.0, 5.0], np.float32)
    out = np.asarray(_run("_sample_normal", [mu, sd], {"shape": (800,)})[0])
    assert abs(out[0].mean()) < 0.2 and abs(out[1].mean() - 50) < 1.0

    a = np.array([2.0, 9.0], np.float32)
    b = np.array([1.0, 0.5], np.float32)
    out = np.asarray(_run("_sample_gamma", [a, b], {"shape": (800,)})[0])
    assert abs(out[0].mean() - 2.0) < 0.5 and abs(out[1].mean() - 4.5) < 0.8

    probs = np.array([[0.0, 0.0, 1.0], [1.0, 0.0, 0.0]], np.float32)
    out = np.asarray(_run("_sample_multinomial", [probs],
                          {"shape": (50,)})[0])
    assert (out[0] == 2).all() and (out[1] == 0).all()

    out = np.asarray(_run("_sample_unique_zipfian", [],
                          {"range_max": 1000, "shape": (1, 64)})[0])
    assert (out >= 0).all() and (out < 1000).all()


# -------------------------------------------- optimizer kernels, directly --
def test_rmspropalex_update():
    rs = RS(0)
    w, g_st, d = (rs.randn(4, 3).astype(np.float32) for _ in range(3))
    n = np.abs(rs.randn(4, 3)).astype(np.float32) + 1.0  # valid E[g^2]
    grad = rs.randn(4, 3).astype(np.float32) * 0.3
    outs = _run("rmspropalex_update", [w, grad, n, g_st, d],
                {"lr": 0.01, "gamma1": 0.95, "gamma2": 0.9})
    new_n = 0.05 * grad ** 2 + 0.95 * n
    new_g = 0.05 * grad + 0.95 * g_st
    new_d = 0.9 * d - 0.01 * grad / np.sqrt(new_n - new_g ** 2 + 1e-8)
    np.testing.assert_allclose(np.asarray(outs[0]), w + new_d, rtol=1e-4,
                               atol=1e-5)


def test_mp_sgd_kernels():
    rs = RS(1)
    w32 = rs.randn(4, 3).astype(np.float32)
    w16 = w32.astype(np.float16)
    g16 = rs.randn(4, 3).astype(np.float16)
    new_w, new_w32 = _run("mp_sgd_update", [w16, g16, w32], {"lr": 0.1})
    np.testing.assert_allclose(np.asarray(new_w32),
                               w32 - 0.1 * g16.astype(np.float32),
                               rtol=1e-3, atol=1e-3)
    assert np.asarray(new_w).dtype == np.float16
    mom = np.zeros_like(w32)
    outs = _run("mp_sgd_mom_update", [w16, g16, mom, w32],
                {"lr": 0.1, "momentum": 0.9})
    np.testing.assert_allclose(np.asarray(outs[2]),
                               w32 - 0.1 * g16.astype(np.float32),
                               rtol=1e-3, atol=1e-3)


def test_multi_tensor_kernels():
    """Aggregated multi-weight updates (reference: optimizer_op.cc
    multi_sgd*, MXNET_OPTIMIZER_AGGREGATION_SIZE)."""
    rs = RS(2)
    w1, g1 = rs.randn(3, 2).astype(np.float32), rs.randn(3, 2).astype(np.float32)
    w2, g2 = rs.randn(5).astype(np.float32), rs.randn(5).astype(np.float32)
    outs = _run("multi_sgd_update", [w1, g1, w2, g2],
                {"lrs": (0.1, 0.2), "wds": (0.0, 0.0), "num_weights": 2})
    np.testing.assert_allclose(np.asarray(outs[0]), w1 - 0.1 * g1,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(outs[1]), w2 - 0.2 * g2,
                               rtol=1e-5)
    m1, m2 = np.zeros_like(w1), np.zeros_like(w2)
    outs = _run("multi_sgd_mom_update", [w1, g1, m1, w2, g2, m2],
                {"lrs": (0.1, 0.2), "wds": (0.0, 0.0), "momentum": 0.9,
                 "num_weights": 2})
    np.testing.assert_allclose(np.asarray(outs[0]), w1 - 0.1 * g1,
                               rtol=1e-5)
    # multi-precision twins
    w1h = w1.astype(np.float16)
    outs = _run("multi_mp_sgd_update", [w1h, g1.astype(np.float16), w1],
                {"lrs": (0.5,), "wds": (0.0,), "num_weights": 1})
    np.testing.assert_allclose(np.asarray(outs[1]), w1 - 0.5 * g1,
                               rtol=1e-2, atol=1e-2)
    mom = np.zeros_like(w1)
    outs = _run("multi_mp_sgd_mom_update",
                [w1h, g1.astype(np.float16), mom, w1],
                {"lrs": (0.5,), "wds": (0.0,), "momentum": 0.0,
                 "num_weights": 1})
    np.testing.assert_allclose(np.asarray(outs[2]), w1 - 0.5 * g1,
                               rtol=1e-2, atol=1e-2)


def test_group_adagrad_update():
    rs = RS(3)
    w = rs.randn(4, 3).astype(np.float32)
    g = rs.randn(4, 3).astype(np.float32)
    h = np.abs(rs.randn(4).astype(np.float32))
    outs = _run("group_adagrad_update", [w, g, h], {"lr": 0.1})
    new_h = h + (g ** 2).mean(axis=1)
    scale = 0.1 / (np.sqrt(new_h) + 1e-5)
    np.testing.assert_allclose(np.asarray(outs[1]), new_h, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(outs[0]),
                               w - scale[:, None] * g, rtol=1e-4,
                               atol=1e-5)


def test_contrib_mp_adamw_update():
    rs = RS(4)
    w32 = rs.randn(3, 2).astype(np.float32)
    w16 = w32.astype(np.float16)
    g = rs.randn(3, 2).astype(np.float16)
    mean = np.zeros_like(w32)
    var = np.zeros_like(w32)
    rescale = np.array([1.0], np.float32)
    outs = _run("_contrib_mp_adamw_update",
                [w16, g, mean, var, w32, rescale],
                {"lr": 0.01, "eta": 1.0, "wd": 0.0})
    assert len(outs) == 4
    assert np.isfinite(np.asarray(outs[0], dtype=np.float64)).all()


def test_sparse_sgd_mom_update_kernel():
    rs = RS(5)
    w = rs.randn(10, 4).astype(np.float32)
    mom = np.zeros_like(w)
    idx = np.array([1, 7], np.int32)
    gval = rs.randn(2, 4).astype(np.float32)
    outs = _run("_sparse_sgd_mom_update", [w, gval, idx, mom],
                {"lr": 0.1, "momentum": 0.9})
    new_w = np.asarray(outs[0])
    np.testing.assert_allclose(new_w[idx], w[idx] - 0.1 * gval, rtol=1e-5)
    untouched = np.setdiff1d(np.arange(10), idx)
    np.testing.assert_array_equal(new_w[untouched], w[untouched])


# ----------------------------------------------- sampler-grid op family ----
def test_bilinear_sampler_identity_grid():
    """An identity grid reproduces the input (reference:
    bilinear_sampler.cc)."""
    x = _f32(1, 2, 5, 5)
    ys, xs = np.meshgrid(np.linspace(-1, 1, 5), np.linspace(-1, 1, 5),
                         indexing="ij")
    grid = np.stack([xs, ys])[None].astype(np.float32)  # (1, 2, 5, 5)
    out = np.asarray(_run("BilinearSampler", [x, grid], {})[0])
    np.testing.assert_allclose(out, x, rtol=1e-5, atol=1e-5)


def test_spatial_transformer_identity():
    """Identity affine theta keeps the image (reference:
    spatial_transformer.cc)."""
    x = _f32(1, 2, 6, 6)
    theta = np.array([[1.0, 0.0, 0.0, 0.0, 1.0, 0.0]], np.float32)
    out = np.asarray(_run("SpatialTransformer", [x, theta],
                          {"target_shape": (6, 6),
                           "transform_type": "affine"})[0])
    np.testing.assert_allclose(out, x, rtol=1e-4, atol=1e-4)


def test_quantized_pool_concat_flatten():
    """INT8 data ops carry their ranges through (reference:
    quantized_pooling.cc / quantized_concat.cc / quantized_flatten.cc)."""
    rs = RS(6)
    q = rs.randint(-127, 128, (1, 2, 4, 4)).astype(np.int8)
    mn = np.array([-1.0], np.float32)
    mx_ = np.array([1.0], np.float32)
    out, omin, omax = _run("_contrib_quantized_pooling", [q, mn, mx_],
                           {"kernel": (2, 2), "stride": (2, 2),
                            "pool_type": "max"})
    assert np.asarray(out).shape == (1, 2, 2, 2)
    assert np.asarray(omin).item() == -1.0 and np.asarray(omax).item() == 1.0

    out, omin, omax = _run("_contrib_quantized_flatten", [q, mn, mx_], {})
    assert np.asarray(out).shape == (1, 32)

    q2 = rs.randint(-127, 128, (1, 2, 4, 4)).astype(np.int8)
    out, omin, omax = _run(
        "_contrib_quantized_concat",
        [q, q2, mn, np.array([-2.0], np.float32), mx_,
         np.array([2.0], np.float32)], {"dim": 1, "num_args": 2})
    assert np.asarray(out).shape == (1, 4, 4, 4)
    assert np.asarray(omax).item() == 2.0


def test_signsgd_and_adamw_kernels():
    rs = RS(7)
    w = rs.randn(4, 3).astype(np.float32)
    g = rs.randn(4, 3).astype(np.float32)
    out = _run("signsgd_update", [w, g], {"lr": 0.1})[0]
    np.testing.assert_allclose(np.asarray(out), w - 0.1 * np.sign(g),
                               rtol=1e-6)
    mean = np.zeros_like(w)
    var = np.zeros_like(w)
    outs = _run("adamw_update", [w, g, mean, var],
                {"lr": 0.01, "eta": 1.0, "wd": 0.1})
    new_mean = 0.1 * g
    new_var = 0.001 * g ** 2
    want = w - 1.0 * (0.01 * new_mean / (np.sqrt(new_var) + 1e-8)
                      + 0.1 * w)
    np.testing.assert_allclose(np.asarray(outs[0]), want, rtol=1e-4,
                               atol=1e-5)
    rescale = np.array([1.0], np.float32)
    outs = _run("_contrib_adamw_update", [w, g, mean, var, rescale],
                {"lr": 0.01, "eta": 1.0, "wd": 0.1})
    np.testing.assert_allclose(np.asarray(outs[0]), want, rtol=1e-4,
                               atol=1e-5)


def test_where_nd_unsupported():
    """where_nd's single-arg form has a data-dependent output shape —
    deliberately unsupported on TPU, with a clear redirect."""
    with pytest.raises(Exception, match="boolean_mask"):
        apply_op("where_nd", (_f32(3, 4) > 0).astype(np.float32))


SPECIAL = {"where_nd"}


# --------------------------------------------------------------------------
# coverage gate
# --------------------------------------------------------------------------
def test_registry_fully_covered():
    """Every registered op must be claimed by some tier; a new op with
    no test fails here."""
    direct = {"signsgd_update", "adamw_update", "_contrib_adamw_update",
              "rmspropalex_update", "adagrad_update", "adadelta_update",
              "mp_sgd_update", "mp_sgd_mom_update",
              "multi_sgd_update", "multi_sgd_mom_update",
              "multi_mp_sgd_update", "multi_mp_sgd_mom_update",
              "group_adagrad_update", "_contrib_mp_adamw_update",
              "_sparse_sgd_mom_update", "BilinearSampler",
              "SpatialTransformer", "_contrib_quantized_pooling",
              "_contrib_quantized_concat", "_contrib_quantized_flatten",
              "_sample_uniform", "_sample_normal", "_sample_gamma",
              "_sample_multinomial", "_sample_unique_zipfian"}
    covered = (set(UNARY) | set(BINARY) | set(SCALAR) | set(REDUCE)
               | set(EXPLICIT) | set(CREATION) | set(ELSEWHERE) | SPECIAL
               | set(RANDOM) | direct)
    all_ops = set(registry.list_ops())
    missing = sorted(all_ops - covered)
    assert not missing, "ops with no test coverage: %s" % missing
    phantom = sorted((set(UNARY) | set(EXPLICIT)) - all_ops)
    assert not phantom, "spec entries for unregistered ops: %s" % phantom
    # ELSEWHERE pointers must name real files AND actually mention the
    # op (by canonical name or a registered alias) — a pointer to a file
    # that never exercises the op is a bogus coverage claim
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    for op, (path, evidence) in ELSEWHERE.items():
        full = os.path.join(os.path.dirname(here), path)
        assert os.path.exists(full), "%s points at missing %s" % (op, path)
        body = open(full).read()
        assert evidence in body, \
            "%s claims coverage in %s but evidence %r is absent" \
            % (op, path, evidence)


def test_conv_nhwc_layout_matches_nchw():
    """layout='NHWC' (channel-last data, OHWI weight — the reference's
    NHWC weight convention) must equal the NCHW result transposed."""
    x = _f32(2, 3, 6, 6)
    w = _f32(4, 3, 3, 3, seed=1)
    b = _f32(4, seed=2)
    want = np.asarray(_run("Convolution", [x, w, b],
                           {"kernel": (3, 3), "num_filter": 4,
                            "pad": (1, 1)})[0])
    got = np.asarray(_run("Convolution",
                          [x.transpose(0, 2, 3, 1),
                           w.transpose(0, 2, 3, 1), b],
                          {"kernel": (3, 3), "num_filter": 4,
                           "pad": (1, 1), "layout": "NHWC"})[0])
    np.testing.assert_allclose(got.transpose(0, 3, 1, 2), want,
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("pool_type", ["max", "avg"])
def test_pool_nhwc_layout_matches_nchw(pool_type):
    """Pooling layout='NHWC' equals the NCHW result transposed —
    completes the channel-last op pair with Convolution."""
    x = _f32(2, 3, 6, 6)
    attrs = {"kernel": (2, 2), "stride": (2, 2), "pool_type": pool_type}
    want = np.asarray(_run("Pooling", [x], attrs)[0])
    got = np.asarray(_run("Pooling", [x.transpose(0, 2, 3, 1)],
                          {**attrs, "layout": "NHWC"})[0])
    np.testing.assert_allclose(got.transpose(0, 3, 1, 2), want,
                               rtol=1e-5, atol=1e-6)
    # global pooling too
    wantg = np.asarray(_run("Pooling", [x],
                            {"pool_type": pool_type,
                             "global_pool": True})[0])
    gotg = np.asarray(_run("Pooling", [x.transpose(0, 2, 3, 1)],
                           {"pool_type": pool_type, "global_pool": True,
                            "layout": "NHWC"})[0])
    np.testing.assert_allclose(gotg.transpose(0, 3, 1, 2), wantg,
                               rtol=1e-5, atol=1e-6)
