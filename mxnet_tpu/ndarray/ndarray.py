"""NDArray — the imperative tensor, backed by a jax.Array in HBM.

Reference: include/mxnet/ndarray.h:82 (class NDArray), src/ndarray/
ndarray.cc, python/mxnet/ndarray/ndarray.py.

TPU-native design notes:

- The reference NDArray is a ref-counted Chunk(Storage::Handle + engine
  var); ops are pushed to the async engine and the user thread never
  blocks until an explicit sync (``asnumpy``/``wait_to_read``).  Here the
  buffer is a ``jax.Array`` — XLA's async dispatch *is* the engine:
  every op returns immediately with a future-backed array, and
  ``asnumpy()``/``wait_to_read()`` are the sync points
  (``jax.Array.block_until_ready``).  No re-implementation of
  ThreadedEngine is needed or wanted (SURVEY.md §7 design stance).
- NDArray is *mutable* at the Python level (``a[:] = x``, ``a += b``,
  optimizer in-place updates): mutation rebinds the internal ``_data``
  to a new functional value (``jax.Array.at[...]``), which XLA turns
  into in-place donation where safe.  Basic-slice reads return a view
  object carrying a writeback link to the base (parity with the
  reference's Slice/At write-through views, ndarray.h:810).
- Eager ops dispatch through the op registry's per-op jit cache
  (ops/registry.py), so steady-state imperative code runs compiled
  kernels; ``hybridize``/Symbol stage whole graphs instead.
"""

from __future__ import annotations

import numpy as _np

from .. import device_memory as _dm
from .. import profiler as _prof
from .. import runtime_stats as _rts
from ..base import MXNetError, np_dtype, numeric_types
from ..context import Context, _accelerator_devices, current_context
from ..ops import registry as _reg

# dict read on every dispatch: cheapest possible "is the profiler on"
# check (guard-first — no event/span allocation when it is off)
_prof_state = _prof._state
# same guard shape for the device-buffer tracker (device_memory.py)
_dm_state = _dm._state

__all__ = ["NDArray", "array", "zeros", "ones", "full", "empty", "arange",
           "concatenate", "save", "load", "waitall", "imperative_invoke",
           "moveaxis", "stack_arrays"]

# ops that consume an explicit PRNG key as first tensor input
RANDOM_OPS = {
    "_random_uniform", "_random_normal", "_random_gamma", "_random_exponential",
    "_random_poisson", "_random_negative_binomial",
    "_random_generalized_negative_binomial", "_random_randint",
    "_sample_multinomial", "_sample_uniform", "_sample_normal", "_sample_gamma",
    "_sample_exponential", "_sample_poisson", "_sample_negative_binomial",
    "_sample_generalized_negative_binomial",
    "_shuffle", "_sample_unique_zipfian", "RNN",
}


def _jnp():
    import jax.numpy as jnp

    return jnp


class NDArray:
    """An n-dimensional array on a device (TPU HBM by default)."""

    __slots__ = ("_data", "_ctx", "_ag_node", "_writeback", "__weakref__")

    # make numpy defer to NDArray in mixed expressions (np * nd)
    __array_priority__ = 1000.0

    def __init__(self, data, ctx=None, _writeback=None):
        self._data = data
        self._ctx = ctx
        self._ag_node = None
        self._writeback = _writeback  # (base NDArray, index) for slice views
        if _dm_state["on"]:
            _dm.track(data)

    # ------------------------------------------------------------- basics
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return _np.dtype(self._data.dtype)

    @property
    def size(self):
        return int(self._data.size)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def context(self):
        if self._ctx is not None:
            return self._ctx
        try:
            dev = list(self._data.devices())[0]
            platform = dev.platform
        except Exception:
            return current_context()
        if platform == "cpu":
            return Context("cpu", dev.id)
        # index among THIS process's chips: a device id is global, and
        # worker r of a multi-process job holds chip id r as its chip 0
        return Context("tpu", _accelerator_devices().index(dev))

    ctx = context

    @property
    def data_jax(self):
        """The underlying jax.Array (TPU-native escape hatch)."""
        return self._data

    def __repr__(self):
        try:
            arr = self.asnumpy()
            body = str(arr)
        except Exception as e:  # async error surfaces at sync point
            body = "<error: %s>" % e
        return "%s\n<NDArray %s @%s>" % (body, "x".join(map(str, self.shape)), self.context)

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __bool__(self):
        if self.size == 1:
            return bool(self.asnumpy().item())
        raise ValueError("ambiguous truth value of multi-element NDArray")

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # ------------------------------------------------------------- sync
    def asnumpy(self):
        """Copy to host, blocking until the value is ready.

        Reference parity: the implicit engine sync point
        (``NDArray::WaitToRead`` + copy, ndarray.h:359).
        """
        return _np.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().item()

    def item(self):
        return self.asscalar()

    def wait_to_read(self):
        self._data.block_until_ready()

    def wait_to_write(self):
        self._data.block_until_ready()

    # ------------------------------------------------------------- dtype/device
    def astype(self, dtype, copy=True):
        d = np_dtype(dtype)
        if not copy and self.dtype == d:
            return self
        return NDArray(self._data.astype(d), self._ctx)

    def as_in_context(self, ctx):
        import jax

        if ctx == self.context:
            return self
        return NDArray(jax.device_put(self._data, ctx.jax_device), ctx)

    as_in_ctx = as_in_context

    def copyto(self, other):
        """Copy into another NDArray/Context (reference: CopyFromTo,
        src/ndarray/ndarray.cc:1186)."""
        import jax

        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device), other)
        if not isinstance(other, NDArray):
            raise TypeError("copyto target must be NDArray or Context")
        if other.shape != self.shape:
            raise ValueError("copyto shape mismatch %s vs %s" % (self.shape, other.shape))
        other._assign(jax.device_put(self._data.astype(other.dtype),
                                     other.context.jax_device))
        return other

    def copy(self):
        return NDArray(self._data + 0 if self.dtype != _np.bool_ else self._data,
                       self._ctx)

    def tostype(self, stype):
        if stype == "default":
            return self
        from . import sparse as _sp

        return _sp.cast_storage(self, stype)

    @property
    def stype(self):
        return "default"

    # ------------------------------------------------------------- mutation
    def _assign(self, new_jax_value):
        """Rebind the buffer; propagate through view writeback if present."""
        from .. import autograd as _ag

        if self._ag_node is not None and _ag.is_recording():
            raise MXNetError(
                "in-place write on an array participating in a recorded graph"
            )
        if _dm_state["on"]:
            _dm.track(new_jax_value, "_assign")
        self._data = new_jax_value
        if self._writeback is not None:
            base, index = self._writeback
            if base._needs_i64():
                import jax

                with jax.enable_x64():
                    base._assign(base._data.at[index].set(new_jax_value))
            else:
                base._assign(base._data.at[index].set(new_jax_value))

    def __setitem__(self, key, value):
        jnp = _jnp()
        if isinstance(value, NDArray):
            v = value._data
        elif isinstance(value, numeric_types):
            v = value
        else:
            v = jnp.asarray(value)
        if key is None or key == slice(None):
            if isinstance(v, (int, float)):
                self._assign(jnp.full(self.shape, v, dtype=self.dtype))
            else:
                v = jnp.asarray(v, dtype=self.dtype)
                self._assign(jnp.broadcast_to(v, self.shape) + 0)
            return
        if self._needs_i64():
            import jax

            key = _clean_index(key, _np.int64)
            with jax.enable_x64():
                self._assign(self._data.at[key].set(v))
            return
        key = _clean_index(key)
        self._assign(self._data.at[key].set(v))

    def _needs_i64(self):
        """Arrays beyond int32 addressing need 64-bit gather/scatter
        indices (reference: INT64_TENSOR_SIZE builds; nightly
        test_large_array.py).  Host/CPU-backed arrays only: XLA's TPU
        backend has no 64-bit scatter, and a single chip's HBM cannot
        hold such a tensor anyway — on device, exceeding int32 addressing
        means sharding over a mesh."""
        return any(d > 2**31 - 1 for d in self._data.shape)

    def _on_tape(self):
        """Whether gradients can flow through this array: it was
        attach_grad()ed or produced by a recorded op."""
        return self._ag_node is not None

    def __getitem__(self, key):
        from .. import autograd as _ag

        record = _ag.is_recording() and self._on_tape()
        if key is None:
            if record:
                from ..ops.matrix import encode_basic_index

                return imperative_invoke(
                    "_basic_index", [self],
                    {"key": encode_basic_index((None,))})[0]
            return NDArray(self._data[None], self._ctx)
        if self._needs_i64():
            import jax

            ck = _clean_index(key, _np.int64)
            if _is_basic_index(ck):
                if record:
                    from ..ops.matrix import encode_basic_index

                    return imperative_invoke(
                        "_basic_index", [self],
                        {"key": encode_basic_index(ck)})[0]
                with jax.enable_x64():
                    out = self._data[ck]
                if isinstance(ck, tuple) and any(k is None for k in ck):
                    return NDArray(out, self._ctx)  # no scatter target
                # keep the reference's Slice/At write-through views on
                # the int64 path too (same program, same semantics,
                # regardless of array size)
                return NDArray(out, self._ctx, _writeback=(self, ck))
            if record:
                raise MXNetError(
                    "advanced indexing of an int64-addressed array is "
                    "not differentiable; read it outside "
                    "autograd.record() or via .detach()")
            with jax.enable_x64():
                return NDArray(self._data[ck], self._ctx)
        ck = _clean_index(key)
        if _is_basic_index(ck):
            if record:
                # an on-tape read through a view would fall off the tape
                # — route through the registered _basic_index op so it
                # joins the autograd graph (reference: record-able
                # Slice/At views, src/ndarray/ndarray.cc:234,267)
                from ..ops.matrix import encode_basic_index

                return imperative_invoke(
                    "_basic_index", [self],
                    {"key": encode_basic_index(ck)})[0]
            if isinstance(ck, tuple) and any(k is None for k in ck):
                # newaxis views have no scatter target — plain copy
                return NDArray(self._data[ck], self._ctx)
            # basic index → view with writeback (reference Slice/At views)
            return NDArray(self._data[ck], self._ctx, _writeback=(self, ck))
        if isinstance(ck, NDArray):
            ck = ck._data.astype("int32")
        if record:
            if not isinstance(ck, tuple) \
                    and getattr(ck, "ndim", None) is not None:
                # single integer-array index of an on-tape array = a row
                # gather; route through `take` so it joins the tape.
                # `take` clamps, so resolve negative indices first
                jnp = _jnp()
                arr = ck if hasattr(ck, "devices") else jnp.asarray(ck)
                arr = jnp.where(arr < 0, arr + self._data.shape[0], arr)
                return imperative_invoke("take", [self, NDArray(arr,
                                                                self._ctx)],
                                         {"axis": 0, "mode": "clip"})[0]
            raise MXNetError(
                "advanced indexing with %r is not differentiable here; "
                "read it outside autograd.record() / via .detach(), or "
                "use take/gather_nd ops" % (key,))
        return NDArray(self._data[ck], self._ctx)

    def slice(self, begin, end, step=None):
        return imperative_invoke("slice", [self], {"begin": begin, "end": end,
                                                   "step": step or ()})[0]

    def slice_axis(self, axis, begin, end):
        return imperative_invoke("slice_axis", [self],
                                 {"axis": axis, "begin": begin, "end": end})[0]

    # ------------------------------------------------------------- autograd
    def attach_grad(self, grad_req="write", stype=None):
        """Allocate gradient buffer and mark for autograd
        (reference: python/mxnet/ndarray/ndarray.py attach_grad →
        MXAutogradMarkVariables)."""
        from .. import autograd as _ag

        _ag.mark_variables([self], [zeros(self.shape, dtype=self.dtype,
                                          ctx=self.context)], grad_req)

    @property
    def grad(self):
        from .. import autograd as _ag

        return _ag.get_grad(self)

    def detach(self):
        out = NDArray(self._data, self._ctx)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd as _ag

        _ag.backward([self], [out_grad] if out_grad is not None else None,
                     retain_graph=retain_graph, train_mode=train_mode)

    # ------------------------------------------------------------- ops sugar
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        shape = kwargs.get("shape", shape)
        return imperative_invoke("Reshape", [self],
                                 {"shape": shape,
                                  "reverse": kwargs.get("reverse", False)})[0]

    def reshape_like(self, other):
        return imperative_invoke("reshape_like", [self, other], {})[0]

    def expand_dims(self, axis):
        return imperative_invoke("expand_dims", [self], {"axis": axis})[0]

    def squeeze(self, axis=None):
        return imperative_invoke("squeeze", [self], {"axis": axis})[0]

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        return imperative_invoke("transpose", [self], {"axes": axes})[0]

    @property
    def T(self):
        return self.transpose()

    def flatten(self):
        return imperative_invoke("Flatten", [self], {})[0]

    def flip(self, axis):
        return imperative_invoke("reverse", [self], {"axis": axis})[0]

    def sum(self, axis=None, keepdims=False, dtype=None, **kw):
        return imperative_invoke("sum", [self], {"axis": axis, "keepdims": keepdims,
                                                 "dtype": dtype})[0]

    def mean(self, axis=None, keepdims=False, dtype=None, **kw):
        return imperative_invoke("mean", [self], {"axis": axis, "keepdims": keepdims,
                                                  "dtype": dtype})[0]

    def max(self, axis=None, keepdims=False):
        return imperative_invoke("max", [self], {"axis": axis, "keepdims": keepdims})[0]

    def min(self, axis=None, keepdims=False):
        return imperative_invoke("min", [self], {"axis": axis, "keepdims": keepdims})[0]

    def prod(self, axis=None, keepdims=False):
        return imperative_invoke("prod", [self], {"axis": axis, "keepdims": keepdims})[0]

    def argmax(self, axis=None, keepdims=False):
        return imperative_invoke("argmax", [self], {"axis": axis, "keepdims": keepdims})[0]

    def pick(self, index, axis=-1, keepdims=False, mode="clip"):
        return imperative_invoke("pick", [self, index],
                                 {"axis": axis, "keepdims": keepdims,
                                  "mode": mode})[0]

    def argmin(self, axis=None, keepdims=False):
        return imperative_invoke("argmin", [self], {"axis": axis, "keepdims": keepdims})[0]

    def norm(self, ord=2, axis=None, keepdims=False):
        return imperative_invoke("norm", [self], {"ord": ord, "axis": axis,
                                                  "keepdims": keepdims})[0]

    def abs(self):
        return imperative_invoke("abs", [self], {})[0]

    def sqrt(self):
        return imperative_invoke("sqrt", [self], {})[0]

    def square(self):
        return imperative_invoke("square", [self], {})[0]

    def exp(self):
        return imperative_invoke("exp", [self], {})[0]

    def log(self):
        return imperative_invoke("log", [self], {})[0]

    def sigmoid(self):
        return imperative_invoke("sigmoid", [self], {})[0]

    def tanh(self):
        return imperative_invoke("tanh", [self], {})[0]

    def relu(self):
        return imperative_invoke("relu", [self], {})[0]

    def softmax(self, axis=-1):
        return imperative_invoke("softmax", [self], {"axis": axis})[0]

    def log_softmax(self, axis=-1):
        return imperative_invoke("log_softmax", [self], {"axis": axis})[0]

    def clip(self, a_min, a_max):
        return imperative_invoke("clip", [self], {"a_min": a_min, "a_max": a_max})[0]

    def round(self):
        return imperative_invoke("round", [self], {})[0]

    def sign(self):
        return imperative_invoke("sign", [self], {})[0]

    def sort(self, axis=-1, is_ascend=True):
        return imperative_invoke("sort", [self], {"axis": axis, "is_ascend": is_ascend})[0]

    def argsort(self, axis=-1, is_ascend=True):
        return imperative_invoke("argsort", [self], {"axis": axis,
                                                     "is_ascend": is_ascend})[0]

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False):
        out = imperative_invoke("topk", [self], {"axis": axis, "k": k,
                                                 "ret_typ": ret_typ,
                                                 "is_ascend": is_ascend})
        return out if len(out) > 1 else out[0]

    def take(self, indices, axis=0, mode="clip"):
        return imperative_invoke("take", [self, _as_nd(indices)],
                                 {"axis": axis, "mode": mode})[0]

    def one_hot(self, depth, **kw):
        return imperative_invoke("one_hot", [self], {"depth": depth, **kw})[0]

    def broadcast_to(self, shape):
        return imperative_invoke("broadcast_to", [self], {"shape": shape})[0]

    def broadcast_like(self, other):
        return imperative_invoke("broadcast_like", [self, other], {})[0]

    def tile(self, reps):
        return imperative_invoke("tile", [self], {"reps": reps})[0]

    def repeat(self, repeats, axis=None):
        return imperative_invoke("repeat", [self], {"repeats": repeats, "axis": axis})[0]

    def pad(self, mode="constant", pad_width=(), constant_value=0.0):
        return imperative_invoke("Pad", [self], {"mode": mode, "pad_width": pad_width,
                                                 "constant_value": constant_value})[0]

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return imperative_invoke("SliceChannel", [self],
                                 {"num_outputs": num_outputs, "axis": axis,
                                  "squeeze_axis": squeeze_axis})

    def diag(self, k=0):
        return imperative_invoke("diag", [self], {"k": k})[0]

    def dot(self, other, transpose_a=False, transpose_b=False):
        return imperative_invoke("dot", [self, other],
                                 {"transpose_a": transpose_a,
                                  "transpose_b": transpose_b})[0]

    # ------------------------------------------------------------- arithmetic
    def _binop(self, other, opname, scalarname, reverse=False):
        if isinstance(other, NDArray):
            args = [other, self] if reverse else [self, other]
            return imperative_invoke(opname, args, {})[0]
        if isinstance(other, numeric_types):
            sname = scalarname
            if reverse and "_r" + scalarname[1:] in _SCALAR_REV:
                sname = "_r" + scalarname[1:]
            return imperative_invoke(sname, [self], {"scalar": float(other)})[0]
        return self._binop(array(other, ctx=self.context), opname, scalarname, reverse)

    def __add__(self, o):
        return self._binop(o, "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binop(o, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, o):
        return self._binop(o, "broadcast_sub", "_minus_scalar", reverse=True)

    def __matmul__(self, o):
        """numpy @ semantics: 2-D dot, batched matmul for higher ranks
        (mx dot is a tensordot over last/first axes — different contract)."""
        import jax.numpy as jnp

        other = o._data if isinstance(o, NDArray) else jnp.asarray(o)
        return NDArray(jnp.matmul(self._data, other), self._ctx)

    def __rmatmul__(self, o):
        import jax.numpy as jnp

        other = o._data if isinstance(o, NDArray) else jnp.asarray(o)
        return NDArray(jnp.matmul(other, self._data), self._ctx)

    def __mul__(self, o):
        return self._binop(o, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binop(o, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, o):
        return self._binop(o, "broadcast_div", "_div_scalar", reverse=True)

    def __mod__(self, o):
        return self._binop(o, "broadcast_mod", "_mod_scalar")

    def __rmod__(self, o):
        return self._binop(o, "broadcast_mod", "_mod_scalar", reverse=True)

    def __pow__(self, o):
        return self._binop(o, "broadcast_power", "_power_scalar")

    def __rpow__(self, o):
        return self._binop(o, "broadcast_power", "_power_scalar", reverse=True)

    def __neg__(self):
        return imperative_invoke("negative", [self], {})[0]

    def __abs__(self):
        return self.abs()

    def __eq__(self, o):
        if o is None:
            return False
        return self._binop(o, "broadcast_equal", "_equal_scalar")

    def __ne__(self, o):
        if o is None:
            return True
        return self._binop(o, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, o):
        return self._binop(o, "broadcast_greater", "_greater_scalar")

    def __ge__(self, o):
        return self._binop(o, "broadcast_greater_equal", "_greater_equal_scalar")

    def __lt__(self, o):
        return self._binop(o, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, o):
        return self._binop(o, "broadcast_lesser_equal", "_lesser_equal_scalar")

    __hash__ = object.__hash__

    def __iadd__(self, o):
        out = self.__add__(o)
        self._assign(out._data)
        return self

    def __isub__(self, o):
        out = self.__sub__(o)
        self._assign(out._data)
        return self

    def __imul__(self, o):
        out = self.__mul__(o)
        self._assign(out._data)
        return self

    def __itruediv__(self, o):
        out = self.__truediv__(o)
        self._assign(out._data)
        return self

    # numpy interop
    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    def to_dlpack_for_read(self):
        return self._data.__dlpack__()

    to_dlpack_for_write = to_dlpack_for_read


_SCALAR_REV = {"_rminus_scalar", "_rdiv_scalar", "_rmod_scalar", "_rpower_scalar"}


def _clean_index(key, idx_dtype=_np.int32):
    """Convert NDArray indices inside a key to jax/numpy arrays.

    idx_dtype: int64 for arrays addressed beyond int32 (INT64_TENSOR_SIZE
    paths) — truncating here would silently wrap large indices."""
    if isinstance(key, NDArray):
        return key._data.astype(idx_dtype)
    if isinstance(key, tuple):
        return tuple(
            k._data.astype(idx_dtype) if isinstance(k, NDArray) else k
            for k in key
        )
    if isinstance(key, (list, _np.ndarray)):
        return _np.asarray(key, dtype=idx_dtype)
    return key


def _is_basic_index(key):
    if isinstance(key, (int, slice)) or key is Ellipsis:
        return True
    if isinstance(key, tuple):
        return all(isinstance(k, (int, slice)) or k is Ellipsis or k is None
                   for k in key)
    return False


def _as_nd(x, ctx=None):
    if isinstance(x, NDArray):
        return x
    return array(x, ctx=ctx)


# ----------------------------------------------------------------- dispatch


import contextlib


@contextlib.contextmanager
def _op_errors(op_name, arrays):
    """Surface op failures as MXNetError (reference: every imperative
    error crosses the C API as MXNetError, src/c_api/c_api_error.cc).
    Under jit tracing the original jax error types are kept — hybrid
    callers and jax itself dispatch on them."""
    try:
        yield
    except (TypeError, ValueError) as e:
        if isinstance(e, ValueError) and "incompatible devices" in str(e):
            raise  # handled by the cross-device retry in the caller
        import jax

        if any(isinstance(a, jax.core.Tracer) for a in arrays):
            raise
        raise MXNetError("%s: %s" % (op_name, e)) from e


def imperative_invoke(op_name, inputs, attrs, out=None):
    """The imperative dispatch path.

    Reference analog: MXImperativeInvokeEx → Imperative::Invoke
    (src/c_api/c_api_ndarray.cc:132, src/imperative/imperative.cc) —
    shape/type inference, engine push, and autograd recording in one.
    Here: unwrap → (jit-cached) pure fn → wrap, with jax.vjp capture when
    autograd is recording.
    """
    from .. import autograd as _ag

    op = _reg.get(op_name)
    attrs = {k: v for k, v in attrs.items() if v is not None}
    attrs = op.canonicalize_attrs(attrs)

    arrays = [a._data if isinstance(a, NDArray) else a for a in inputs]
    ctx = None
    for a in inputs:
        if isinstance(a, NDArray):
            ctx = a._ctx
            break

    needs_key = op_name in RANDOM_OPS
    if op_name == "RNN" and not _ag.is_training():
        # inference pass disables inter-layer dropout (reference: cuDNN RNN
        # forward-inference path, src/operator/cudnn_rnn-inl.h)
        attrs = dict(attrs, p=0.0)
    if op_name == "IdentityAttachKLSparseReg":
        # the aux moving-average updates only in the training pass
        # (reference updates it in Backward,
        # identity_attach_KL_sparse_reg-inl.h).  Resolved HERE so the
        # flag lands in the jit cache key — a Python branch inside the
        # op fn would be baked in by whichever mode compiled first.
        attrs = dict(attrs, _train=_ag.is_training())
    if op_name == "Dropout":
        # training-mode gate (reference: dropout.cc runs only in train pass)
        if attrs.get("mode", "training") == "always" or _ag.is_training():
            needs_key = True  # key=... kwarg threaded below
        else:
            return _wrap_outputs((arrays[0],), ctx, out, op=op.name)

    if needs_key:
        from ..random import next_key

        arrays = [next_key()] + arrays

    recording = _ag.is_recording() and _ag._any_recorded(inputs)
    if recording:
        import jax

        fn = op.bind_attrs(attrs)
        # telemetry is keyed on op.name so aliases (nd.identity vs
        # '_copy') aggregate into ONE per-op row, matching jitted_ex.
        # vjp capture bypasses the static jit cache by design — the
        # span still shows where forward-trace time goes in training
        _rts.record_dispatch(op.name, "uncached")
        with _prof.span("dispatch:" + op.name, "operator",
                        args={"op": op.name, "cache": "bypass-autograd"}
                        if _prof_state["running"] else None):
            with _op_errors(op_name, arrays):
                if needs_key:
                    outv, vjp_fn = _vjp_with_aux(fn, arrays)
                else:
                    outv, vjp_fn = jax.vjp(fn, *arrays)
        result = outv if isinstance(outv, tuple) else (outv,)
        out_nds = _wrap_outputs(result, ctx, out, op=op.name)
        _ag.record_op(inputs, out_nds, vjp_fn, op_name=op_name, attrs=attrs)
        return out_nds

    if needs_key:
        # keys vary per call → bypass the static jit cache (jax still
        # compiles the underlying primitives)
        _rts.record_dispatch(op.name, "uncached")
        with _prof.span("dispatch:" + op.name, "operator",
                        args={"op": op.name, "cache": "bypass-rng"}
                        if _prof_state["running"] else None):
            with _op_errors(op_name, arrays):
                result = op.bind_attrs(attrs)(*arrays)
    else:
        result = _dispatch_jit(op, op_name, attrs, arrays)
    result = result if isinstance(result, tuple) else (result,)
    return _wrap_outputs(result, ctx, out, op=op.name)


def _dispatch_jit(op, op_name, attrs, arrays):
    """The jit-cached dispatch path, instrumented.

    Always (profiler on or off): the registry counts the cache hit/miss
    and storms (inside ``jitted_ex``), and a miss's wall-time — which
    the trace+XLA-compile dominates, execution being async-dispatched —
    is attributed to ``runtime_stats`` compile_seconds.  Guard-first:
    when the profiler is off and the cache hits, the extra cost is one
    flag read — no timestamps, no event allocation, no host sync."""
    entry, hit = op.jitted_ex(attrs)
    cname = op.name  # canonical — jitted_ex counts under this name
    prof_on = _prof_state["running"]
    if hit and not prof_on and not _rts.DIAG_TIMING:
        return _call_jit_entry(op_name, cname, entry, arrays)
    t0 = _prof._now_us()
    result = _call_jit_entry(op_name, cname, entry, arrays)
    dur = _prof._now_us() - t0
    if not hit:
        _rts.add_compile_seconds(cname, dur / 1e6)
        # compile-time-only XLA cost/memory analysis of the fresh
        # entry (flops, bytes accessed, output/temp footprint) — feeds
        # the runtime_stats roofline/footprint sections.  Never on the
        # hit path; no-op unless cost capture is active (registry).
        op.analyze_entry(attrs, arrays)
    else:
        # timed CACHE-WARM wall-time per op (profiler on, or a
        # MXNET_TPU_DIAG run — the dump needs rate denominators): the
        # achieved GB/s / GFLOP/s divisor.  Misses are excluded —
        # their dur is compile-dominated and already attributed to
        # compile_seconds; folding it in would put every freshly
        # compiled op at the top of the roofline table
        _rts.add_dispatch_seconds(cname, dur / 1e6)
    if prof_on:
        # aval churn recompiles inside the jax.jit entry (registry-level
        # hit!) — feed shape/dtype signatures to the storm detector
        _rts.note_aval_key(cname, tuple(
            (tuple(getattr(a, "shape", ())), str(getattr(a, "dtype", "")))
            for a in arrays))
        ev_args = {"op": cname, "cache": "hit" if hit else "miss"}
        if not hit:
            ev_args["compile_ms"] = round(dur / 1e3, 3)
        _prof.add_event("dispatch:" + cname, "operator", "X", ts=t0,
                        dur=dur, args=ev_args)
    return result


def _call_jit_entry(op_name, cname, entry, arrays):
    try:
        with _op_errors(op_name, arrays):
            return entry(*arrays)
    except ValueError as e:
        if "incompatible devices" not in str(e):
            raise
        # cross-device inputs (e.g. kvstore reduce over per-device
        # grads): gather to the first input's device, like the
        # reference's CommCPU copy-to-reduce (src/kvstore/comm.h:103)
        import jax

        _rts.record_fallback(cname, "cross-device")
        dev = list(arrays[0].devices())[0]
        arrays = [jax.device_put(a, dev) for a in arrays]
        with _op_errors(op_name, arrays):
            return entry(*arrays)


def _vjp_with_aux(fn, arrays):
    """vjp over (key, *tensors): drop the key cotangent."""
    import jax

    outv, vjp_all = jax.vjp(fn, *arrays)

    def vjp_fn(ct):
        grads = vjp_all(ct)
        return grads[1:]  # drop key cotangent

    return outv, vjp_fn


def _wrap_outputs(result, ctx, out=None, op=None):
    if _dm_state["on"]:
        # label output buffers with the creating op for the per-op
        # memory breakdown; restore so unrelated wraps don't inherit it
        prev = _dm.set_origin(op)
        try:
            nds = [NDArray(r, ctx) for r in result]
        finally:
            _dm.set_origin(prev)
    else:
        nds = [NDArray(r, ctx) for r in result]
    if out is not None:
        outs = out if isinstance(out, (list, tuple)) else [out]
        for dst, src in zip(outs, nds):
            dst._assign(src._data)
        return list(outs)
    return nds


# ----------------------------------------------------------------- creation


def array(source, ctx=None, dtype=None):
    import jax

    if isinstance(source, (NDArray, jax.Array)):
        # device-backed sources stay on device: a host roundtrip here
        # (asnumpy + re-upload) would block eager dispatch — this is
        # the hot path for `nd +/* raw-jax-array` arithmetic (mxlint:
        # trace-host-sync caught the old copy).  Typed sources keep
        # their dtype (f64 narrows: fp32-native framework).
        src = source._data if isinstance(source, NDArray) else source
        if dtype is not None:
            d = np_dtype(dtype)
        elif src.dtype == _np.float64:
            d = _np.float32  # framework is fp32-native
        else:
            d = src.dtype
        ctx = ctx or current_context()
        dev = ctx.jax_device  # outside the try: a bad ctx must raise
        try:
            same_device = dev in src.devices()
        except Exception:  # tracer / abstract value: no device yet
            same_device = None
        if src.dtype != d:
            src = src.astype(d)  # fresh buffer, already a snapshot
        elif same_device:
            # nd.array is documented as a snapshot — a same-device
            # device_put would alias the source buffer, and a later
            # donated jit step (parallel/gluon_step.py) would delete
            # it out from under the snapshot.  The cross-device
            # transfer below already yields an independent buffer.
            src = _jnp().array(src, copy=True)
        if same_device is False:
            src = jax.device_put(src, dev)
        if _dm_state["on"]:
            _dm.track(src, "array")
        return NDArray(src, ctx)
    src = _np.asarray(source)
    if dtype is None:
        if isinstance(source, _np.ndarray):
            # typed sources keep their dtype (float64 narrows: the
            # framework is fp32-native, reference does the same)
            dtype = src.dtype if src.dtype != _np.float64 else _np.float32
        else:
            # python lists/scalars default to float32 — the reference's
            # documented nd.array semantics (python/mxnet/ndarray/
            # utils.py array: dtype = float32 when source has no dtype)
            dtype = _np.float32
    src = src.astype(np_dtype(dtype))
    ctx = ctx or current_context()
    d = jax.device_put(src, ctx.jax_device)
    if _dm_state["on"]:
        _dm.track(d, "array")
    return NDArray(d, ctx)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None, **kwargs):
    import jax

    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    ctx = ctx or current_context()
    jnp = _jnp()
    with jax.default_device(ctx.jax_device):
        d = jnp.zeros(shape, dtype=np_dtype(dtype))
    if _dm_state["on"]:
        _dm.track(d, "zeros")
    return NDArray(d, ctx)


def ones(shape, ctx=None, dtype=None, **kwargs):
    import jax

    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    ctx = ctx or current_context()
    jnp = _jnp()
    with jax.default_device(ctx.jax_device):
        d = jnp.ones(shape, dtype=np_dtype(dtype))
    if _dm_state["on"]:
        _dm.track(d, "ones")
    return NDArray(d, ctx)


def full(shape, val, ctx=None, dtype=None, **kwargs):
    import jax

    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    ctx = ctx or current_context()
    jnp = _jnp()
    with jax.default_device(ctx.jax_device):
        d = jnp.full(shape, val, dtype=np_dtype(dtype or "float32"))
    if _dm_state["on"]:
        _dm.track(d, "full")
    return NDArray(d, ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype="float32"):
    return imperative_invoke("_arange", [],
                             {"start": start, "stop": stop, "step": step,
                              "repeat": repeat, "dtype": dtype})[0]


def concatenate(arrays, axis=0, always_copy=True):
    return imperative_invoke("Concat", list(arrays), {"dim": axis})[0]


def stack_arrays(arrays, axis=0):
    return imperative_invoke("stack", list(arrays), {"axis": axis})[0]


def moveaxis(tensor, source, destination):
    jnp = _jnp()
    return NDArray(jnp.moveaxis(tensor._data, source, destination), tensor._ctx)


def maximum(lhs, rhs):
    """Elementwise broadcast max of arrays/scalars (reference:
    python/mxnet/ndarray/ndarray.py:3008 maximum)."""
    return _scalar_or_broadcast(lhs, rhs, "broadcast_maximum",
                                "_maximum_scalar", max)


def minimum(lhs, rhs):
    """reference: ndarray.py:3065 minimum."""
    return _scalar_or_broadcast(lhs, rhs, "broadcast_minimum",
                                "_minimum_scalar", min)


def _scalar_or_broadcast(lhs, rhs, array_op, scalar_op, py_fn):
    if isinstance(lhs, NDArray) and isinstance(rhs, NDArray):
        return imperative_invoke(array_op, [lhs, rhs], {})[0]
    if isinstance(lhs, NDArray):
        return imperative_invoke(scalar_op, [lhs],
                                 {"scalar": float(rhs)})[0]
    if isinstance(rhs, NDArray):
        return imperative_invoke(scalar_op, [rhs],
                                 {"scalar": float(lhs)})[0]
    return py_fn(lhs, rhs)


def waitall():
    """Block until all async computation completes
    (reference: MXNDArrayWaitAll)."""
    import jax

    try:
        jax.effects_barrier()
    except Exception:
        pass


# ----------------------------------------------------------------- save/load

_MAGIC = b"MXTPU001"


def save(fname, data):
    """Serialize NDArrays (reference: src/ndarray/ndarray.cc Save/Load,
    mx.nd.save — dict or list of arrays).  Format: npz under the hood."""
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, dict):
        arrays = {k: v.asnumpy() for k, v in data.items()}
        _np.savez(_ensure_ext(fname), __format__="dict", **arrays)
    elif isinstance(data, (list, tuple)):
        arrays = {"arr_%d" % i: v.asnumpy() for i, v in enumerate(data)}
        _np.savez(_ensure_ext(fname), __format__="list", **arrays)
    else:
        raise TypeError("save expects NDArray, list or dict")
    import os

    if os.path.exists(fname + ".npz") and not fname.endswith(".npz"):
        os.replace(fname + ".npz", fname)


def _ensure_ext(fname):
    return fname


def load_frombuffer(buf, ctx=None):
    """Deserialize an in-memory `save` blob (reference:
    MXNDArrayLoadFromBuffer, python/mxnet/ndarray/utils.py:185)."""
    import io

    return _load_npz(_np.load(io.BytesIO(bytes(buf)), allow_pickle=False),
                     ctx)


def load(fname, ctx=None):
    return _load_npz(_np.load(fname, allow_pickle=False), ctx)


def _parse_npz(data):
    """Shared save-blob format parser → numpy ('list', [...]) or
    ('dict', {...}).  Used by load/load_frombuffer and
    predictor.load_ndarray_file."""
    try:
        fmt = str(data["__format__"])
    except KeyError:
        fmt = "dict"
    if fmt == "list":
        n = len([k for k in data.files if k.startswith("arr_")])
        return "list", [data["arr_%d" % i] for i in range(n)]
    return "dict", {k: data[k] for k in data.files if k != "__format__"}


def _load_npz(data, ctx):
    fmt, parsed = _parse_npz(data)
    if fmt == "list":
        return [array(v, ctx=ctx) for v in parsed]
    return {k: array(v, ctx=ctx) for k, v in parsed.items()}
