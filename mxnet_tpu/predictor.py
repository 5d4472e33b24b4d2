"""Lightweight prediction-only API (deployment surface).

Reference: `include/mxnet/c_predict_api.h` (MXPredCreate/SetInput/Forward/
GetOutput/Reshape) and its Python wrapper `amalgamation/python/
mxnet_predict.py` (class Predictor, load_ndarray_file), exercised by
`tests/python/unittest/test_predictor.py`.

TPU-native form: the predictor binds an exported Symbol (JSON) plus its
saved parameters and stages the forward pass through the normal XLA jit
path — there is no separate stripped-down inference engine to maintain,
XLA *is* the deployment runtime.  The same surface is exported over the
C ABI for non-Python consumers in `native/src/predict.cc`
(MXTPUPred* — see cpp-package/ for the C++ RAII wrapper).
"""

from __future__ import annotations

import io
import time

import numpy as np

from . import histogram as _histogram
from . import profiler as _profiler
from . import runtime_stats as _rts

__all__ = ["Predictor", "load_ndarray_file"]


def load_ndarray_file(nd_bytes):
    """Deserialize an `mx.nd.save` blob (bytes) to numpy arrays.

    Returns a dict (name → array) when the blob was saved from a dict,
    else a list.  Reference: MXNDListCreate in c_predict_api.h /
    load_ndarray_file in amalgamation/python/mxnet_predict.py.
    """
    from .ndarray.ndarray import _parse_npz

    data = np.load(io.BytesIO(bytes(nd_bytes)), allow_pickle=False)
    _fmt, parsed = _parse_npz(data)
    return parsed


class Predictor:
    """Runs forward passes over an exported model.

    Parameters
    ----------
    symbol_json_str : str
        Contents of the ``*-symbol.json`` file (NOT a path).
    param_raw_bytes : bytes
        Contents of the ``*.params`` file ("arg:name"/"aux:name" keys).
    input_shapes : dict of str to tuple
        Shapes of the input variables.
    dev_type : str, optional
        "cpu" or "tpu" ("gpu" accepted as an alias of "tpu").  Default:
        ``current_context()`` — the accelerator when the process has
        one, like ``Parameter.initialize`` and ``nd.array``.
    dev_id : int, optional
    type_dict : dict of str to dtype, optional
        Input dtypes (default float32).
    """

    def __init__(self, symbol_json_str, param_raw_bytes, input_shapes,
                 dev_type=None, dev_id=0, type_dict=None):
        from . import context as _context
        from . import ndarray as _nd
        from . import symbol as _symbol

        self._symbol = _symbol.load_json(symbol_json_str)
        self._symbol_json = symbol_json_str
        self._type_dict = dict(type_dict or {})
        if dev_type is None:
            self._ctx = _context.current_context()
        elif dev_type in ("tpu", "gpu"):
            self._ctx = _context.tpu(dev_id)
        else:
            self._ctx = _context.cpu(dev_id)

        params = load_ndarray_file(param_raw_bytes)
        if not isinstance(params, dict):
            raise ValueError("params blob must be a dict of arg:/aux: keys")
        # parsed once; reshape() rebinds from these device arrays without
        # touching the serialized blob again (reference: MXPredReshape
        # shares weights with the source predictor)
        self._arg_params = {k[4:]: _nd.array(v, ctx=self._ctx, dtype=v.dtype)
                            for k, v in params.items()
                            if k.startswith("arg:")}
        self._aux_params = {k[4:]: _nd.array(v, ctx=self._ctx, dtype=v.dtype)
                            for k, v in params.items()
                            if k.startswith("aux:")}
        self._bind(input_shapes)

    def _bind(self, input_shapes):
        if not isinstance(input_shapes, dict):
            raise ValueError("Expect input_shapes to be dict str->tuple")
        for v in input_shapes.values():
            if not isinstance(v, tuple):
                raise ValueError("Expect input_shapes to be dict str->tuple")
        arg_names = set(self._symbol.list_arguments())
        unknown = set(input_shapes) - arg_names
        if unknown:
            raise ValueError("input_shapes names %s not in symbol arguments"
                             % sorted(unknown))
        self._input_names = sorted(input_shapes)
        self._exec = self._symbol.simple_bind(
            ctx=self._ctx, grad_req="null", type_dict=self._type_dict,
            **input_shapes)
        self._exec.copy_params_from(self._arg_params, self._aux_params,
                                    allow_extra_params=True)
        # output shapes are fixed by the bound input shapes; computed once
        # (get_output_shape sits on the C ABI per-inference path)
        _, out_shapes, _ = self._symbol.infer_shape(**input_shapes)
        self._out_shapes = [tuple(s) for s in out_shapes]
        self._inputs = {}
        self._outputs = None

    # ------------------------------------------------------------ running
    def forward(self, **kwargs):
        """Run forward with named inputs (numpy arrays); then
        ``get_output(i)``.

        Telemetry seam (the ``Trainer.step`` convention): the forward
        rides a ``predictor:forward`` profiler span, lands in the
        ``predictor:forward`` latency histogram (guard-first — one dict
        read when collection is off), and bumps the always-on
        ``predictor_forwards`` counter, so legacy predictor and serving
        runs show up in diag dumps / ``--compare`` like training
        steps do.  The executor underneath feeds the ``forward``
        stepstats phase as usual."""
        hist_on = _histogram._state["on"]
        if hist_on:
            t0 = time.perf_counter()
        with _profiler.span("predictor:forward", "predictor"):
            self._forward_impl(**kwargs)
        _rts.inc("predictor_forwards")
        if hist_on:
            _histogram.observe("predictor:forward",
                               time.perf_counter() - t0)
        return self

    def _forward_impl(self, **kwargs):
        for k, v in kwargs.items():
            if not isinstance(v, np.ndarray):
                raise ValueError("Expect numpy ndarray as input")
            if k not in self._input_names:
                raise ValueError("unknown input '%s' (expected %s)"
                                 % (k, self._input_names))
            dt = np.dtype(self._type_dict.get(k, np.float32))
            expect = tuple(self._exec.arg_dict[k].shape)
            v = np.asarray(v, dtype=dt, order="C")
            if tuple(v.shape) != expect:
                raise ValueError("input '%s' shape %s != bound shape %s "
                                 "(use reshape())" % (k, v.shape, expect))
            self._inputs[k] = v
        self._outputs = self._exec.forward(is_train=False, **self._inputs)

    def get_output(self, index):
        """The index-th output as a numpy array."""
        if self._outputs is None:
            raise RuntimeError("call forward() before get_output()")
        return self._outputs[index].asnumpy()

    @property
    def num_outputs(self):
        return len(self._symbol)

    def get_output_shape(self, index):
        return self._out_shapes[index]

    def get_input_names(self):
        return list(self._input_names)

    # ------------------------------------------------------------ reshape
    def reshape(self, input_shapes):
        """Rebind with new input shapes, sharing the already-loaded
        weights (reference: MXPredReshape; here the jit cache keys on the
        new signature)."""
        self._bind(input_shapes)
        return self

    def _reshape_clone(self, input_shapes):
        """New predictor over the same weight arrays (the C ABI's
        MXTPUPredReshape returns a fresh handle)."""
        new = Predictor.__new__(Predictor)
        new._symbol = self._symbol
        new._symbol_json = self._symbol_json
        new._type_dict = dict(self._type_dict)
        new._ctx = self._ctx
        new._arg_params = self._arg_params
        new._aux_params = self._aux_params
        new._bind(input_shapes)
        return new
