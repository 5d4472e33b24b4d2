"""Gluon Block / HybridBlock / SymbolBlock.

Reference: python/mxnet/gluon/block.py (Block:127, HybridBlock:671,
hybridize:504, _build_cache:748 -> CachedOp, export:868, SymbolBlock:952).

TPU-native design
-----------------
The reference's ``hybridize()`` traces ``hybrid_forward`` with Symbols
and builds a C++ CachedOp that caches fwd+bwd nnvm graphs per input
signature (src/imperative/cached_op.cc:266,842).  Here hybridize stages
the same ``hybrid_forward`` — run with real NDArrays whose buffers are
jax tracers — into ONE jitted XLA computation per input signature:

- signature key = input shapes/dtypes + train-mode flag (exactly the
  CachedOp SetForwardGraph signature match);
- parameters enter as traced arguments (so one executable serves every
  step — no retrace on update);
- randomness (Dropout) derives from a traced seed via random.TraceRNG,
  so compiled graphs get fresh keys without retracing;
- BatchNorm-style running-stat updates are collected as extra traced
  outputs (the `_StagingScope.aux_updates` channel) and written back
  eagerly — keeping the staged function pure for XLA;
- under ``autograd.record()``, backward is a second cached jitted
  function computing vjp-with-recompute (XLA remat of the forward),
  registered on the imperative tape like any other op.
"""

from __future__ import annotations

import re
import threading

import numpy as _np

from .. import autograd, initializer, ndarray
from .. import random as _random
from .. import xray as _xray
from ..base import MXNetError
from ..context import Context, cpu, current_context
from ..ndarray import NDArray
from .parameter import (DeferredInitializationError, Parameter, ParameterDict,
                        param_override)

__all__ = ["Block", "HybridBlock", "SymbolBlock", "is_staging",
           "staged_call", "recomputed"]


class _BlockScope:
    """Name scoping for Blocks (reference: gluon/block.py _BlockScope)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                prefix = _name_unique(hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            current._counter[hint] = count + 1
            prefix = "%s%d_" % (hint, count)
        if params is None:
            parent = current._block._params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, *a):
        if self._block._empty_prefix:
            return
        _BlockScope._current.value = self._old_scope


_NAME_COUNTER = {}


def _name_unique(hint):
    count = _NAME_COUNTER.get(hint, 0)
    _NAME_COUNTER[hint] = count + 1
    return "%s%d" % (hint, count)


class Block:
    """Base class for all neural-network layers and models
    (reference: gluon/block.py:127)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _BlockScope(self)
        self._children = {}
        self._reg_params = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []

    def _alias(self):
        return self.__class__.__name__.lower()

    # ------------------------------------------------------------ attrs
    def __setattr__(self, name, value):
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and \
                    not isinstance(value, type(existing)) and \
                    not isinstance(existing, type(value)):
                raise TypeError("Changing attribute type for %s from %s to %s "
                                "is not allowed." % (name, type(existing), type(value)))
        if isinstance(value, Block):
            self._children[name] = value
        elif isinstance(value, Parameter):
            self._reg_params[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return hook

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return hook

    # ------------------------------------------------------------ info
    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        return self._params

    def __repr__(self):
        s = "{name}(\n{body}\n)" if self._children else "{name}()"
        body = "\n".join("  (%s): %s" % (k, _indent(repr(v)))
                         for k, v in self._children.items())
        return s.format(name=self.__class__.__name__, body=body)

    def collect_aux_losses(self):
        """Sum the ``aux_loss`` of every descendant block that exposes
        one (MoE load-balancing losses today; any block may publish an
        ``aux_loss`` property holding its most recent forward's
        auxiliary loss).

        Call after the forward, inside the same autograd/staging scope
        — or let ``GluonTrainStep(aux_loss_weight=w)`` do both the
        collection and the weighting for you.  Raises if no descendant
        publishes an aux loss (a silent 0.0 would hide a wiring bug).
        """
        total = None
        stack = [self]
        seen = set()  # a shared block reachable twice contributes once
        while stack:
            b = stack.pop()
            if id(b) in seen:
                continue
            seen.add(id(b))
            aux = getattr(type(b), "aux_loss", None)
            if aux is not None:
                val = b.aux_loss
                total = val if total is None else total + val
            stack.extend(b._children.values())
        if total is None:
            raise ValueError(
                "no descendant of %r publishes an aux_loss" % (self,))
        return total

    # ------------------------------------------------------------ params
    def collect_params(self, select=None):
        """All Parameters of this block and its descendants, optionally
        filtered by a regex over names (reference: Block.collect_params)."""
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({k: v for k, v in self.params.items()
                        if pattern.match(k)})
        for child in self._children.values():
            ret.update(child.collect_params(select))
        return ret

    def _collect_params_with_prefix(self, prefix=""):
        """Structural (attribute-path) parameter names, used by
        save_parameters/load_parameters (reference: block.py)."""
        if prefix:
            prefix += "."
        ret = {prefix + k: v for k, v in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        from .. import initializer as _init

        self.collect_params().initialize(init or _init.Uniform(), ctx,
                                         verbose, force_reinit)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for p in self._reg_params.values():
            p.cast(dtype)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # ------------------------------------------------------------ save/load
    def save_parameters(self, filename):
        """Save parameters by structural name (reference:
        block.py save_parameters) — atomically via
        ``checkpoint.atomic_write`` so a crash mid-save can never leave
        a torn params file under the final name."""
        from ..checkpoint import atomic_write

        params = self._collect_params_with_prefix()
        arg_dict = {k: v.data().as_in_context(cpu()) for k, v in params.items()}
        with atomic_write(filename) as tmp:
            ndarray.save(tmp, arg_dict)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False):
        loaded = ndarray.load(filename)
        params = self._collect_params_with_prefix()
        if not loaded and not params:
            return
        # legacy files saved with full prefixed names
        if loaded and not any("." in k for k in loaded.keys()) and \
                any("." in k for k in params.keys()):
            loaded = {k.replace(self.prefix, "", 1) if k.startswith(self.prefix)
                      else k: v for k, v in loaded.items()}
        if not allow_missing:
            for name in params:
                if name not in loaded:
                    raise IOError("Parameter %s is missing in file %s"
                                  % (name, filename))
        for name, value in loaded.items():
            if name not in params:
                if not ignore_extra:
                    raise IOError("Parameter %s in file %s is not present in "
                                  "this Block" % (name, filename))
                continue
            p = params[name]
            if p._data is None:
                p.shape = tuple(value.shape)
                if p._deferred_init:
                    p._finish_deferred_init(value.shape)
                else:
                    p.initialize(ctx=p._ctx_list or ctx or [current_context()])
            if cast_dtype:
                p.cast(value.dtype)
            p.set_data(value)

    save_params = save_parameters
    load_params = load_parameters

    # ------------------------------------------------------------ run
    def __call__(self, *args):
        # fused-step x-ray: inside a staging trace, each block's forward
        # runs under a named scope so the compiled program's HLO carries
        # the block path in op_name metadata (xray.analyze attributes
        # per-instruction cost back to it).  Off OR eager = one dict
        # read + the is_staging check — nothing on the eager hot path.
        if _xray._state["on"] and is_staging():
            with _xray.block_scope(self):
                return self._hooked_forward(args)
        return self._hooked_forward(args)

    def _hooked_forward(self, args):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self.forward(*args)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def hybridize(self, active=True, **kwargs):
        """no-op on plain Blocks; recurses so nested HybridBlocks engage."""
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def summary(self, *inputs):
        """Print a per-layer summary (reference: block.py summary)."""
        rows = []

        def make_hook(name):
            def hook(block, ins, outs):
                out = outs[0] if isinstance(outs, (list, tuple)) else outs
                n_params = sum(_np.prod(p.shape)
                               for p in block._reg_params.values()
                               if p.shape is not None)
                rows.append((name, type(block).__name__,
                             tuple(getattr(out, "shape", ())), int(n_params)))
            return hook

        handles = []
        def attach(block, path):
            h = block.register_forward_hook(make_hook(path))
            handles.append((block, h))
            for k, c in block._children.items():
                attach(c, path + "." + k if path else k)
        attach(self, "")
        try:
            self(*inputs)
        finally:
            for b, h in handles:
                b._forward_hooks.remove(h)
        print("%-30s %-20s %-20s %s" % ("Layer", "Type", "Output", "Params"))
        total = 0
        for name, typ, shape, n in rows:
            total += n
            print("%-30s %-20s %-20s %d" % (name or "(self)", typ, shape, n))
        print("Total params: %d" % total)


def _indent(s):
    return s.replace("\n", "\n  ")


# ------------------------------------------------------------------ staging


class _StagingScope:
    """Active while a HybridBlock subtree is being traced into one XLA
    computation.  Collects aux-state updates (BatchNorm running stats) as
    traced outputs — the functional analog of the reference executor
    mutating aux NDArrays in place."""

    _current = threading.local()

    def __init__(self):
        self.aux_updates = {}   # Parameter -> traced jax value (insertion-ordered)

    def __enter__(self):
        stack = getattr(_StagingScope._current, "stack", None)
        if stack is None:
            stack = _StagingScope._current.stack = []
        stack.append(self)
        return self

    def __exit__(self, *a):
        _StagingScope._current.stack.pop()

    @classmethod
    def current(cls):
        stack = getattr(cls._current, "stack", None)
        return stack[-1] if stack else None


def is_staging():
    """True while a HybridBlock subtree is being traced into one XLA
    computation — hook code that must not leak tracers (monitors,
    health observers) checks this (or buffer concreteness) before
    queueing values across the trace boundary."""
    return _StagingScope.current() is not None


def staged_call(block, override, seed, args, train=True):
    """Run ``block(*args)`` under a fresh staging scope with parameter
    overrides and a traced RNG: the one idiom every whole-graph tracer
    shares (``parallel/gluon_step.py``'s SPMD step builder and
    ``compiled_step.py``'s whole-step program).

    ``block`` is any callable over NDArrays (a Block, or a closure
    composing forward + loss); ``override`` maps Parameter -> NDArray
    (typically tracer-backed); ``seed`` is a traced PRNG key (or None
    to keep the ambient RNG); ``args`` are NDArray inputs.  Returns
    ``(out, scope)`` where ``scope.aux_updates`` holds the traced
    auxiliary-state updates (BatchNorm running stats) collected during
    the call."""
    from .. import random as _rand

    scope = _StagingScope()
    mode = autograd.train_mode() if train else autograd.predict_mode()
    with param_override(override), scope, \
            (_rand.TraceRNG(seed) if seed is not None else _nullctx()), \
            mode:
        out = block(*args)
    return out, scope


def recomputed(fn, x):
    """``fn(x)`` (NDArray -> NDArray, or a tuple of them: a block's stream
    and what it hands on beside it) with its forward recomputed in the
    backward pass (``jax.checkpoint``) while a step is being staged; plain
    ``fn(x)`` otherwise.  Of the activations inside, only the flash
    attention kernel's results are kept (``ops/attention.py`` names them:
    a small part of what a block holds, and the kernel then runs once).
    Every result leaves the recomputed region as a value of the program,
    with its gradient.  The auxiliary-state updates made inside leave it as
    results too and are posted to the staging scope outside it."""
    import jax

    from ..ops.attention import FLASH_RESIDUALS

    outer = _StagingScope.current()
    if outer is None:
        return fn(x)
    keys = []

    def is_array(a):
        return isinstance(a, NDArray)

    def pure(value):
        with _StagingScope() as inner:
            out = fn(NDArray(value))
        keys[:] = list(inner.aux_updates)
        return (jax.tree.map(lambda a: a._data, out, is_leaf=is_array),
                tuple(inner.aux_updates[k] for k in keys))

    policy = jax.checkpoint_policies.save_only_these_names(*FLASH_RESIDUALS)
    out, updates = jax.checkpoint(pure, policy=policy)(x._data)
    outer.aux_updates.update(zip(keys, updates))
    return jax.tree.map(NDArray, out)


def update_aux_state(param, new_value):
    """Write an auxiliary state (running stat): eager write normally,
    traced side-output inside a staged graph."""
    scope = _StagingScope.current()
    if scope is not None:
        scope.aux_updates[param] = (
            new_value._data if isinstance(new_value, NDArray) else new_value)
        return
    with autograd.pause():
        data = param.data()
        data._assign(new_value._data if isinstance(new_value, NDArray)
                     else new_value)


class _CachedGraph:
    """One staged (forward, backward) pair for a fixed input signature —
    the analog of CachedOp's per-signature graph cache
    (src/imperative/cached_op.cc:266)."""

    def __init__(self, block, params, template_args, is_train):
        import jax

        self.params = params            # list[Parameter], traced order
        self.aux_order = []             # list[Parameter] discovered at trace
        self.out_treedef = None
        block_ref = block

        def core(pvals, avals, seed):
            nds = [NDArray(a) for a in avals]
            override = {p: NDArray(v) for p, v in zip(params, pvals)}
            scope = _StagingScope()
            with param_override(override), scope, \
                    _random.TraceRNG(seed) if seed is not None else _nullctx():
                out = block_ref._plain_forward(*nds)
            outs = _flatten_outputs(out)
            self.out_treedef = _treedef_of(out)
            self.aux_order = list(scope.aux_updates.keys())
            aux_vals = [scope.aux_updates[p] for p in self.aux_order]
            return tuple(o._data for o in outs), tuple(aux_vals)

        self._core = core
        self._fwd = jax.jit(core)

        def bwd(pvals, avals, seed, cts):
            # vjp-with-recompute: XLA sees fwd+bwd in one module and CSEs /
            # remats (reference analog: CachedOp::SetBackwardGraph caches
            # the grad graph; mirror policy graph_executor.cc:261).
            # The recompute must re-trace under the FORWARD's train mode:
            # this jit is first traced inside backward(), outside the
            # record() scope, and without the pin BatchNorm/Dropout would
            # take their inference branches — differentiating a different
            # function than the one that produced the outputs (grads
            # through running stats instead of batch stats, dropout
            # masks dropped from the backward).
            mode = autograd.train_mode() if is_train \
                else autograd.predict_mode()
            with mode:
                _outs, vjp = jax.vjp(
                    lambda p, a: core(p, a, seed)[0], pvals, avals)
            return vjp(cts)

        self._bwd = jax.jit(bwd)
        self.is_train = is_train

    def __call__(self, block, args):
        import jax

        pvals = tuple(p.data(args[0].context if args else None)._data
                      for p in self.params)
        avals = tuple(a._data for a in args)
        seed = _random.next_key()

        recording = autograd.is_recording() and (
            _np.any([p.grad_req != "null" for p in self.params]) or
            autograd._any_recorded(args))
        outs, aux_vals = self._fwd(pvals, avals, seed)

        for p, v in zip(self.aux_order, aux_vals):
            with autograd.pause():
                p.data()._assign(v)

        ctx = args[0]._ctx if args else None
        out_nds = [NDArray(o, ctx) for o in outs]
        # numerics-health note: steady-state hybridized forward never
        # re-enters child __call__ (the whole subtree is one cached
        # executable), so per-child forward hooks can't observe — but
        # the ROOT block's forward hooks fire in Block.__call__ with
        # these concrete outputs, so an installed HealthMonitor still
        # covers the staged graph's outputs (and skips the tracer
        # values seen during the staging trace itself).

        if recording:
            param_nds = [p.data(args[0].context if args else None)
                         for p in self.params]
            bwd_jit = self._bwd

            def vjp_fn(cts):
                cts = cts if isinstance(cts, tuple) else (cts,)
                gp, ga = bwd_jit(pvals, avals, seed, tuple(cts))
                return tuple(gp) + tuple(ga)

            autograd.record_op(list(param_nds) + list(args), out_nds, vjp_fn)

        return _unflatten_outputs(out_nds, self.out_treedef)


class _nullctx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def _flatten_outputs(out):
    if isinstance(out, NDArray):
        return [out]
    if isinstance(out, (list, tuple)):
        flat = []
        for o in out:
            flat.extend(_flatten_outputs(o))
        return flat
    raise TypeError("HybridBlock output must be NDArray or (nested) list, got %s"
                    % type(out))


def _treedef_of(out):
    if isinstance(out, NDArray):
        return None
    return [_treedef_of(o) for o in out]


def _unflatten_outputs(flat, treedef):
    it = iter(flat)

    def build(td):
        if td is None:
            return next(it)
        return [build(t) for t in td]

    return build(treedef)


class HybridBlock(Block):
    """A Block that can be staged into one compiled XLA graph
    (reference: gluon/block.py:671)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_graphs = {}
        self._flags = {}

    def hybridize(self, active=True, **kwargs):
        self._active = active
        self._flags = kwargs
        self._cached_graphs = {}
        super().hybridize(active, **kwargs)

    def cast(self, dtype):
        self._cached_graphs = {}
        super().cast(dtype)

    def infer_shape(self, *args):
        """Deferred-shape resolution hook; leaf layers override."""
        raise NotImplementedError(
            "%s has deferred-initialized parameters whose shape could not "
            "be inferred; implement infer_shape() or initialize with full "
            "shapes." % type(self).__name__)

    # ------------------------------------------------------------ forward
    def forward(self, x, *args):
        from .. import symbol as _sym

        if isinstance(x, _sym.Symbol):
            params = {k: p.var() for k, p in self._reg_params.items()}
            with _name_prefix_scope(self._prefix):
                return self.hybrid_forward(_sym, x, *args, **params)
        if not isinstance(x, NDArray):
            raise TypeError("HybridBlock input must be NDArray or Symbol, got %s"
                            % type(x))
        if self._active and _StagingScope.current() is None:
            return self._call_cached(x, *args)
        return self._plain_forward(x, *args)

    def _plain_forward(self, x, *args):
        ctx = x.context
        try:
            params = {k: p.data(ctx) for k, p in self._reg_params.items()}
        except DeferredInitializationError:
            self._deferred_init_params(x, *args)
            params = {k: p.data(ctx) for k, p in self._reg_params.items()}
        return self.hybrid_forward(ndarray, x, *args, **params)

    def _deferred_init_params(self, *args):
        self.infer_shape(*args)
        for p in self._reg_params.values():
            if p._deferred_init:
                p._finish_deferred_init(p.shape)

    def _call_cached(self, *args):
        # warm any deferred params across the subtree with one eager pass
        key = (tuple((a.shape, str(a.dtype)) for a in args),
               autograd.is_training())
        graph = self._cached_graphs.get(key)
        if graph is None:
            try:
                params = list(self.collect_params().values())
                for p in params:
                    p._check_initialized()
            except DeferredInitializationError:
                with autograd.pause():
                    self._plain_forward(*args)
                params = list(self.collect_params().values())
            graph = _CachedGraph(self, params, args, autograd.is_training())
            self._cached_graphs[key] = graph
        return graph(self, args)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # ------------------------------------------------------------ export
    def export(self, path, epoch=0):
        """Export to symbol JSON + params, loadable by SymbolBlock /
        Module (reference: HybridBlock.export block.py:868)."""
        from .. import symbol as _sym

        inp = _sym.Variable("data")
        out = self(inp)
        if isinstance(out, (list, tuple)):
            out = _sym.Group(list(out))
        out.save("%s-symbol.json" % path)
        aux_names = set(out.list_auxiliary_states())
        arg_dict = {}
        for name, param in self.collect_params().items():
            kind = "aux" if name in aux_names else "arg"
            arg_dict["%s:%s" % (kind, name)] = param.data().as_in_context(cpu())
        ndarray.save("%s-%04d.params" % (path, epoch), arg_dict)
        return out


class _name_prefix_scope:
    """Route auto-generated symbol node names under the block prefix."""

    def __init__(self, prefix):
        from ..base import NameManager
        self._prefix = prefix
        self._nm = NameManager

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


class SymbolBlock(HybridBlock):
    """Wrap a Symbol graph as a Block for imperative use
    (reference: gluon/block.py SymbolBlock:952)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=None)
        from .. import symbol as _sym

        if isinstance(outputs, (list, tuple)):
            outputs = _sym.Group(list(outputs))
        if isinstance(inputs, _sym.Symbol):
            inputs = [inputs]
        self._symbol = outputs
        self._input_names = [i.name for i in inputs]
        arg_names = [n for n in outputs.list_arguments()
                     if n not in self._input_names]
        aux_names = set(outputs.list_auxiliary_states())
        for name in arg_names:
            p = Parameter(name, allow_deferred_init=True)
            self._params._params[name] = p
        for name in outputs.list_auxiliary_states():
            p = Parameter(name, grad_req="null", allow_deferred_init=True)
            self._params._params[name] = p
        if params is not None:
            for name, v in params.items():
                clean = name
                if name.startswith(("arg:", "aux:")):
                    clean = name[4:]
                if clean in self._params._params:
                    p = self._params._params[clean]
                    p.shape = tuple(v.shape)
                    p.dtype = v.dtype
                    # values are set right below — zero-init avoids the
                    # name-pattern initializer (e.g. *_quantize params)
                    p.initialize(init=initializer.Zero(), ctx=v.context)
                    p.set_data(v)
        self._fn_cache = {}

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        """Load an exported model (reference: SymbolBlock.imports)."""
        from .. import symbol as _sym

        sym = _sym.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [_sym.Variable(n) for n in input_names]
        params = ndarray.load(param_file) if param_file else None
        if params is not None and ctx is not None:
            params = {k: v.as_in_context(ctx) for k, v in params.items()}
        return SymbolBlock(sym, inputs, params=params)

    def forward(self, *args):
        import jax

        from ..executor import make_eval_fn

        is_train = autograd.is_training()
        entry = self._fn_cache.get(is_train)
        if entry is None:
            fn, meta = make_eval_fn(self._symbol, is_train)
            entry = (jax.jit(fn), meta)
            self._fn_cache[is_train] = entry
        fn, meta = entry
        input_map = dict(zip(self._input_names, args))
        arg_vals = []
        for name in meta["arg_names"]:
            if name in input_map:
                arg_vals.append(input_map[name]._data)
            else:
                arg_vals.append(self._params[name].data().data_jax)
        aux_vals = [self._params[n].data().data_jax for n in meta["aux_names"]]
        seed = _np.random.randint(0, 2**31 - 1)
        outs, new_aux = fn(arg_vals, aux_vals, seed)
        ctx = args[0]._ctx if args else None
        out_nds = [NDArray(o, ctx) for o in outs]
        for name, v in zip(meta["aux_names"], new_aux):
            with autograd.pause():
                self._params[name].data()._assign(v)
        return out_nds if len(out_nds) > 1 else out_nds[0]
