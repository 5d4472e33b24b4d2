"""Static donation-safety analysis — the ``donation-safety`` rule.

The compiled-step/parallel paths jit with ``donate_argnums``: XLA may
reuse the donated input buffers for outputs, so after the call the
donated arrays are INVALID.  Correctness therefore rests on two
disciplines this pass proves instead of remembers:

1. **Rebind-after-call.**  Every direct call of a donating jitted
   callable must consume its result and rebind the donated inputs —
   either functionally (the call is a ``return`` expression: ownership
   transfers to the caller) or imperatively (the donated ``self.x`` /
   local appears as an assignment target of the call's own statement,
   or is rebound later in the function).  Flagged: a discarded result
   (``jitted(a, b)`` as a bare statement), a donated local read after
   the call without rebinding, a donated ``self.x`` never rebound.
   Metadata reads (``.shape``/``.dtype``/``.ndim``/``.size``/``.aval``)
   are exempt — donation invalidates the buffer, not the aval.

2. **Pin-before-capture.**  In modules that interact with donation
   (they call ``donation_active()`` or contain a donating jit site), a
   by-reference capture of an NDArray's ``_data`` that ESCAPES the
   function (stored into ``self``/a global, or passed into a method
   that stores it) must be guarded by the materialization seam: a call
   consuming the captured value under an ``if`` whose condition is
   (derived from) ``donation_active()`` — the PR 11
   donation-vs-async-checkpoint race class.

Donating callables are tracked through the bindings the runtime
actually uses: ``self._step = jax.jit(..., donate_argnums=...)``,
``fn = jax.jit(...)`` locals (including enclosing-function closures),
and one-hop factories (``return jax.jit(...)`` → ``self._step =
self._jit()``).  ``donate_argnums`` values resolve through
literal tuples/ints and single-assignment locals of literal
conditionals (``donate = (0, 1) if donate_params else ()``).  Call
sites with ``*args`` are conservatively skipped — the argument mapping
is not statically provable (compiled_step's ``entry.fn(*args)``).

Suppression: ``# mxlint: disable=donation-safety`` on the finding's
line."""

from __future__ import annotations

import ast

from .checkers import _Loc
from .callgraph import _module_name, resolve_callable

__all__ = ["check_donation", "find_donation_sites", "RULE"]

RULE = "donation-safety"

# aval metadata stays valid after donation (only the buffer dies)
_METADATA_ATTRS = frozenset({"shape", "dtype", "ndim", "size", "aval",
                             "sharding"})
_SINK_MUTATORS = frozenset({"append", "add", "put", "update", "insert",
                            "setdefault"})


def _literal_argnums(node):
    """(0, 1, 2) / 0 / () -> frozenset of ints, else None."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return frozenset({node.value})
    if isinstance(node, (ast.Tuple, ast.List)):
        out = set()
        for e in node.elts:
            if not (isinstance(e, ast.Constant)
                    and isinstance(e.value, int)):
                return None
            out.add(e.value)
        return frozenset(out)
    return None


def _resolve_argnums(value, fn_node):
    """donate_argnums expression -> frozenset of possible argnums, or
    None (unresolvable).  Resolves literals, IfExp of literals, and a
    single same-scope ``name = <literal-or-ifexp>`` assignment."""
    lit = _literal_argnums(value)
    if lit is not None:
        return lit
    if isinstance(value, ast.IfExp):
        a = _resolve_argnums(value.body, fn_node)
        b = _resolve_argnums(value.orelse, fn_node)
        if a is not None and b is not None:
            return a | b
        return None
    if isinstance(value, ast.Name) and fn_node is not None:
        assigns = [n for n in ast.walk(fn_node)
                   if isinstance(n, ast.Assign)
                   and len(n.targets) == 1
                   and isinstance(n.targets[0], ast.Name)
                   and n.targets[0].id == value.id]
        if len(assigns) == 1:
            return _resolve_argnums(assigns[0].value, None)
    return None


class _Site:
    """One ``jax.jit(..., donate_argnums=<non-empty>)`` call."""

    __slots__ = ("ctx", "call", "argnums", "fn")

    def __init__(self, ctx, call, argnums, fn):
        self.ctx = ctx
        self.call = call
        self.argnums = argnums  # frozenset of ints, or None (unknown)
        self.fn = fn            # enclosing FnNode (None: module level)


def _enclosing_fn_map(graph, ctx, module):
    """{id(ast node): innermost enclosing FnNode or None}."""
    by_ast = {id(fn.ast_node): fn
              for fn in graph.by_module.get(module, {}).values()
              if fn.path == ctx.path}
    out = {}

    def rec(node, owner):
        for child in ast.iter_child_nodes(node):
            fn = by_ast.get(id(child))
            out[id(child)] = fn if fn is not None else owner
            rec(child, fn if fn is not None else owner)

    rec(ctx.tree, None)
    return out


def find_donation_sites(contexts, graph=None):
    """Every donating-jit call site: [(path, lineno, argnums)].
    Argnums=() sites (donation disabled) are excluded; non-literal but
    resolvable conditionals count with their union."""
    sites = []
    for ctx in contexts:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if not ctx.aliases.is_jax_jit(node.func):
                continue
            kw = {k.arg: k.value for k in node.keywords if k.arg}
            if "donate_argnums" not in kw:
                continue
            enclosing = None
            for anc in ast.walk(ctx.tree):
                if isinstance(anc, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    if any(sub is node for sub in ast.walk(anc)):
                        enclosing = anc  # innermost wins: keep walking
            argnums = _resolve_argnums(kw["donate_argnums"], enclosing)
            if argnums == frozenset():
                continue  # provably donation-free
            sites.append((ctx.path, node.lineno, argnums))
    return sites


def check_donation(contexts, config, graph):
    """Run the donation-safety rule; appends findings to contexts."""
    if RULE not in config.rules:
        return
    for ctx in contexts:
        module = _module_name(ctx.path)
        if module not in graph.imports:
            continue
        fn_map = _enclosing_fn_map(graph, ctx, module)
        donating = _collect_donating_bindings(ctx, module, graph, fn_map)
        _check_call_sites(ctx, module, graph, fn_map, donating)
        if _module_touches_donation(ctx, donating):
            _check_unpinned_captures(ctx, module, graph)


# ------------------------------------------------- donating bindings


def _donate_kw(call, ctx, fn_node):
    """jax.jit call -> argnums frozenset / None-unknown, or False when
    not a donating jit call."""
    if not (isinstance(call, ast.Call)
            and ctx.aliases.is_jax_jit(call.func)):
        return False
    kw = {k.arg: k.value for k in call.keywords if k.arg}
    if "donate_argnums" not in kw:
        return False
    argnums = _resolve_argnums(kw["donate_argnums"], fn_node)
    if argnums == frozenset():
        return False
    return argnums if argnums is not None else None


def _collect_donating_bindings(ctx, module, graph, fn_map):
    """All names/attrs provably bound to donating jitted callables.

    Returns {"attr": {(cls, name): argnums},
             "local": {(fn qualname, name): argnums},
             "global": {name: argnums}}."""
    out = {"attr": {}, "local": {}, "global": {}}
    factories = {}  # FnNode key -> argnums (fn returns a donating jit)

    def ast_fn(fn):
        return fn.ast_node if fn is not None else None

    # pass 1: direct jit bindings + factory returns
    for node in ast.walk(ctx.tree):
        fn = fn_map.get(id(node))
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            argnums = _donate_kw(node.value, ctx, ast_fn(fn))
            if argnums is False or argnums is None:
                continue  # unresolvable argnums: not statically provable
            t = node.targets[0]
            if isinstance(t, ast.Attribute) \
                    and isinstance(t.value, ast.Name) \
                    and t.value.id == "self" and fn is not None \
                    and fn.cls is not None:
                out["attr"][(fn.cls, t.attr)] = argnums
            elif isinstance(t, ast.Name):
                if fn is None:
                    out["global"][t.id] = argnums
                else:
                    out["local"][(fn.qualname, t.id)] = argnums
        elif isinstance(node, ast.Return) and node.value is not None:
            argnums = _donate_kw(node.value, ctx, ast_fn(fn))
            if argnums is not False and argnums is not None \
                    and fn is not None:
                factories[fn.key] = argnums

    # pass 2: one-hop factory bindings (self._step = self._jit())
    if factories:
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.value, ast.Call)):
                continue
            fn = fn_map.get(id(node))
            target = resolve_callable(graph, module, fn,
                                      node.value.func, ctx.aliases)
            if not isinstance(target, tuple) or target not in factories:
                continue
            argnums = factories[target]
            t = node.targets[0]
            if isinstance(t, ast.Attribute) \
                    and isinstance(t.value, ast.Name) \
                    and t.value.id == "self" and fn is not None \
                    and fn.cls is not None:
                out["attr"][(fn.cls, t.attr)] = argnums
            elif isinstance(t, ast.Name) and fn is not None:
                out["local"][(fn.qualname, t.id)] = argnums
    return out


# --------------------------------------------- rebind-after-call rule


def _lookup_donating(call, fn, donating, mod_fns):
    """The donating argnums for this call's callee, or None."""
    fnx = call.func
    if isinstance(fnx, ast.Attribute) and isinstance(fnx.value, ast.Name) \
            and fnx.value.id == "self" and fn is not None \
            and fn.cls is not None:
        return donating["attr"].get((fn.cls, fnx.attr))
    if isinstance(fnx, ast.Name):
        cur = fn
        while cur is not None:
            hit = donating["local"].get((cur.qualname, fnx.id))
            if hit is not None:
                return hit
            cur = mod_fns.get(cur.parent) if cur.parent else None
        return donating["global"].get(fnx.id)
    return None


def _check_call_sites(ctx, module, graph, fn_map, donating):
    if not (donating["attr"] or donating["local"] or donating["global"]):
        return
    mod_fns = graph.by_module.get(module, {})
    for fn in mod_fns.values():
        if fn.path != ctx.path:
            continue
        fn_node = fn.ast_node
        if isinstance(fn_node, ast.Lambda):
            continue
        for call in ast.walk(fn_node):
            if not isinstance(call, ast.Call):
                continue
            if fn_map.get(id(call)) is not fn:
                continue
            argnums = _lookup_donating(call, fn, donating, mod_fns)
            if argnums is None:
                continue
            if any(isinstance(a, ast.Starred) for a in call.args):
                continue  # *args mapping not statically provable
            stmt = _innermost_stmt(fn_node, call)
            if stmt is None:
                continue
            if isinstance(stmt, ast.Return):
                continue  # functional transfer: caller owns the result
            if isinstance(stmt, ast.Expr):
                ctx.add(RULE, call,
                        "donating call discards its result — "
                        "donate_argnums invalidated the input buffers "
                        "but nothing rebinds them; assign the outputs "
                        "back (rebind-after-call) or drop donation",
                        fn.qualname)
                continue
            if not isinstance(stmt, ast.Assign):
                continue
            targets = _flat_targets(stmt)
            for i in sorted(argnums):
                if i >= len(call.args):
                    continue
                arg = call.args[i]
                if isinstance(arg, ast.Name):
                    _check_local_arg(ctx, fn, fn_node, call, stmt, arg,
                                     targets)
                elif isinstance(arg, ast.Attribute) \
                        and isinstance(arg.value, ast.Name) \
                        and arg.value.id == "self":
                    _check_attr_arg(ctx, fn, fn_node, call, stmt, arg,
                                    targets)


def _innermost_stmt(fn_node, call):
    hit = None
    for node in ast.walk(fn_node):
        if isinstance(node, ast.stmt) \
                and any(sub is call for sub in ast.walk(node)):
            hit = node
    return hit


def _flat_targets(stmt):
    """('name', n) / ('attr', obj, attr) ids the statement rebinds."""
    out = set()
    stack = list(stmt.targets)
    while stack:
        t = stack.pop()
        if isinstance(t, (ast.Tuple, ast.List)):
            stack.extend(t.elts)
        elif isinstance(t, ast.Starred):
            stack.append(t.value)
        elif isinstance(t, ast.Name):
            out.add(("name", t.id))
        elif isinstance(t, ast.Attribute) \
                and isinstance(t.value, ast.Name):
            out.add(("attr", t.value.id, t.attr))
    return out


def _check_local_arg(ctx, fn, fn_node, call, stmt, arg, targets):
    if ("name", arg.id) in targets:
        return  # rebound by this very statement
    # the rebind window: reads past the call but before any reassignment
    rebind = None
    for node in ast.walk(fn_node):
        if isinstance(node, ast.Assign) and node.lineno > stmt.lineno \
                and ("name", arg.id) in _flat_targets(node):
            if rebind is None or node.lineno < rebind:
                rebind = node.lineno
    parents = {id(c): p for p in ast.walk(fn_node)
               for c in ast.iter_child_nodes(p)}
    for node in ast.walk(fn_node):
        if not (isinstance(node, ast.Name) and node.id == arg.id
                and isinstance(node.ctx, ast.Load)
                and node.lineno > stmt.lineno
                and (rebind is None or node.lineno < rebind)):
            continue
        parent = parents.get(id(node))
        if isinstance(parent, ast.Attribute) \
                and parent.attr in _METADATA_ATTRS:
            continue  # aval metadata survives donation
        ctx.add(RULE, node,
                "donated argument %r is read after the donating call "
                "(line %d) — donation invalidated its buffer; rebind "
                "it from the call's outputs first" % (arg.id,
                                                      call.lineno),
                fn.qualname)
        return


def _check_attr_arg(ctx, fn, fn_node, call, stmt, arg, targets):
    if ("attr", "self", arg.attr) in targets:
        return
    # rebound anywhere later in the function?
    for node in ast.walk(fn_node):
        if isinstance(node, ast.Assign) and node.lineno >= stmt.lineno:
            for t in _flat_targets(node):
                if t == ("attr", "self", arg.attr):
                    return
    ctx.add(RULE, call,
            "donating call passes self.%s but never rebinds it — the "
            "donated buffer is invalid after the call; assign the "
            "matching output back to self.%s (rebind-after-call)"
            % (arg.attr, arg.attr), fn.qualname)


# --------------------------------------------- pin-before-capture rule


def _module_touches_donation(ctx, donating):
    if donating["attr"] or donating["local"] or donating["global"]:
        return True
    return "donation_active" in ctx.source


def _check_unpinned_captures(ctx, module, graph):
    """Flag `_data` captures that escape without the donation_active()
    materialization seam."""
    for fn in graph.by_module.get(module, {}).values():
        if fn.path != ctx.path or isinstance(fn.ast_node, ast.Lambda):
            continue
        _scan_captures(ctx, module, graph, fn)


def _contains_data_capture(node):
    return any(isinstance(sub, ast.Attribute) and sub.attr == "_data"
               and isinstance(sub.ctx, ast.Load)
               for sub in ast.walk(node))


def _contains_name(node, names):
    return any(isinstance(sub, ast.Name) and sub.id in names
               and isinstance(sub.ctx, ast.Load)
               for sub in ast.walk(node))


def _scan_captures(ctx, module, graph, fn):
    fn_node = fn.ast_node
    own = _own_stmts(fn_node)
    tainted = set()    # locals holding by-reference _data captures
    pin_names = set()  # locals derived from donation_active()
    sanitized = set()
    finding_site = {}  # name -> first capture node (anchor)

    def is_pin_test(test):
        for sub in ast.walk(test):
            if isinstance(sub, ast.Call):
                name = getattr(sub.func, "attr",
                               getattr(sub.func, "id", None))
                if name == "donation_active":
                    return True
            if isinstance(sub, ast.Name) and sub.id in pin_names:
                return True
        return False

    def scan_stmt(node, under_pin):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return
        if isinstance(node, ast.Assign):
            val = node.value
            taints = _contains_data_capture(val) \
                or _contains_name(val, tainted)
            for t in node.targets:
                if isinstance(t, ast.Name):
                    if taints:
                        tainted.add(t.id)
                        finding_site.setdefault(t.id, node)
                    else:
                        tainted.discard(t.id)
                        sanitized.discard(t.id)
                    for sub in ast.walk(val):
                        if isinstance(sub, ast.Call):
                            nm = getattr(sub.func, "attr",
                                         getattr(sub.func, "id", None))
                            if nm == "donation_active":
                                pin_names.add(t.id)
                elif isinstance(t, ast.Subscript):
                    base = t.value
                    if taints and isinstance(base, ast.Name):
                        tainted.add(base.id)
                        finding_site.setdefault(base.id, node)
                    if _is_escape_target(base) and taints \
                            and not under_pin:
                        _flag(node)
                if isinstance(t, ast.Attribute) and taints:
                    if _is_escape_target(t):
                        _flag(node)
        elif isinstance(node, ast.Expr) \
                and isinstance(node.value, ast.Call):
            call = node.value
            names = _call_tainted_args(call)
            if names:
                if under_pin:
                    sanitized.update(names)
                elif _is_storing_call(call, fn, names):
                    for n in sorted(names - sanitized):
                        _flag(node, via=n)
                        sanitized.add(n)  # one finding per value
        elif isinstance(node, ast.Return) and node.value is not None:
            names = set()
            if isinstance(node.value, ast.Call):
                names = _call_tainted_args(node.value)
                if names - sanitized and _is_storing_call(node.value,
                                                          fn, names):
                    for n in sorted(names - sanitized):
                        _flag(node, via=n)
                        sanitized.add(n)
        elif isinstance(node, ast.If):
            pin = is_pin_test(node.test)
            for stmt in node.body:
                scan_stmt(stmt, under_pin or pin)
            for stmt in node.orelse:
                scan_stmt(stmt, under_pin)
            return
        for child in _stmt_children(node):
            scan_stmt(child, under_pin)

    def _call_tainted_args(call):
        out = set()
        for a in list(call.args) + [k.value for k in call.keywords]:
            for sub in ast.walk(a):
                if isinstance(sub, ast.Name) and sub.id in tainted:
                    out.add(sub.id)
        return out

    def _is_escape_target(t):
        return (isinstance(t, ast.Attribute)
                and isinstance(t.value, ast.Name)
                and t.value.id == "self")

    def _is_storing_call(call, fn, tainted_names):
        """self.method(x) whose body stores the PARAM RECEIVING the
        tainted value into self state, or a mutator (.append/.put) on
        self state — the capture outlives this frame.  Only the params
        the tainted arguments map onto are considered: a callee storing
        some other argument does not leak the capture."""
        fnx = call.func
        if isinstance(fnx, ast.Attribute) \
                and fnx.attr in _SINK_MUTATORS:
            return True
        target = resolve_callable(graph, module, fn, fnx, ctx.aliases)
        if not isinstance(target, tuple):
            return False
        callee = graph.nodes.get(target)
        if callee is None or isinstance(callee.ast_node, ast.Lambda):
            return False
        params = [a.arg for a in callee.ast_node.args.args]
        if callee.cls:
            params = params[1:]
        # map tainted argument positions/keywords -> callee params
        hot = set()
        for i, a in enumerate(call.args):
            if isinstance(a, ast.Starred):
                if _contains_name(a, tainted_names):
                    hot.update(params)  # mapping unknown: all params
            elif _contains_name(a, tainted_names) and i < len(params):
                hot.add(params[i])
        for k in call.keywords:
            if _contains_name(k.value, tainted_names):
                if k.arg is None:
                    hot.update(params)
                elif k.arg in params:
                    hot.add(k.arg)
        if not hot:
            return False
        for node in ast.walk(callee.ast_node):
            if isinstance(node, ast.Assign):
                stores = _contains_name(node.value, hot)
                if stores and any(
                        isinstance(t, (ast.Attribute, ast.Subscript))
                        for t in node.targets):
                    return True
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _SINK_MUTATORS \
                    and _contains_name(node, hot):
                return True
        return False

    def _flag(node, via=None):
        anchor = finding_site.get(via, node) if via else node
        ctx.add(RULE, anchor,
                "by-reference `_data` capture escapes this call frame "
                "without the donation seam — a later donating step "
                "invalidates the captured buffer; materialize under "
                "`if donation_active():` (the pin=True contract) "
                "before it escapes", fn.qualname)

    for stmt in own:
        scan_stmt(stmt, False)


def _own_stmts(fn_node):
    return list(fn_node.body)


def _stmt_children(node):
    out = []
    for field in ("body", "orelse", "finalbody", "handlers"):
        for child in getattr(node, field, ()) or ():
            if isinstance(child, ast.ExceptHandler):
                out.extend(child.body)
            elif isinstance(child, ast.stmt):
                out.append(child)
    return out
