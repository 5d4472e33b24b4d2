"""Tier-1 gate: mxnet_tpu/ must be mxlint-clean against the baseline.

Runs mxlint in-process (no subprocess, no new CI infra) so the gate
rides the existing tier-1 pytest command.  Pre-existing findings are
grandfathered in tools/mxlint/baseline.json; anything NEW fails here
with the exact finding list.  To intentionally accept a finding, run

    python -m tools.mxlint mxnet_tpu/ --update-baseline

and justify the baseline diff in review (see docs/LINTING.md).

Beyond the static rules this module also gates the *runtime* registry
audits: table consistency, per-op eval_shape traceability, docstring
coverage, and — new — transform conformance (every canonical-spec op
must trace under jax.vjp and jax.vmap, or be pragma'd/grandfathered;
the grandfather lists in the baseline's "transforms" section only ever
shrink) plus the generated capability matrix staying in sync.  A
wall-time budget keeps the whole gate honest about its tier-1 cost.

PR 16 extends the gate over the threaded runtime: the thread-topology
pass must keep discovering the known asynchronous entry points (>= 8
distinct roots — fewer means root discovery regressed and the race
rules silently lost coverage), the donation pass must see all three
donate_argnums sites, and docs/ENV_VARS.md must stay in two-way sync
with the MXNET_TPU_*/MXTPU_* reads in the tree.
"""

import functools
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.mxlint import (DEFAULT_BASELINE, apply_baseline,  # noqa: E402
                          lint_paths, load_baseline)
from tools.mxlint.findings import (load_registry_grandfather,  # noqa: E402
                                   load_transform_grandfather)
from tools.mxlint.registry_audit import (audit_registry,  # noqa: E402
                                         transform_audit)

# wall-time spent in each (cold) gate component, for the budget test
_TIMINGS = {}

# generous-but-real bound for the full static lint (now including the
# interprocedural call-graph pass) + eval_shape audit + dual-transform
# audit on CPU: observed ~15s cold on the CI-class container, so 8x
# headroom before the gate is considered to have outgrown tier-1
_BUDGET_SECONDS = 120.0


def _timed(key, fn):
    t0 = time.monotonic()
    out = fn()
    _TIMINGS[key] = _TIMINGS.get(key, 0.0) + (time.monotonic() - t0)
    return out


@functools.lru_cache(maxsize=None)
def _run_lint():
    """One full-tree lint shared by every gate test in this module."""
    findings, errors = _timed("lint", lambda: lint_paths(
        [os.path.join(REPO, "mxnet_tpu")], base=REPO))
    assert errors == [], "mxlint could not parse the tree:\n%s" \
        % "\n".join(errors)
    return apply_baseline(findings, load_baseline(DEFAULT_BASELINE))


@functools.lru_cache(maxsize=None)
def _audit(eval_shapes):
    # share the transform matrix so each op is traced once per session
    matrix = _transforms() if eval_shapes else None
    return _timed("audit", lambda: audit_registry(
        eval_shapes=eval_shapes, matrix=matrix))


@functools.lru_cache(maxsize=None)
def _transforms():
    return _timed("transforms", transform_audit)


def test_mxlint_zero_new_findings():
    """No non-baselined static findings anywhere under mxnet_tpu/."""
    result = _run_lint()
    assert result.new == [], (
        "mxlint found NEW violations (fix them, or — only for "
        "deliberate exceptions — add a `# mxlint: disable=<rule>` "
        "pragma or update the baseline):\n"
        + "\n".join(f.format() for f in result.new))


def test_mxlint_baseline_not_stale():
    """Fixed findings must leave the baseline (run --update-baseline)."""
    result = _run_lint()
    assert result.stale == [], (
        "stale baseline entries (the flagged code was fixed/moved; run "
        "`python -m tools.mxlint mxnet_tpu/ --update-baseline`):\n"
        + "\n".join("%s %s %r" % (e.get("rule"), e.get("path"),
                                  e.get("code_line"))
                    for e in result.stale))


def test_registry_audit_tables_consistent():
    """Runtime tables (incl. dynamically-added entries) match the
    registry: every key registered, aux/label subsets hold."""
    res = _audit(False)
    assert res.table_errors == [], "\n".join(res.table_errors)


def test_registry_audit_ops_trace_under_eval_shape():
    """Every OP_INPUT_NAMES op traces on its canonical spec — zero-cost
    proof the op stays inside the jax-traceable subset."""
    res = _audit(True)
    assert res.shape_errors == [], "\n".join(res.shape_errors)


def test_registry_audit_no_new_docless_ops():
    """Newly registered ops must carry docstrings; the pre-existing
    doc-less ones are grandfathered in the baseline's registry section."""
    res = _audit(False)
    allowed = load_registry_grandfather(DEFAULT_BASELINE)
    docless = {name for name, _fn in res.missing_docstrings}
    new = sorted(docless - allowed)
    assert new == [], (
        "newly registered ops without docstrings: %s (document them; "
        "only pre-existing ops are grandfathered)" % ", ".join(new))


# ------------------------------------------------ transform conformance


def test_transform_verdicts_complete():
    """Every canonical-spec table op has a recorded trace/grad/vmap
    verdict — a new table entry cannot dodge the audit."""
    from mxnet_tpu.ops import registry as R

    matrix = _transforms()
    assert set(matrix) == set(R.OP_INPUT_NAMES), (
        "ops missing from the transform matrix: %s"
        % sorted(set(R.OP_INPUT_NAMES) - set(matrix)))
    for name, caps in matrix.items():
        assert set(caps) == {"trace", "grad", "vmap"}, name
        for t, (verdict, _detail) in caps.items():
            assert verdict in ("ok", "fail", "pragma", "n/a"), (name, t)


def test_transform_conformance_gate():
    """New ops must be grad- and vmap-clean (or explicitly pragma'd in
    TRANSFORM_PRAGMAS); the baseline's transforms section grandfathers
    pre-existing failures and only ever shrinks."""
    matrix = _transforms()
    allowed = load_transform_grandfather(DEFAULT_BASELINE)
    new, stale = [], []
    for t in ("grad", "vmap"):
        failing = {op for op, caps in matrix.items()
                   if caps[t][0] == "fail"}
        grandfathered = allowed.get(t, set())
        for op in sorted(failing - grandfathered):
            new.append("%s under %s: %s" % (op, t, matrix[op][t][1]))
        for op in sorted(grandfathered - failing):
            stale.append("%s under %s" % (op, t))
    assert new == [], (
        "ops newly failing a transform (fix the op, or — only for "
        "by-design cases — add a TRANSFORM_PRAGMAS entry in "
        "tools/mxlint/registry_audit.py with a reason):\n"
        + "\n".join(new))
    assert stale == [], (
        "stale transforms grandfather entries (the op now conforms; "
        "run `python -m tools.mxlint.registry_audit "
        "--update-baseline`):\n" + "\n".join(stale))


def test_capability_matrix_up_to_date():
    """docs/OP_CAPABILITIES.md is generated and deterministic: the
    committed file must match a fresh regeneration byte-for-byte."""
    from tools.mxlint.capabilities import DOC_PATH, generate

    with open(DOC_PATH, encoding="utf-8") as f:
        committed = f.read()
    assert committed == generate(_transforms()), (
        "docs/OP_CAPABILITIES.md is stale — regenerate with "
        "`python -m tools.mxlint.capabilities`")


# ------------------------------------------------- threaded-runtime gate


@functools.lru_cache(maxsize=None)
def _tree_contexts():
    """Parsed _FileCtx list for the whole mxnet_tpu/ package (shared)."""
    from tools.mxlint.checkers import Config, _FileCtx, _iter_py_files

    def build():
        ctxs, errors = [], []
        for path in _iter_py_files([os.path.join(REPO, "mxnet_tpu")],
                                   errors):
            rel = os.path.relpath(os.path.abspath(path), REPO)
            with open(path, encoding="utf-8") as f:
                ctxs.append(_FileCtx(rel, f.read(), Config()))
        assert errors == [], "\n".join(errors)
        return tuple(ctxs)

    return _timed("tree-parse", build)


@functools.lru_cache(maxsize=None)
def _tree_graph():
    from tools.mxlint.callgraph import build_graph

    ctxs = list(_tree_contexts())
    return _timed("tree-graph", lambda: build_graph(ctxs))


def test_thread_roots_discovered_across_runtime():
    """Root discovery keeps seeing the runtime's asynchronous entry
    points; a drop below 8 distinct roots means the race rules silently
    lost coverage (they only check code reachable from a root)."""
    from tools.mxlint.threads import discover_roots

    roots = list(discover_roots(_tree_graph(), list(_tree_contexts())))
    distinct = {(r.kind, r.key) for r in roots}
    assert len(distinct) >= 8, (
        "only %d thread roots discovered: %s"
        % (len(distinct), sorted("%s:%s" % (k, key[-1])
                                 for k, key in distinct)))
    kinds = {r.kind for r in roots}
    # the runtime spawns worker threads AND registers GC finalizers;
    # both discovery modes must stay alive
    assert "thread" in kinds, kinds
    assert "finalizer" in kinds, kinds


def test_donation_sites_all_discovered():
    """The donation pass proves every donate_argnums site is in scope
    (``CompiledStep._build``; ``GluonTrainStep._jit`` and
    ``_compilers_orders``) — if one vanishes from discovery, its callers
    go unchecked."""
    from tools.mxlint.donation import find_donation_sites

    sites = find_donation_sites(list(_tree_contexts()))
    per_file = {}
    for path, _lineno, _argnums in sites:
        per_file[path] = per_file.get(path, 0) + 1
    expected = {"mxnet_tpu/compiled_step.py": 1,
                "mxnet_tpu/parallel/gluon_step.py": 2}
    found = {path: per_file.get(path, 0) for path in expected}
    assert found == expected, "donate sites per file: %s" % found


def test_env_registry_fully_synced():
    """docs/ENV_VARS.md <-> code two-way sync, asserted directly (the
    env-registry rule enforces it too; this spells out both sets so a
    failure names the exact variables)."""
    from tools.mxlint import conformance as C

    ctxs = list(_tree_contexts())
    read, mentioned = set(), set()
    for ctx in ctxs:
        read.update(v for v, _node in C._env_reads(ctx))
        mentioned.update(C._ENV_RE.findall(ctx.source))
    rows = C._documented_rows(os.path.join(REPO, "docs", "ENV_VARS.md"))
    assert rows, "docs/ENV_VARS.md missing or has no table rows"
    undocumented = sorted(read - set(rows))
    assert undocumented == [], (
        "env vars read in mxnet_tpu/ without a docs/ENV_VARS.md row: %s"
        % undocumented)
    evidence = read | mentioned | C._aux_mentions(REPO)
    stale = sorted(set(rows) - evidence)
    assert stale == [], (
        "docs/ENV_VARS.md rows no code/tooling reads or mentions: %s"
        % stale)


# --------------------------------------------------- graph verification


@functools.lru_cache(maxsize=None)
def _graph_zoo():
    """One zoo verification (builders + pass outputs) shared by the
    graph gate tests; ``seconds`` is the zoo's own wall-time clock so
    the < 60 s acceptance bound measures the run, not pytest."""
    from tools.mxlint.graph import verify_zoo

    results, seconds = _timed("graph-zoo", verify_zoo)
    return results, seconds


def test_graph_zoo_verifies_clean():
    """Every Symbol graph in the zoo — all builder surfaces plus the
    partition/quantize/AMP pass outputs — verifies with ZERO findings.
    There is deliberately no baseline for graph findings: builders,
    passes and verifier are all in-repo, so any finding is a bug in
    one of them."""
    from tools.mxlint.graph import collect_findings

    results, _seconds = _graph_zoo()
    flat = collect_findings(results)
    assert flat == [], (
        "graph verifier findings in the model zoo:\n"
        + "\n".join("%s: %s" % (g, f.format()) for g, f in flat))
    # the zoo must actually abstract-interpret, not just skip: every
    # graph got full input shapes, so no node may be left unevaluated
    for gname, r in results:
        assert r.evaluated > 0, "%s: nothing traced" % gname
        assert r.skipped == [], (
            "%s: nodes skipped for unknown shapes: %s — the zoo must "
            "seed full input shapes" % (gname, r.skipped))


def test_graph_zoo_runtime_budget():
    """Acceptance bound: the full zoo + pass outputs verify in < 60 s."""
    _results, seconds = _graph_zoo()
    assert seconds < 60.0, (
        "graph zoo verification took %.1fs (>= 60s acceptance bound)"
        % seconds)


def test_lint_and_audit_runtime_budget():
    """The full gate (static lint incl. the interprocedural pass +
    eval_shape audit + dual-transform audit + graph zoo) must stay
    cheap enough to ride tier-1 on CPU."""
    _run_lint()
    _audit(True)
    _transforms()
    _graph_zoo()
    total = sum(_TIMINGS.values())
    assert total < _BUDGET_SECONDS, (
        "lint+audit gate took %.1fs (> %.0fs budget): %s — profile the "
        "analyzer before letting tier-1 eat this"
        % (total, _BUDGET_SECONDS,
           ", ".join("%s=%.1fs" % kv for kv in sorted(_TIMINGS.items()))))
