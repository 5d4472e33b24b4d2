"""mxlint's own tests: each rule fires on its known-bad fixture with an
exact count, stays silent on the known-good one, and the baseline /
pragma mechanisms suppress and expire correctly.

The fixtures live in tests/fixtures/mxlint/ and are linted under
synthetic mxnet_tpu/ paths so the default rule scoping applies.
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.mxlint import (ALL_RULES, Config, apply_baseline,  # noqa: E402
                          fingerprint, lint_sources, load_baseline,
                          save_baseline)

FIXTURES = os.path.join(REPO, "tests", "fixtures", "mxlint")


def _fixture_src(name):
    with open(os.path.join(FIXTURES, name), encoding="utf-8") as f:
        return f.read()


def _lint_fixture(name, rule, as_path="mxnet_tpu/ops/fixture.py"):
    findings, errors = lint_sources({as_path: _fixture_src(name)},
                                    Config(rules=(rule,)))
    assert not errors
    return findings


# ------------------------------------------------------------ per rule

BAD_GOOD = [
    ("trace-host-sync", "bad_trace.py", 7, "good_trace.py"),
    ("static-argnames", "bad_static.py", 4, "good_static.py"),
    ("registry-consistency", "bad_registry.py", 4, "good_registry.py"),
    ("dtype-default", "bad_dtype.py", 4, "good_dtype.py"),
    ("host-sync-reachability", "bad_reach.py", 9, "good_reach.py"),
    ("thread-shared-state", "bad_threads.py", 3, "good_threads.py"),
    ("thread-lock-order", "bad_threads.py", 1, "good_threads.py"),
    ("donation-safety", "bad_donation.py", 4, "good_donation.py"),
    ("guard-first", "bad_guard.py", 1, "good_guard.py"),
    ("env-registry", "bad_env.py", 3, "good_env.py"),
]

# guard-first checks the telemetry-feed registry, which is keyed by the
# real module paths — lint those fixtures under a registered feed path
RULE_FIXTURE_PATH = {"guard-first": "mxnet_tpu/histogram.py"}


def test_every_rule_has_fixtures():
    assert {r for r, _, _, _ in BAD_GOOD} == set(ALL_RULES)


@pytest.mark.parametrize("rule,bad,count,good", BAD_GOOD,
                         ids=["%s-%s" % (r, b) for r, b, _, _ in BAD_GOOD])
def test_rule_fires_exactly_on_bad_fixture(rule, bad, count, good):
    as_path = RULE_FIXTURE_PATH.get(rule, "mxnet_tpu/ops/fixture.py")
    findings = _lint_fixture(bad, rule, as_path=as_path)
    assert len(findings) == count, "\n".join(f.format() for f in findings)
    assert all(f.rule == rule for f in findings)
    assert _lint_fixture(good, rule, as_path=as_path) == []


def test_trace_rule_details():
    findings = _lint_fixture("bad_trace.py", "trace-host-sync")
    msgs = "\n".join(f.format() for f in findings)
    # one finding per documented pattern
    for needle in (".item()", ".tolist()", ".asnumpy()",
                   ".block_until_ready()", "device_get", "float()",
                   "np.asarray"):
        assert needle in msgs, "missing %r in:\n%s" % (needle, msgs)
    # the pragma'd line and the whitelisted wait_to_read stayed silent
    symbols = {f.symbol for f in findings}
    assert "suppressed" not in symbols
    assert "wait_to_read" not in symbols


def test_trace_rule_scoped_to_compute_paths():
    """The same bad source outside the compute path is not trace-linted."""
    src = _fixture_src("bad_trace.py")
    findings, _ = lint_sources({"mxnet_tpu/metric.py": src},
                               Config(rules=("trace-host-sync",)))
    assert findings == []


def test_dtype_rule_scoped_to_ops():
    src = _fixture_src("bad_dtype.py")
    findings, _ = lint_sources({"mxnet_tpu/executor.py": src},
                               Config(rules=("dtype-default",)))
    assert findings == []


def test_registry_cross_file():
    """Registration in one file satisfies a table key in another."""
    table_src = ("OP_INPUT_NAMES = {'Remote': ('data',)}\n"
                 "OP_AUX_INPUTS = {}\n")
    op_src = ("from mxnet_tpu.ops.registry import register\n\n\n"
              "@register('Remote')\n"
              "def remote(data):\n"
              "    \"\"\"doc\"\"\"\n"
              "    return data\n")
    findings, _ = lint_sources(
        {"mxnet_tpu/ops/registry.py": table_src,
         "mxnet_tpu/ops/other.py": op_src},
        Config(rules=("registry-consistency",)))
    assert findings == []


# ---------------------------------------------- interprocedural rule


def test_reach_rule_details():
    """The seeded fixture reports full call paths, incl. the two-hop
    chain, the sync-by-contract edge, and the tensor host-branch."""
    findings = _lint_fixture("bad_reach.py", "host-sync-reachability")
    msgs = "\n".join(f.format() for f in findings)
    # the acceptance two-hop: compute fn -> helper -> .item(), with the
    # whole path in the message
    assert "dispatch_like → _indirect → _to_scalar → .item()" in msgs
    assert "(sync by contract)" in msgs           # flush_cache -> save
    assert "if data:" in msgs                     # host branch
    assert "np.asarray(<tensor>)" in msgs         # aliased _np import
    assert ".block_until_ready()" in msgs         # cycle sink
    symbols = {f.symbol for f in findings}
    assert "grab" in symbols                      # name = lambda
    assert "decorated_reader" in symbols          # decorated fn
    # the by-design pragma'd helper and whitelisted fns stayed silent
    assert "save" not in symbols
    assert "_to_scalar" not in symbols            # direct rule owns it


def test_reach_cross_file():
    """A sync hidden in a helper MODULE is caught at the compute-path
    call site, with the cross-module path reported."""
    util = ("def leak(v):\n"
            "    return v.item()\n")
    comp = ("from mxnet_tpu.util import leak\n\n\n"
            "def dispatch(x):\n"
            "    return leak(x)\n")
    findings, _ = lint_sources(
        {"mxnet_tpu/util.py": util, "mxnet_tpu/executor.py": comp},
        Config(rules=("host-sync-reachability",)))
    assert len(findings) == 1, \
        "\n".join(f.format() for f in findings)
    assert findings[0].path == "mxnet_tpu/executor.py"
    assert "dispatch → leak → .item()" in findings[0].message


def test_reach_partial_scope_is_conservative():
    """Without the helper's module in scope the callee is unresolvable
    -> unknown -> silent (no false positives on partial runs)."""
    comp = ("from mxnet_tpu.util import leak\n\n\n"
            "def dispatch(x):\n"
            "    return leak(x)\n")
    findings, _ = lint_sources({"mxnet_tpu/executor.py": comp},
                               Config(rules=("host-sync-reachability",)))
    assert findings == []


def test_reach_scoped_to_compute_paths():
    """The same chain OUTSIDE the compute-path globs is not flagged."""
    src = ("def leak(v):\n"
           "    return v.item()\n\n\n"
           "def caller(x):\n"
           "    return leak(x)\n")
    findings, _ = lint_sources({"mxnet_tpu/metric.py": src},
                               Config(rules=("host-sync-reachability",)))
    assert findings == []


def test_reach_pragma_at_call_site():
    util = ("def leak(v):\n"
            "    return v.item()\n")
    comp = ("from mxnet_tpu.util import leak\n\n\n"
            "def dispatch(x):\n"
            "    return leak(x)  "
            "# mxlint: disable=host-sync-reachability -- bridge\n")
    findings, _ = lint_sources(
        {"mxnet_tpu/util.py": util, "mxnet_tpu/executor.py": comp},
        Config(rules=("host-sync-reachability",)))
    assert findings == []


def test_reach_pragma_at_sink_clears_all_callers():
    """trace-host-sync pragmas carry over: a by-design bridge pragma'd
    at the SOURCE clears every transitive call site at once."""
    util = ("def leak(v):\n"
            "    return v.item()  "
            "# mxlint: disable=trace-host-sync -- host bridge\n")
    comp = ("from mxnet_tpu.util import leak\n\n\n"
            "def dispatch(x):\n"
            "    return leak(x)\n"
            "def dispatch2(x):\n"
            "    return leak(x)\n")
    findings, _ = lint_sources(
        {"mxnet_tpu/util.py": util, "mxnet_tpu/executor.py": comp},
        Config(rules=("host-sync-reachability",)))
    assert findings == []


def test_callgraph_classification():
    from tools.mxlint.callgraph import build_graph, classify
    from tools.mxlint.checkers import _FileCtx

    src = ("import jax.numpy as jnp\n"
           "def syncer(v):\n"
           "    return v.item()\n"
           "def pure_fn(v):\n"
           "    return jnp.exp(v)\n"
           "def caller(v):\n"
           "    return pure_fn(v)\n"
           "def transitive(v):\n"
           "    return syncer(v)\n"
           "def unknown_fn(cb, v):\n"
           "    return cb(v)\n"
           "def tainted(cb, v):\n"
           "    return unknown_fn(cb, v)\n")
    ctx = _FileCtx("mxnet_tpu/ops/x.py", src, Config())
    cls = classify(build_graph([ctx]))

    def k(n):
        return ("mxnet_tpu.ops.x", n)

    assert cls[k("syncer")] == "host-syncing"
    assert cls[k("pure_fn")] == "pure"
    assert cls[k("caller")] == "pure"
    assert cls[k("transitive")] == "host-syncing"
    assert cls[k("unknown_fn")] == "unknown"
    assert cls[k("tainted")] == "unknown"  # unknown-ness propagates


def test_callgraph_pure_cycle_terminates():
    src = ("import jax.numpy as jnp\n"
           "def a(v, n):\n"
           "    if n:\n"
           "        return b(v, n - 1)\n"
           "    return v\n"
           "def b(v, n):\n"
           "    return a(jnp.tanh(v), n)\n")
    findings, _ = lint_sources({"mxnet_tpu/ops/x.py": src},
                               Config(rules=("host-sync-reachability",)))
    assert findings == []


def test_reach_branch_descs_match_source_construct():
    """While-loops and negated tests are reported as written, not as a
    generic `if name:`."""
    src = ("from mxnet_tpu.ops.registry import register\n\n\n"
           "@register('_w')\n"
           "def spin(data):\n"
           "    \"\"\"doc\"\"\"\n"
           "    while data:\n"
           "        data = data - 1\n"
           "    return data\n\n\n"
           "@register('_n')\n"
           "def neg(mask):\n"
           "    \"\"\"doc\"\"\"\n"
           "    if not mask:\n"
           "        return mask\n"
           "    return mask\n")
    findings, _ = lint_sources({"mxnet_tpu/ops/x.py": src},
                               Config(rules=("host-sync-reachability",)))
    msgs = "\n".join(f.format() for f in findings)
    assert len(findings) == 2, msgs
    assert "while data:" in msgs
    assert "if not mask:" in msgs


def test_reach_param_and_local_shadowing():
    """A parameter or local rebinding shadowing a syncing module-level
    name makes the call UNKNOWN, never a false positive."""
    src = ("def leak(v):\n"
           "    return v.item()\n\n\n"
           "def via_param(x, leak):\n"
           "    return leak(x)\n\n\n"
           "def via_local(x):\n"
           "    leak = abs\n"
           "    return leak(x)\n\n\n"
           "def via_loop(x, fns):\n"
           "    for leak in fns:\n"
           "        x = leak(x)\n"
           "    return x\n\n\n"
           "def real_call(x):\n"
           "    return leak(x)\n")
    findings, _ = lint_sources({"mxnet_tpu/ops/x.py": src},
                               Config(rules=("host-sync-reachability",)))
    assert len(findings) == 1, "\n".join(f.format() for f in findings)
    assert findings[0].symbol == "real_call"


def test_reach_nested_def_resolution():
    """A nested def shadowing a syncing module-level name wins — python
    scoping, not dotted-name guessing."""
    src = ("def leak(v):\n"
           "    return v.item()\n\n\n"
           "def dispatch(x):\n"
           "    def leak(y):\n"
           "        return y * 2\n"
           "    return leak(x)\n")
    findings, _ = lint_sources({"mxnet_tpu/ops/x.py": src},
                               Config(rules=("host-sync-reachability",)))
    assert findings == []


# ------------------------------------------------------------ pragmas


def test_pragma_disables_single_rule():
    src = ("import numpy as np\n"
           "def f(n):\n"
           "    return np.zeros((n,))  # mxlint: disable=dtype-default\n")
    findings, _ = lint_sources({"mxnet_tpu/ops/x.py": src},
                               Config(rules=("dtype-default",)))
    assert findings == []


def test_pragma_other_rule_does_not_disable():
    src = ("import numpy as np\n"
           "def f(n):\n"
           "    return np.zeros((n,))  # mxlint: disable=trace-host-sync\n")
    findings, _ = lint_sources({"mxnet_tpu/ops/x.py": src},
                               Config(rules=("dtype-default",)))
    assert len(findings) == 1


def test_pragma_bare_disable_allows_reason_suffix():
    src = ("import numpy as np\n"
           "def f(n):\n"
           "    return np.zeros((n,))  # mxlint: disable -- host table\n")
    findings, _ = lint_sources({"mxnet_tpu/ops/x.py": src},
                               Config(rules=("dtype-default",)))
    assert findings == []


def test_pragma_unknown_spelling_is_not_disable_all():
    """pylint-style 'disable-next-line=' (or a typo) must not silently
    suppress every rule on the line."""
    src = ("import numpy as np\n"
           "def f(n):\n"
           "    return np.zeros((n,))"
           "  # mxlint: disable-next-line=dtype-default\n")
    findings, _ = lint_sources({"mxnet_tpu/ops/x.py": src},
                               Config(rules=("dtype-default",)))
    assert len(findings) == 1


def test_duplicate_key_within_one_table_literal_flagged():
    src = ("OP_INPUT_NAMES = {'dot': ('a', 'b'), 'dot': ('x',)}\n")
    findings, _ = lint_sources({"mxnet_tpu/ops/registry.py": src},
                               Config(rules=("registry-consistency",)))
    assert len(findings) == 1
    assert "appears twice" in findings[0].message


# ----------------------------------------------------------- baseline


def _bad_dtype_findings(path="mxnet_tpu/ops/fixture.py"):
    return _lint_fixture("bad_dtype.py", "dtype-default", as_path=path)


def test_baseline_suppresses_grandfathered(tmp_path):
    findings = _bad_dtype_findings()
    bl_path = str(tmp_path / "baseline.json")
    save_baseline(bl_path, findings)
    result = apply_baseline(findings, load_baseline(bl_path))
    assert result.new == []
    assert len(result.suppressed) == len(findings)
    assert result.stale == []


def test_baseline_reports_new_findings(tmp_path):
    findings = _bad_dtype_findings()
    bl_path = str(tmp_path / "baseline.json")
    save_baseline(bl_path, findings[:-1])  # one finding not grandfathered
    result = apply_baseline(findings, load_baseline(bl_path))
    assert len(result.new) == 1
    assert fingerprint(result.new[0]) == fingerprint(findings[-1])


def test_baseline_expires_when_code_fixed(tmp_path):
    bad = _bad_dtype_findings()
    bl_path = str(tmp_path / "baseline.json")
    save_baseline(bl_path, bad)
    good = _lint_fixture("good_dtype.py", "dtype-default")
    result = apply_baseline(good, load_baseline(bl_path))
    assert result.new == [] and result.suppressed == []
    # every grandfathered entry is now stale -> reported for removal
    assert len(result.stale) == len(load_baseline(bl_path))


def test_baseline_counts_duplicate_violations(tmp_path):
    """Copy-pasting a baselined violation is still a new finding."""
    src = ("import numpy as np\n"
           "def f(n):\n"
           "    return np.zeros((n,))\n")
    cfg = Config(rules=("dtype-default",))
    one, _ = lint_sources({"mxnet_tpu/ops/x.py": src}, cfg)
    assert len(one) == 1
    bl_path = str(tmp_path / "baseline.json")
    save_baseline(bl_path, one)
    dup = ("import numpy as np\n"
           "def f(n):\n"
           "    return np.zeros((n,))\n"
           "def g(n):\n"
           "    return np.zeros((n,))\n")
    two, _ = lint_sources({"mxnet_tpu/ops/x.py": dup}, cfg)
    assert len(two) == 2
    result = apply_baseline(two, load_baseline(bl_path))
    # same function name + same code line -> same fingerprint, but the
    # count budget (1) absorbs only one of them... unless the enclosing
    # symbol differs (f vs g), which keeps fingerprints distinct
    assert len(result.new) == 1
    assert len(result.suppressed) == 1


def test_baseline_partial_fix_goes_stale(tmp_path):
    """A count-2 entry with one occurrence fixed is stale until the
    baseline is regenerated — counts only ever shrink."""
    two_src = ("import numpy as np\n"
               "def f(n):\n"
               "    a = np.zeros((n,))\n"
               "    b = np.zeros((n,))\n"
               "    return a, b\n")
    one_src = ("import numpy as np\n"
               "def f(n):\n"
               "    a = np.zeros((n,))\n"
               "    return a\n")
    cfg = Config(rules=("dtype-default",))
    two, _ = lint_sources({"mxnet_tpu/ops/x.py": two_src}, cfg)
    assert len(two) == 2
    bl_path = str(tmp_path / "baseline.json")
    save_baseline(bl_path, two)
    one, _ = lint_sources({"mxnet_tpu/ops/x.py": one_src}, cfg)
    result = apply_baseline(one, load_baseline(bl_path))
    assert result.new == [] and len(result.suppressed) == 1
    assert len(result.stale) == 1
    assert result.stale[0]["unmatched"] == 1


def test_tables_merged_across_files():
    """Tables split across registry files are still cross-checked."""
    a = "OP_INPUT_NAMES = {'Norm': ('data',)}\n"
    b = "OP_AUX_INPUTS = {'Phantom': ('state',)}\n"
    op = ("from mxnet_tpu.ops.registry import register\n\n\n"
          "@register('Norm')\n"
          "def norm(data):\n"
          "    \"\"\"doc\"\"\"\n"
          "    return data\n")
    findings, _ = lint_sources(
        {"mxnet_tpu/ops/registry.py": a, "mxnet_tpu/ops/extra.py": b,
         "mxnet_tpu/ops/impl.py": op},
        Config(rules=("registry-consistency",)))
    assert len(findings) == 1
    assert "Phantom" in findings[0].message


def test_duplicate_table_key_across_files_flagged():
    a = ("OP_INPUT_NAMES = {'Norm': ('data',)}\n")
    b = ("OP_INPUT_NAMES = {'Norm': ('data', 'gamma')}\n")
    op = ("from mxnet_tpu.ops.registry import register\n\n\n"
          "@register('Norm')\n"
          "def norm(data):\n"
          "    \"\"\"doc\"\"\"\n"
          "    return data\n")
    findings, _ = lint_sources(
        {"mxnet_tpu/ops/registry.py": a, "mxnet_tpu/ops/extra.py": b,
         "mxnet_tpu/ops/impl.py": op},
        Config(rules=("registry-consistency",)))
    assert len(findings) == 1
    assert "more than one file" in findings[0].message


def test_nonexistent_path_is_an_error(capsys):
    from tools.mxlint import lint_paths as lp
    from tools.mxlint import main

    _findings, errors = lp(["no/such/dir"])
    assert errors and "does not exist" in errors[0]
    assert main(["no/such/dir", "--no-baseline"]) == 2


def test_non_python_file_is_an_error():
    from tools.mxlint import lint_paths as lp

    _findings, errors = lp([os.path.join(REPO, "docs", "LINTING.md")])
    assert errors and "not a python file" in errors[0]


def test_table_internal_checks_run_without_register_sites():
    """A tables-only file (like ops/registry.py) still gets duplicate/
    subset checks even when no @register site is in scope."""
    src = ("OP_INPUT_NAMES = {'Foo': ('data',)}\n"
           "OP_AUX_INPUTS = {'Foo': ('gamma',)}\n")
    findings, _ = lint_sources({"mxnet_tpu/ops/registry.py": src},
                               Config(rules=("registry-consistency",)))
    assert len(findings) == 1
    assert "gamma" in findings[0].message


def test_partial_scope_skips_unregistered_key_check():
    """Linting registry.py without its siblings must not flag table
    keys whose @register sites live in the unlinted files."""
    from tools.mxlint import lint_paths as lp

    findings, errors = lp(
        [os.path.join(REPO, "mxnet_tpu", "ops", "registry.py")],
        base=REPO)
    assert errors == []
    assert not any("does not name a registered op" in f.message
                   for f in findings)


def test_fingerprint_survives_line_drift():
    src = _fixture_src("bad_dtype.py")
    shifted = "# padding\n# padding\n\n" + src
    cfg = Config(rules=("dtype-default",))
    a, _ = lint_sources({"mxnet_tpu/ops/x.py": src}, cfg)
    b, _ = lint_sources({"mxnet_tpu/ops/x.py": shifted}, cfg)
    assert [fingerprint(f) for f in a] == [fingerprint(f) for f in b]
    assert [f.line for f in a] != [f.line for f in b]


def test_baseline_roundtrip_preserves_registry_section(tmp_path):
    from tools.mxlint.findings import (load_registry_grandfather,
                                       save_registry_grandfather)

    bl_path = str(tmp_path / "baseline.json")
    save_registry_grandfather(bl_path, ["op_a", "op_b"])
    save_baseline(bl_path, _bad_dtype_findings())
    assert load_registry_grandfather(bl_path) == {"op_a", "op_b"}
    with open(bl_path) as f:
        data = json.load(f)
    assert data["findings"]


# ---------------------------------------------------------------- CLI


def test_cli_bad_file_exits_nonzero(tmp_path, capsys):
    """CLI flags findings in a compute-path-shaped tree and exits 1."""
    import shutil

    from tools.mxlint import main

    ops_dir = tmp_path / "mxnet_tpu" / "ops"
    ops_dir.mkdir(parents=True)
    shutil.copy(os.path.join(FIXTURES, "bad_dtype.py"),
                str(ops_dir / "bad.py"))
    rc = main([str(tmp_path / "mxnet_tpu"), "--no-baseline",
               "--rules", "dtype-default"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "4 new finding(s)" in out


def test_cli_repo_gate_is_clean(capsys):
    """`python -m tools.mxlint mxnet_tpu/` exits 0 against the baseline."""
    from tools.mxlint import main

    old = os.getcwd()
    os.chdir(REPO)
    try:
        rc = main(["mxnet_tpu"])
    finally:
        os.chdir(old)
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "0 new finding(s)" in out


def test_cli_gate_is_cwd_independent(tmp_path, capsys):
    """Fingerprints anchor to the repo root, not the invoking cwd.
    One cheap rule suffices — path anchoring is rule-independent, and
    test_cli_repo_gate_is_clean already runs the full set."""
    from tools.mxlint import main

    old = os.getcwd()
    os.chdir(str(tmp_path))
    try:
        rc = main([os.path.join(REPO, "mxnet_tpu"),
                   "--rules", "dtype-default"])
    finally:
        os.chdir(old)
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "0 new finding(s)" in out and "0 stale" in out


def test_cli_partial_scope_reports_no_bogus_stale(capsys):
    """Linting one file must not flag the rest of the baseline stale."""
    from tools.mxlint import main

    old = os.getcwd()
    os.chdir(REPO)
    try:
        rc = main(["mxnet_tpu/ops/elemwise.py"])
    finally:
        os.chdir(old)
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "0 stale" in out


def test_partial_update_baseline_keeps_out_of_scope(tmp_path, capsys):
    """--update-baseline on a sub-path preserves other files' entries."""
    import shutil

    from tools.mxlint import main

    ops = tmp_path / "mxnet_tpu" / "ops"
    ops.mkdir(parents=True)
    shutil.copy(os.path.join(FIXTURES, "bad_dtype.py"), str(ops / "a.py"))
    shutil.copy(os.path.join(FIXTURES, "bad_dtype.py"), str(ops / "b.py"))
    bl = str(tmp_path / "bl.json")
    assert main([str(ops), "--baseline", bl, "--rules", "dtype-default",
                 "--update-baseline"]) == 0
    # "fix" a.py, then partially update only a.py: b.py entries survive
    (ops / "a.py").write_text("x = 1\n")
    assert main([str(ops / "a.py"), "--baseline", bl, "--rules",
                 "dtype-default", "--update-baseline"]) == 0
    entries = load_baseline(bl)
    paths = {e["path"] for e in entries.values()}
    assert any(p.endswith("ops/b.py") for p in paths)
    assert not any(p.endswith("ops/a.py") for p in paths)
    capsys.readouterr()


def test_cli_unknown_rule_usage_error(capsys):
    from tools.mxlint import main

    assert main(["--rules", "no-such-rule"]) == 2


# ------------------------------------------------------ runtime audit


def test_registry_audit_clean():
    from tools.mxlint.registry_audit import audit_registry

    res = audit_registry(eval_shapes=False)
    assert res.table_errors == []


def test_registry_audit_detects_injected_drift():
    from mxnet_tpu.ops import registry as R
    from tools.mxlint.registry_audit import audit_registry

    R.OP_INPUT_NAMES["_mxlint_ghost_op"] = ("data",)
    try:
        res = audit_registry(eval_shapes=False)
        assert any("_mxlint_ghost_op" in e for e in res.table_errors)
    finally:
        del R.OP_INPUT_NAMES["_mxlint_ghost_op"]


def test_registry_audit_detects_aux_drift():
    from mxnet_tpu.ops import registry as R
    from tools.mxlint.registry_audit import audit_registry

    R.OP_AUX_INPUTS["BatchNorm"] = R.OP_AUX_INPUTS["BatchNorm"] + \
        ("not_an_input",)
    try:
        res = audit_registry(eval_shapes=False)
        assert any("not_an_input" in e for e in res.table_errors)
    finally:
        R.OP_AUX_INPUTS["BatchNorm"] = \
            R.OP_AUX_INPUTS["BatchNorm"][:-1]


def test_canonical_specs_cover_input_table():
    """Every table op has an eval_shape spec with matching arity."""
    from mxnet_tpu.ops import registry as R
    from tools.mxlint.registry_audit import canonical_spec

    for name, input_names in R.OP_INPUT_NAMES.items():
        spec = canonical_spec(name)
        assert spec is not None, "no canonical spec for %r" % name
        input_specs, _attrs = spec
        assert len(input_specs) == len(input_names), name


# ------------------------------------------- transform conformance


def test_check_grad_flags_bad_cotangent_shape():
    """A custom_vjp whose backward emits the wrong shape is caught —
    the audit checks cotangents against primals, not just 'it traced'."""
    import jax
    import jax.numpy as jnp

    from tools.mxlint.registry_audit import _check_grad

    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, None

    def bwd(res, g):
        return (jnp.zeros((7,), g.dtype),)  # wrong: primal is (3,)

    f.defvjp(fwd, bwd)
    spec = [jax.ShapeDtypeStruct((3,), jnp.float32)]
    err = _check_grad(f, spec, [0])
    # jax itself validates custom_vjp bwd shapes at trace time (newer
    # versions); the audit's own cotangent check is the backstop —
    # either way a shape-lying backward must surface as an error
    assert err is not None and ("cotangent shape" in err
                                or "bwd rule" in err)


def test_check_grad_ok_on_plain_fn():
    import jax
    import jax.numpy as jnp

    from tools.mxlint.registry_audit import _check_grad

    spec = [jax.ShapeDtypeStruct((3, 4), jnp.float32)]
    assert _check_grad(lambda x: jnp.sum(jnp.tanh(x)), spec, [0]) is None


def test_check_vmap_flags_unbatchable_callback():
    """A host-callback op (the CustomOp analog) does not compose with
    vmap — the audit reports it instead of letting it crash later."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import io_callback

    from tools.mxlint.registry_audit import _check_vmap

    def f(x):
        return io_callback(
            lambda a: a, jax.ShapeDtypeStruct(x.shape, x.dtype), x,
            ordered=True)

    spec = [jax.ShapeDtypeStruct((3,), jnp.float32)]
    err = _check_vmap(f, spec)
    assert err is not None and "vmap" in err


def test_transform_audit_excludes_aux_and_int_inputs():
    """BatchNorm's moving stats (aux) and Embedding's indices (int) are
    not differentiated — mirroring executor grad_req semantics."""
    from mxnet_tpu.ops import registry as R
    from tools.mxlint.registry_audit import _diff_argnums, canonical_spec

    bn_specs, _ = canonical_spec("BatchNorm")
    nums = _diff_argnums("BatchNorm", bn_specs, 0)
    names = R.OP_INPUT_NAMES["BatchNorm"]
    picked = [names[i] for i in nums]
    assert "moving_mean" not in picked and "moving_var" not in picked
    assert "data" in picked and "gamma" in picked

    emb_specs, _ = canonical_spec("Embedding")
    nums = _diff_argnums("Embedding", emb_specs, 0)
    assert [R.OP_INPUT_NAMES["Embedding"][i] for i in nums] == ["weight"]


def test_transform_pragma_renders_in_matrix():
    """A TRANSFORM_PRAGMAS entry turns the verdict into 'pragma' and
    the generated doc footnotes the reason."""
    from tools.mxlint import capabilities, registry_audit

    registry_audit.TRANSFORM_PRAGMAS["dot"] = {
        "vmap": "test-only pragma reason"}
    try:
        matrix = registry_audit.transform_audit()
        assert matrix["dot"]["vmap"] == ("pragma",
                                         "test-only pragma reason")
        doc = capabilities.generate(matrix)
        assert "pragma[^1]" in doc
        assert "[^1]: test-only pragma reason" in doc
    finally:
        del registry_audit.TRANSFORM_PRAGMAS["dot"]


def test_capability_doc_deterministic():
    from tools.mxlint.capabilities import generate
    from tools.mxlint.registry_audit import transform_audit

    m = transform_audit()
    assert generate(m) == generate(m)
    assert generate(m) == generate(transform_audit())


def test_transform_baseline_roundtrip(tmp_path):
    from tools.mxlint.findings import (load_transform_grandfather,
                                       save_registry_grandfather,
                                       save_transform_grandfather)

    bl = str(tmp_path / "baseline.json")
    save_transform_grandfather(bl, {"grad": ["OpA"], "vmap": []})
    save_registry_grandfather(bl, ["op_x"])      # preserves transforms
    save_baseline(bl, _bad_dtype_findings())     # preserves both
    assert load_transform_grandfather(bl) == {"grad": {"OpA"},
                                              "vmap": set()}
    with open(bl) as f:
        data = json.load(f)
    assert data["registry"]["missing_docstrings"] == ["op_x"]
    assert data["findings"]


def test_registry_audit_cli_fails_on_new_transform_failure(tmp_path,
                                                           capsys):
    """The standalone audit's exit code must reflect non-grandfathered
    grad/vmap failures (an rc-checking CI step may run it without the
    pytest gate)."""
    from tools.mxlint import registry_audit

    # inject a vmap failure by monkeypatching the matrix for one op
    real = registry_audit.transform_audit

    def fake():
        m = real()
        m["dot"] = dict(m["dot"], vmap=("fail", "injected failure"))
        return m

    registry_audit.transform_audit = fake
    try:
        rc = registry_audit.main([])
    finally:
        registry_audit.transform_audit = real
    out = capsys.readouterr().out
    assert rc == 1
    assert "dot under vmap: injected failure" in out
    # and an 'op does not trace' collapse is NOT a grandfather
    # candidate: --update-baseline to a scratch copy must skip it

    def fake2():
        m = real()
        m["dot"] = dict(m["dot"],
                        grad=("fail", "op does not trace"),
                        vmap=("fail", "real vmap defect"))
        return m

    import shutil

    from tools.mxlint import cli as mxcli

    scratch = str(tmp_path / "bl.json")
    shutil.copy(mxcli.DEFAULT_BASELINE, scratch)
    registry_audit.transform_audit = fake2
    old_default = mxcli.DEFAULT_BASELINE
    mxcli.DEFAULT_BASELINE = scratch
    try:
        registry_audit.main(["--update-baseline"])
    finally:
        mxcli.DEFAULT_BASELINE = old_default
        registry_audit.transform_audit = real
    from tools.mxlint.findings import load_transform_grandfather

    gf = load_transform_grandfather(scratch)
    assert "dot" not in gf.get("grad", set())   # trace collapse skipped
    assert "dot" in gf.get("vmap", set())       # genuine defect kept
    capsys.readouterr()


# --------------------------------------------------- github format


def test_cli_github_format_annotations(tmp_path, capsys):
    import shutil

    from tools.mxlint import main

    ops_dir = tmp_path / "mxnet_tpu" / "ops"
    ops_dir.mkdir(parents=True)
    shutil.copy(os.path.join(FIXTURES, "bad_dtype.py"),
                str(ops_dir / "bad.py"))
    rc = main([str(tmp_path / "mxnet_tpu"), "--no-baseline",
               "--rules", "dtype-default", "--format", "github"])
    out = capsys.readouterr().out
    assert rc == 1
    lines = [ln for ln in out.splitlines()
             if ln.startswith("::error file=")]
    assert len(lines) == 4
    assert all(",line=" in ln and "title=mxlint dtype-default" in ln
               for ln in lines)
    # workflow-command escaping: no raw newline can survive inside a
    # message, and the summary line still prints
    assert "4 new finding(s)" in out


def test_cli_github_format_clean_repo(capsys):
    """A clean run emits no ::error lines (rule-restricted for speed;
    repo cleanliness under ALL rules is test_cli_repo_gate_is_clean's
    job, and github formatting of findings is covered above)."""
    from tools.mxlint import main

    old = os.getcwd()
    os.chdir(REPO)
    try:
        rc = main(["mxnet_tpu", "--format", "github",
                   "--rules", "dtype-default,trace-host-sync"])
    finally:
        os.chdir(old)
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "::error" not in out


def test_cli_github_format_show_baselined(tmp_path, capsys):
    """--show-baselined surfaces suppressed findings as ::notice
    annotations in github mode (it is not silently ignored). Runs
    against a fixture with a scratch baseline — the repo's own
    baseline is empty."""
    import shutil

    from tools.mxlint import main

    ops_dir = tmp_path / "mxnet_tpu" / "ops"
    ops_dir.mkdir(parents=True)
    shutil.copy(os.path.join(FIXTURES, "bad_dtype.py"),
                str(ops_dir / "bad.py"))
    bl = str(tmp_path / "bl.json")
    assert main([str(tmp_path / "mxnet_tpu"), "--baseline", bl,
                 "--rules", "dtype-default",
                 "--update-baseline"]) == 0
    capsys.readouterr()
    rc = main([str(tmp_path / "mxnet_tpu"), "--baseline", bl,
               "--rules", "dtype-default", "--format", "github",
               "--show-baselined"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "::error" not in out
    notices = [ln for ln in out.splitlines()
               if ln.startswith("::notice file=")]
    assert len(notices) == 4, out
    assert "%d baselined" % len(notices) in out  # one notice per entry
    assert all("mxlint baselined dtype-default" in ln
               for ln in notices)


# ------------------------------------------------- threaded runtime


def test_thread_rule_details():
    """The three shared-state findings name the variable, both roots,
    and both held-lock sets (or call out the unlocked RMW)."""
    findings = _lint_fixture("bad_threads.py", "thread-shared-state")
    msgs = "\n".join(f.format() for f in findings)
    assert "unlocked read-modify-write" in msgs
    assert "_counter" in msgs and "thread:_worker" in msgs
    assert "_shared written under root 'api' holding no lock" in msgs
    assert "{fixture._lock_a}" in msgs
    assert "Server.state written under root 'thread:Server._loop'" in msgs
    assert "{Server._lock_b}" in msgs
    assert "lock sets never intersect" in msgs


def test_lock_order_inversion_prints_both_paths():
    """The inversion finding is actionable only if BOTH acquisition
    paths appear, each with its own file:line."""
    findings = _lint_fixture("bad_threads.py", "thread-lock-order")
    assert len(findings) == 1
    msg = findings[0].message
    assert msg.count("acquires") == 2
    assert "_path_ab acquires fixture._lock_a then fixture._lock_b" in msg
    assert "_path_ba acquires fixture._lock_b then fixture._lock_a" in msg
    assert msg.count("fixture.py:") == 2   # one site per path
    assert "deadlock" in msg


THREADED_BRIDGE = (
    "import threading\n\n"
    "_lock = threading.Lock()\n"
    "_table = {}%s\n\n\n"
    "def _worker():\n"
    "    _table['k'] = 1\n\n\n"
    "def start():\n"
    "    threading.Thread(target=_worker).start()\n\n\n"
    "def read():\n"
    "    with _lock:\n"
    "        return dict(_table)\n")


def test_thread_pragma_at_definition_clears_every_site():
    """Without a pragma the cross-root lock disagreement fires; a
    pragma at the variable DEFINITION clears every access site."""
    cfg = Config(rules=("thread-shared-state",))
    bare, _ = lint_sources(
        {"mxnet_tpu/ops/x.py": THREADED_BRIDGE % ""}, cfg)
    assert len(bare) == 1, "\n".join(f.format() for f in bare)
    pragma = ("  # mxlint: disable=thread-shared-state -- by-design "
              "bridge")
    cleared, _ = lint_sources(
        {"mxnet_tpu/ops/x.py": THREADED_BRIDGE % pragma}, cfg)
    assert cleared == []


def test_thread_unknown_lock_callee_is_conservative():
    """A `with <call>:` whose lock cannot be resolved statically poisons
    the held set -> the access is dropped, never guessed at (zero false
    positives by construction)."""
    src = ("import threading\n\n"
           "_lock = threading.Lock()\n"
           "_table = {}\n\n\n"
           "def _row_lock(i):\n"
           "    return threading.Lock()\n\n\n"
           "def _worker():\n"
           "    with _row_lock(0):\n"
           "        _table['k'] = 1\n\n\n"
           "def start():\n"
           "    threading.Thread(target=_worker).start()\n\n\n"
           "def read():\n"
           "    with _lock:\n"
           "        return dict(_table)\n")
    findings, _ = lint_sources({"mxnet_tpu/ops/x.py": src},
                               Config(rules=("thread-shared-state",)))
    assert findings == []


def test_thread_roots_discovered_in_fixture():
    """Root discovery sees the Thread targets and the bound-method
    thread inside the class."""
    from tools.mxlint.callgraph import build_graph
    from tools.mxlint.checkers import _FileCtx
    from tools.mxlint.threads import discover_roots

    ctx = _FileCtx("mxnet_tpu/ops/fixture.py",
                   _fixture_src("bad_threads.py"), Config())
    roots = list(discover_roots(build_graph([ctx]), [ctx]))
    labels = {"%s:%s" % (r.kind, r.key[-1]) for r in roots}
    assert any("_worker" in l for l in labels), labels
    assert any("_loop" in l for l in labels), labels
    assert all(r.kind == "thread" for r in roots)


# ------------------------------------------------- donation safety


def test_donation_rule_details():
    """Each bad-donation pattern gets its own actionable message."""
    findings = _lint_fixture("bad_donation.py", "donation-safety")
    msgs = "\n".join(f.format() for f in findings)
    assert "discards its result" in msgs            # bare-Expr call
    assert "read after the donating call" in msgs   # stale local read
    assert "never rebinds it" in msgs               # self._w not rebound
    assert "`_data` capture escapes" in msgs        # unpinned capture
    assert "donation_active()" in msgs              # points at the seam
    symbols = {f.symbol for f in findings}
    assert symbols == {"Stepper.run_discard", "Stepper.run_stale_read",
                       "Stepper.run_attr", "Stepper.snap"}


def test_donation_pinned_capture_and_rebinds_silent():
    """The good fixture exercises every clean idiom: return-transfer,
    tuple rebind, attr rebind, metadata-only reads, pinned capture."""
    assert _lint_fixture("good_donation.py", "donation-safety") == []


def test_donation_sites_cover_all_three_jit_wrappers():
    """The repo's donate_argnums sites are all discovered."""
    from tools.mxlint.checkers import _FileCtx
    from tools.mxlint.donation import find_donation_sites

    expected = {"mxnet_tpu/compiled_step.py",
                "mxnet_tpu/parallel/gluon_step.py"}
    ctxs = []
    for rel in sorted(expected):
        with open(os.path.join(REPO, rel), encoding="utf-8") as f:
            ctxs.append(_FileCtx(rel, f.read(), Config()))
    sites = find_donation_sites(ctxs)
    assert {path for path, _lineno, _argnums in sites} == expected
    assert all(argnums for _path, _lineno, argnums in sites)


# --------------------------------------- baseline & CLI, new rules


def test_update_baseline_refuses_lock_order_inversion(tmp_path, capsys):
    """An inversion is a latent deadlock, never a legacy wart: the
    baseline updater hard-errors instead of grandfathering it."""
    import shutil

    from tools.mxlint import main

    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir(parents=True)
    shutil.copy(os.path.join(FIXTURES, "bad_threads.py"),
                str(pkg / "racy.py"))
    bl = str(tmp_path / "bl.json")
    rc = main([str(pkg), "--baseline", bl,
               "--rules", "thread-lock-order", "--update-baseline"])
    err = capsys.readouterr().err
    assert rc == 2
    assert "refusing to baseline a lock-order inversion" in err
    assert not os.path.exists(bl)   # nothing was grandfathered


def test_cli_github_format_new_rules(tmp_path, capsys):
    """The github annotations are rule-generic: thread findings come
    out as ::error lines with the rule in the title."""
    import shutil

    from tools.mxlint import main

    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir(parents=True)
    shutil.copy(os.path.join(FIXTURES, "bad_threads.py"),
                str(pkg / "racy.py"))
    rc = main([str(pkg), "--no-baseline", "--format", "github",
               "--rules", "thread-shared-state,thread-lock-order"])
    out = capsys.readouterr().out
    assert rc == 1
    lines = [ln for ln in out.splitlines()
             if ln.startswith("::error file=")]
    assert len(lines) == 4
    assert sum("title=mxlint thread-shared-state" in ln
               for ln in lines) == 3
    assert sum("title=mxlint thread-lock-order" in ln
               for ln in lines) == 1
