"""Perf doctor — ranked bottleneck findings from a trace + diag dump.

The observability layers (PR 2/3/5/7) record what happened; this module
*interprets* it: given a chrome trace (``MXNET_TPU_PROFILE``) and/or a
diag dump (``MXNET_TPU_DIAG``), :func:`diagnose` returns findings
**ranked by estimated share of step time**, each naming the concrete
span/op/shard it indicts and a concrete next action — the
measure-compare-decide loop the autotune roadmap item needs (TVM,
arXiv:1802.04799) and the fusion/idle-gap lens of XLA perf work
(arXiv:2301.13062), automated so every perf PR ships with a verdict
instead of a hand-read trace.

Rules
-----
- **step-anatomy shares** — a phase (data wait, allreduce/kvstore,
  optimizer update, checkpoint snapshot) eating an outsized share of
  the per-step wall time (``stepstats`` section of the dump).
- **recompile storms** — ops compiling past the storm threshold, with
  the churned attr/aval evidence from ``recent_storm_keys`` and the
  compile share of step time.
- **eager dispatch tax** — warm per-op dispatch (+ compile) dominating
  an eager run's step time: recommends the compiled whole-step path
  (``trainer.compile``) with projected savings derived from the
  warm-dispatch counters.
- **host-sync stalls** — monitor/health host-sync seconds on the hot
  path (the deliberate sync sinks, when their cost stops being small).
- **idle gaps inside steps** — wall time inside ``trainer:step`` spans
  covered by NO recorded span (untracked host work or device waits),
  from the chrome trace.
- **roofline headroom** — the top profiled ops whose cache-warm
  dispatch time sits far above their cost-model roofline bound.
- **kvstore stragglers** — one PS shard's push/pull RTT p99 an outlier
  vs the other shards' median (``histogram.median_of_others``).
- **serving** — ``serve-queue-dominated`` (queue-wait p99 past
  ``SERVE_QUEUE_RATIO`` x the batch-compute p99: this replica is past
  capacity) and ``serve-bucket-churn`` (bucket executables rebuilt past
  the one-per-bucket warmup) from an ``InferenceServer`` run's dump.
- **kvstore self-healing** — dead-shard heartbeat warnings
  (``kvstore_dead_shard_warnings``: a PS shard went unresponsive past
  ``MXNET_TPU_KV_DEADLINE``) and server-side duplicate suppression
  (``kvstore_dup_suppressed`` on a server's dump: retried mutations
  were acked from the exactly-once table instead of re-applying — the
  fingerprint of reply loss / restart drills).
- **fused-step x-ray** (PR 15, ``xray`` section of the dump) —
  ``xray-scope-dominated`` (one Gluon block's fwd+bwd scopes carry
  most of the fused program's flops/bytes, named by path),
  ``xray-zero-collective-share`` (collective vs compute bytes inside
  the ZeRO program, docs/ZERO.md "When not to shard") and
  ``xray-optimizer-share`` (the fused update region's bytes dominate:
  state-dtype/sharding check).

Trend rules (PR 10) run over a **timeline** — the per-step time series
``metrics_timeline`` records (its live ring, a ``MXNET_TPU_METRICS``
JSONL file, or the ``timeline`` section of a diag dump):

- **timeline-leak** — monotonic live-device-bytes growth past a slope
  threshold: the signature of retained NDArrays / autograd graphs that
  OOMs a long run at step 400k, invisible to any single snapshot.
- **timeline-throughput** — the recent window's mean step wall time vs
  the early window's: sustained decay (fragmentation, queue buildup,
  input starvation), with the fastest-growing phase named when the
  samples carry a stepstats breakdown.
- **timeline-spikes** — step-time spikes vs the series median, with
  periodicity detection (a spike every N steps is a cadence —
  checkpoint, eval, logging) and the offending phase named.
- **timeline-kv-drift** — one kv push/pull-RTT series' windowed p99
  drifting up over the run, per shard: the *emerging* straggler the
  end-of-run skew report only catches after the damage.

Findings are ``{"rule", "severity": "warn"|"info", "score",
"title", "anchor", "evidence": [...], "action"}`` — ``score`` is the
estimated fraction of step time at stake (what the ranking sorts by),
``anchor`` the span/op/rank/shard name the evidence points at.

CLI: ``python tools/diagnose.py --doctor <trace.json|diag.json ...>``
(``--format github`` emits ``::error``/``::notice`` workflow
annotations, the mxlint convention).
"""

from __future__ import annotations

from . import histogram as _histogram
from . import runtime_stats as _rts
from . import slo as _slo
from . import stepstats as _stepstats

__all__ = ["diagnose", "classify", "render", "render_github",
           "gh_annotation", "live_dump", "live_findings",
           "SHARE_NOTICE", "SHARE_WARN",
           "HEADROOM_RATIO", "IDLE_GAP_SHARE", "TREND_MIN_SAMPLES",
           "TREND_SLOWDOWN", "LEAK_SLOPE_BYTES", "SPIKE_RATIO",
           "KV_DRIFT_RATIO", "SERVE_QUEUE_RATIO", "SERVE_MIN_REQUESTS",
           "XRAY_DOMINANT_SHARE", "XRAY_ZERO_COLL_SHARE",
           "XRAY_OPT_SHARE"]

# a phase/rule at or above this share of step time is worth a line /
# a warning; tunable per call via diagnose(..., notice=, warn=)
SHARE_NOTICE = 0.10
SHARE_WARN = 0.25
# host-sync sinks are meant to be cheap: flag earlier
SYNC_SHARE_NOTICE = 0.05
# an op is "far off its roofline" when headroom exceeds this fraction
# of its dispatch time AND it carries a meaningful share of total time
HEADROOM_RATIO = 0.5
# untracked time inside trainer:step spans worth flagging
IDLE_GAP_SHARE = 0.20

# ---- trend-rule knobs (timeline series) --------------------------------
# samples below this leave every trend rule silent (too little signal)
TREND_MIN_SAMPLES = 8
# late-window mean step wall must exceed the early window's by this
# fraction before the throughput rule fires (0.5 = 50% slower)
TREND_SLOWDOWN = 0.5
# live-bytes leak: regression slope past this many bytes/step AND total
# growth past LEAK_MIN_GROWTH AND mostly-nondecreasing deltas
LEAK_SLOPE_BYTES = 4096.0
LEAK_MIN_GROWTH = 1 << 20
LEAK_MONOTONIC_FRAC = 0.6
# step-time spikes: > SPIKE_RATIO x the series median, at least
# SPIKE_MIN_COUNT of them past the warmup tail, carrying at least
# SPIKE_MIN_SHARE of the windowed wall time
SPIKE_RATIO = 4.0
SPIKE_MIN_COUNT = 2
SPIKE_WARMUP = 3
SPIKE_MIN_SHARE = 0.10
# a kv-RTT series' late-window mean p99 / early-window mean p99 past
# this ratio is drift
KV_DRIFT_RATIO = 2.0

# ---- serving-rule knobs (InferenceServer dumps) ------------------------
# queue-wait p99 past this multiple of the batch-compute p99 means the
# server is queue-dominated: requests wait longer than they compute
SERVE_QUEUE_RATIO = 2.0
# served requests below this leave the serving rules silent (a handful
# of warmup requests carries no operating-point signal)
SERVE_MIN_REQUESTS = 32

# ---- ZeRO-sharding knobs (parallel/gluon_step.py zero=True runs) -------
# the per-step parameter all-gather past this fraction of the compiled
# step's total bytes-accessed means collectives dominate the traffic the
# sharding saves in state — the model is too small (or the per-device
# batch too thin) for the current dp width
ZERO_AG_RATIO = 0.5

# ---- fused-step x-ray knobs (xray.py per-scope tables) -----------------
# one block scope at or above this share of the whole program's flops
# OR bytes dominates the fused step — name it so the next perf PR
# knows where to aim; warns when it crosses XRAY_DOMINANT_WARN
XRAY_DOMINANT_SHARE = 0.5
XRAY_DOMINANT_WARN = 0.75
# collective traffic inside the ZeRO program past this fraction of the
# forward+backward scopes' bytes (the compute the gather feeds) means
# the sharding's data movement rivals the math — the in-program cousin
# of ZERO_AG_RATIO
XRAY_ZERO_COLL_SHARE = 0.5
# the fused optimizer-update region moving more than this fraction of
# program bytes means the step is state-bound, not math-bound
XRAY_OPT_SHARE = 0.4


def classify(path):
    """Load ``path`` and say what it is: ``("trace", data)`` for a
    chrome trace, ``("dump", data)`` for a diag dump / snapshot, or
    ``("timeline", {"samples": [...]})`` for a metrics-timeline source
    (``MXNET_TPU_METRICS`` JSONL — even a one-line file — or a bare
    JSON sample array).  A file that is neither JSON nor JSONL raises
    ``ValueError`` — a corrupt input must never read as a finding-free
    clean run."""
    from . import metrics_timeline as _mt

    with open(path) as f:
        text = f.read()
    kind, data = _mt.sniff_text(text, path=path)
    if kind != "trace":
        data.setdefault("_path", path)
    return kind, data


def _finding(rule, score, title, anchor, evidence, action,
             warn_at=SHARE_WARN):
    return {"rule": rule, "score": float(score),
            "severity": "warn" if score >= warn_at else "info",
            "title": title, "anchor": anchor,
            "evidence": list(evidence), "action": action}


# ------------------------------------------------------------ dump rules


def _anatomy_of(dump):
    snap = dump.get("snapshot", dump)
    return _stepstats.anatomy(snap.get("stepstats") or {})


def _check_step_anatomy(dump):
    """Phase-share findings: the phases an operator can act on
    directly (data wait / kvstore / optimizer / checkpoint /
    unattributed remainder)."""
    a = _anatomy_of(dump)
    if not a.get("steps"):
        return []
    actions = {
        "data_wait": "overlap input with compute (PrefetchingIter / "
                     "wider io workers) or cache preprocessing "
                     "(docs/OBSERVABILITY.md 'Step anatomy')",
        "kvstore": "check shard placement and gradient sizes; compare "
                   "push/pull RTT histograms per shard (--cluster for "
                   "multi-rank runs)",
        "optimizer_update": "fuse the update (update_on_kvstore or the "
                            "multi-tensor optimizer ops) or batch "
                            "small parameters",
        "checkpoint_write": "raise MXNET_TPU_CKPT_INTERVAL or keep "
                            "MXNET_TPU_CKPT_ASYNC=1 (the capture "
                            "should be microseconds; a large share "
                            "means sync mode or host-resident params)",
        "unattributed": "wall time no instrumented phase covers: "
                        "profile with MXNET_TPU_PROFILE and look for "
                        "host syncs / untracked user code between "
                        "spans (tools/mxlint host-sync-reachability)",
    }
    out = []
    for phase, action in actions.items():
        d = a["phases"].get(phase) if phase != "unattributed" \
            else a.get("unattributed")
        if not d or d["share"] < SHARE_NOTICE:
            continue
        out.append(_finding(
            "step-anatomy", d["share"],
            "%s is %.0f%% of step time"
            % (_stepstats.PHASE_LABELS.get(phase, phase),
               d["share"] * 100),
            phase,
            ["per-step mean %.3f ms (p99 %.3f ms) over %d step(s); "
             "step wall mean %.3f ms"
             % (d["mean_ms"] or 0, d["p99_ms"] or 0, a["steps"],
                a["step_wall_ms"]["mean_ms"] or 0)],
            action))
    return out


def _check_recompiles(dump):
    """Recompile storms: per-op compile counts past the storm
    threshold, scored by the compile phase's share of step time."""
    snap = dump.get("snapshot", dump)
    storms = snap.get("storms") or {}
    threshold = _rts.STORM_THRESHOLD or 8
    hot = {name: st for name, st in storms.items()
           if st.get("compiles", 0) > threshold
           or st.get("distinct_avals", 0) > threshold}
    if not hot:
        return []
    a = _anatomy_of(dump)
    compile_share = (a.get("phases", {}).get("compile") or
                     {}).get("share")
    if compile_share is None:
        # no anatomy in the dump: fall back to compile seconds vs
        # profiled dispatch+compile time (coarse, but still ranks)
        totals = snap.get("totals") or {}
        denom = (totals.get("dispatch_seconds") or 0.0) \
            + (totals.get("compile_seconds") or 0.0)
        compile_share = (totals.get("compile_seconds", 0.0) / denom) \
            if denom else 0.5
    worst = max(hot, key=lambda n: hot[n].get("compiles", 0))
    keys = (dump.get("recent_storm_keys") or {}).get(worst) or []
    evidence = ["%s: %d compile(s), %d distinct input signature(s)"
                % (name, st.get("compiles", 0),
                   st.get("distinct_avals", 0))
                for name, st in sorted(
                    hot.items(), key=lambda kv: -kv[1]["compiles"])]
    if keys:
        evidence.append("recent %s cache keys: %s"
                        % (worst, "; ".join(keys[-3:])))
    return [_finding(
        "recompile-storm", compile_share,
        "recompile storm: %d op(s), worst %r (%d compiles) — "
        "compile is %.0f%% of step time"
        % (len(hot), worst, hot[worst].get("compiles", 0),
           compile_share * 100),
        worst, evidence,
        "hoist the churning attr into traced_attrs or stabilize input "
        "shapes — every recompile stalls dispatch for a full XLA "
        "compile (docs/OBSERVABILITY.md 'Recompile-storm detector')")]


def _check_eager_dispatch(dump):
    """Eager per-op dispatch tax: warm dispatch (+ compile) dominating
    the step while the run never used the compiled whole-step path —
    the exact profile ``trainer.compile`` exists for
    (compiled_step.py: fwd+bwd+update traced into ONE donated XLA
    program, ~1 warm dispatch per step instead of one per op).
    Projected savings derive from the warm-dispatch counters: of the
    measured ``dispatch_warm`` share, a compiled step keeps roughly
    1/calls-per-step (one remaining dispatch) and fuses the rest."""
    snap = dump.get("snapshot", dump)
    counters = snap.get("counters") or {}
    if counters.get("compiled_step_steps"):
        return []  # the run already trains through the compiled path
    steps = counters.get("trainer_steps", 0)
    if not steps:
        return []
    a = _anatomy_of(dump)
    if not a.get("steps"):
        return []
    dw = (a["phases"].get("dispatch_warm") or {}).get("share") or 0.0
    comp = (a["phases"].get("compile") or {}).get("share") or 0.0
    share = dw + comp
    if share < SHARE_WARN:
        return []
    totals = snap.get("totals") or {}
    warm = totals.get("jit_cache_hits", 0)
    calls_per_step = warm / steps
    if calls_per_step < 2:
        return []  # already ~one dispatch per step: nothing to collapse
    projected = dw * (1.0 - 1.0 / calls_per_step)
    dw_ms = (a["phases"].get("dispatch_warm") or {}).get("mean_ms") or 0.0
    return [_finding(
        "eager-dispatch-tax", share,
        "eager dispatch is %.0f%% of step time (%.0f warm op "
        "dispatches/step) — whole-step compilation would collapse "
        "them to ~1, saving ~%.0f%% of step time"
        % (share * 100, calls_per_step, projected * 100),
        "dispatch_warm",
        ["%d warm jit-cache hits over %d step(s): %.1f dispatches/"
         "step at %.3f ms/step of warm-dispatch wall"
         % (warm, steps, calls_per_step, dw_ms),
         "compile share %.0f%% also amortizes to one program per "
         "input signature under the compiled step" % (comp * 100)],
        "train through the fused whole-step program: "
        "cs = trainer.compile(net, loss); cs.step(x, y) "
        "(docs/COMPILED_STEP.md); the eager path remains the "
        "debugging/interop mode")]


def _check_host_sync(dump):
    """Deliberate host-sync sinks (monitor stats, health drain) whose
    per-step cost stopped being small."""
    snap = dump.get("snapshot", dump)
    counters = snap.get("counters") or {}
    a = _anatomy_of(dump)
    wall_sum_ms = (a.get("step_wall_ms") or {}).get("sum_ms") \
        if a.get("steps") else None
    out = []
    for counter, anchor, what, action in (
            ("monitor_seconds", "monitor:stat",
             "Monitor stat host-syncs",
             "drop the Monitor (or raise its interval) for production "
             "runs; the default stat path is device-resident but "
             "toc() still syncs"),
            ("health_seconds", "health:drain",
             "numerics-health drains",
             "raise MXNET_TPU_HEALTH_INTERVAL or trim "
             "MXNET_TPU_HEALTH_STATS — the drain is the layer's one "
             "deliberate sync")):
        secs = counters.get(counter, 0.0)
        if not secs:
            continue
        if wall_sum_ms:
            share = (secs * 1e3) / wall_sum_ms
        else:
            continue  # no step clock: cannot rank, skip
        if share < SYNC_SHARE_NOTICE:
            continue
        out.append(_finding(
            "host-sync", share,
            "%s are %.0f%% of step time" % (what, share * 100),
            anchor,
            ["%s=%.3fs over %d step(s)"
             % (counter, secs, a["steps"])],
            action, warn_at=2 * SYNC_SHARE_NOTICE))
    return out


def _check_roofline(dump, top=3):
    """Top profiled ops sitting far above their cost-model roofline
    bound, weighted by their share of total profiled dispatch time."""
    snap = dump.get("snapshot", dump)
    rows = dump.get("roofline") or _rts.roofline(snap)
    totals = snap.get("totals") or {}
    total_secs = totals.get("dispatch_seconds") or 0.0
    if not total_secs:
        return []
    # scores are "share of step time": scale each op's share of the
    # profiled dispatch time by dispatch_warm's share of the step when
    # the anatomy is available (dispatch is only part of a step)
    a = _anatomy_of(dump)
    dispatch_share = (a.get("phases", {}).get("dispatch_warm")
                      or {}).get("share", 1.0) if a.get("steps") else 1.0
    culprits = []
    for r in rows:
        if "headroom_us" not in r or "us_per_call" not in r:
            continue
        if r["headroom_us"] < HEADROOM_RATIO * r["us_per_call"]:
            continue
        op = (snap.get("ops") or {}).get(r["op"]) or {}
        op_secs = op.get("dispatch_seconds", 0.0)
        share = op_secs / total_secs
        if share < SHARE_NOTICE / 2:
            continue
        culprits.append((share * dispatch_share, share, r))
    if not culprits:
        return []
    culprits.sort(key=lambda sr: -sr[0])
    culprits = culprits[:top]
    total_share = sum(s for s, _, _ in culprits)
    worst = culprits[0][2]
    evidence = []
    for _score, share, r in culprits:
        evidence.append(
            "%s: %.1f us/call vs %.1f us roofline bound (%.0f us "
            "headroom/call, %.0f%% of profiled dispatch time%s)"
            % (r["op"], r["us_per_call"], r.get("bound_us", 0.0),
               r["headroom_us"], share * 100,
               (", %.1f GB/s achieved" % r["achieved_gbps"])
               if r.get("achieved_gbps") else ""))
    return [_finding(
        "roofline-headroom", total_share,
        "%d op(s) far above their roofline bound, worst %r"
        % (len(culprits), worst["op"]),
        worst["op"], evidence,
        "these are cache-warm HOST dispatch rates — confirm with a "
        "measured device trace (a benchmark cell's `--trace 1` run), "
        "then fuse/batch the op or fix its layout")]


def _check_stragglers(dump):
    """One PS shard's RTT p99 an outlier vs the other shards — the
    single-rank view of the cluster straggler check (per-shard
    ``kv:push_rtt:shardN`` / ``kv:pull_rtt:shardN`` histograms)."""
    snap = dump.get("snapshot", dump)
    hists = snap.get("histograms") or {}
    out = []
    for op in ("push", "pull"):
        prefix = "kv:%s_rtt:shard" % op
        group = [(name, h) for name, h in hists.items()
                 if name.startswith(prefix)
                 and h.get("p99") is not None]
        if len(group) < 2:
            continue
        worst_name, worst = max(group, key=lambda nh: nh[1]["p99"])
        med = _histogram.median_of_others(
            [(n, h["p99"]) for n, h in group], worst_name)
        if not med or med <= 0:
            continue
        ratio = worst["p99"] / med
        if ratio <= _histogram.STRAGGLER_RATIO:
            continue
        a = _anatomy_of(dump)
        kv_share = (a.get("phases", {}).get("kvstore") or {}).get(
            "share", 0.0) if a.get("steps") else 0.0
        out.append(_finding(
            "kvstore-straggler", max(kv_share, SHARE_NOTICE),
            "PS shard straggler: %s p99 %.1f ms is %.1fx the other "
            "shards' median"
            % (worst_name, worst["p99"] * 1e3, ratio),
            worst_name,
            ["%s p99 %.3f ms vs median-of-others %.3f ms over %d "
             "sample(s)" % (worst_name, worst["p99"] * 1e3, med * 1e3,
                            worst.get("count", 0))],
            "investigate that shard's host/network; kvstore waits "
            "serialize the step (docs/OBSERVABILITY.md 'Distributed "
            "telemetry'; cross-rank view: diagnose.py --cluster)"))
    return out


def _check_retries(dump):
    snap = dump.get("snapshot", dump)
    counters = snap.get("counters") or {}
    retries = counters.get("kvstore_retries", 0)
    if not retries:
        return []
    return [_finding(
        "kvstore-retries", SHARE_NOTICE / 2,
        "%d kvstore retry(ies) (%d reconnect(s)) during the run"
        % (retries, counters.get("kvstore_reconnects", 0)),
        "kvstore",
        ["each retry adds a full backoff to some step's push/pull"],
        "check PS server health/logs; transient faults are retried "
        "with backoff but still stall the step "
        "(docs/CHECKPOINTING.md 'Dist kvstore hardening')")]


def _check_self_healing(dump):
    """Self-healing signals: dead-shard heartbeat warnings (a PS shard
    silent past MXNET_TPU_KV_DEADLINE — worker dumps) and server-side
    duplicate suppression (retried mutations acked from the
    exactly-once seq table — server dumps), so recovery drills and
    real incidents both show up in the doctor report."""
    snap = dump.get("snapshot", dump)
    counters = snap.get("counters") or {}
    out = []
    dead = counters.get("kvstore_dead_shard_warnings", 0)
    if dead:
        out.append(_finding(
            "kvstore-dead-shard", SHARE_WARN,
            "%d dead-shard warning(s): a PS shard went unresponsive "
            "past MXNET_TPU_KV_DEADLINE" % dead,
            "kvstore",
            ["every deadline window a shard stays silent, pushes to it "
             "sit in the retry/backoff ladder"],
            "check that server process/host; run under tools/launch.py "
            "with MXNET_TPU_SUPERVISE=N so a dead server is relaunched "
            "and self-restores from its durable shard checkpoint "
            "(docs/CHECKPOINTING.md 'Server-side durability')"))
    dup = counters.get("kvstore_dup_suppressed", 0)
    if dup:
        restores = counters.get("kvstore_server_restores", 0)
        evidence = ["reply-loss retries were acked from the "
                    "(client_id, seq) table without re-applying — "
                    "exactly-once held"]
        if restores:
            evidence.append("%d store restore(s) from the durable "
                            "shard manifest this run" % restores)
        out.append(_finding(
            "kvstore-dedup", SHARE_NOTICE / 4,
            "%d retried mutation(s) suppressed as duplicate(s) "
            "server-side" % dup,
            "kvstore", evidence,
            "expected during reply_drop/restart_after drills; in "
            "production it means replies are being lost — check the "
            "network and server load (docs/CHECKPOINTING.md "
            "'Server-side durability')"))
    return out


def _check_zero_allgather(dump):
    """ZeRO weight-update sharding: the per-step parameter all-gather
    is pure overhead bought to shrink per-device state ~n×.  When it
    moves more than ``ZERO_AG_RATIO`` of the compiled step's total
    bytes-accessed, the trade has inverted — the collectives cost more
    traffic than the forward/backward math moves, the signature of a
    model too small (or a per-device batch too thin) for the dp width.
    """
    snap = dump.get("snapshot", dump)
    counters = snap.get("counters") or {}
    zsteps = counters.get("zero_steps", 0)
    ag = counters.get("zero_allgather_bytes", 0)
    if not zsteps or not ag:
        return []
    per_step = ag / zsteps
    bpc = ((snap.get("costs") or {}).get("compiled_step") or {}).get(
        "bytes_per_call")
    if not bpc:
        return []
    share = per_step / bpc
    if share < ZERO_AG_RATIO:
        return []
    rs = counters.get("zero_reduce_bytes", 0)
    return [_finding(
        "zero-allgather-dominated", min(share, 1.0),
        "ZeRO param all-gather moves %.0f%% of the compiled step's "
        "bytes-accessed (%.1f MB/step of %.1f MB/step)"
        % (share * 100, per_step / 1e6, bpc / 1e6),
        "zero",
        ["%.1f MB/step all-gather + %.1f MB/step reduce-scatter over "
         "%d zero step(s); compiled-step cost model reads %.1f "
         "MB/step total" % (per_step / 1e6,
                            rs / zsteps / 1e6, zsteps, bpc / 1e6)],
        "raise the per-device batch (amortizes the gather over more "
        "math), shrink the dp width, or drop zero=True — at this "
        "model size replicated state is cheaper than the collectives "
        "(docs/ZERO.md 'When not to shard')")]


# ---------------------------------------------------------- x-ray rules


def _xray_newest(dump, zero=None):
    """The newest x-ray table in ``dump`` (optionally restricted to
    zero / non-zero programs), or None."""
    snap = dump.get("snapshot", dump)
    programs = ((snap.get("xray") or {}).get("programs")) or []
    if zero is not None:
        programs = [t for t in programs if bool(t.get("zero")) == zero]
    return programs[-1] if programs else None


def _check_xray_scope(dump):
    """**xray-scope-dominated** — one block's scope (forward+backward
    summed) carries ``XRAY_DOMINANT_SHARE`` of the fused program's
    flops or bytes: the named block is where the step's cost lives."""
    t = _xray_newest(dump)
    if t is None:
        return []
    blocks = {}
    for scope, rec in (t.get("scopes") or {}).items():
        if scope.startswith("forward/"):
            path = scope[len("forward/"):]
        elif scope.startswith("backward/"):
            path = scope[len("backward/"):]
        else:
            continue  # optimizer / zero_* regions have their own rules
        agg = blocks.setdefault(path, {"flops": 0.0, "bytes": 0.0})
        agg["flops"] += rec.get("flops_share") or 0.0
        agg["bytes"] += rec.get("bytes_share") or 0.0
    if not blocks:
        return []
    path, agg = max(blocks.items(),
                    key=lambda kv: max(kv[1]["flops"], kv[1]["bytes"]))
    share = max(agg["flops"], agg["bytes"])
    if share < XRAY_DOMINANT_SHARE:
        return []
    return [_finding(
        "xray-scope-dominated", min(share, 1.0),
        "block '%s' carries %.0f%% of the fused program's %s"
        % (path, share * 100,
           "flops" if agg["flops"] >= agg["bytes"] else "bytes"),
        path,
        ["fwd+bwd share of program %s: flops %.0f%%, bytes %.0f%% "
         "(x-ray of %s, %d instruction(s))"
         % (t.get("label", "compiled_step"), agg["flops"] * 100,
            agg["bytes"] * 100, t.get("label", "compiled_step"),
            t.get("instructions", 0))],
        "this block is the fused step — aim kernel/layout/precision "
        "work here and cite the x-ray share in the perf PR "
        "(docs/OBSERVABILITY.md 'Fused-step X-ray')",
        warn_at=XRAY_DOMINANT_WARN)]


def _check_xray_zero_collective(dump):
    """**xray-zero-collective-share** — collective bytes vs compute
    bytes INSIDE the ZeRO program: the param all-gather / grad
    reduce-scatter traffic against the forward+backward scopes' bytes
    (the math that traffic feeds).  Prefers the HLO-measured collective
    instructions; on single-device traces (where GSPMD elides the
    collectives) it falls back to the measured per-step
    ``zero_allgather_bytes``/``zero_reduce_bytes`` counters."""
    t = _xray_newest(dump, zero=True)
    if t is None:
        return []
    scopes = t.get("scopes") or {}
    compute = sum((rec.get("bytes") or 0.0)
                  for scope, rec in scopes.items()
                  if scope.startswith(("forward/", "backward/")))
    if not compute:
        compute = (t.get("totals") or {}).get("bytes_accessed") or 0.0
    if not compute:
        return []
    coll = sum((rec.get("collective_bytes") or 0.0)
               for rec in scopes.values())
    coll += (t.get("unattributed") or {}).get("collective_bytes") or 0.0
    source = "HLO collective instructions"
    if not coll:
        snap = dump.get("snapshot", dump)
        counters = snap.get("counters") or {}
        zsteps = counters.get("zero_steps", 0)
        if zsteps:
            coll = (counters.get("zero_allgather_bytes", 0)
                    + counters.get("zero_reduce_bytes", 0)) / zsteps
            source = "zero_allgather/reduce counters (single-device " \
                     "trace: GSPMD elided the collectives)"
    if not coll:
        return []
    ratio = coll / compute
    if ratio < XRAY_ZERO_COLL_SHARE:
        return []
    # score = collectives' fraction of the combined collective+compute
    # traffic, so it stays a [0,1) share like every other rule
    return [_finding(
        "xray-zero-collective-share", coll / (coll + compute),
        "ZeRO collectives move %.0f%% of what the fwd+bwd math moves "
        "(%.1f vs %.1f MB/step)" % (ratio * 100, coll / 1e6,
                                    compute / 1e6),
        "zero",
        ["measured from %s; program %s, %d instruction(s); "
         "forward+backward scopes move %.1f MB"
         % (source, t.get("label", "zero_step"),
            t.get("instructions", 0), compute / 1e6)],
        "the sharding's data movement rivals the math it feeds: raise "
        "the per-device batch, shrink the dp width, or drop zero=True "
        "(docs/ZERO.md 'When not to shard')")]


def _check_xray_optimizer(dump):
    """**xray-optimizer-share** — the fused update region's bytes
    dominate the program: the step is optimizer-state-bound."""
    t = _xray_newest(dump)
    if t is None:
        return []
    rec = (t.get("scopes") or {}).get("optimizer")
    if not rec:
        return []
    share = rec.get("bytes_share") or 0.0
    if share < XRAY_OPT_SHARE:
        return []
    return [_finding(
        "xray-optimizer-share", min(share, 1.0),
        "the fused optimizer update moves %.0f%% of the program's "
        "bytes (%.1f of %.1f MB)"
        % (share * 100, rec.get("bytes", 0.0) / 1e6,
           ((t.get("totals") or {}).get("bytes_accessed") or 0.0)
           / 1e6),
        "optimizer",
        ["update-region flops share %.0f%%, bytes share %.0f%% "
         "(x-ray of %s)" % ((rec.get("flops_share") or 0.0) * 100,
                            share * 100,
                            t.get("label", "compiled_step"))],
        "the step is state-bound: check the optimizer state dtype "
        "(fp32 master copies double the traffic), shard the state "
        "with zero=True (docs/ZERO.md), or pick a lighter-state "
        "optimizer")]


# --------------------------------------------------------- serving rules


def _check_serving(dump):
    """Serving-layer findings from an ``InferenceServer`` run's dump:

    - **serve-queue-dominated** — the ``serve:queue_wait`` p99 exceeds
      ``SERVE_QUEUE_RATIO`` x the ``serve:batch`` compute p99: requests
      spend longer waiting for a batch slot than being computed, the
      signature of offered load past this replica's capacity.
    - **serve-bucket-churn** — more bucket-executable builds than the
      ladder has buckets past warmup: executables are being rebuilt
      (reconstructed servers, shape churn reaching the build path),
      each one a full XLA compile on the serving path.
    """
    snap = dump.get("snapshot", dump)
    serving = snap.get("serving") or {}
    counters = snap.get("counters") or {}
    hists = snap.get("histograms") or {}
    requests = serving.get("requests") or counters.get(
        "serve_requests", 0)
    if not requests:
        return []
    out = []
    qw = hists.get("serve:queue_wait") or {}
    batch = hists.get("serve:batch") or {}
    e2e = hists.get("serve:e2e") or {}
    if requests >= SERVE_MIN_REQUESTS and qw.get("p99") \
            and batch.get("p99"):
        ratio = qw["p99"] / batch["p99"]
        if ratio > SERVE_QUEUE_RATIO:
            # score = the fraction of a served request's life spent
            # queueing (the serving analog of "share of step time")
            share = (qw["mean"] / e2e["mean"]) \
                if e2e.get("mean") else min(1.0, ratio / 10.0)
            occ = serving.get("mean_occupancy")
            evidence = [
                "queue_wait p99 %.3f ms vs batch compute p99 %.3f ms "
                "(%.1fx) over %d request(s)"
                % (qw["p99"] * 1e3, batch["p99"] * 1e3, ratio,
                   requests)]
            if e2e.get("p99") is not None:
                evidence.append("end-to-end p99 %.3f ms"
                                % (e2e["p99"] * 1e3))
            if occ is not None:
                evidence.append("mean bucket occupancy %.0f%% (ladder "
                                "%s)" % (occ * 100,
                                         serving.get("buckets")))
            out.append(_finding(
                "serve-queue-dominated", share,
                "serving is queue-dominated: queue-wait p99 is %.1fx "
                "the batch-compute p99" % ratio,
                "serve:queue_wait", evidence,
                "this replica is past capacity — raise the max bucket "
                "(bigger batches amortize dispatch), add a replica "
                "behind the load balancer, or shed load earlier with a "
                "smaller MXNET_TPU_SERVE_QUEUE (docs/SERVING.md "
                "'Latency SLOs')"))
    # take the MAX of the newest server's section and the process-wide
    # counters: a process re-creating servers per batch (the exact
    # churn scenario) shows a small per-server section value while the
    # cumulative counter carries the real build count
    compiles = max(serving.get("bucket_compiles") or 0,
                   counters.get("serve_bucket_compiles", 0))
    ladder = serving.get("buckets") or []
    batches = max(serving.get("batches") or 0,
                  counters.get("serve_batches", 0))
    # guard only on having SERVED something (a warmup-only process
    # compiles <= len(ladder) and stays silent anyway); requiring
    # batches > compiles would mute exactly the worst churn —
    # server-per-batch recreation compiles the ladder per batch
    if ladder and compiles > len(ladder) and batches:
        extra = compiles - len(ladder)
        out.append(_finding(
            "serve-bucket-churn", SHARE_NOTICE * min(4.0, extra),
            "bucket-executable churn: %d build(s) for a %d-bucket "
            "ladder" % (compiles, len(ladder)),
            "serve_bucket_compiles",
            ["%d build(s) past the one-per-bucket warmup across %d "
             "batch(es) — every extra build is a full XLA compile on "
             "the serving path" % (extra, batches)],
            "executables should compile once per bucket and be cached "
            "for the server's life — avoid re-creating servers per "
            "request batch and keep request shapes on the configured "
            "ladder (docs/SERVING.md 'Bucket ladder')"))
    return out


def _check_slo(dump):
    """SLO / error-budget findings over the ``slo`` section (the
    multi-window burn-rate evaluation ``mxnet_tpu/slo.py`` bakes into
    every snapshot/diag dump):

    - **slo-fast-burn** — an objective's fast window pair (5m/1h,
      scaled) both burn at >= ``slo.FAST_BURN`` (14.4): at that rate a
      30-day error budget is gone in hours.  The page-now signal, and
      the trigger of the ``MXNET_TPU_AUTOPILOT_SLO`` reflex.
    - **slo-budget-exhausted** — the objective's whole error budget is
      already spent over the observed run: every further bad event is
      an SLO violation in the open.
    """
    snap = dump.get("snapshot", dump)
    slo = snap.get("slo") or {}
    out = []
    for ob in slo.get("objectives") or []:
        name = ob.get("name")
        budget = 1.0 - (ob.get("target") or 0.0)
        w = ob.get("windows") or {}
        b5 = (w.get("5m") or {}).get("burn", 0.0)
        b1h = (w.get("1h") or {}).get("burn", 0.0)
        rem = ob.get("budget_remaining")
        if ob.get("fast_burn"):
            # score 0.5 at the firing threshold, saturating at 2x it —
            # a fast burn is always at least a warn
            score = min(1.0, max(b5, b1h) / (2.0 * _slo.FAST_BURN))
            evidence = [
                "fast pair burning: 5m burn %.1f (%d event(s)), 1h "
                "burn %.1f (%d event(s)) — both >= %.1f"
                % (b5, (w.get("5m") or {}).get("events", 0), b1h,
                   (w.get("1h") or {}).get("events", 0),
                   _slo.FAST_BURN),
                "objective %s: target %.5g%%, budget %.5g%%, %d good /"
                " %d bad" % (name, (ob.get("target") or 0) * 100,
                             budget * 100, ob.get("good", 0),
                             ob.get("bad", 0))]
            if rem is not None:
                evidence.append("error budget remaining %.1f%%"
                                % (rem * 100))
            out.append(_finding(
                "slo-fast-burn", score,
                "SLO %r fast burn: spending error budget at %.1fx the "
                "sustainable rate" % (name, max(b5, b1h)),
                "slo:%s" % name, evidence,
                "act now — shed load (smaller MXNET_TPU_SERVE_QUEUE), "
                "add capacity, or roll back the last change; the "
                "MXNET_TPU_AUTOPILOT_SLO reflex can nudge the serving "
                "knobs (dry-run unless armed; docs/OBSERVABILITY.md "
                "'Request x-ray & SLOs')"))
        if rem is not None and rem <= 0.0 \
                and (ob.get("total") or 0) >= _slo.MIN_EVENTS:
            out.append(_finding(
                "slo-budget-exhausted", min(1.0, 0.5 - rem),
                "SLO %r error budget exhausted (%.1f%% remaining)"
                % (name, rem * 100),
                "slo:%s" % name,
                ["%d bad of %d event(s) vs a %.5g%% budget"
                 % (ob.get("bad", 0), ob.get("total", 0),
                    budget * 100)],
                "the objective is blown for this window — freeze risky "
                "rollouts, fix the dominant bad-outcome class (see the "
                "per-outcome breakdown in the serving section / "
                "diagnose.py --requests), and let the budget recover"))
    return out


# ----------------------------------------------------------- trend rules


def _lin_slope(xs, ys):
    """Least-squares slope of ys over xs (0 for a degenerate x span)."""
    n = len(xs)
    mx = sum(xs) / n
    my = sum(ys) / n
    den = sum((x - mx) ** 2 for x in xs)
    if not den:
        return 0.0
    return sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / den


def _window_means(vals):
    """``(early mean, late mean, window size)`` over the first/last
    quarter of the series (min 3 samples per window)."""
    k = max(3, len(vals) // 4)
    early = vals[:k]
    late = vals[-k:]
    return sum(early) / len(early), sum(late) / len(late), k


def _phase_means(samples):
    """Per-phase mean ms over samples that carry a stepstats window."""
    sums: dict = {}
    counts: dict = {}
    for s in samples:
        for p, v in (s.get("phases_ms") or {}).items():
            sums[p] = sums.get(p, 0.0) + v
            counts[p] = counts.get(p, 0) + 1
    return {p: sums[p] / counts[p] for p in sums}


def _grown_phase(early_samples, late_samples):
    """``(phase, early ms, late ms)`` of the phase whose mean grew the
    most between the windows, or None without phase data."""
    early = _phase_means(early_samples)
    late = _phase_means(late_samples)
    best = None
    for p, lv in late.items():
        ev = early.get(p, 0.0)
        if best is None or lv - ev > best[2] - best[1]:
            best = (p, ev, lv)
    if best is None or best[2] <= best[1]:
        return None
    return best


def _check_leak(samples):
    """Monotonic live-device-bytes growth: the leak signature no single
    snapshot can see.  Needs the device-memory tracker feeding the
    samples (``MXNET_TPU_DIAG`` / ``MXNET_TPU_MEMORY_TRACK=1``)."""
    pts = [(s.get("step", i), s["live_bytes"])
           for i, s in enumerate(samples)
           if s.get("live_bytes") is not None]
    if len(pts) < TREND_MIN_SAMPLES:
        return []
    xs = [p[0] for p in pts]
    ys = [p[1] for p in pts]
    growth = ys[-1] - ys[0]
    slope = _lin_slope(xs, ys)
    nondec = sum(1 for a, b in zip(ys, ys[1:]) if b >= a) \
        / max(1, len(ys) - 1)
    if slope < LEAK_SLOPE_BYTES or growth < LEAK_MIN_GROWTH \
            or nondec < LEAK_MONOTONIC_FRAC:
        return []
    steps = max(1, xs[-1] - xs[0])
    return [_finding(
        "timeline-leak", 2 * SHARE_WARN,
        "device-memory leak: live bytes grew %.1f MB over %d step(s) "
        "(%.1f KB/step slope)"
        % (growth / 1e6, steps, slope / 1e3),
        "live_bytes",
        ["live bytes %.2f MB at step %s -> %.2f MB at step %s"
         % (ys[0] / 1e6, xs[0], ys[-1] / 1e6, xs[-1]),
         "regression slope %.0f bytes/step; %.0f%% of deltas "
         "non-decreasing" % (slope, nondec * 100)],
        "find the retaining op in the dump's device-memory per-op "
        "table (python -m mxnet_tpu.runtime_stats <dump>); usual "
        "suspects: a growing Python list of NDArrays, autograd graphs "
        "kept past backward, metric state never reset "
        "(docs/OBSERVABILITY.md 'Live metrics & trends')")]


def _check_throughput(samples):
    """Sustained slowdown: recent-window mean step wall vs the early
    window's, with the fastest-growing phase named when the samples
    carry a stepstats breakdown."""
    timed = [s for s in samples if s.get("wall_ms") is not None]
    if len(timed) < TREND_MIN_SAMPLES:
        return []
    walls = [s["wall_ms"] for s in timed]
    early, late, k = _window_means(walls)
    if early <= 0:
        return []
    ratio = late / early
    if ratio < 1.0 + TREND_SLOWDOWN:
        return []
    slow_frac = 1.0 - early / late
    evidence = ["step wall mean %.3f ms (first %d sample(s)) -> "
                "%.3f ms (last %d): %.2fx" % (early, k, late, k, ratio)]
    thr = [s.get("throughput") for s in timed if s.get("throughput")]
    if len(thr) >= 2 * k:
        te = sum(thr[:k]) / k
        tl = sum(thr[-k:]) / k
        evidence.append("throughput %.1f -> %.1f samples/s" % (te, tl))
    grown = _grown_phase(timed[:k], timed[-k:])
    action = ("profile an early and a late window (MXNET_TPU_PROFILE) "
              "and diff their dumps (diagnose.py --compare); no phase "
              "attribution in these samples — enable "
              "MXNET_TPU_STEPSTATS to name the growing phase")
    anchor = "step_wall"
    if grown is not None:
        p, ev, lv = grown
        evidence.append("fastest-growing phase: %s %.3f -> %.3f "
                        "ms/step" % (p, ev, lv))
        anchor = "phase:%s" % p
        action = ("the growth sits in phase %r — check that "
                  "subsystem's inputs over time (io queue depth, kv "
                  "RTT drift, compile churn); confirm with "
                  "diagnose.py --compare on an early vs late diag dump"
                  % p)
    return [_finding(
        "timeline-throughput", slow_frac,
        "throughput regression: recent steps %.2fx slower than the "
        "early window" % ratio,
        anchor, evidence, action, warn_at=1.0 - 1.0 /
        (1.0 + TREND_SLOWDOWN))]


def _check_spikes(samples):
    """Step-time spikes vs the series median, with periodicity
    detection and the offending phase named.  The first
    ``SPIKE_WARMUP`` samples are exempt (late compiles / allocator
    warmup read as spikes otherwise)."""
    body = [s for s in samples[SPIKE_WARMUP:]
            if s.get("wall_ms") is not None]
    if len(body) < TREND_MIN_SAMPLES:
        return []
    ordered = sorted(s["wall_ms"] for s in body)
    med = ordered[len(ordered) // 2]
    if med <= 0:
        return []
    spikes = [s for s in body if s["wall_ms"] > SPIKE_RATIO * med]
    if len(spikes) < SPIKE_MIN_COUNT:
        return []
    total = sum(s["wall_ms"] for s in body)
    excess = sum(s["wall_ms"] - med for s in spikes)
    share = excess / total if total else 0.0
    if share < SPIKE_MIN_SHARE:
        return []
    steps = [s.get("step", 0) for s in spikes]
    diffs = [b - a for a, b in zip(steps, steps[1:])]
    period = None
    if diffs and diffs[0] > 1 and \
            all(abs(d - diffs[0]) <= 1 for d in diffs):
        period = diffs[0]
    worst = max(spikes, key=lambda s: s["wall_ms"])
    evidence = ["%d spike(s) > %.0fx the median step wall (%.3f ms); "
                "worst step %s at %.3f ms"
                % (len(spikes), SPIKE_RATIO, med,
                   worst.get("step", "?"), worst["wall_ms"])]
    if period:
        evidence.append("periodic: one spike every ~%d step(s) — a "
                        "cadence, not noise" % period)
    # name the phase carrying the spike: worst spike's phases vs the
    # non-spike phase means
    quiet = [s for s in body if s not in spikes]
    grown = _grown_phase(quiet, [worst])
    anchor = "step_wall"
    action = ("align the spike steps with your loop's cadences "
              "(checkpoint/eval/logging every N steps); no phase "
              "attribution in these samples — enable "
              "MXNET_TPU_STEPSTATS to name the phase")
    if grown is not None:
        p, ev, lv = grown
        evidence.append("offending phase: %s %.3f ms (quiet steps) -> "
                        "%.3f ms in the worst spike" % (p, ev, lv))
        anchor = "phase:%s" % p
        action = ("the spikes sit in phase %r — check that "
                  "subsystem's every-N-steps work (checkpoint "
                  "interval, eval loop, log flush); spread or async "
                  "it" % p)
    return [_finding(
        "timeline-spikes", share,
        "step-time spikes: %d step(s) > %.0fx the median%s"
        % (len(spikes), SPIKE_RATIO,
           (", every ~%d steps" % period) if period else ""),
        anchor, evidence, action)]


def _check_kv_drift(samples, top=3):
    """A kv push/pull-RTT series whose windowed p99 drifts up over the
    run — the emerging straggler, per shard."""
    series: dict = {}
    for s in samples:
        for name, h in (s.get("kv_rtt_ms") or {}).items():
            if h.get("p99_ms") is not None:
                series.setdefault(name, []).append(h["p99_ms"])
    out = []
    for name, vals in sorted(series.items()):
        if len(vals) < TREND_MIN_SAMPLES:
            continue
        early, late, k = _window_means(vals)
        if early <= 0:
            continue
        ratio = late / early
        if ratio <= KV_DRIFT_RATIO:
            continue
        out.append(_finding(
            "timeline-kv-drift", min(1.0, SHARE_NOTICE * ratio),
            "kv RTT drift: %s windowed p99 %.2fx its early window"
            % (name, ratio),
            name,
            ["windowed p99 mean %.3f ms (first %d sample(s)) -> "
             "%.3f ms (last %d)" % (early, k, late, k)],
            "that shard/route is degrading mid-run (host load, "
            "network, GC) — watch it live via the /metrics endpoint, "
            "cross-check ranks with diagnose.py --cluster, and see "
            "the MXNET_TPU_STRAGGLER_* warnings "
            "(docs/OBSERVABILITY.md 'Distributed telemetry')"))
    out.sort(key=lambda f: -f["score"])
    return out[:top]


def _check_timeline(samples):
    """Every trend rule over one timeline (a list of per-step sample
    dicts, oldest first)."""
    samples = [s for s in samples if isinstance(s, dict)]
    if len(samples) < TREND_MIN_SAMPLES:
        return []
    out = []
    out += _check_leak(samples)
    out += _check_throughput(samples)
    out += _check_spikes(samples)
    out += _check_kv_drift(samples)
    return out


# ----------------------------------------------------------- trace rules


def _union_us(intervals):
    """Total length of the union of (start, end) microsecond spans."""
    total = 0.0
    end = -1.0
    for s, e in sorted(intervals):
        if s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _check_idle_gaps(trace):
    """Wall time inside ``trainer:step`` spans covered by NO other
    recorded span: untracked host work, or a host-sync wait the
    framework spans cannot see."""
    events = [e for e in trace.get("traceEvents", [])
              if e.get("ph") == "X" and "ts" in e]
    steps = [e for e in events if e.get("name") == "trainer:step"]
    if not steps:
        return []
    # coverage is per process track: in a merged multi-rank trace,
    # another rank's spans must not mask this rank's gap
    others_by_pid: dict = {}
    for e in events:
        if e.get("name") != "trainer:step":
            others_by_pid.setdefault(e.get("pid", 0), []).append(
                (e["ts"], e["ts"] + e.get("dur", 0.0)))
    total_gap = 0.0
    total_dur = 0.0
    worst = (0.0, None)
    for st in steps:
        s0, s1 = st["ts"], st["ts"] + st.get("dur", 0.0)
        others = others_by_pid.get(st.get("pid", 0), ())
        covered = _union_us([(max(a, s0), min(b, s1))
                             for a, b in others if b > s0 and a < s1])
        gap = max(0.0, (s1 - s0) - covered)
        total_gap += gap
        total_dur += s1 - s0
        if gap > worst[0]:
            worst = (gap, st)
    if not total_dur:
        return []
    share = total_gap / total_dur
    if share < IDLE_GAP_SHARE:
        return []
    wev = worst[1]
    return [_finding(
        "idle-gaps", share,
        "%.0f%% of trainer:step time is covered by no span"
        % (share * 100),
        "trainer:step",
        ["total gap %.3f ms across %d step span(s); worst step at "
         "ts=%.0f us with %.3f ms untracked"
         % (total_gap / 1e3, len(steps), wev["ts"], worst[0] / 1e3)],
        "host syncs or untracked user code inside step(): profile the "
        "gap region (chrome://tracing), audit with tools/mxlint "
        "host-sync-reachability, or wrap user phases in "
        "profiler.scope()")]


# --------------------------------------------------------------- driver


def live_dump(serving=True):
    """A LIGHT synthetic dump over the live process — just the
    sections the cheap rules (:func:`_check_recompiles`,
    :func:`_check_serving`) read: storm/counter dict reads plus the
    histogram and serving snapshots.  Deliberately NOT
    ``runtime_stats.snapshot()``: no cost aggregation, no xray, no
    memory walk — this runs inside the autopilot's evaluation tick and
    the ``/metrics`` scrape.  ``serving=False`` skips the serving
    snapshot too (the training-side tick doesn't read it)."""
    import sys as _sys

    storms = {}
    storm_keys = {}
    for name, st in list(_rts._STORM.items()):
        storms[name] = {"compiles": st.get("compiles", 0),
                        "warned": st.get("warned", 0),
                        "distinct_avals": len(st.get("avals") or ())}
        storm_keys[name] = [repr(k) for k in list(st.get("keys") or ())]
    snap = {"storms": storms, "counters": dict(_rts._COUNTERS),
            "histograms": _histogram.snapshot()}
    if serving:
        _serving = _sys.modules.get("mxnet_tpu.serving")
        snap["serving"] = _serving.snapshot() if _serving is not None \
            else {"enabled": False}
        # the SLO burn verdicts ride the serving-side dump: one guard
        # read when the layer is off, a bounded ring walk when on
        snap["slo"] = _slo.snapshot()
    else:
        snap["serving"] = {"enabled": False}
        snap["slo"] = {"enabled": False}
    return {"snapshot": snap, "recent_storm_keys": storm_keys}


def live_findings(top=20):
    """Doctor findings over the LIVE process: the trend rules over
    ``metrics_timeline``'s ring plus the recompile-storm and serving
    rules over :func:`live_dump`, ranked worst-first.  This is the
    shared signal the ``mxnet_tpu_doctor_finding`` Prometheus gauges
    export and the autopilot's reflexes act on — snapshot reads only,
    and it never raises (a scrape must not take down the endpoint)."""
    findings = []
    try:
        from . import metrics_timeline as _metrics

        samples = [s for s in _metrics.samples() if isinstance(s, dict)]
        if samples:
            findings += _check_timeline(samples)
        dump = live_dump()
        findings += _check_recompiles(dump)
        findings += _check_serving(dump)
        findings += _check_slo(dump)
    except Exception:  # diagnosis must never break the surface it rides
        pass
    findings.sort(key=lambda f: -f["score"])
    return findings[:top]


def diagnose(trace=None, dump=None, timeline=None, top=20):
    """Run every applicable rule over a loaded chrome ``trace``, diag
    ``dump``, and/or per-step ``timeline`` and return findings ranked
    worst-first (by estimated share of step time).  Any input may be
    None; rules missing their data contribute nothing.

    ``timeline`` is a list of ``metrics_timeline`` samples (or a
    ``{"samples": [...]}`` wrapper).  When omitted and the dump embeds
    a ``timeline`` section (``runtime_stats.diag_snapshot`` attaches
    the live ring), the trend rules run over that."""
    findings = []
    if dump is not None:
        findings += _check_step_anatomy(dump)
        findings += _check_recompiles(dump)
        findings += _check_eager_dispatch(dump)
        findings += _check_host_sync(dump)
        findings += _check_roofline(dump)
        findings += _check_stragglers(dump)
        findings += _check_retries(dump)
        findings += _check_self_healing(dump)
        findings += _check_zero_allgather(dump)
        findings += _check_xray_scope(dump)
        findings += _check_xray_zero_collective(dump)
        findings += _check_xray_optimizer(dump)
        findings += _check_serving(dump)
        findings += _check_slo(dump)
        if timeline is None:
            timeline = dump.get("timeline")
    if isinstance(timeline, dict):
        timeline = timeline.get("samples")
    if timeline:
        findings += _check_timeline(list(timeline))
    if trace is not None:
        findings += _check_idle_gaps(trace)
    findings.sort(key=lambda f: -f["score"])
    return findings[:top]


def render(findings, inputs=()):
    """Human report: ranked findings with evidence and next actions."""
    lines = ["Perf doctor: %d finding(s)%s"
             % (len(findings),
                (" over %s" % ", ".join(inputs)) if inputs else "")]
    if not findings:
        lines.append("no bottleneck past the reporting thresholds — "
                     "nothing obviously wrong in the provided "
                     "trace/dump")
    for i, f in enumerate(findings, 1):
        lines.append("%d. [%s] (%3.0f%% of step time) %s"
                     % (i, f["severity"].upper(), f["score"] * 100,
                        f["title"]))
        for ev in f["evidence"]:
            lines.append("     evidence: %s" % ev)
        lines.append("     next: %s" % f["action"])
    return "\n".join(lines)


def gh_annotation(level, message):
    """One GitHub workflow-command annotation line (the
    ``tools/mxlint --format github`` escaping convention)."""
    msg = message.replace("%", "%25").replace("\r", "%0D") \
        .replace("\n", "%0A")
    return "::%s::%s" % (level, msg)


def render_github(findings):
    """``::error``/``::notice`` annotation lines: warn-severity
    findings error, the rest notice."""
    lines = []
    for f in findings:
        level = "error" if f["severity"] == "warn" else "notice"
        lines.append(gh_annotation(
            level, "perf-doctor[%s] %s — next: %s"
            % (f["rule"], f["title"], f["action"])))
    return "\n".join(lines)
