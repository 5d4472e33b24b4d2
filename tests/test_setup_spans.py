"""PR 36: what happens once per ``GluonTrainStep`` is kept in memory.

``profiler.boundary_span(..., keep=True)`` leaves a ``KeptSpan`` on
``time.time_ns()`` (the clock the profiler stamps its annotation with), and
every boundary span opened inside it on its thread is kept too:
``mxtpu.setup.place``, ``mxtpu.setup.orders`` (``.learn``, ``.relay``) and
the ``mxtpu.step*`` spans of call 0.  ``profiler.compile_log()`` holds jax's
own report of every program built: ``trace``, ``lower`` and ``compile`` or
``cache_load``, each with its ``fun_name`` and the kept span it ran under.
Both lists are bounded, and neither grows after the first calls."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark.harness import trace  # noqa: E402

from mxnet_tpu import profiler
from test_step_spans import (KEY, LAUNCH, PUT, SCALARS, STEP, _batch,
                             _step)

PLACE, ORDERS, LEARN, RELAY = (
    "mxtpu.setup.place", "mxtpu.setup.orders", "mxtpu.setup.orders.learn",
    "mxtpu.setup.orders.relay")


@pytest.fixture(autouse=True)
def _nothing_kept_and_no_compile_cache(monkeypatch):
    """These tests say ``compile`` and ``compiled``: an earlier test file of
    the worker may have turned a persistent compile cache on for the process
    (``tests/benchmark`` does), from which the same program would load."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from mxnet_tpu.parallel import gluon_step

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    monkeypatch.setattr(gluon_step, "_orders_dir", lambda: None)
    profiler.set_state("stop")
    profiler.clear_kept()
    yield
    profiler.clear_kept()
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("with_optimizer", [False, True],
                         ids=["fused_sgd", "optimizer"])
@pytest.mark.parametrize("zero", [False, True], ids=["dp", "zero"])
def test_a_steps_set_up_and_call_0_are_kept_and_nothing_after(
        zero, with_optimizer):
    step = _step("kept%d%d_" % (zero, with_optimizer), zero, with_optimizer)
    placed, = profiler.kept_spans()
    assert (placed.name, placed.parent) == (PLACE, None)
    held = [v for tree in step._held for v in tree]
    assert placed.stats == {"leaves": len(held),
                            "bytes": sum(v.nbytes for v in held)}
    x, y = _batch()
    profiler.clear_kept()
    built_before = time.time_ns()
    for _ in range(3):
        float(np.asarray(step(x, y)))

    spans = profiler.kept_spans()
    # a span is recorded when it closes: children first, in order
    in_orders = [LEARN] * (not zero) + [RELAY] * bool(step._relaid)
    assert [(s.name, s.parent) for s in spans] == (
        [(PUT, STEP), (KEY, STEP)] + [(SCALARS, STEP)] * with_optimizer
        + [(name, ORDERS) for name in in_orders]
        + [(ORDERS, STEP), (LAUNCH, STEP), (STEP, None)])
    by_name = {s.name: s for s in spans}
    assert by_name[STEP].stats == {"step_num": 0}
    assert by_name[LAUNCH].stats == {"leaves": step._leaves,
                                     "relaid_leaves": step._relaid}
    if not zero:
        # no compile cache in this process: nothing to read the orders from
        assert by_name[LEARN].stats == {"source": "compiled"}
    if step._relaid:
        assert by_name[RELAY].stats == {"relaid_leaves": step._relaid}
    whole = by_name[STEP]
    assert built_before <= whole.start_ns <= whole.end_ns <= time.time_ns()
    for s in spans:
        assert s.thread == threading.get_ident()
        assert whole.start_ns <= s.start_ns <= s.end_ns <= whole.end_ns
    # one after the other inside the step
    inside = [s for s in spans if s.parent == STEP]
    assert all(a.end_ns <= b.start_ns for a, b in zip(inside, inside[1:]))

    # the step's program, by jax's name for it, inside call 0's launch
    launch = by_name[LAUNCH]
    program = [r for r in profiler.compile_log() if r.span == LAUNCH]
    assert [(r.kind, r.fun_name) for r in program] == [
        ("trace", "step"), ("lower", "jit(step)"), ("compile", "jit(step)")]
    for r in program:
        assert launch.start_ns <= r.start_ns <= r.end_ns <= launch.end_ns
        assert r.thread == launch.thread and r.retrieval_s is None
    assert all(a.end_ns <= b.start_ns for a, b in zip(program, program[1:]))
    # the program that is only read for its layouts, under the span that
    # asked for it
    learned = [(r.kind, r.fun_name) for r in profiler.compile_log()
               if r.span == LEARN]
    assert learned == ([] if zero else [
        ("trace", "asked"), ("lower", "jit(asked)"),
        ("compile", "jit(asked)")])
    assert profiler.kept_dropped() == {"kept_spans": 0, "compile_log": 0}


@pytest.mark.parametrize("zero", [False, True], ids=["dp", "zero"])
def test_a_thousand_calls_hold_what_the_first_two_left(zero):
    """Call 0 is kept; call 1 may add one ``trace`` record of microseconds
    (jax looks the step up again once its arguments are its own results:
    the jaxpr is cached, nothing is lowered or compiled); from there on a
    call leaves nothing."""
    step = _step("thousand%d_" % zero, zero, True)
    on_device = step.put_batch(*_batch())
    profiler.clear_kept()
    step(*on_device)
    after_0 = len(profiler.kept_spans())
    step(*on_device)
    counts = len(profiler.kept_spans()), len(profiler.compile_log())
    assert counts[0] == after_0
    for _ in range(998):
        loss = step(*on_device)
    float(np.asarray(loss))
    assert step._calls == 1000
    assert (len(profiler.kept_spans()),
            len(profiler.compile_log())) == counts
    assert [r.kind for r in profiler.compile_log()
            if r.span is None] in ([], ["trace"])


_TWO_PROCESSES = """
import json, os, sys
sys.path.insert(0, %(tests)r)
import jax
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
import numpy as np
from mxnet_tpu import profiler
from test_step_spans import _batch, _step
step = _step("cached_", False, True)
float(np.asarray(step(*_batch())))
print(json.dumps({
    "step": [[r.kind, r.retrieval_s is not None]
             for r in profiler.compile_log()
             if r.fun_name == "jit(step)" and r.kind != "lower"],
    "asked": [r.kind for r in profiler.compile_log()
              if r.span == "mxtpu.setup.orders.learn"],
    "source": [s.stats["source"] for s in profiler.kept_spans()
               if s.name == "mxtpu.setup.orders.learn"]}))
"""


def test_the_next_process_loads_the_step_and_reads_the_orders(tmp_path):
    """Two processes on one compile cache directory: the first compiles the
    step and learns the orders by compiling; the second reads ``cache_load``
    for the step, with the seconds the read took, and ``source`` = file."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    script = _TWO_PROCESSES % {"tests": os.path.dirname(
        os.path.abspath(__file__))}
    runs = [subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=300)
            for _ in range(2)]
    assert [r.returncode for r in runs] == [0, 0], runs[1].stderr[-2000:]
    first, second = (json.loads(r.stdout.strip().splitlines()[-1])
                     for r in runs)
    assert first == {"step": [["compile", False]],
                     "asked": ["trace", "lower", "compile"],
                     "source": ["compiled"]}
    assert second == {"step": [["cache_load", True]], "asked": [],
                      "source": ["file"]}


def test_past_the_bound_the_oldest_goes_and_is_counted(monkeypatch):
    import jax
    import jax.numpy as jnp

    ones = jnp.ones((3,))       # a program of its own
    monkeypatch.setattr(profiler, "_kept_spans", profiler._Kept(3))
    monkeypatch.setattr(profiler, "_compile_log", profiler._Kept(4))
    for i in range(5):
        with profiler.boundary_span("mxtpu.test.%d" % i, keep=True):
            pass
    assert [s.name for s in profiler.kept_spans()] == [
        "mxtpu.test.2", "mxtpu.test.3", "mxtpu.test.4"]
    # two programs, three records each: the first's trace and lower are gone
    for i in range(2):
        jax.jit(lambda v, i=i: v * (i + 2))(ones)
    assert [r.kind for r in profiler.compile_log()] == [
        "compile", "trace", "lower", "compile"]
    assert profiler.kept_dropped() == {"kept_spans": 2, "compile_log": 2}
    profiler.clear_kept()
    assert profiler.kept_spans() == profiler.compile_log() == []
    assert profiler.kept_dropped() == {"kept_spans": 0, "compile_log": 0}


def test_a_span_inside_a_kept_one_is_kept_on_its_thread_only():
    import jax

    plain = profiler.boundary_span("mxtpu.test.plain")
    assert isinstance(plain, jax.profiler.TraceAnnotation)
    seen = []

    def elsewhere():
        seen.append(profiler.boundary_span("mxtpu.test.elsewhere"))

    with pytest.raises(KeyError):
        with profiler.boundary_span("mxtpu.test.outer", keep=True, n=1):
            with profiler.boundary_span("mxtpu.test.inner") as inner:
                inner.stats["found"] = "it"
            other = threading.Thread(target=elsewhere)
            other.start()
            other.join(timeout=30)
            raise KeyError("x")
    assert isinstance(seen[0], jax.profiler.TraceAnnotation)
    # an error leaves the record and nothing open
    assert isinstance(profiler.boundary_span("mxtpu.test.after"),
                      jax.profiler.TraceAnnotation)
    assert [(s.name, s.parent, s.stats) for s in profiler.kept_spans()] == [
        ("mxtpu.test.inner", "mxtpu.test.outer", {"found": "it"}),
        ("mxtpu.test.outer", None, {"n": 1})]


def test_a_trace_inside_a_trace_leaves_no_record():
    """jax reports a trace for every ``jit`` it meets while it traces or
    lowers another: the log holds the program's."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(v):
        return jnp.sin(v) + 1

    def outer(v):
        return inner(v) * inner(v + 1)

    with profiler.boundary_span("mxtpu.test.build", keep=True):
        jax.jit(outer)(jnp.ones((4,)))
    assert [(r.kind, r.fun_name, r.span) for r in profiler.compile_log()] \
        == [("trace", "outer", "mxtpu.test.build"),
            ("lower", "jit(outer)", "mxtpu.test.build"),
            ("compile", "jit(outer)", "mxtpu.test.build")]


def test_a_kept_span_is_stamped_on_the_profilers_clock(tmp_path):
    """Under a live profile (python tracer off, as the benchmark traces): a
    kept span starts within 1 ms of its annotation in the xplane, whose
    times count from the session's start on ``time.time_ns()``."""
    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    before = time.time_ns()
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    started = time.time_ns()
    try:
        for i in range(3):
            with profiler.boundary_span("mxtpu.test.clock", keep=True, i=i):
                time.sleep(0.05)
    finally:
        jax.profiler.stop_trace()
    data = ProfileData.from_file(trace.newest_xplane(str(tmp_path)))
    annotations, session_start = [], None
    for plane in data.planes:
        if plane.name == trace.HOST_PLANE:
            annotations = sorted(
                (int(e.start_ns), int(e.duration_ns)) for line in plane.lines
                for e in line.events if e.name == "mxtpu.test.clock")
        for name, value in plane.stats:
            if name == "profile_start_time":
                session_start = int(value)
    kept = profiler.kept_spans()
    assert len(annotations) == len(kept) == 3
    ms = 1_000_000
    offsets = [s.start_ns - start for s, (start, _) in zip(kept, annotations)]
    # one clock: the same distance for every span, and that distance is the
    # session's start, which lies inside the call that started it
    assert max(offsets) - min(offsets) < ms
    assert before - ms < min(offsets) and max(offsets) < started + ms
    if session_start is not None:
        assert all(abs(o - session_start) < ms for o in offsets)
    for s, (_, duration) in zip(kept, annotations):
        assert 0 <= (s.end_ns - s.start_ns) - duration < ms
