"""The measured loop of every training entry.

Steps are dispatched in groups that end in one loss fetch: a training loop
that logs every ``steps_per_fetch`` steps.  ``dispatch()`` starts one step
and returns a handle to its loss without waiting; ``fetch(handle)`` returns
that loss on the host as a float, which waits for the device.  Throughput
is the samples of whole groups over the host-clock time from the first
dispatch to the last fetch.
"""

import contextlib
import math
import time


def run_groups(dispatch, fetch, steps_per_fetch, seconds=None, groups=None,
               annotate=None):
    """Run groups for ``seconds`` (whole groups; at least one) or exactly
    ``groups`` of them.  ``annotate(name)`` is a context manager that puts a
    host span on the profiler's clock; None in an untraced window, which
    then carries no span at all.
    -> {"steps", "elapsed_s", "losses", "dispatch_s", "fetch_s"}."""
    span = annotate or (lambda _name: contextlib.nullcontext())
    losses, dispatch_s, fetch_s = [], [], []
    clock = time.perf_counter
    start = clock()
    while True:
        handle = None
        for _ in range(steps_per_fetch):
            t0 = clock()
            with span("dispatch"):
                handle = dispatch()
            dispatch_s.append(clock() - t0)
        t0 = clock()
        with span("loss_fetch"):
            losses.append(fetch(handle))
        now = clock()
        fetch_s.append(now - t0)
        if groups is not None:
            if len(losses) >= groups:
                break
        elif now - start >= seconds:
            break
    return {"steps": len(losses) * steps_per_fetch, "elapsed_s": now - start,
            "losses": losses, "dispatch_s": dispatch_s, "fetch_s": fetch_s}


def verdict(window):
    """(ok, failed steps, why): every fetched loss finite and the last below
    the first on the fixed batch."""
    losses = window["losses"]
    per_group = window["steps"] // max(len(losses), 1)
    bad = sum(1 for v in losses if not math.isfinite(v))
    if bad:
        return False, bad * per_group, "%d non-finite losses" % bad
    if len(losses) > 1 and not losses[-1] < losses[0]:
        return False, 0, "loss did not fall on the fixed batch: %r -> %r" \
            % (losses[0], losses[-1])
    return True, 0, ""


class TrainSession:
    """The measuring half of a training entry.  A subclass provides
    ``batch`` (samples per step, all chips together), ``steps_per_fetch``,
    ``traced_groups``, ``dispatch()`` and ``fetch(handle)``."""

    def measure(self, seconds):
        """-> the window: ``values`` (end-to-end numbers it yields),
        ``attempted``/``failed`` steps, ``ok``/``why``, and the raw lists."""
        return self._describe(run_groups(
            self.dispatch, self.fetch, self.steps_per_fetch, seconds=seconds))

    def measure_traced(self, annotate):
        """A short tail of the same loop with host spans, run while the
        profiler is on."""
        return self._describe(run_groups(
            self.dispatch, self.fetch, self.steps_per_fetch,
            groups=self.traced_groups, annotate=annotate))

    def _describe(self, window):
        ok, failed, why = verdict(window)
        samples = window["steps"] * self.batch
        rate = samples / window["elapsed_s"]
        window.update(
            ok=ok, failed=failed, why=why, attempted=window["steps"],
            values={"train_samples_per_s": rate},
            summary="%d steps (%d samples) in %.4f s -> %.2f samples/s; "
                    "loss %.4f -> %.4f"
                    % (window["steps"], samples, window["elapsed_s"], rate,
                       window["losses"][0], window["losses"][-1]))
        return window
