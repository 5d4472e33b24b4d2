"""Layer ``entry``: host time for one step's dispatch call to return, the
median over the untraced window (host clock around the call, in the
benchmark's own loop).  It bounds throughput only where it exceeds
``step.device_ms``: then the chip waits for the host."""

import statistics


def read(obs):
    times = obs["window"].get("dispatch_s")
    return statistics.median(times) * 1e3 if times else None
