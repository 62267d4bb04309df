"""Device: the share of the traced stretches of the round loop (levels
from each depth in `run.py`'s `TRACE_AT`) in which no operation ran on
the chip, from the profiler trace (`trace.py`)."""


def read(run: dict):
    trace = run["trace"]
    if trace is None or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
