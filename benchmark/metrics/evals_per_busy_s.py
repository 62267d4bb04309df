"""Batched protocol and kernels: VIDPF node evaluations of the rounds
run under the profiler (the round records' `node_evals`, every traced
stretch) per second in which an operation ran on the chip
(`trace.py`)."""


def read(run: dict):
    trace = run["trace"]
    stretches = [s for r in run["collections"] for s in r["traced"]]
    if trace is None or not stretches or trace["busy_s"] <= 0:
        return None
    return sum(s["node_evals"] for s in stretches) / trace["busy_s"]
