"""Round loop: milliseconds per report from the start of the first
round to the published result, over the window's collections
(benchmark clock)."""


def read(run: dict):
    recs = run["collections"]
    return 1e3 * sum(r["rounds_s"] for r in recs) / sum(
        r["reports"] for r in recs)
