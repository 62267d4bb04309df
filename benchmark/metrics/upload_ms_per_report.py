"""Upload front and WAL: milliseconds per report from the first PUT to
the last acknowledgement, over the window's collections (benchmark
clock)."""


def read(run: dict):
    recs = run["collections"]
    return 1e3 * sum(r["upload_s"] for r in recs) / sum(
        r["reports"] for r in recs)
