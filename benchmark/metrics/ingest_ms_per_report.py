"""Service epochs: milliseconds per report from the last upload
acknowledgement (epoch cut, WAL compaction, page decode, device
marshal, run set-up) to the start of the first round, over the
window's collections (benchmark clock)."""


def read(run: dict):
    recs = run["collections"]
    return 1e3 * sum(r["ingest_s"] for r in recs) / sum(
        r["reports"] for r in recs)
