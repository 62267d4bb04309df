"""Program acquisition in the window: the round records' `compile` and
`warm` phases (`RoundMetrics.extra["pipeline"]["phases"]`), summed over
the window's collections.  Nothing where the rounds stamp no phases."""


def read(run: dict):
    values = [r["compile_s"] for r in run["collections"]]
    if not values or any(v is None for v in values):
        return None
    return sum(values)
