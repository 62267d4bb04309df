"""Weighted heavy hitters: the seeded traffic, the plain reference, and
the comparison that decides `correct`.

The configuration's `keys` picks the key distribution:

- "planted" (`tools/northstar.py`'s workload, copied here so that the
  yardstick stays fixed while the program changes): `planted_paths`
  full-width paths, one pair sharing its first three quarters, carry
  `heavy_share` of the reports at the top weight; a uniform tail
  carries weight 1; the threshold is `threshold_share` of one planted
  path's expected weight.  The depths at which the planted paths part
  are fixed (`DIVERGE`), so that every seed walks the same frontier
  widths (2, then 4 for three levels, 6 to 3/4 depth, 8 after it).
- "zipf", as in the heavy-hitters evaluations of the DPF literature
  (Poplar): `support` distinct keys, the key of rank k drawn with
  probability proportional to k ** -zipf_s; weights step through
  1..max_weight; the threshold is `threshold_of_total` of the
  collection's total weight.  The reports' ranks are the Zipf
  quantiles at evenly spaced points and the key set is drawn once
  from `key_space_seed`; the seed draws a mask XORed into every key
  (which keeps the prefix tree's shape) and the arrival order, so that
  every seed walks the same frontier widths.

Either way the seed draws the bits and the order, and every seed gets
the same round programs.  The reference is a plain threshold walk in
numpy.  It imports nothing of the program.
"""

import numpy as np


# Where planted path r first leaves the paths before it: row 1 shares
# row 0's first 3/4 (`None`), row 2 leaves them at bit 3, row 3 at
# bit 0.
DIVERGE = (None, None, 3, 0)
# Step between consecutive Zipf reports' weights; coprime to 255, so
# the weights of any 255 consecutive reports are 1..255 once each.
WEIGHT_STEP = 97


def plant_paths(rng, planted: int, bits: int) -> np.ndarray:
    """Pairwise distinct full-width paths, (planted, bits) bool, that
    diverge from each other at the fixed depths of `DIVERGE`."""
    paths = rng.integers(0, 2, (planted, bits)).astype(bool)
    split = min(max(1, (3 * bits) // 4), bits - 1)
    for r in range(1, planted):
        at = split if r == 1 else DIVERGE[r]
        paths[r, :at] = paths[0, :at]
        paths[r, at] = ~paths[0, at]
    return paths


def _heavy(cfg: dict) -> int:
    return int(cfg["reports"] * cfg["heavy_share"])


def _zipf_ranks(cfg: dict) -> np.ndarray:
    """Each report's key rank (0 = most popular): the Zipf quantiles
    at the midpoints of `reports` equal steps."""
    p = np.arange(1, cfg["support"] + 1, dtype=np.float64) \
        ** -cfg["zipf_s"]
    cdf = np.cumsum(p / p.sum())
    u = (np.arange(cfg["reports"]) + 0.5) / cfg["reports"]
    return np.minimum(np.searchsorted(cdf, u), cfg["support"] - 1)


def _zipf_weights(cfg: dict) -> np.ndarray:
    return 1 + (np.arange(cfg["reports"], dtype=np.int64) * WEIGHT_STEP
                % cfg["max_weight"])


def threshold(cfg: dict) -> int:
    if cfg["keys"] == "zipf":
        return int(np.ceil(cfg["threshold_of_total"]
                           * _zipf_weights(cfg).sum()))
    per_path = _heavy(cfg) / cfg["planted_paths"] * cfg["max_weight"]
    return int(per_path * cfg["threshold_share"])


def tenant(cfg: dict) -> dict:
    """The tenant's mode parameters (`TenantSpec` keywords)."""
    return {"mode": "heavy_hitters",
            "thresholds": {"default": threshold(cfg)}}


def traffic(cfg: dict, rng) -> dict:
    """One collection's measurements: (reports, bits) bool paths and
    their weights."""
    (reports, bits) = (cfg["reports"], cfg["bits"])
    if cfg["keys"] == "zipf":
        keys = np.random.default_rng(cfg["key_space_seed"]).integers(
            0, 2, (cfg["support"], bits)).astype(bool)
        mask = rng.integers(0, 2, bits).astype(bool)
        alphas = keys[_zipf_ranks(cfg)] ^ mask
        weights = _zipf_weights(cfg)
    else:
        planted = cfg["planted_paths"]
        paths = plant_paths(rng, planted, bits)
        heavy = _heavy(cfg)
        choice = rng.integers(0, planted, heavy)
        alphas = np.concatenate([
            paths[choice],
            rng.integers(0, 2, (reports - heavy, bits)).astype(bool)])
        weights = np.concatenate([
            np.full(heavy, cfg["max_weight"], np.int64),
            np.ones(reports - heavy, np.int64)])
    order = rng.permutation(reports)
    return {"alphas": alphas[order], "weights": weights[order]}


def reference(cfg: dict, alphas: np.ndarray, weights: np.ndarray) -> dict:
    """Plain threshold walk: at each level the weight under each
    candidate prefix; the children of the prefixes that reach the
    threshold are the next level's candidates."""
    limit = threshold(cfg)
    (num, bits) = alphas.shape
    prefixes = [[False], [True]]
    # Index of each report's prefix among the current candidates'
    # parents; -1 once its prefix was pruned.
    parent = np.zeros(num, np.int64)
    widths: list = []
    sums_by_level: list = []
    hitters: list = []
    for level in range(bits):
        widths.append(len(prefixes))
        live = parent >= 0
        cand = np.where(live, 2 * parent + alphas[:, level], 0)
        sums = np.zeros(len(prefixes), np.int64)
        np.add.at(sums, cand[live], weights[live])
        sums_by_level.append([int(x) for x in sums])
        keep = [i for i in range(len(prefixes)) if sums[i] >= limit]
        if level == bits - 1:
            hitters = [prefixes[i] for i in keep]
            break
        remap = np.full(len(prefixes), -1, np.int64)
        remap[keep] = np.arange(len(keep))
        parent = np.where(live, remap[cand], -1)
        prefixes = [prefixes[i] + [b] for i in keep for b in (False, True)]
        if not prefixes:
            break
    return {"hitters": hitters, "widths": widths, "sums": sums_by_level}


def published(run, record: dict) -> dict:
    """What the program published for one collection: the epoch
    record's hitters, and each level's candidate count and aggregate
    from the run's round records."""
    return {"hitters": [list(p) for p in record["result"]],
            "widths": [mx.frontier_width for mx in run.metrics],
            "sums": [[int(x) for x in agg] for agg in run.aggregates]}


def compare(got: dict, ref: dict) -> dict:
    """Counts of what differs; each is held to 0."""
    hitters = ({tuple(h) for h in got["hitters"]}
               ^ {tuple(h) for h in ref["hitters"]})
    widths = sum(a != b for (a, b) in zip(got["widths"], ref["widths"]))
    widths += abs(len(got["widths"]) - len(ref["widths"]))
    sums = abs(len(got["sums"]) - len(ref["sums"]))
    for (a, b) in zip(got["sums"], ref["sums"]):
        sums += sum(x != y for (x, y) in zip(a, b)) + abs(len(a) - len(b))
    return {"hitter_mismatches": len(hitters),
            "width_mismatches": widths,
            "aggregate_mismatches": sums}
