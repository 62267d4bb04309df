"""Check the plain references against the program, on the CPU.

    JAX_PLATFORMS=cpu python benchmark/check_reference.py

For each cell, at a small size (8-bit indices, 64 reports a
collection), with its own keys and with the Zipf keys of `ZIPF`: one
whole run of the harness with the chip check skipped, so the program's
published results of every collection (the cache-filling child's
too), through the upload front, the WAL and the service epochs, are
held to the plain reference of `benchmark/modes/`; the run must come
out correct.  Then the heavy-hitters reference is held to
`chip_smoke.reference_walk` on the same traffic, at the small size and
at the full one, and every seed must walk the same frontier widths.
Exits non-zero on the first difference.
"""

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as harness  # noqa: E402

SMALL = {
    "hh256-collect": {"bits": 8, "reports": 64,
                      "mastic": {"class": "MasticSum", "args": [8, 255]}},
}
# The Zipf keys that the program fails on at 1024 reports on one v5e
# (PERF.md, Open questions), at the full size.
ZIPF = {"keys": "zipf", "support": 10000, "zipf_s": 1.03,
        "key_space_seed": 0, "threshold_of_total": 0.01, "reports": 1024}
SEEDS = (3, 2 ** 31 + 11)


def check_cells() -> None:
    for (cell, small) in SMALL.items():
        for (seed, keys) in zip(SEEDS, ({}, ZIPF)):
            sized = dict(keys, **small)
            out = harness.run_cell(cell, seed, 0.0, trace=False,
                                   require_tpu=False, overrides=sized)
            bad = {k: v for (k, v) in out["checks"].items()
                   if v["value"] > v["limit"]}
            assert out["correct"] and not bad, (cell, seed, keys, bad)
            print(f"{cell} seed {seed} {sized.get('keys', 'own')} keys: "
                  f"correct, checks {sorted(out['checks'])}")


def check_hh_against_smoke() -> None:
    sys.path.insert(0, harness.ROOT)
    from chip_smoke import reference_walk

    mode = harness.load_module("modes", "heavy_hitters")
    cfg = harness.load_json(HERE, "configs", "hh-sum256.json")
    for keys in ({}, ZIPF):
        full = dict(cfg, **keys)
        shapes = set()
        for size in (SMALL["hh256-collect"], {}):
            small = dict(full, **size)
            for seed in SEEDS:
                t = mode.traffic(small, harness.rng_for(seed, 1))
                ref = mode.reference(small, t["alphas"], t["weights"])
                (hitters, widths, sums) = reference_walk(
                    t["alphas"], t["weights"], mode.threshold(small))
                assert ref == {"hitters": hitters, "widths": widths,
                               "sums": sums}, (small["bits"], seed)
                assert hitters, (small["bits"], seed)
                if not size:
                    shapes.add((tuple(widths), len(hitters)))
        # Every seed walks the same frontier widths at the full size.
        assert len(shapes) == 1, shapes
        (widths, hitters) = shapes.pop()
        print(f"heavy-hitters reference equals chip_smoke.reference_walk;"
              f" {full['keys']} keys at {full['bits']} bits x "
              f"{full['reports']} reports: every seed finds {hitters} "
              f"hitters over frontiers of {min(widths)}..{max(widths)} "
              f"({sum(widths)} candidates over the levels)")


if __name__ == "__main__":
    check_hh_against_smoke()
    check_cells()
    print("check_reference: ok")
