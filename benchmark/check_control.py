"""The control: the plain reference put in the program's place with one
guarantee broken must come out not correct.

    python benchmark/check_control.py --workload <cell> --seeds 1 2 3

The configurations state their arithmetic as exact, so there is no
lower precision to step down to; the control breaks the guarantee
that every admitted report is aggregated.  For each collection that a
run of the cell can make (the cache-filling one and the window's first
three, from the same seed streams as `run.py`), it computes the
reference over the collection's reports with one report, drawn from
the seed, left out, and compares that with the reference over all of
them through the cell's own comparison.  Every number compared has the limit 0; the
control must exceed it in some number on every seed.  Runs at the
cell's own size by default; `--small` takes the sizes of
`check_reference.py`.
"""

import argparse
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as harness  # noqa: E402
from check_reference import SMALL  # noqa: E402

COLLECTIONS = 4


def control(cell: str, seed: int, overrides: dict = None) -> dict:
    workload = harness.load_json(HERE, "workloads", f"{cell}.json")
    cfg = harness.load_json(HERE, "configs", f"{workload['config']}.json")
    cfg.update(overrides or {})
    mode = harness.load_module("modes", cfg["mode"])
    totals: dict = {}
    for stream in range(1, 1 + COLLECTIONS):
        t = mode.traffic(cfg, harness.rng_for(seed, stream))
        ref = mode.reference(cfg, t["alphas"], t["weights"])
        drop = int(harness.rng_for(seed, 2 ** 32 + stream).integers(
            len(t["weights"])))
        keep = np.arange(len(t["weights"])) != drop
        got = mode.reference(cfg, t["alphas"][keep], t["weights"][keep])
        for (k, v) in mode.compare(got, ref).items():
            totals[k] = totals.get(k, 0) + int(v)
    return totals


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--small", action="store_true")
    args = parser.parse_args()
    overrides = SMALL[args.workload] if args.small else None
    failed = 0
    for seed in args.seeds:
        totals = control(args.workload, seed, overrides)
        caught = any(v > 0 for v in totals.values())
        print(f"control {args.workload} seed {seed}: {totals} "
              f"(limit 0 each): {'not correct' if caught else 'CORRECT'}")
        failed += not caught
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
