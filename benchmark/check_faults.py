"""Drive whole runs with the timed path broken underneath, and see
`correct` come out false.

    JAX_PLATFORMS=cpu python benchmark/check_faults.py

Each run skips the harness's look for a chip and runs at the sizes of
`check_reference.py`, through the upload front, the WAL and the service
epochs, with one fault planted in the program for the whole run:

- state_unchanged: each round hands back the aggregate it started
  from (zeros at the root, the previous level's after it) instead of
  its own;
- half_batch: the epoch's reports are its first half, twice over, so
  the count is kept and the sum is twice the half's;
- answer_altered: one aggregate is off by one where the round
  produces it.

A cell on one chip has no exchange between chips to leave out.
"""

import os
import sys
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as harness  # noqa: E402
from check_reference import SMALL  # noqa: E402

SEED = 5


def _wrap_rounds(alter):
    """Patch the resident heavy-hitters runner's round collector so
    that each result passes through `alter(result, previous)`."""
    from mastic_tpu.drivers import heavy_hitters

    prev = {"result": None}
    runner_collect = heavy_hitters._IncrementalRunner.round_collect

    def pass_through(result):
        out = alter(list(result), prev["result"])
        prev["result"] = list(result)
        return out

    def runner(self, handle, metrics_out=None):
        return pass_through(runner_collect(self, handle,
                                           metrics_out=metrics_out))

    return [mock.patch.object(heavy_hitters._IncrementalRunner,
                              "round_collect", runner)]


def state_unchanged():
    def alter(result, previous):
        start = previous if previous is not None else []
        return (start + [0] * len(result))[:len(result)]
    return _wrap_rounds(alter)


def answer_altered():
    def alter(result, previous):
        return [result[0] + 1] + result[1:]
    return _wrap_rounds(alter)


def half_batch():
    from mastic_tpu.drivers.service import CollectorService

    reports_of = CollectorService._epoch_reports

    def halved(self, t, epoch):
        reports = reports_of(self, t, epoch)
        half = reports[:len(reports) // 2]
        return half + half
    return [mock.patch.object(CollectorService, "_epoch_reports", halved)]


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "answer_altered": answer_altered}


def main() -> int:
    missed = []
    for (cell, small) in SMALL.items():
        print(f"{cell} exchange_left_out: not applicable (one chip)")
        for (name, fault) in FAULTS.items():
            patches = fault()
            for p in patches:
                p.start()
            try:
                out = harness.run_cell(cell, SEED, 0.0, trace=False,
                                       require_tpu=False, overrides=small)
            finally:
                for p in patches:
                    p.stop()
            failing = {k: v["value"] for (k, v) in out["checks"].items()
                       if v["value"] > v["limit"]}
            print(f"{cell} {name}: correct={out['correct']} {failing}")
            if out["correct"]:
                missed.append((cell, name))
    if missed:
        print(f"check_faults: faults not caught: {missed}")
        return 1
    print("check_faults: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
