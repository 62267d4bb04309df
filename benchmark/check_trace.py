"""Check `trace.py` on a small trace recorded on the chip.

    python benchmark/check_trace.py                 # check the fixture
    python benchmark/check_trace.py --record OUT    # on the chip: record
                                                    # a new fixture to OUT

The fixture (`fixtures/small.xplane.pb`) holds one `bench.collection`
window on one v5e chip: a `bench.upload` span in which the host sleeps
0.2 s with the chip idle, a `bench.rounds` span that runs a jitted
matrix product 20 times, and a `bench.first_step` span in which the
host sleeps 0.05 s.  The check recomputes the busy union and the gaps
from the raw events by a sweep of its own, and holds the reduction to
it and to the sleeps the recording made.
"""

import argparse
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "small.xplane.pb")
sys.path.insert(0, HERE)

import run as harness  # noqa: E402

UPLOAD_SLEEP_S = 0.2
STEP_SLEEP_S = 0.05


def record(out: str) -> None:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        sys.exit("check_trace --record: no TPU")
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((2048, 2048), jnp.float32)
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp,
                                profiler_options=harness.profile_options()):
            with jax.profiler.TraceAnnotation("bench.collection"):
                with jax.profiler.TraceAnnotation("bench.upload"):
                    time.sleep(UPLOAD_SLEEP_S)
                with jax.profiler.TraceAnnotation("bench.rounds"):
                    for _ in range(20):
                        f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.first_step"):
                    time.sleep(STEP_SLEEP_S)
        tr = harness.load_module(".", "trace")
        path = tr.find_xplane(tmp)
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        shutil.copyfile(path, out)
    describe(out)


def describe(path: str) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = [(line.name, sum(1 for _ in line.events))
                 for line in plane.lines]
        print(f"plane {plane.name}: {lines}")
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in list(line.events)[:5]:
                    print(f"  op {ev.name} {ev.start_ns} {ev.duration_ns} "
                          f"{dict(ev.stats)}")


def sweep(ops: list, lo: float, hi: float) -> tuple:
    """Busy time and gaps by counting open intervals at each edge."""
    edges = []
    for (a, b, _) in ops:
        (a, b) = (max(a, lo), min(b, hi))
        if b > a:
            edges += [(a, 1), (b, -1)]
    edges.sort(key=lambda e: (e[0], -e[1]))
    busy = 0.0
    gaps = []
    depth = 0
    mark = lo
    for (t, d) in edges:
        if depth == 0 and d == 1 and t > mark:
            gaps.append(t - mark)
        if depth > 0:
            busy += t - mark
        depth += d
        mark = t
    if hi > mark:
        gaps.append(hi - mark)
    return (busy, gaps)


def check(path: str) -> None:
    tr = harness.load_module(".", "trace")
    got = tr.reduce(path, chips=1)
    raw = tr.read(path)
    ((lo, hi),) = [(a, b) for (n, a, b) in raw["spans"]
                   if n == "collection"]
    chip = sorted(raw["devices"])[0]
    (busy, gaps) = sweep(raw["devices"][chip], lo, hi)
    assert raw["devices"][chip], "no device ops in the fixture"
    assert abs(got["busy_s"] - busy / 1e9) < 1e-9, (got["busy_s"], busy)
    assert abs(got["idle_s"] - sum(gaps) / 1e9) < 1e-9
    assert abs(got["busy_s"] + got["idle_s"] - got["window_s"]) < 1e-6
    assert 0 < got["busy_s"] < got["window_s"]
    lengths = [g[1] for g in got["idle_gaps"]]
    assert lengths == sorted(lengths, reverse=True)
    assert abs(lengths[0] - max(gaps) / 1e9) < 1e-9
    # The device timeline sits ~1 ms from the host's in the recorded
    # trace, so a gap may read a little shorter than its sleep.
    (longest, second) = got["idle_gaps"][:2]
    assert longest[0].startswith("upload at "), longest
    assert 0.95 * UPLOAD_SLEEP_S <= longest[1] < UPLOAD_SLEEP_S + 0.1, \
        longest
    assert second[0].startswith("first_step at "), second
    assert 0.95 * STEP_SLEEP_S <= second[1] < STEP_SLEEP_S + 0.05, second
    assert got["device_ops"] and all(t > 0 for (_, t) in got["device_ops"])
    assert all(name.startswith("jit_") and "/" in name and " " not in name
               for (name, _) in got["device_ops"]), got["device_ops"]
    assert sum(t for (_, t) in got["device_ops"]) >= got["busy_s"] - 1e-9
    # Two stretches: times add, ops add by name, gaps carry their label.
    whole = tr.reduce(path, chips=1, top=None)
    both = tr.combine([("a", whole), ("b", whole)])
    assert abs(both["busy_s"] - 2 * got["busy_s"]) < 1e-12
    assert abs(both["window_s"] - 2 * got["window_s"]) < 1e-12
    assert both["device_ops"][0] == [got["device_ops"][0][0],
                                     2 * got["device_ops"][0][1]]
    assert [g[0] for g in both["idle_gaps"][:2]] == [
        f"a: {longest[0]}", f"b: {longest[0]}"], both["idle_gaps"][:2]
    print(f"check_trace: ok (busy {got['busy_s']:.6f}s of "
          f"{got['window_s']:.6f}s, {len(gaps)} gaps, longest "
          f"{longest}, ops {got['device_ops'][:3]})")


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--record", metavar="OUT")
    args = parser.parse_args()
    if args.record:
        record(args.record)
        check(args.record)
    else:
        check(FIXTURE)
