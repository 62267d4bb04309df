"""One real weighted heavy-hitters collection on the chip, through the
normal serving path, checked against a plain numpy reference.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the report axis sharded over a
                                      # 4-chip mesh, at 32 bits

The deployment is one `MasticSum(256, 255)` heavy-hitters tenant,
resident.  The report count is the bench headline's 4096, halved
until a round's worst-case peak on one chip fits the device budget
(`drivers/chunked.memory_envelope`).  With the compile cache on, the
round programs do not donate their carries, so a round holds its
output carries beside its inputs; at 256 bits that gives 2048.
Measurements follow `tools/northstar.py`: four planted paths (one
pair diverging at 3/4 depth) carry 60% of the reports at weight 255,
a uniform tail carries weight 1, and the threshold is half a planted
path's expected weight.  Everything is drawn from `--seed`.

Flow: device-batched client sharding (`BatchedMastic.shard_device`)
-> upload blobs (`net/loadgen.encode_upload_batch`) -> real HTTP PUTs
to the upload front on 127.0.0.1 with the admission WAL under it,
wired by `tools/serve.py`'s own functions -> epoch cut -> service
rounds until drained -> the published hitters.

`--chips 4` runs the same collection with the tenant's report axis
sharded over a 4-chip mesh, at 32 bits instead of 256: a 4-chip host
compiles the mesh round programs afresh, and the cut depth keeps
that run to a few minutes.  Nothing else runs in that mode.

The script exits non-zero and prints no ok line when JAX finds no
TPU, any upload is answered with other than 2xx, any report is
quarantined or shed, any round fails or is retried, the epoch is
truncated, a warm compile failed, a level accepted fewer reports than
were admitted, or the hitters, a level's candidate count or a level's
aggregate weights differ from the reference.  The last line of stdout
is `{"ok": true, "device": {"platform", "kind", "count"}}`, where
count is the number of chips the collection ran on.
"""

import argparse
import hashlib
import json
import os
import sys
import tempfile
import threading
import time
from http.client import HTTPConnection
from types import SimpleNamespace

import numpy as np

BITS = 256
MESH_BITS = 32
MAX_WEIGHT = 255
HEADLINE_REPORTS = 4096
# Candidate prefixes per level: four planted paths, two children each.
WIDTH = 8
PLANTED = 4
HEAVY_SHARE = 0.6
CTX = b"chip smoke"
TENANT = "hh"
UPLOAD_THREADS = 8


class SmokeFailure(Exception):
    """A check of the collection failed."""


_T0 = time.perf_counter()


def say(msg: str) -> None:
    print(f"chip_smoke [{time.perf_counter() - _T0:7.1f}s] {msg}",
          flush=True)


def make_workload(bits: int, reports: int, seed: int) -> dict:
    """The seeded measurements and client randomness."""
    from tools.northstar import plant_paths

    rng = np.random.default_rng(seed)
    paths = plant_paths(rng, PLANTED, bits)
    heavy = int(reports * HEAVY_SHARE)
    choice = rng.integers(0, PLANTED, heavy)
    alphas = np.concatenate([
        paths[choice],
        rng.integers(0, 2, (reports - heavy, bits)).astype(bool)])
    weights = np.concatenate([np.full(heavy, MAX_WEIGHT, np.int64),
                              np.full(reports - heavy, 1, np.int64)])
    return {
        "alphas": alphas, "weights": weights,
        "threshold": int(heavy / PLANTED * MAX_WEIGHT * 0.5),
        "nonces": rng.integers(0, 256, (reports, 16), dtype=np.uint8),
        "rand_seed": int(rng.integers(0, 2 ** 63)),
        "verify_key": bytes(rng.integers(0, 256, 32, dtype=np.uint8)),
    }


def reference_walk(alphas, weights, threshold: int) -> tuple:
    """Plain threshold walk over the measurements: at each level, the
    weight under each candidate prefix; the children of the prefixes
    that reach the threshold are the next level's candidates.
    Returns (hitters as lists of bools, candidates per level, each
    level's weight per candidate)."""
    (num, bits) = alphas.shape
    prefixes = [[False], [True]]
    # Index of each report's prefix among the current candidates'
    # parents; -1 once its prefix was pruned.
    parent = np.zeros(num, np.int64)
    widths = []
    level_sums = []
    for level in range(bits):
        widths.append(len(prefixes))
        live = parent >= 0
        cand = np.where(live, 2 * parent + alphas[:, level], 0)
        sums = np.zeros(len(prefixes), np.int64)
        np.add.at(sums, cand[live], weights[live])
        level_sums.append([int(x) for x in sums])
        keep = [i for i in range(len(prefixes)) if sums[i] >= threshold]
        if level == bits - 1:
            return ([prefixes[i] for i in keep], widths, level_sums)
        remap = np.full(len(prefixes), -1, np.int64)
        remap[keep] = np.arange(len(keep))
        parent = np.where(live, remap[cand], -1)
        prefixes = [prefixes[i] + [b] for i in keep for b in (False, True)]
        if not prefixes:
            break
    return ([], widths, level_sums)


def shard_blobs(m, wl: dict) -> list:
    """Device-batched client sharding, then the wire blobs.  Lanes
    where the device shard's XOF rejection fired are re-sharded
    through the scalar layer from the same nonce and rand."""
    import jax

    from mastic_tpu.backend.mastic_jax import BatchedMastic
    from mastic_tpu.drivers.service import encode_upload
    from mastic_tpu.net.loadgen import encode_upload_batch

    bm = BatchedMastic(m)
    (alphas, weights) = (wl["alphas"], wl["weights"])
    num = alphas.shape[0]
    rand = np.random.default_rng(wl["rand_seed"]).integers(
        0, 256, (num, m.RAND_SIZE), dtype=np.uint8)
    (_, betas) = bm.encode_measurements(
        [(alphas[r], int(weights[r])) for r in range(num)])
    shard_fn = jax.jit(
        lambda a, b, n, r: bm.shard_device(CTX, a, b, n, r))
    (batch, ok) = shard_fn(alphas, betas, wl["nonces"], rand)
    ok = np.asarray(ok)
    blobs = encode_upload_batch(bm, batch)
    if not ok.all():
        say(f"shard: {int((~ok).sum())} lanes re-sharded through the "
            f"scalar client (XOF rejection)")
    for r in np.flatnonzero(~ok):
        nonce = wl["nonces"][r].tobytes()
        meas = (tuple(bool(b) for b in alphas[r]), int(weights[r]))
        blobs[r] = encode_upload(
            m, (nonce, *m.shard(CTX, meas, nonce, rand[r].tobytes())))
    return blobs


def upload(port_file: str, blobs: list, codes: dict,
           errors: list) -> None:
    """The client side: PUT every blob over keep-alive connections,
    then ask for the epoch cut and the drain.  The drain is requested
    whatever happened, so the serving loop never waits out its
    window."""
    from mastic_tpu.net.ingest import MEDIA_TYPE
    from mastic_tpu.net.loadgen import _no_nagle_connection

    def port() -> int:
        give_up = time.monotonic() + 120.0
        while time.monotonic() < give_up:
            try:
                with open(port_file) as f:
                    return json.load(f)["upload_port"]
            except (OSError, ValueError, KeyError):
                time.sleep(0.02)
        raise SmokeFailure("the upload front never published its port")

    mu = threading.Lock()
    cursor = [0]

    def worker(p: int) -> None:
        conn = None
        try:
            conn = _no_nagle_connection("127.0.0.1", p, timeout=120)
            while True:
                with mu:
                    i = cursor[0]
                    cursor[0] += 1
                if i >= len(blobs):
                    return
                conn.request("PUT", f"/v1/tenants/{TENANT}/reports",
                             body=blobs[i],
                             headers={"Content-Type": MEDIA_TYPE})
                resp = conn.getresponse()
                resp.read()
                with mu:
                    codes[resp.status] = codes.get(resp.status, 0) + 1
        except Exception as exc:
            with mu:
                errors.append(f"{type(exc).__name__}: {exc}")
        finally:
            if conn is not None:
                conn.close()

    def post(p: int, path: str) -> None:
        conn = HTTPConnection("127.0.0.1", p, timeout=120)
        try:
            conn.request("POST", path, headers={"Content-Length": "0"})
            resp = conn.getresponse()
            resp.read()
            if resp.status != 202:
                errors.append(f"POST {path} -> {resp.status}")
        finally:
            conn.close()

    try:
        p = port()
    except SmokeFailure as exc:
        errors.append(str(exc))
        return
    try:
        threads = [threading.Thread(target=worker, args=(p,))
                   for _ in range(UPLOAD_THREADS)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        post(p, f"/v1/tenants/{TENANT}/epoch")
    except Exception as exc:
        errors.append(f"{type(exc).__name__}: {exc}")
    finally:
        try:
            post(p, "/v1/admin/drain")
        except OSError as exc:
            errors.append(f"drain: {type(exc).__name__}: {exc}")


def run_collection(blobs: list, bits: int, threshold: int,
                   verify_key: bytes, mesh=None) -> dict:
    """One collection through the serving path; raises SmokeFailure
    on any refused upload, shed, quarantine, failed or retried round,
    truncated epoch, warm-compile error or short accept count.
    Returns the hitters, the per-level round records and aggregates,
    and timings."""
    from mastic_tpu.drivers.service import (CollectorService,
                                            ServiceConfig, TenantSpec)
    from mastic_tpu.drivers.wal import AdmissionWal
    from mastic_tpu.obs.registry import get_registry
    from tools.serve import run_upload_window, write_snapshot

    spec = TenantSpec(name=TENANT,
                      spec={"class": "MasticSum",
                            "args": [bits, MAX_WEIGHT]},
                      ctx=CTX, verify_key=verify_key,
                      thresholds={"default": threshold})
    # No epoch deadline: the epoch runs every level or fails.
    svc = CollectorService([spec],
                           config=ServiceConfig(max_buffered=len(blobs),
                                                epoch_deadline=None),
                           mesh=mesh)
    retries = get_registry().counter("mastic_session_retries_total",
                                     tenant=TENANT)
    retries_before = retries.value()
    codes: dict = {}
    errors: list = []
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
        snap = os.path.join(tmp, "collector.snap")
        wal = AdmissionWal(snap + ".wal", injector=svc.injector,
                           fresh=True)
        wal.mark_covered(wal.tail_seq(), write_snapshot(svc, snap))
        window = SimpleNamespace(
            upload_port=0, port_file=os.path.join(tmp, "port.json"),
            snapshot=snap, snapshot_every=600.0, upload_window=900.0)
        client = threading.Thread(
            target=upload, args=(window.port_file, blobs, codes, errors))
        t0 = time.perf_counter()
        client.start()
        run_upload_window(window, svc, None, wal=wal)
        client.join()
        upload_s = time.perf_counter() - t0
        if errors:
            raise SmokeFailure(f"upload client failed: {errors[:3]}")
        if any(not 200 <= code < 300 for code in codes) \
                or sum(codes.values()) != len(blobs):
            raise SmokeFailure(f"uploads not all admitted: {codes}")
        say(f"upload: {len(blobs)} PUTs answered {codes} in "
            f"{upload_s:.1f}s (WAL appends {wal.stats()['appends']})")

        tenant = svc.tenants[TENANT]
        run = None
        ingest_s = None
        t0 = time.perf_counter()
        while True:
            ts = time.perf_counter()
            more = svc.step()
            if tenant.active is not None:
                run = tenant.active.run
                if run.level % 64 == 0:
                    say(f"rounds: {run.level} of {bits} levels done")
            if ingest_s is None and run is not None and run.metrics:
                # The first quantum starts the epoch (page decode,
                # device marshal, runner set-up) and runs level 0.
                ingest_s = (time.perf_counter() - ts
                            - run.metrics[0].extra["round_wall_ms"] / 1e3)
            if not more:
                break
        steps_s = time.perf_counter() - t0
        wal.mark_covered(wal.tail_seq(), write_snapshot(svc, snap))
        wal.close()

    mx = svc.metrics()["tenants"][TENANT]
    c = mx["counters"]
    if c["quarantined"] or c["shed"] or c["quarantine_reasons"] \
            or c["shed_reasons"]:
        raise SmokeFailure(f"quarantine or shed: {c}")
    if c["admitted"] != len(blobs):
        raise SmokeFailure(f"admitted {c['admitted']} of {len(blobs)}")
    if c["epochs_failed"] or c["epochs_truncated"] \
            or c["deadline_misses"] or c["epochs_completed"] != 1 \
            or retries.value() != retries_before:
        raise SmokeFailure(
            f"epoch not clean: {c}, retries "
            f"{retries.value() - retries_before}")
    (rec,) = mx["epochs"]
    if rec["truncated"] or "error" in rec:
        raise SmokeFailure(f"epoch record: {rec}")
    if run is None or len(run.metrics) != rec["levels_completed"]:
        raise SmokeFailure("the epoch's round records are missing")
    for mx in run.metrics:
        if mx.accepted != c["admitted"] \
                or mx.reports_total != c["admitted"]:
            raise SmokeFailure(
                f"level {mx.level}: accepted {mx.accepted} of "
                f"{mx.reports_total}, admitted {c['admitted']}")
        if mx.retries or mx.respawns or mx.timeouts \
                or mx.xof_fallbacks:
            raise SmokeFailure(f"level {mx.level}: {mx.as_dict()}")
    warm_errors = max(mx.extra["pipeline"]["aot"]["warm_errors"]
                      for mx in run.metrics)
    if warm_errors:
        raise SmokeFailure(f"{warm_errors} warm compiles failed")
    # Where the rounds' wall went: the per-round timeline summed
    # (drivers/heavy_hitters.py stamps it), plus the scheduler.
    phases: dict = {}
    for mx in run.metrics:
        for (k, v) in mx.extra["pipeline"]["phases"].items():
            phases[k] = phases.get(k, 0.0) + v / 1e3
        phases["scheduler_ms"] = (phases.get("scheduler_ms", 0.0)
                                  + mx.extra["service"]
                                  ["sched_overhead_ms"] / 1e3)
    return {
        "hitters": rec["result"],
        "levels": [(mx.level, mx.frontier_width, mx.accepted)
                   for mx in run.metrics],
        "aggregates": run.aggregates,
        "upload_s": upload_s, "ingest_s": ingest_s,
        "rounds_s": steps_s - ingest_s,
        "compile_inline_s": sum(
            mx.extra["pipeline"]["compile_inline_ms"]
            for mx in run.metrics) / 1e3,
        "round_phases_s": phases,
        "node_evals": sum(mx.node_evals for mx in run.metrics),
        "warm_errors": warm_errors,
    }


def check_against_reference(got: dict, wl: dict) -> list:
    """Raise unless the hitters, and every level's candidate count and
    aggregate weights, equal the plain reference's; returns the
    reference hitters."""
    (hitters, widths, sums) = reference_walk(
        wl["alphas"], wl["weights"], wl["threshold"])
    if got["hitters"] != hitters:
        raise SmokeFailure(f"hitters differ from the reference: "
                           f"{len(got['hitters'])} found, "
                           f"{len(hitters)} expected")
    got_widths = [w for (_, w, _) in got["levels"]]
    if got_widths != widths:
        bad = [i for (i, (a, b)) in enumerate(zip(got_widths, widths))
               if a != b]
        raise SmokeFailure(f"frontier widths differ from the reference "
                           f"(levels {len(got_widths)} vs "
                           f"{len(widths)}, first differences {bad[:5]})")
    bad = [i for (i, (a, b)) in enumerate(zip(got["aggregates"], sums))
           if a != b]
    if len(got["aggregates"]) != len(sums) or bad:
        raise SmokeFailure(f"aggregates differ from the reference at "
                           f"levels {bad[:5]}")
    return hitters


def digest(got: dict) -> str:
    """A short hash of everything the collection published."""
    return hashlib.sha256(json.dumps(
        [got["hitters"], got["levels"], got["aggregates"]]).encode()
    ).hexdigest()[:16]


def resident_reports(bm) -> int:
    """The bench headline's report count, halved until a round's
    worst-case peak on one chip fits the device budget."""
    from mastic_tpu.drivers.chunked import memory_envelope

    reports = HEADLINE_REPORTS
    while reports > 1:
        env = memory_envelope(bm, reports, WIDTH, reports)
        budget = env["device_budget_bytes"]
        if budget <= 0 or env["device_peak_bytes_per_chunk"] <= budget:
            break
        reports //= 2
    return reports


def report(tag: str, got: dict) -> None:
    say(f"{tag}: ingest {got['ingest_s']:.1f}s, rounds "
        f"{got['rounds_s']:.1f}s over {len(got['levels'])} levels, "
        f"inline compile {got['compile_inline_s']:.1f}s, warm errors "
        f"{got['warm_errors']}")
    say(f"{tag}: rounds split (s): " + ", ".join(
        f"{k.removesuffix('_ms')} {v:.1f}"
        for (k, v) in sorted(got["round_phases_s"].items())))
    say(f"{tag}: {got['node_evals']} node evals, "
        f"{got['node_evals'] / got['rounds_s']:.0f} evals/s over the "
        f"round wall")
    say(f"{tag}: every level accepted all admitted reports; hitters "
        f"found {len(got['hitters'])}")


def main() -> int:
    parser = argparse.ArgumentParser(
        description="one heavy-hitters collection through the serving "
                    "path on the chip, checked against a reference")
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help=f"4: shard the report axis over a 4-chip "
                             f"mesh, at {MESH_BITS} bits")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    import jax

    from mastic_tpu import MasticSum, compile_cache
    from mastic_tpu.backend.mastic_jax import BatchedMastic
    from mastic_tpu.drivers.chunked import memory_envelope

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {devices[0].platform} "
              f"devices", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    cache_dir = compile_cache.configure()
    say(f"device {devices[0].device_kind} x{len(devices)}, compile "
        f"cache {cache_dir}")

    reports = resident_reports(BatchedMastic(MasticSum(BITS, MAX_WEIGHT)))
    bits = BITS if args.chips == 1 else MESH_BITS
    m = MasticSum(bits, MAX_WEIGHT)
    env = memory_envelope(BatchedMastic(m), reports, WIDTH, reports,
                          n_device_shards=args.chips)
    say(f"memory envelope, {reports} reports x {bits} bits at width "
        f"{WIDTH} on {args.chips} chip(s): "
        f"{env['device_bytes_per_chunk_per_shard']} resident bytes, "
        f"{env['device_round_bytes_per_chunk_per_shard']} in a round "
        f"({env['carry_copies']} carry copies), worst-case peak "
        f"{env['device_peak_bytes_per_chunk_per_shard']} per chip; "
        f"budget {env['device_budget_bytes']}")

    wl = make_workload(bits, reports, args.seed)
    mesh = None
    tag = "collection"
    if args.chips == 4:
        from mastic_tpu.parallel import make_mesh

        mesh = make_mesh(4, nodes_axis=1)
        tag = "4-chip mesh"
    try:
        t0 = time.perf_counter()
        blobs = shard_blobs(m, wl)
        say(f"shard: {reports} reports of {len(blobs[0])} bytes in "
            f"{time.perf_counter() - t0:.1f}s (compile included)")
        got = run_collection(blobs, bits, wl["threshold"],
                             wl["verify_key"], mesh=mesh)
        report(tag, got)
        expected = check_against_reference(got, wl)
        say(f"{tag}: hitters found {len(got['hitters'])}, expected "
            f"{len(expected)} by the plain reference: equal, and every "
            f"level's candidate count and aggregate weights equal "
            f"(result digest {digest(got)})")
    except SmokeFailure as exc:
        print(f"chip_smoke: FAIL: {exc}", file=sys.stderr, flush=True)
        return 1
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:args.chips])
    say(f"peak device memory {peak} bytes (largest over the "
        f"{args.chips} chip(s))")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
