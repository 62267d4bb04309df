"""Collector-service driver: boot a long-lived multi-tenant service
(`mastic_tpu/drivers/service.py`), stream synthetic uploads through
it, and drain epochs — the serving twin of the offline
`tools/northstar.py` batch run.

Three modes:

* default — build the demo tenants (a heavy-hitters Count collection
  and an attribute-metrics collection at a different bit-width),
  admit `--reports` seeded uploads per tenant per epoch, run
  `--epochs` epochs each through the scheduler, and print one JSON
  line with the per-tenant results and the full service metrics.
  With `--snapshot PATH` the service state is written (atomic
  rename) after admission and after every scheduler round, so a
  `kill -9` at any point loses at most the round in flight;
  `--resume` restores from the snapshot instead of re-admitting —
  the kill-and-resume test drives exactly this pair.

* ``--smoke`` — the `make serve-smoke` gate: two tenants plus
  overload/deadline scratch tenants, a malformed-upload burst
  (quarantined, tenant-attributed), sustained overload against a
  tiny quota (bounded memory, sheds counted under both policies), an
  epoch-deadline miss (degrades to the truncated frontier, marked),
  and a mid-epoch crash drill (snapshot, discard the live service,
  resume, bit-identical result).  Any violated expectation exits
  non-zero with the reason; the JSON line carries ``"ok": true``
  otherwise.

* ``--soak SECONDS`` — the unattended chip-session cell: loop
  admit -> epoch -> drain under one deadline, reporting epochs
  completed, rounds, and counter totals (a service that leaks,
  wedges, or sheds silently fails loudly here).

`MASTIC_FAULTS` (party ``collector``) is honored end to end — the
service arms its injector from the environment, so e.g.
``kill:party=collector:step=epoch_round:nth=2`` exercises a real
process death mid-epoch against the snapshot/resume pair.

Observability (ISSUE 7): ``--status-port N`` starts the live status
surface (`mastic_tpu/obs/statusz.py`) on 127.0.0.1:N — ``/metrics``
(Prometheus), ``/statusz`` (human text: per-tenant occupancy, queue
depths, shed/quarantine totals, last-round timelines) and ``/varz``
(JSON snapshot).  Port 0 binds an ephemeral port (printed in the JSON
line as ``status_port``).  The scheduler stays single-threaded: it
publishes an immutable snapshot after every quantum and the server
thread only reads published snapshots (snapshot-under-lock).  With
``--smoke --status-port`` the smoke gate additionally self-fetches
all three endpoints and asserts the expected per-tenant series are
present — the `make obs-smoke` cell.  `MASTIC_TRACE_FILE=path` gets
a JSONL span trace of every epoch/round/chunk (USAGE.md
"Observability").
"""

import argparse
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build_reports(m, ctx, rng, values, bits):
    """Seeded client uploads: shard each value with rng-derived
    nonce/rand so two processes with one --seed build byte-identical
    reports (the unfaulted / faulted+resumed comparison needs it)."""
    reports = []
    for v in values:
        alpha = m.vidpf.test_index_from_int(v, bits)
        nonce = bytes(rng.integers(0, 256, m.NONCE_SIZE,
                                   dtype="uint8"))
        rand = bytes(rng.integers(0, 256, m.RAND_SIZE, dtype="uint8"))
        (ps, shares) = m.shard(ctx, (alpha, True), nonce, rand)
        reports.append((nonce, ps, shares))
    return reports


def strip_wall(records):
    """Epoch records minus wall-clock stamps (the bit-identity
    comparison target: everything except timing — compile accounting
    is timing too: a resumed run recomputes fewer rounds)."""
    out = []
    for rec in records:
        rec = dict(rec)
        for key in ("wall_s", "compile_ms", "inline_compiles"):
            rec.pop(key, None)
        out.append(rec)
    return out


def admit_all(svc, tenant, m, reports, expect=None):
    from mastic_tpu.drivers.service import encode_upload

    outcomes = []
    for r in reports:
        outcomes.append(svc.submit(tenant, encode_upload(m, r)))
    if expect is not None:
        bad = [o for o in outcomes if o[0] != expect]
        if bad:
            fail(f"admission to {tenant}: expected {expect}, "
                 f"got {bad[:3]}")
    return outcomes


def fail(msg: str) -> None:
    print(f"serve: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def drain(svc, snapshot_path=None, deadline=None, status=None) -> None:
    from mastic_tpu.drivers.session import Deadline

    if deadline is None:
        # The drain itself is deadline-bounded (the scheduler's
        # per-epoch deadlines bound each epoch; this bounds the loop).
        deadline = Deadline(3600.0)
    while svc.step():
        # Snapshots are quiescent points: with the overlapped
        # executor armed, writing one mid-window would force-drain
        # the in-flight rounds every quantum — snapshot only when
        # nothing is staged (serial mode: every quantum, as before).
        if snapshot_path and svc.inflight_rounds() == 0:
            write_snapshot(svc, snapshot_path)
        publish_status(status, svc)
        if deadline.expired():
            fail("drain deadline expired with epochs still queued")
    publish_status(status, svc)


def start_status(port):
    """The --status-port surface, or None when the flag is absent.
    Port 0 binds an ephemeral port (server.port has the real one)."""
    if port is None:
        return None
    from mastic_tpu.obs.statusz import StatusServer

    return StatusServer(port=port).start()


def publish_status(status, svc) -> None:
    """One scheduler quantum's snapshot to the status server — the
    single-threaded scheduler's only contact with the server thread
    (snapshot-under-lock; the server never touches `svc`)."""
    if status is not None:
        status.publish(svc.metrics())


def check_status_endpoints(status) -> None:
    """Self-fetch /metrics, /statusz and /varz over real HTTP and
    assert the series the acceptance criteria name are present (the
    `make obs-smoke` gate's teeth)."""
    import urllib.request

    def get(path: str) -> bytes:
        url = f"http://127.0.0.1:{status.port}{path}"
        with urllib.request.urlopen(url, timeout=10) as resp:
            if resp.status != 200:
                fail(f"GET {path} -> {resp.status}")
            return resp.read()

    metrics = get("/metrics").decode()
    for needle in (
            'mastic_reports_admitted_total{tenant="count"}',
            'mastic_reports_quarantined_total{tenant="count"',
            'mastic_reports_shed_total{tenant="flood"',
            'mastic_rounds_total{tenant="count"}',
            'mastic_session_retries_total{tenant="count"}',
            "mastic_chunk_phase_ms_bucket",
            "mastic_epochs_total{",
            "mastic_round_wall_ms_bucket"):
        if needle not in metrics:
            fail(f"/metrics missing expected series {needle!r}")
    statusz = get("/statusz").decode()
    for needle in ("tenant count", "occupancy:", "counters:"):
        if needle not in statusz:
            fail(f"/statusz missing {needle!r}")
    varz = json.loads(get("/varz"))
    for key in ("metrics", "trace", "service"):
        if key not in varz:
            fail(f"/varz missing {key!r}")
    if "count" not in varz["service"].get("tenants", {}):
        fail("/varz service snapshot has no tenants")


def run_upload_window(args, svc, status, wal=None):
    """The HTTP-ingest window (ISSUE 11, `mastic_tpu/net/ingest.py`):
    serve the DAP-shaped upload endpoint for `--upload-window`
    seconds — or until a client POSTs the admin drain control — then
    cut every tenant's buffered pages into epochs and fall through to
    the normal drain.

    Plane separation: handler threads only admit (`submit()` is the
    r15 thread-safe seam) and ENQUEUE — epoch cuts and snapshots
    execute here, on this thread, which owns the whole scheduler
    plane (the CC001 pass holds the tree to exactly this split).
    Durability (ISSUE 18): with `--snapshot` a WAL sits under
    admission — each handler's 2xx waits only for its record's
    (group-committed) fsync, not a full snapshot, so a client holding
    an ack can never lose that report to a kill -9; an un-acked
    upload is the client's to retry (the DAP upload contract).  The
    snapshot-before-ack ticket loop this replaces survives only as
    the compaction trigger: this thread snapshots PERIODICALLY
    (`--snapshot-every`) and truncates the WAL segments the snapshot
    covers — `tools/loadgen.py --smoke`'s mid-upload crash drill and
    `--wal-drill` drive the kill/--resume pair."""
    from mastic_tpu.drivers.session import Deadline
    from mastic_tpu.net.ingest import UploadFront

    front = UploadFront(
        svc, port=args.upload_port, admin=True,
        injector=svc.injector,
        persist=(wal.append_report if wal is not None
                 else None)).start()
    if args.port_file:
        tmp = args.port_file + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"upload_port": front.port}, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, args.port_file)
        fsync_dir(os.path.dirname(args.port_file))

    def compact() -> None:
        # Covered-seq FIRST: anything appended while to_bytes runs
        # is not provably in the snapshot, so it stays replayable.
        seq = wal.tail_seq()
        digest = write_snapshot(svc, args.snapshot)
        wal.mark_covered(seq, digest)

    def cut_epoch(tenant: str) -> None:
        if wal is not None:
            # Log the cut before executing it: a crash between the
            # two replays the same cut over the same reports.
            wal.append_epoch_cut(tenant)
        svc.begin_epoch(tenant)

    next_compact = time.monotonic() + args.snapshot_every
    deadline = Deadline(args.upload_window)
    while not deadline.expired():
        drain_now = front.drain_requested.wait(0.02)
        for tenant in front.pop_epoch_requests():
            cut_epoch(tenant)
        if wal is not None and time.monotonic() >= next_compact:
            compact()
            next_compact = time.monotonic() + args.snapshot_every
        publish_status(status, svc)
        if drain_now:
            break
    front.stop()
    for tenant in front.pop_epoch_requests():
        cut_epoch(tenant)
    for name in list(svc.tenants):
        cut_epoch(name)
    if wal is not None:
        compact()
    elif args.snapshot:
        write_snapshot(svc, args.snapshot)
    return front.port


def fsync_dir(path: str) -> None:
    from mastic_tpu.drivers import wal as wal_mod

    wal_mod.fsync_dir(path or ".")


def write_snapshot(svc, path: str) -> str:
    """Crash-safe snapshot write — the full tmp → fsync(file) →
    os.replace → fsync(dir) sequence (RB006's required idiom: rename
    alone can land with the bytes still in the page cache).  Returns
    the SHA-256 hexdigest of the snapshot bytes: the WAL's covered
    marker records it, and recovery re-verifies it before trusting
    the marker over replay."""
    data = svc.to_bytes()
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        # mastic-allow: SF004 — the snapshot is the durable
        # crash-resume medium and MUST carry the tenant key bindings
        # (the resumed process re-derives nothing); the trust
        # boundary is filesystem permissions on the operator's
        # --snapshot path, not the codec layer
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    fsync_dir(os.path.dirname(path))
    return hashlib.sha256(data).hexdigest()


def main() -> None:
    parser = argparse.ArgumentParser(
        description="long-lived collector service driver "
                    "(USAGE.md 'Collector service')")
    parser.add_argument("--bits", type=int, default=2,
                        help="tree depth of the heavy-hitters tenant")
    parser.add_argument("--reports", type=int, default=6,
                        help="uploads per tenant per epoch")
    parser.add_argument("--epochs", type=int, default=1)
    parser.add_argument("--page-size", type=int, default=4)
    parser.add_argument("--chunk-size", type=int, default=None)
    parser.add_argument("--mesh", type=int, default=0)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--snapshot", type=str, default=None)
    parser.add_argument("--resume", action="store_true")
    parser.add_argument("--smoke", action="store_true",
                        help="the serve-smoke robustness gate")
    parser.add_argument("--overlap-drill", action="store_true",
                        help="the overlapped-epoch drill: concurrent "
                             "submit burst against the ingest front, "
                             "then a kill-9 + --resume pair with the "
                             "overlapped executor armed (part of "
                             "`make serve-smoke`)")
    parser.add_argument("--soak", type=float, default=0.0,
                        help="unattended soak for SECONDS "
                             "(chip-session cell)")
    parser.add_argument("--chaos-drill", type=int, default=None,
                        metavar="SEED",
                        help="seeded network-chaos campaign (ISSUE "
                             "14): full two-party collections over "
                             "TCP+mTLS standalone parties "
                             "(tools/party.py) under a randomized "
                             "conn_drop/partition/tls_handshake/"
                             "slow_loris schedule — bit-identity vs "
                             "the loopback path, every injected "
                             "fault recovered and attributed "
                             "(USAGE.md 'Transport security')")
    parser.add_argument("--chaos-seeds", type=int, default=3,
                        help="distinct chaos schedules to run, "
                             "seeds SEED..SEED+N-1 (default 3)")
    parser.add_argument("--wal", type=str, default=None,
                        help="directory of the durable admission WAL "
                             "(ISSUE 18; default <snapshot>.wal — "
                             "armed whenever --snapshot and "
                             "--upload-port are both set; USAGE.md "
                             "'Durability')")
    parser.add_argument("--snapshot-every", type=float, default=5.0,
                        help="seconds between periodic compaction "
                             "snapshots while the upload window is "
                             "open (the WAL subsumed per-ack "
                             "snapshots)")
    parser.add_argument("--wal-drill", type=int, default=None,
                        metavar="SEED",
                        help="the disk-fault leg of the seeded chaos "
                             "campaign (ISSUE 18): kill -9 at every "
                             "WAL checkpoint plus randomized kill/"
                             "torn-tail/ENOSPC schedules over the "
                             "HTTP ingest path — each must recover "
                             "bit-identical with zero lost acked "
                             "reports and zero duplicates (`make "
                             "wal-smoke`)")
    parser.add_argument("--wal-seeds", type=int, default=3,
                        help="randomized WAL fault schedules to run, "
                             "seeds SEED..SEED+N-1 (default 3)")
    parser.add_argument("--status-port", type=int, default=None,
                        help="serve /metrics, /statusz and /varz on "
                             "127.0.0.1:PORT (0 = ephemeral; USAGE.md "
                             "'Observability')")
    parser.add_argument("--upload-port", type=int, default=None,
                        help="serve the DAP-shaped HTTP upload "
                             "endpoint (PUT /v1/tenants/{id}/reports) "
                             "on 127.0.0.1:PORT for --upload-window "
                             "seconds before cutting epochs and "
                             "draining (0 = ephemeral; USAGE.md "
                             "'Network front')")
    parser.add_argument("--upload-window", type=float, default=30.0,
                        help="seconds the upload endpoint accepts "
                             "reports (a client POST to "
                             "/v1/admin/drain closes it early)")
    parser.add_argument("--port-file", type=str, default=None,
                        help="write the bound upload port as JSON to "
                             "this path (atomic rename) — how a "
                             "driver finds an ephemeral --upload-port "
                             "0")
    parser.add_argument("--overlap", type=int, default=None,
                        help="keep up to K tenants' rounds in flight "
                             "(overlapped epoch executor; sets "
                             "MASTIC_SERVICE_OVERLAP — <2 = the "
                             "serial round-robin scheduler)")
    parser.add_argument("--ingest-threads", type=int, default=None,
                        help="decode-validate admissions on this "
                             "many worker threads behind a bounded "
                             "queue (concurrent ingest front; sets "
                             "MASTIC_SERVICE_INGEST_THREADS — 0 = "
                             "in-process admission)")
    parser.add_argument("--artifact-dir", type=str, default=None,
                        help="AOT artifact store (tools/bake.py) — "
                             "preloaded at startup and on tenant "
                             "admission so rounds never trace "
                             "(USAGE.md 'AOT artifacts'; equivalent "
                             "to MASTIC_ARTIFACT_DIR)")
    parser.add_argument("--out", type=str, default=None)
    args = parser.parse_args()

    if args.resume and not args.snapshot:
        parser.error("--resume needs --snapshot PATH")
    # argv-time environment pinning (tools/envpin.py): these writes
    # happen strictly before any thread or the jax import exists.
    from tools import envpin

    if args.artifact_dir:
        # The env lever is the one seam every runner reads
        # (drivers/artifacts.store_from_env); the flag just sets it.
        envpin.pin("MASTIC_ARTIFACT_DIR", args.artifact_dir)
    if args.overlap is not None:
        envpin.pin("MASTIC_SERVICE_OVERLAP", str(args.overlap))
    if args.ingest_threads is not None:
        envpin.pin("MASTIC_SERVICE_INGEST_THREADS",
                   str(args.ingest_threads))
    if args.mesh:
        envpin.force_host_devices(args.mesh)

    import numpy as np
    import jax

    from mastic_tpu import compile_cache

    compile_cache.configure()
    mesh = None
    if args.mesh:
        from mastic_tpu.parallel import make_mesh
        mesh = make_mesh(args.mesh, nodes_axis=1)

    if args.smoke:
        run_smoke(args, mesh, status=start_status(args.status_port))
        return
    if args.overlap_drill:
        run_overlap_drill(args)
        return
    if args.chaos_drill is not None:
        run_chaos_drill(args)
        return
    if args.wal_drill is not None:
        run_wal_drill(args)
        return

    from mastic_tpu.drivers.service import (CollectorService,
                                            ServiceConfig, TenantSpec)
    from mastic_tpu.mastic import MasticCount

    t_start = time.time()
    bits = args.bits
    m_count = MasticCount(bits)
    m_attr = MasticCount(8)
    rng = np.random.default_rng(args.seed)
    # Deterministic keys: the resumed process must rebuild the same
    # tenant bindings the snapshot header carries.
    vk_count = bytes(rng.integers(0, 256, m_count.VERIFY_KEY_SIZE,
                                  dtype="uint8"))
    vk_attr = bytes(rng.integers(0, 256, m_attr.VERIFY_KEY_SIZE,
                                 dtype="uint8"))
    threshold = max(2, int(args.reports * 0.4))
    tenants = [
        TenantSpec(name="count",
                   spec={"class": "MasticCount", "args": [bits]},
                   ctx=b"serve count", verify_key=vk_count,
                   thresholds={"default": threshold},
                   chunk_size=args.chunk_size),
        TenantSpec(name="attrs",
                   spec={"class": "MasticCount", "args": [8]},
                   ctx=b"serve attrs", verify_key=vk_attr,
                   mode="attribute_metrics",
                   attributes=["checkout.html", "landing.html"],
                   chunk_size=args.chunk_size),
    ]
    config = ServiceConfig.from_env()
    config.page_size = args.page_size

    snap_sha256 = None
    if args.resume:
        with open(args.snapshot, "rb") as f:
            snap_bytes = f.read()
        snap_sha256 = hashlib.sha256(snap_bytes).hexdigest()
        svc = CollectorService.from_bytes(snap_bytes, config=config,
                                          mesh=mesh)
    else:
        svc = CollectorService(tenants, config=config, mesh=mesh)

    # The durable admission log (ISSUE 18): armed whenever the HTTP
    # ingest plane and a snapshot path are both configured.  On
    # --resume, recovery replays every record the restored snapshot
    # does not cover (verified by digest) BEFORE the window reopens.
    wal = None
    wal_recovery = None
    if args.upload_port is not None and args.snapshot:
        from mastic_tpu.drivers.wal import AdmissionWal

        wal = AdmissionWal(args.wal or (args.snapshot + ".wal"),
                           injector=svc.injector,
                           fresh=not args.resume)
        if args.resume:
            wal_recovery = wal.recover(svc,
                                       snapshot_sha256=snap_sha256)
        else:
            # Seed the compaction baseline: the snapshot file exists
            # from boot, so a crash at ANY later point resumes from
            # snapshot + WAL replay, never from nothing.
            wal.mark_covered(wal.tail_seq(),
                             write_snapshot(svc, args.snapshot))
    status = start_status(args.status_port)
    publish_status(status, svc)

    hot = args.reports // 2
    count_values = [0] * hot + [2 ** bits - 1] * (args.reports - hot)
    from mastic_tpu.drivers.attribute_metrics import hash_attribute
    attr_alpha = hash_attribute(m_attr, "checkout.html")
    attr_int = int("".join("1" if b else "0" for b in attr_alpha), 2)
    attr_values = [attr_int] * max(1, args.reports - 2) \
        + [0] * min(2, args.reports)

    if args.soak:
        run_soak(args, svc, m_count, count_values, rng, t_start,
                 status=status)
        return

    upload_port = None
    if args.upload_port is not None:
        # HTTP ingest replaces the synthetic admission loop entirely
        # (on --resume too: the reopened window is where a client
        # retries the uploads the crashed process never acked).
        upload_port = run_upload_window(args, svc, status, wal=wal)
    elif not args.resume:
        for _ in range(args.epochs):
            reports = build_reports(m_count, b"serve count", rng,
                                    count_values, bits)
            admit_all(svc, "count", m_count, reports)
            svc.begin_epoch("count")
            reports = build_reports(m_attr, b"serve attrs", rng,
                                    attr_values, 8)
            admit_all(svc, "attrs", m_attr, reports)
            svc.begin_epoch("attrs")
        if args.snapshot:
            write_snapshot(svc, args.snapshot)
    drain(svc, snapshot_path=args.snapshot, status=status)
    if args.snapshot:
        digest = write_snapshot(svc, args.snapshot)
        if wal is not None:
            wal.mark_covered(wal.tail_seq(), digest)
            wal.close()

    metrics = svc.metrics()
    out = {
        "mode": "resume" if args.resume else "serve",
        "upload_port": upload_port,
        "platform": jax.devices()[0].platform,
        "bits": bits, "reports": args.reports,
        "epochs": args.epochs,
        "mesh_devices": args.mesh or 1,
        "status_port": status.port if status is not None else None,
        "artifact_dir": args.artifact_dir,
        "wall_seconds": round(time.time() - t_start, 1),
        "results": {name: strip_wall(t["epochs"])
                    for (name, t) in metrics["tenants"].items()},
        "metrics": metrics,
        "ok": True,
    }
    if wal is not None:
        out["wal"] = wal.stats()
        if wal_recovery is not None:
            out["wal"]["recovery"] = wal_recovery
            out["wal"]["replayed_records"] = \
                wal_recovery["replayed"]
            out["wal"]["recovery_wall_ms"] = \
                wal_recovery["recovery_wall_ms"]
    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    if os.environ.get("MASTIC_HARD_EXIT"):
        # Drill children (--wal-drill spawns ~a dozen of these): the
        # work is done and durably on disk — skip the interpreter's
        # atexit teardown, where jaxlib's clear_backends segfaults
        # flakily on CPU and would be misread as a lost-ack failure.
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(0)


def run_overlap_drill(args) -> None:
    """The overlapped-epoch drill (`make serve-smoke`, ISSUE 10):

    1. concurrent submit burst — 4 client threads stream uploads into
       2 tenants through a live ingest front (3 workers, a 16-deep
       bounded queue): every submission must be accounted exactly
       once (admitted + shed == submitted, the buffered pages hold
       exactly the admitted blobs — zero lost, zero duplicated), and
       the burst must never block on the scheduler;
    2. kill-9 + --resume — the default two-tenant serve scenario runs
       as child processes with `--overlap 2 --ingest-threads 2`: a
       clean run, a run hard-killed mid-drain by the injector at the
       scheduler's epoch_round checkpoint, and a `--resume` from the
       killed run's snapshot, whose results must equal the clean
       run's bit for bit.
    """
    import subprocess
    import tempfile
    import threading

    import numpy as np

    from mastic_tpu.drivers import faults
    from mastic_tpu.drivers.service import (QUEUED, SHED,
                                            CollectorService,
                                            ServiceConfig, TenantSpec,
                                            encode_upload)
    from mastic_tpu.mastic import MasticCount

    t_start = time.time()
    bits = 2
    m = MasticCount(bits)
    rng = np.random.default_rng(args.seed)
    vk = bytes(rng.integers(0, 256, m.VERIFY_KEY_SIZE, dtype="uint8"))
    specs = [
        TenantSpec(name=f"t{i}",
                   spec={"class": "MasticCount", "args": [bits]},
                   ctx=b"drill", verify_key=vk,
                   thresholds={"default": 2}, max_buffered=64)
        for i in range(2)
    ]
    cfg = ServiceConfig(page_size=4, max_buffered=64,
                        shed_policy="reject-newest",
                        overlap=2, ingest_threads=3, ingest_queue=16,
                        epoch_deadline=600.0)
    svc = CollectorService(specs, config=cfg)
    per_thread = 10
    blobs = [encode_upload(m, r)
             for r in build_reports(m, b"drill", rng,
                                    [0] * per_thread, bits)]
    outcomes: list = []
    mu = threading.Lock()

    def feed(tenant: str) -> None:
        got = [svc.submit(tenant, b) for b in blobs]
        with mu:
            outcomes.extend(got)

    threads = [threading.Thread(target=feed, args=(f"t{i % 2}",))
               for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    svc.flush_ingest()
    queued = sum(1 for o in outcomes if o[0] == QUEUED)
    shed_at_queue = sum(1 for o in outcomes
                        if o == (SHED, "ingest-queue-full"))
    if queued + shed_at_queue != 4 * per_thread:
        fail(f"burst outcomes unaccounted: {queued} queued + "
             f"{shed_at_queue} queue-shed != {4 * per_thread}")
    mx = svc.metrics()["tenants"]
    landed = 0
    queue_shed_counted = 0
    for name in ("t0", "t1"):
        c = mx[name]["counters"]
        qshed = c["shed_reasons"].get("ingest-queue-full", 0)
        queue_shed_counted += qshed
        landed += c["admitted"] + c["quarantined"] \
            + (c["shed"] - qshed)
        if c["admitted"] != mx[name]["buffered_reports"]:
            fail(f"{name}: admitted {c['admitted']} != buffered "
                 f"{mx[name]['buffered_reports']} (lost/dup pages)")
    if queue_shed_counted != shed_at_queue:
        fail(f"queue-full sheds miscounted: counters say "
             f"{queue_shed_counted}, callers saw {shed_at_queue}")
    if landed + shed_at_queue != 4 * per_thread:
        fail(f"burst accounting: {landed} landed + {shed_at_queue} "
             f"queue-shed != {4 * per_thread}")
    svc.stop_ingest()

    # 2. kill-9 + --resume with overlap + ingest armed, as children.
    print("overlap drill: children run with JAX_PLATFORMS=cpu "
          "(they never touch the chip)", file=sys.stderr,
          flush=True)
    tmp = tempfile.mkdtemp(prefix="mastic_overlap_drill_")
    me = os.path.abspath(__file__)

    def run_child(extra, fault=None, expect_rc=0):
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        env.pop("MASTIC_FAULTS", None)
        if fault is not None:
            env["MASTIC_FAULTS"] = fault
        # All three children run the parser-default seed: the drill
        # needs them deterministic relative to EACH OTHER, nothing
        # else (and argv stays free of anything seed-derived).
        cmd = [sys.executable, me, "--reports", "4",
               "--overlap", "2", "--ingest-threads", "2"] + extra
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=1800, env=env)
        if proc.returncode != expect_rc:
            fail(f"drill child {extra} rc={proc.returncode} "
                 f"(wanted {expect_rc}): {proc.stderr[-1500:]}")
        return proc

    clean = run_child(["--snapshot", os.path.join(tmp, "clean.snap")])
    clean_out = json.loads(clean.stdout.strip().splitlines()[-1])
    snap = os.path.join(tmp, "killed.snap")
    run_child(["--snapshot", snap],
              fault="kill:party=collector:step=epoch_round:nth=2",
              expect_rc=faults.KILL_EXIT_CODE)
    if not os.path.exists(snap):
        fail("killed child left no snapshot")
    resumed = run_child(["--snapshot", snap, "--resume"])
    resumed_out = json.loads(resumed.stdout.strip().splitlines()[-1])
    if resumed_out["results"] != clean_out["results"]:
        fail(f"overlap kill-9 resume diverged: "
             f"{resumed_out['results']} != {clean_out['results']}")

    out = {
        "mode": "overlap-drill",
        "burst_submitted": 4 * per_thread,
        "burst_admitted": landed,
        "burst_queue_shed": shed_at_queue,
        "kill9_resume_bit_identical": True,
        "wall_seconds": round(time.time() - t_start, 1),
        "ok": True,
    }
    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")


def run_chaos_drill(args) -> None:
    """The seeded network-chaos campaign (`--chaos-drill SEED`):

    1. loopback baseline — the spawn-path AggregationSession walks a
       full heavy-hitters collection; its per-round results, accept
       masks and raw share bytes are the bit-identity target;
    2. TCP+mTLS pair — two standalone `tools/party.py serve`
       processes on distinct listen addresses (certs minted by
       tools/certs.py), collector in connect mode; must reproduce
       the loopback collection byte for byte;
    3. chaos runs — for each of `--chaos-seeds` seeds, a fresh party
       pair runs the same collection under a seeded random schedule
       of conn_drop / partition / slow_loris / tls_handshake-delay
       faults.  Every run must be bit-identical, every injected rule
       must have fired, every recovery must be attributed
       (RoundMetrics.reconnects / replayed_frames and the
       mastic_session_reconnects_total / mastic_frames_replayed_total
       series nonzero), and zero uploads lost or duplicated
       (quarantine empty, accept masks identical).
    """
    import random as random_mod
    import subprocess
    import tempfile

    import numpy as np

    from mastic_tpu.drivers.parties import AggregationSession
    from mastic_tpu.drivers.session import SessionConfig
    from mastic_tpu.net.transport import TlsConfig
    from mastic_tpu.obs.registry import get_registry
    from tools import certs as certs_mod

    t_start = time.time()
    bits = 2
    from mastic_tpu.mastic import MasticCount

    m = MasticCount(bits)
    spec = {"class": "MasticCount", "args": [bits]}
    ctx = b"chaos drill"
    rng = np.random.default_rng(args.seed)
    vk = bytes(rng.integers(0, 256, m.VERIFY_KEY_SIZE, dtype="uint8"))
    reports = build_reports(m, ctx, rng, [0, 0, 3, 3], bits)
    thresholds = {"default": 2}
    cfg = SessionConfig(connect_timeout=30.0, exchange_timeout=240.0,
                        ack_timeout=60.0, round_deadline=600.0,
                        shutdown_timeout=5.0, retries=2, backoff=0.2)

    print("chaos drill: children run with JAX_PLATFORMS=cpu "
          "(they never touch the chip)", file=sys.stderr,
          flush=True)
    tmp = tempfile.mkdtemp(prefix="mastic_chaos_")
    certdir = certs_mod.mint_party_set(os.path.join(tmp, "certs"))
    tls = TlsConfig(str(certdir / "collector.pem"),
                    str(certdir / "collector.key"),
                    str(certdir / "ca.pem"))

    def walk(sess):
        """Full threshold-pruned heavy-hitters collection; returns
        (hitters, per-round records, metrics records)."""
        from mastic_tpu.drivers.heavy_hitters import get_threshold

        rounds = []
        metrics: list = []
        try:
            sess.upload(reports)
            prefixes = [(False,), (True,)]
            for level in range(bits):
                param = (level, tuple(prefixes), level == 0)
                (result, accept, shares) = sess.round(
                    param, metrics_out=metrics)
                rounds.append((list(result),
                               [bool(x) for x in accept], shares))
                survivors = [p for (p, c) in zip(prefixes, result)
                             if c >= get_threshold(thresholds, p)]
                prefixes = (survivors if level == bits - 1 else
                            [p + (b,) for p in survivors
                             for b in (False, True)])
        finally:
            sess.close()
        return (sorted(prefixes), rounds, metrics)

    def spawn_pair(tag):
        """Two standalone mTLS parties on distinct listen
        addresses; returns (procs, connect map)."""
        pdir = os.path.join(tmp, tag)
        os.makedirs(pdir, exist_ok=True)
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        env.pop("MASTIC_FAULTS", None)
        procs = []
        for (name, extra) in (("leader",
                               ["--peer-listen", "127.0.0.1:0"]),
                              ("helper", [])):
            procs.append(subprocess.Popen(
                [sys.executable,
                 os.path.join(os.path.dirname(
                     os.path.abspath(__file__)), "party.py"),
                 "serve", "--listen", "127.0.0.1:0",
                 "--tls-cert", str(certdir / f"{name}.pem"),
                 "--tls-key", str(certdir / f"{name}.key"),
                 "--tls-ca", str(certdir / "ca.pem"),
                 "--port-file", os.path.join(pdir, f"{name}.ports")]
                + extra,
                env=env, stdout=sys.stderr, stderr=sys.stderr))

        def ports(name):
            path = os.path.join(pdir, f"{name}.ports")
            give_up = time.monotonic() + 120.0
            while time.monotonic() < give_up:
                try:
                    with open(path) as f:
                        return json.load(f)
                except (FileNotFoundError, ValueError):
                    time.sleep(0.1)
            fail(f"party {name} never published its ports ({tag})")

        (lp, hp) = (ports("leader"), ports("helper"))
        connect = {"leader": ("127.0.0.1", lp["listen"]),
                   "helper": ("127.0.0.1", hp["listen"]),
                   "leader_peer": ("127.0.0.1", lp["peer_listen"])}
        return (procs, connect)

    def reap(procs):
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()

    def chaos_schedule(seed):
        """A seeded random fault schedule, all rules addressed to the
        collector (whose injector we can audit after the run): at
        least one hard drop (so reconnect-and-replay provably runs),
        a guaranteed-firing tls_handshake delay, and a random tail of
        partitions / extra drops / stalled writers."""
        r = random_mod.Random(seed)
        rules = [
            f"conn_drop:party=collector:step=upload"
            f":nth={r.randint(1, 2)}",
            f"delay:party=collector:step=tls_handshake:nth=1"
            f":delay={r.uniform(0.1, 0.3):.2f}",
        ]
        extras = r.randint(1, 2)
        for _ in range(extras):
            pick = r.choice(("partition", "conn_drop", "slow_loris"))
            if pick == "partition":
                rules.append(
                    f"partition:party=collector:step=agg_param"
                    f":nth={r.randint(1, 4)}"
                    f":delay={r.uniform(0.3, 0.8):.2f}")
            elif pick == "conn_drop":
                rules.append(
                    f"conn_drop:party=collector:step=agg_param"
                    f":nth={r.randint(1, 4)}")
            else:
                rules.append(
                    f"slow_loris:party=collector:step=upload"
                    f":nth={r.randint(1, 2)}"
                    f":delay={r.uniform(0.2, 0.5):.2f}")
        # Distinct (step, nth) per rule — two rules on one occurrence
        # would leave the later one unfired and the audit ambiguous.
        seen = set()
        out = []
        for rule in rules:
            key = tuple(sorted(
                kv for kv in rule.split(":")
                if kv.startswith(("step=", "nth="))))
            if key in seen:
                continue
            seen.add(key)
            out.append(rule)
        return ";".join(out)

    # 1. loopback baseline (the spawn path).
    base = walk(AggregationSession(m, spec, ctx, vk, config=cfg))
    print(f"chaos: loopback baseline hitters={base[0]}",
          file=sys.stderr, flush=True)

    # 2. undisturbed TCP+mTLS pair on distinct listen addresses.
    (procs, connect) = spawn_pair("undisturbed")
    try:
        tcp = walk(AggregationSession(m, spec, ctx, vk, config=cfg,
                                      connect=connect, tls=tls))
    finally:
        reap(procs)
    if tcp[:2] != base[:2]:
        fail(f"TCP+mTLS pair diverged from loopback: {tcp[:2]} != "
             f"{base[:2]}")
    print("chaos: TCP+mTLS pair bit-identical to loopback",
          file=sys.stderr, flush=True)

    # 3. the seeded chaos campaign.
    seeds = list(range(args.chaos_drill,
                       args.chaos_drill + args.chaos_seeds))
    runs = []
    for seed in seeds:
        spec_str = chaos_schedule(seed)
        drops = sum(1 for r in spec_str.split(";")
                    if r.startswith(("conn_drop", "partition")))
        (procs, connect) = spawn_pair(f"seed{seed}")
        sess = AggregationSession(m, spec, ctx, vk, config=cfg,
                                  faults_spec=spec_str,
                                  connect=connect, tls=tls)
        try:
            chaos = walk(sess)
            rel = sess.coll.reliability_counters()
            unfired = [f"{r.action}:{r.step}:nth={r.nth}"
                       for r in sess.coll.injector.rules
                       if not r.fired]
            quarantined = dict(sess.coll.quarantine)
        finally:
            reap(procs)
        if chaos[:2] != base[:2]:
            fail(f"seed {seed}: chaos run diverged: {chaos[:2]} != "
                 f"{base[:2]}")
        if unfired:
            fail(f"seed {seed}: injected rules never fired: "
                 f"{unfired} (schedule {spec_str})")
        if rel["reconnects"] < drops:
            fail(f"seed {seed}: {drops} drops/partitions injected "
                 f"but only {rel['reconnects']} reconnects counted")
        if rel["replayed_frames"] < 1:
            fail(f"seed {seed}: no frames replayed despite "
                 f"{drops} drops — recovery path not exercised")
        if quarantined:
            fail(f"seed {seed}: uploads quarantined under chaos: "
                 f"{quarantined}")
        last = chaos[2][-1]
        if last.reconnects < drops or last.replayed_frames < 1:
            fail(f"seed {seed}: RoundMetrics missing recovery "
                 f"attribution: reconnects={last.reconnects} "
                 f"replayed_frames={last.replayed_frames}")
        print(f"chaos: seed {seed} ok — schedule [{spec_str}] "
              f"reconnects={rel['reconnects']} "
              f"replayed={rel['replayed_frames']}",
              file=sys.stderr, flush=True)
        runs.append({"seed": seed, "schedule": spec_str,
                     "reconnects": rel["reconnects"],
                     "replayed_frames": rel["replayed_frames"]})

    reg = get_registry()
    if not reg.counter("mastic_session_reconnects_total",
                       tenant="").value():
        fail("mastic_session_reconnects_total never incremented")
    if not reg.counter("mastic_frames_replayed_total",
                       tenant="").value():
        fail("mastic_frames_replayed_total never incremented")

    out = {
        "mode": "chaos-drill",
        "party_platform": "cpu",
        "seeds": seeds,
        "tcp_mtls_bit_identical": True,
        "runs": runs,
        "hitters": [[bool(b) for b in p] for p in base[0]],
        "wall_seconds": round(time.time() - t_start, 1),
        "ok": True,
    }
    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")


def run_wal_drill(args) -> None:
    """The disk-fault leg of the seeded chaos campaign (ISSUE 18,
    `make wal-smoke`): drive the HTTP ingest path with the WAL armed
    and (a) kill -9 at EVERY WAL checkpoint — `wal_append` (before
    the record's write), `wal_fsync` (written, not yet durable),
    `wal_ack` (durable, not yet acked) — then (b) `--wal-seeds`
    randomized schedules drawn from the disk-fault vocabulary
    (kill-at-checkpoint, short_write torn tail, ENOSPC brownout).
    Every schedule must end bit-identical to the undisturbed run
    with EXACTLY the clean run's reports admitted: zero acked-but-
    lost, zero duplicates.  Recovery must attribute itself (replayed
    / torn_tail counts and wall time in the resumed child's JSON)."""
    import random
    import shutil
    import subprocess
    import tempfile
    from http.client import HTTPConnection

    import numpy as np

    from mastic_tpu.drivers import faults
    from mastic_tpu.drivers.service import encode_upload
    from mastic_tpu.mastic import MasticCount
    from mastic_tpu.net.ingest import MEDIA_TYPE

    t_start = time.time()
    serve_py = os.path.abspath(__file__)
    bits = 2
    m = MasticCount(bits)
    rng = np.random.default_rng(args.wal_drill)
    blobs = []
    for value in [0, 0, 0, 3, 3, 3]:
        alpha = m.vidpf.test_index_from_int(value, bits)
        nonce = bytes(rng.integers(0, 256, m.NONCE_SIZE,
                                   dtype="uint8"))
        rand = bytes(rng.integers(0, 256, m.RAND_SIZE,
                                  dtype="uint8"))
        (ps, shares) = m.shard(b"serve count", (alpha, True), nonce,
                               rand)
        blobs.append(encode_upload(m, (nonce, ps, shares)))
    print("wal drill: children run with JAX_PLATFORMS=cpu "
          "(they never touch the chip)", file=sys.stderr,
          flush=True)
    tmp = tempfile.mkdtemp(prefix="mastic-wal-drill-")

    def spawn(tag, fault=None, resume=False, snap_tag=None):
        pf = os.path.join(tmp, f"{tag}.port")
        snap = os.path.join(tmp, f"{snap_tag or tag}.snap")
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "PYTHONFAULTHANDLER": "1",
               "MASTIC_HARD_EXIT": "1"}
        env.pop("MASTIC_FAULTS", None)
        env.pop("MASTIC_NET_SHAPE", None)
        # The campaign spawns ~a dozen collector children that all
        # lower the same tiny programs — share one persistent compile
        # cache so only the first child pays the XLA lowering.  A
        # child running under a fault (it may die by kill-9) gets a
        # throwaway COPY of the warm cache instead: jax's cache
        # writes are not atomic, so a kill mid-write plants a torn
        # entry that heap-corrupts the next reader.
        shared_cache = os.path.join(tmp, "jaxcache")
        if fault is None:
            cache = shared_cache
        else:
            cache = os.path.join(tmp, f"jaxcache-{tag}")
            if os.path.isdir(shared_cache) \
                    and not os.path.isdir(cache):
                shutil.copytree(shared_cache, cache)
        os.makedirs(cache, exist_ok=True)
        env["JAX_COMPILATION_CACHE_DIR"] = cache
        env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       "0.5")
        if fault is not None:
            env["MASTIC_FAULTS"] = fault
        cmd = [sys.executable, serve_py, "--reports", "6", "--bits",
               str(bits), "--page-size", "2", "--upload-port", "0",
               "--upload-window", "120", "--port-file", pf,
               "--snapshot", snap]
        if resume:
            cmd.append("--resume")
        proc = subprocess.Popen(cmd, env=env, text=True,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE)
        return (proc, pf, snap)

    def wait_port(path, deadline_s=120.0):
        deadline = time.monotonic() + deadline_s
        while time.monotonic() < deadline:
            try:
                with open(path) as f:
                    return json.load(f)["upload_port"]
            except (OSError, ValueError, KeyError):
                time.sleep(0.05)
        fail(f"wal drill: no port file at {path}")

    def put_one(port, blob):
        """One PUT; returns (status_code, retry_after) — status None
        when the collector died mid-request."""
        try:
            conn = HTTPConnection("127.0.0.1", port, timeout=30)
            conn.request("PUT", "/v1/tenants/count/reports",
                         body=blob,
                         headers={"Content-Type": MEDIA_TYPE})
            resp = conn.getresponse()
            resp.read()
            retry_after = resp.getheader("Retry-After")
            conn.close()
            return (resp.status, retry_after)
        except OSError:
            return (None, None)

    def put_all(port, send, brownouts=None):
        """PUT each (index, blob); 503s honor Retry-After and retry
        in place (the brownout contract); a dead socket stops the
        loop — the tail is the client's to retry after resume."""
        acked = []
        for (i, blob) in send:
            while True:
                (code, retry_after) = put_one(port, blob)
                if code == 503:
                    if brownouts is not None:
                        brownouts.append(i)
                        if retry_after is None:
                            fail(f"wal drill: 503 without "
                                 f"Retry-After on upload {i}")
                    time.sleep(min(float(retry_after or 1), 2.0))
                    continue
                break
            if code in (201, 202):
                acked.append(i)
            elif code is None:
                break
            else:
                fail(f"wal drill: upload {i} got {code}")
        return acked

    def cut_and_drain(port):
        conn = HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("POST", "/v1/tenants/count/epoch",
                     headers={"Content-Length": "0"})
        conn.getresponse().read()
        conn.request("POST", "/v1/admin/drain",
                     headers={"Content-Length": "0"})
        conn.getresponse().read()
        conn.close()

    def finish(proc, tag, expect_rc=0):
        (out, err) = proc.communicate(timeout=1500)
        if proc.returncode != expect_rc:
            fail(f"wal drill {tag}: rc={proc.returncode} (wanted "
             f"{expect_rc}): {err[-1500:]}")
        if expect_rc != 0:
            return {}
        return json.loads(out.strip().splitlines()[-1])

    def admitted_total(result):
        return result["metrics"]["tenants"]["count"]["counters"][
            "admitted"]

    def run_schedule(tag, fault, lethal):
        """One campaign entry: run under `fault`; if `lethal`, the
        child must die with the kill exit code and a resumed child
        finishes the collection.  Returns the final run's JSON plus
        the acked bookkeeping."""
        (proc, pf, snap) = spawn(tag, fault=fault)
        port = wait_port(pf)
        brownouts = []
        acked = put_all(port, list(enumerate(blobs)),
                        brownouts=brownouts)
        if not lethal:
            if len(acked) != 6:
                proc.kill()
                fail(f"wal drill {tag}: acked {acked}, wanted all 6")
            cut_and_drain(port)
            return (finish(proc, tag), acked, brownouts, None)
        finish(proc, tag, expect_rc=faults.KILL_EXIT_CODE)
        if os.environ.get("MASTIC_WAL_DRILL_KEEP"):
            pre = os.path.join(tmp, f"{tag}.pre-resume")
            os.makedirs(pre, exist_ok=True)
            shutil.copy(os.path.join(tmp, f"{tag}.snap"), pre)
            shutil.copytree(os.path.join(tmp, f"{tag}.snap.wal"),
                            os.path.join(pre, f"{tag}.snap.wal"),
                            dirs_exist_ok=True)
        (proc, pf2, _s) = spawn(f"{tag}-resumed", resume=True,
                                snap_tag=tag)
        port = wait_port(pf2)
        retries = [(i, blobs[i]) for i in range(6) if i not in acked]
        re_acked = put_all(port, retries)
        if len(re_acked) != len(retries):
            proc.kill()
            fail(f"wal drill {tag}: retries {re_acked} of "
                 f"{[i for (i, _b) in retries]}")
        cut_and_drain(port)
        return (finish(proc, f"{tag}-resumed"), acked + re_acked,
                brownouts, None)

    # Undisturbed baseline.
    (clean, _acked, _b, _r) = run_schedule("clean", None, False)
    clean_admitted = admitted_total(clean)

    runs = []
    # (a) kill -9 at every WAL checkpoint, deterministically.
    for step in ("wal_append", "wal_fsync", "wal_ack"):
        tag = f"kill-{step}"
        fault = f"kill:party=collector:step={step}:nth=4"
        (result, acked, _b, _r) = run_schedule(tag, fault, True)
        if result["results"]["count"] != clean["results"]["count"]:
            print(json.dumps(result), file=sys.stderr, flush=True)
            fail(f"wal drill {tag}: results diverge\n"
                 f"  clean: {clean['results']['count']}\n"
                 f"  {tag}: {result['results']['count']}")
        if admitted_total(result) != clean_admitted:
            fail(f"wal drill {tag}: {admitted_total(result)} "
                 f"admitted, wanted {clean_admitted} (lost or "
                 f"duplicated)")
        wal_info = result.get("wal") or {}
        if "recovery_wall_ms" not in wal_info:
            fail(f"wal drill {tag}: resumed child did not stamp "
                 f"recovery attribution: {wal_info}")
        runs.append({"schedule": fault,
                     "replayed": wal_info.get("replayed_records"),
                     "recovery_wall_ms":
                         wal_info.get("recovery_wall_ms")})

    # (b) seeded randomized disk-fault schedules.
    seeds = list(range(args.wal_drill,
                       args.wal_drill + args.wal_seeds))
    for seed in seeds:
        r = random.Random(seed)
        kind = r.choice(["kill", "kill", "short_write", "enospc"])
        nth = r.randint(2, 5)
        if kind == "kill":
            step = r.choice(["wal_append", "wal_fsync", "wal_ack"])
            fault = f"kill:party=collector:step={step}:nth={nth}"
            lethal = True
        elif kind == "short_write":
            cut = r.randint(1, 24)
            fault = (f"short_write:party=collector:step=wal_append"
                     f":nth={nth}:cut={cut}")
            lethal = True
        else:
            fault = f"enospc:party=collector:step=wal_append:nth={nth}"
            lethal = False
        (result, acked, brownouts, _r2) = run_schedule(
            f"seed-{seed}", fault, lethal)
        if result["results"]["count"] != clean["results"]["count"]:
            fail(f"wal drill seed {seed}: results diverge under "
                 f"[{fault}]\n"
                 f"  clean: {clean['results']['count']}\n"
                 f"  seed-{seed}: {result['results']['count']}")
        if admitted_total(result) != clean_admitted:
            fail(f"wal drill seed {seed}: "
                 f"{admitted_total(result)} admitted, wanted "
                 f"{clean_admitted} under [{fault}] (lost or "
                 f"duplicated)")
        rec = {"seed": seed, "schedule": fault}
        if kind == "enospc":
            if not brownouts:
                fail(f"wal drill seed {seed}: injected ENOSPC but "
                     f"no 503 brownout was observed")
            shed = result["metrics"]["tenants"]["count"][
                "counters"]["shed_reasons"]
            if not shed.get("wal-full"):
                fail(f"wal drill seed {seed}: brownout not "
                     f"attributed as wal-full: {shed}")
            rec["brownouts"] = len(brownouts)
        if kind == "short_write":
            torn = (result.get("wal") or {}).get(
                "recovery", {}).get("torn_tail", 0)
            if not torn:
                fail(f"wal drill seed {seed}: injected torn tail "
                     f"was not counted at recovery: "
                     f"{result.get('wal')}")
            rec["torn_tail"] = torn
        if lethal:
            rec["recovery_wall_ms"] = (result.get("wal") or {}).get(
                "recovery_wall_ms")
        runs.append(rec)
        print(f"wal drill: seed {seed} ok — [{fault}]",
              file=sys.stderr, flush=True)

    shutil.rmtree(tmp, ignore_errors=True)
    out = {
        "mode": "wal-drill",
        "seeds": seeds,
        "checkpoints": ["wal_append", "wal_fsync", "wal_ack"],
        "admitted": clean_admitted,
        "bit_identical": True,
        "runs": runs,
        "wall_seconds": round(time.time() - t_start, 1),
        "ok": True,
    }
    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")


def run_soak(args, svc, m_count, count_values, rng, t_start,
             status=None) -> None:
    """Unattended soak: admit -> epoch -> drain in a loop under one
    deadline; every epoch's output is checked against the expected
    hitters, so a service that degrades mid-soak fails the cell."""
    import jax

    from mastic_tpu.drivers.service import encode_upload
    from mastic_tpu.drivers.session import Deadline

    bits = args.bits
    expected = sorted([[False] * bits, [True] * bits])
    deadline = Deadline(args.soak)
    epochs = 0
    while not deadline.expired():
        reports = build_reports(m_count, b"serve count", rng,
                                count_values, bits)
        for r in reports:
            svc.submit("count", encode_upload(m_count, r))
        svc.begin_epoch("count")
        drain(svc, snapshot_path=args.snapshot, deadline=deadline,
              status=status)
        recs = svc.metrics()["tenants"]["count"]["epochs"]
        if recs and not recs[-1]["truncated"]:
            epochs += 1
            got = sorted(recs[-1]["result"])
            if got != expected:
                fail(f"soak epoch {epochs}: hitters {got} != "
                     f"{expected}")
    counters = svc.metrics()["tenants"]["count"]["counters"]
    out = {
        "mode": "soak",
        "platform": jax.devices()[0].platform,
        "soak_seconds": args.soak,
        "epochs_completed": epochs,
        "rounds": counters["rounds"],
        "wall_seconds": round(time.time() - t_start, 1),
        "counters": counters,
        "ok": epochs >= 1,
    }
    print(json.dumps(out), flush=True)
    if not out["ok"]:
        sys.exit(1)


def run_smoke(args, mesh, status=None) -> None:
    """The serve-smoke gate: one process, every defensive behavior
    demonstrated and asserted (module docstring lists them).  With a
    status server attached (`--status-port`), the three observability
    endpoints are self-fetched over real HTTP mid-run and their
    expected per-tenant series asserted (the obs-smoke gate)."""
    import numpy as np
    import jax

    from mastic_tpu.drivers.service import (ADMITTED, QUARANTINED,
                                            SHED, CollectorService,
                                            ServiceConfig, TenantSpec,
                                            encode_upload)
    from mastic_tpu.mastic import MasticCount

    t_start = time.time()
    rng = np.random.default_rng(args.seed)
    bits = 2
    m = MasticCount(bits)
    m_attr = MasticCount(8)
    vk = bytes(rng.integers(0, 256, m.VERIFY_KEY_SIZE, dtype="uint8"))
    vk_attr = bytes(rng.integers(0, 256, m_attr.VERIFY_KEY_SIZE,
                                 dtype="uint8"))

    def specs():
        return [
            TenantSpec(name="count",
                       spec={"class": "MasticCount", "args": [bits]},
                       ctx=b"smoke count", verify_key=vk,
                       thresholds={"default": 2},
                       chunk_size=args.chunk_size),
            TenantSpec(name="attrs",
                       spec={"class": "MasticCount", "args": [8]},
                       ctx=b"smoke attrs", verify_key=vk_attr,
                       mode="attribute_metrics",
                       attributes=["checkout.html", "landing.html"],
                       chunk_size=args.chunk_size),
            # Overload scratch tenant: tiny quota, never scheduled.
            TenantSpec(name="flood",
                       spec={"class": "MasticCount", "args": [bits]},
                       ctx=b"smoke flood", verify_key=vk,
                       thresholds={"default": 2}, max_buffered=5),
            # Deadline tenant: an already-expired epoch budget, so
            # its epoch degrades to the truncated frontier.
            TenantSpec(name="slow",
                       spec={"class": "MasticCount", "args": [bits]},
                       ctx=b"smoke slow", verify_key=vk,
                       thresholds={"default": 2}, epoch_deadline=0.0),
        ]

    config = ServiceConfig(page_size=3, max_buffered=64,
                           max_pending_epochs=2,
                           shed_policy="reject-newest",
                           quarantine_limit=16,
                           epoch_deadline=600.0)
    svc = CollectorService(specs(), config=config, mesh=mesh)

    # 1. malformed-upload burst: reason-coded quarantine, tenant-
    # attributed; the other tenants are untouched.
    for blob in (b"", b"\x07garbage", b"\xff" * 40):
        (outcome, detail) = svc.submit("count", blob)
        if outcome != QUARANTINED:
            fail(f"malformed blob admitted: {(outcome, detail)}")
    qm = svc.metrics()["tenants"]
    if qm["count"]["counters"]["quarantined"] != 3 \
            or qm["count"]["suspended"] \
            or qm["attrs"]["counters"]["quarantined"] != 0:
        fail(f"quarantine counters wrong: {qm['count']['counters']}")

    # 2. sustained overload against the flood tenant's quota of 5:
    # admission stays bounded, sheds are counted, memory is pages
    # not uploads.
    flood_reports = build_reports(m, b"smoke flood", rng,
                                  [0] * 12, bits)
    outcomes = admit_all(svc, "flood", m, flood_reports)
    admitted = sum(1 for o in outcomes if o[0] == ADMITTED)
    shed = sum(1 for o in outcomes if o[0] == SHED)
    fm = svc.metrics()["tenants"]["flood"]
    if admitted != 5 or shed != 7 \
            or fm["buffered_reports"] != 5 \
            or fm["counters"]["shed_reasons"].get("reject-newest") != 7:
        fail(f"reject-newest overload wrong: admitted={admitted} "
             f"shed={shed} {fm['counters']}")

    # 2b. oldest-epoch-first on a scratch service: the oldest queued
    # epoch is dropped to admit fresh load.  (Fresh spec: the flood
    # tenant above carries its own tighter max_buffered override.)
    svc_old = CollectorService(
        [TenantSpec(name="flood",
                    spec={"class": "MasticCount", "args": [bits]},
                    ctx=b"smoke flood", verify_key=vk,
                    thresholds={"default": 2}, max_buffered=6)],
        config=ServiceConfig(page_size=3,
                             max_pending_epochs=2,
                             shed_policy="oldest-epoch-first",
                             epoch_deadline=600.0))
    admit_all(svc_old, "flood", m,
              build_reports(m, b"smoke flood", rng, [0] * 6, bits),
              expect=ADMITTED)
    first_epoch = svc_old.begin_epoch("flood")
    outcomes = admit_all(svc_old, "flood", m,
                         build_reports(m, b"smoke flood", rng,
                                       [1] * 3, bits),
                         expect=ADMITTED)   # room made by the drop
    om = svc_old.metrics()["tenants"]["flood"]
    if first_epoch != 0 or om["pending_epochs"] != 0 \
            or om["counters"]["shed_reasons"] \
            .get("oldest-epoch-first") != 6:
        fail(f"oldest-epoch-first wrong: {om}")

    # 3. real multi-tenant work, admission continuing mid-flight.
    count_values = [0, 0, 0, 3, 3]
    count_reports = build_reports(m, b"smoke count", rng,
                                  count_values, bits)
    admit_all(svc, "count", m, count_reports, expect=ADMITTED)
    svc.begin_epoch("count")
    from mastic_tpu.drivers.attribute_metrics import hash_attribute
    alpha = hash_attribute(m_attr, "checkout.html")
    attr_int = int("".join("1" if b else "0" for b in alpha), 2)
    attr_reports = build_reports(m_attr, b"smoke attrs", rng,
                                 [attr_int, attr_int, 0], 8)
    admit_all(svc, "attrs", m_attr, attr_reports, expect=ADMITTED)
    svc.begin_epoch("attrs")
    # deadline tenant: its expired budget must degrade, not hang.
    admit_all(svc, "slow", m,
              build_reports(m, b"smoke slow", rng, [0, 0, 3], bits),
              expect=ADMITTED)
    svc.begin_epoch("slow")

    steps = 0
    while svc.step():
        steps += 1
        publish_status(status, svc)
        if steps == 1:
            # admission while rounds are in flight: lands in the
            # open page, joins the NEXT epoch.
            admit_all(svc, "count", m,
                      build_reports(m, b"smoke count", rng,
                                    count_values, bits),
                      expect=ADMITTED)
        if steps > 200:
            fail("drain did not converge")
    publish_status(status, svc)
    if status is not None:
        # The obs-smoke teeth: fetch all three endpoints over HTTP
        # during the live process and assert the acceptance series.
        check_status_endpoints(status)

    mx = svc.metrics()["tenants"]
    count_rec = mx["count"]["epochs"][0]
    expected_hitters = sorted([[False] * bits, [True] * bits])
    if count_rec["truncated"] \
            or sorted(count_rec["result"]) != expected_hitters:
        fail(f"count epoch wrong: {count_rec}")
    attr_rec = mx["attrs"]["epochs"][0]
    if attr_rec["truncated"] or attr_rec["result"][0][1] != [2] \
            and attr_rec["result"][0][1] != 2:
        fail(f"attrs epoch wrong: {attr_rec}")
    slow_rec = mx["slow"]["epochs"][0]
    if not slow_rec["truncated"] \
            or mx["slow"]["counters"]["deadline_misses"] != 1:
        fail(f"deadline miss not degraded: {slow_rec}")

    # 4. crash drill: second count epoch, snapshot mid-epoch, discard
    # the live service, resume, drain — result bit-identical to the
    # first epoch's (same reports are NOT required; same VALUES are,
    # so compare against epoch 0's result).
    svc.begin_epoch("count")   # the mid-flight admissions from step 1
    svc.step()                 # one round into the epoch
    blob = svc.to_bytes()
    del svc
    svc2 = CollectorService.from_bytes(blob, config=config, mesh=mesh)
    drain(svc2)
    mx2 = svc2.metrics()["tenants"]
    resumed_rec = mx2["count"]["epochs"][1]
    if resumed_rec["truncated"] \
            or sorted(resumed_rec["result"]) != expected_hitters:
        fail(f"resumed epoch wrong: {resumed_rec}")
    if not mx2["count"]["counters"]["resumes"]:
        fail("resume not counted")

    out = {
        "mode": "smoke",
        "platform": jax.devices()[0].platform,
        "wall_seconds": round(time.time() - t_start, 1),
        "tenants": {name: t["counters"]
                    for (name, t) in mx2.items()},
        "scheduler_rounds": steps,
        "status_port": status.port if status is not None else None,
        "ok": True,
    }
    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
