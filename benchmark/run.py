"""Run one benchmark cell once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is `benchmark/workloads/<cell>.json`; it names a deployment
`benchmark/configs/<config>.json`, whose `mode` picks the traffic
generator and plain reference in `benchmark/modes/<mode>.py`.  Each
per-layer metric is read by `benchmark/metrics/<metric>.py`.  The
metrics a cell reports are those `BENCHMARK.json` lists for it.

One run: where the compile cache does not yet hold this cell's
programs (a marker keyed by the cell and every source file of the
program and of the benchmark), a child process runs one collection of
the cell's own shape to fill it, is held to the plain reference, and
exits (set-up); so the measuring process is the same whether or not
the cache was cold.  Then start the collector service (upload front,
admission WAL, epochs) with the cell's tenant and start collections
back to back while less than `--seconds` of collection time has
passed, each run to its published result.  The client sharding of
each collection's uploads is traffic generation and happens between
collections, outside the timed window.  After the window every
collection is held to the plain reference.  With `--trace 1` the
first window collection's rounds run under `jax.profiler` for
`TRACE_ROUNDS` levels from each depth in `TRACE_AT`, and the
per-layer metrics are printed.

The last line of stdout is one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device`, with `--trace 1` `breakdown`, and last
`checks`, each compared number beside its limit.  Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from http.client import HTTPConnection  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CTX = b"mastic benchmark"
# The traced run profiles TRACE_ROUNDS levels from each of these
# shares of the depth: past the early levels, whose rounds are mostly
# program acquisition, where the level steps and binder hashing run.
TRACE_AT = (0.5, 0.75)
TRACE_ROUNDS = 8
TENANT = "bench"
# Streams of the seed: the run's keys, then one per collection.
KEY_STREAM = 0


class BenchFailure(Exception):
    """The harness could not run the cell."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`benchmark/<kind>/<name>.py`, found by name."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind.strip('.')}_{name.replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def warm_marker(cache_dir: str, name: str, workload: dict,
                cfg: dict) -> str:
    """The file that says a collection of this cell filled the compile
    cache: keyed by the cell, the JAX version, the platform asked for,
    and every source file of the program and of the benchmark (the
    harness, the traffic generators, the readers), so that a changed
    program or traffic fills it again.  Read before JAX touches a
    device, so it cannot name the device kind; XLA's own cache keys do."""
    import hashlib

    import jax

    h = hashlib.sha256(json.dumps(
        [workload, cfg, jax.__version__,
         os.environ.get("JAX_PLATFORMS", "")], sort_keys=True).encode())
    sources = sorted(
        glob.glob(os.path.join(ROOT, "mastic_tpu", "**", "*.py"),
                  recursive=True)
        + glob.glob(os.path.join(HERE, "**", "*.py"), recursive=True))
    for path in sources + [os.path.join(ROOT, "tools", "serve.py")]:
        h.update(os.path.relpath(path, ROOT).encode())
        with open(path, "rb") as f:
            h.update(f.read())
    return os.path.join(cache_dir, "mastic-bench",
                        f"{name}-{h.hexdigest()[:16]}.warm")


def rng_for(seed: int, stream: int):
    return np.random.default_rng([seed % 2 ** 64, stream])


def say(msg: str) -> None:
    print(f"bench [{time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


# -- the client side ----------------------------------------------------

def upload(port_file: str, blobs: list, threads: int, out: dict) -> None:
    """PUT every blob over keep-alive connections from `threads`
    clients, then ask for the epoch cut and the drain.  The drain is
    requested whatever happened, so the serving loop never waits out
    its window.  Fills `out`: codes, errors, first_put, last_ack."""
    import jax

    from mastic_tpu.net.ingest import MEDIA_TYPE
    from mastic_tpu.net.loadgen import _no_nagle_connection

    mu = threading.Lock()
    cursor = [0]
    codes = out["codes"]
    errors = out["errors"]

    def port() -> int:
        give_up = time.monotonic() + 120.0
        while time.monotonic() < give_up:
            try:
                with open(port_file) as f:
                    return json.load(f)["upload_port"]
            except (OSError, ValueError, KeyError):
                time.sleep(0.005)
        raise BenchFailure("the upload front never published its port")

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
    except BenchFailure as exc:
        errors.append(str(exc))
        return
    try:
        workers = [threading.Thread(target=worker, args=(p,))
                   for _ in range(threads)]
        with jax.profiler.TraceAnnotation("bench.upload"):
            out["first_put"] = time.perf_counter()
            for th in workers:
                th.start()
            for th in workers:
                th.join()
            out["last_ack"] = time.perf_counter()
        post(p, f"/v1/tenants/{TENANT}/epoch")
    except Exception as exc:
        errors.append(f"{type(exc).__name__}: {exc}")
    finally:
        try:
            post(p, "/v1/admin/drain")
        except OSError as exc:
            errors.append(f"drain: {type(exc).__name__}: {exc}")


# -- the cell -----------------------------------------------------------

class Cell:
    """One cell's service, tenant and traffic, driven collection by
    collection."""

    def __init__(self, workload: dict, cfg: dict, seed: int, tmp: str):
        from mastic_tpu.backend.mastic_jax import BatchedMastic
        from mastic_tpu.drivers.parties import instantiate
        from mastic_tpu.drivers.service import (CollectorService,
                                                ServiceConfig,
                                                TenantSpec)
        from mastic_tpu.drivers.wal import AdmissionWal
        from tools.serve import write_snapshot

        self.workload = workload
        self.cfg = cfg
        self.seed = seed
        self.tmp = tmp
        self.mode = load_module("modes", cfg["mode"])
        self.m = instantiate(cfg["mastic"])
        self.bm = BatchedMastic(self.m)
        self._shard_fn = None
        key_rng = rng_for(seed, KEY_STREAM)
        verify_key = bytes(key_rng.integers(0, 256, self.m.VERIFY_KEY_SIZE,
                                            dtype=np.uint8))
        spec = TenantSpec(name=TENANT, spec=cfg["mastic"], ctx=CTX,
                          verify_key=verify_key,
                          chunk_size=cfg["chunk_size"],
                          **self.mode.tenant(cfg))
        # No epoch deadline: an epoch runs every level or fails.
        self.svc = CollectorService(
            [spec], config=ServiceConfig(max_buffered=cfg["reports"],
                                         epoch_deadline=None))
        self.snap = os.path.join(tmp, "collector.snap")
        self.wal = AdmissionWal(self.snap + ".wal",
                                injector=self.svc.injector, fresh=True)
        self.wal.mark_covered(self.wal.tail_seq(),
                              write_snapshot(self.svc, self.snap))
        self.collections = 0

    def close(self) -> None:
        self.wal.close()

    def traffic(self, stream: int) -> dict:
        """One collection's measurements and upload blobs, from the
        seed's `stream`."""
        rng = rng_for(self.seed, stream)
        t = self.mode.traffic(self.cfg, rng)
        num = len(t["weights"])
        t["nonces"] = rng.integers(0, 256, (num, 16), dtype=np.uint8)
        t["rand"] = rng.integers(0, 256, (num, self.m.RAND_SIZE),
                                 dtype=np.uint8)
        t["blobs"] = self._shard(t)
        return t

    def _shard(self, t: dict) -> list:
        """Device-batched client sharding, then the wire blobs.  Lanes
        where the device shard's XOF rejection fired are re-sharded
        through the scalar client from the same nonce and rand."""
        import jax

        from mastic_tpu.drivers.service import encode_upload
        from mastic_tpu.net.loadgen import encode_upload_batch

        (alphas, weights) = (t["alphas"], t["weights"])
        (_, betas) = self.bm.encode_measurements(
            [(alphas[r], int(weights[r])) for r in range(len(weights))])
        if self._shard_fn is None:
            bm = self.bm
            self._shard_fn = jax.jit(
                lambda a, b, n, r: bm.shard_device(CTX, a, b, n, r))
        (batch, ok) = self._shard_fn(alphas, betas, t["nonces"], t["rand"])
        ok = np.asarray(ok)
        blobs = encode_upload_batch(self.bm, batch)
        for r in np.flatnonzero(~ok):
            nonce = t["nonces"][r].tobytes()
            meas = (tuple(bool(b) for b in alphas[r]), int(weights[r]))
            blobs[r] = encode_upload(self.m, (nonce, *self.m.shard(
                CTX, meas, nonce, t["rand"][r].tobytes())))
        return blobs

    def collect(self, blobs: list, trace_dir: str = None) -> dict:
        """One collection through the serving path: PUTs to the upload
        front with the WAL under it, the epoch cut, service steps until
        drained.  Returns its timings, fault counts and what it
        published.  With `trace_dir`, `TRACE_ROUNDS` levels from each
        depth in `TRACE_AT` run under the profiler, each stretch into a
        directory of its own under `trace_dir`."""
        import jax

        from mastic_tpu.obs.registry import get_registry
        from tools.serve import run_upload_window

        svc = self.svc
        self.collections += 1
        retries = get_registry().counter("mastic_session_retries_total",
                                         tenant=TENANT)
        retries_before = retries.value()
        before = json.loads(json.dumps(
            svc.metrics()["tenants"][TENANT]["counters"]))
        epochs_before = len(svc.tenants[TENANT].completed)
        window = SimpleNamespace(
            upload_port=0,
            port_file=os.path.join(self.tmp,
                                   f"port{self.collections}.json"),
            snapshot=self.snap, snapshot_every=600.0,
            upload_window=900.0)
        client_out = {"codes": {}, "errors": [], "first_put": None,
                      "last_ack": None}
        client = threading.Thread(
            target=upload, args=(window.port_file, blobs,
                                 self.workload["upload_threads"],
                                 client_out))
        tenant = svc.tenants[TENANT]
        with jax.profiler.TraceAnnotation("bench.collection"):
            client.start()
            with jax.profiler.TraceAnnotation("bench.upload_window"):
                run_upload_window(window, svc, None, wal=self.wal)
            client.join()
            # The epoch the upload window cut; its run is built by the
            # first step and stays on it.
            epoch = tenant.pending[-1] if tenant.pending else None
            with jax.profiler.TraceAnnotation("bench.first_step"):
                ts = time.perf_counter()
                more = svc.step()
                te = time.perf_counter()
            run = epoch.run if epoch is not None else None
            bits = self.cfg["bits"]
            starts = ([int(share * bits) for share in TRACE_AT]
                      if trace_dir is not None and run is not None else [])
            traced = []
            with jax.profiler.TraceAnnotation("bench.rounds"):
                while more:
                    if starts and len(run.metrics) >= starts[0]:
                        path = os.path.join(trace_dir, f"at{starts.pop(0)}")
                        (more, stretch) = self._traced_steps(path, run)
                        traced.append(stretch)
                    else:
                        more = svc.step()
            t_end = time.perf_counter()
        if trace_dir is not None and not traced:
            raise BenchFailure("the traced collection reached none of the "
                               "levels to trace")
        codes = client_out["codes"]
        first_put = client_out["first_put"] or ts
        last_ack = client_out["last_ack"] or ts
        mx = svc.metrics()["tenants"][TENANT]
        c = mx["counters"]
        recs = mx["epochs"][epochs_before:]
        rec = recs[-1] if recs else {"result": [], "truncated": True,
                                     "levels_completed": 0}
        if run is not None and run.metrics:
            # The first quantum starts the epoch (page decode, device
            # marshal, run set-up) and runs the first round.
            first_round = te - run.metrics[0].extra["round_wall_ms"] / 1e3
        else:
            first_round = te
        delta = {k: c[k] - before[k] for k in c
                 if isinstance(c[k], (int, float))}
        admitted = delta["admitted"]
        faults = {
            "refused_uploads": (sum(n for (code, n) in codes.items()
                                    if not 200 <= code < 300)
                                + len(blobs) - sum(codes.values())
                                + len(client_out["errors"])),
            "shed_or_quarantined": delta["quarantined"] + delta["shed"],
            "epoch_faults": (delta["epochs_failed"]
                             + delta["epochs_truncated"]
                             + delta["deadline_misses"]
                             + abs(delta["epochs_completed"] - 1)
                             + abs(len(recs) - 1)
                             + int(bool(rec["truncated"]))
                             + int("error" in rec)
                             + int(retries.value() - retries_before)),
            "accept_shortfall": len(blobs) - admitted,
            "round_faults": 0,
            "warm_errors": 0,
        }
        if "error" in rec:
            say(f"epoch failed: {rec['error']}")
        if client_out["errors"]:
            say(f"upload client: {client_out['errors'][:3]}")
        got = None
        compile_s = None
        node_evals = 0
        if run is None or len(run.metrics) != rec["levels_completed"] \
                or not run.metrics:
            faults["epoch_faults"] += 1
        else:
            for r in run.metrics:
                faults["accept_shortfall"] += (
                    abs(admitted - r.accepted)
                    + abs(admitted - r.reports_total))
                faults["round_faults"] += (r.retries + r.respawns
                                           + r.timeouts)
                node_evals += r.node_evals
            pipes = [r.extra.get("pipeline") for r in run.metrics]
            if all(p and "phases" in p for p in pipes):
                faults["warm_errors"] = max(p["aot"]["warm_errors"]
                                            for p in pipes)
                compile_s = sum(p["phases"]["compile_ms"]
                                + p["phases"]["warm_ms"]
                                for p in pipes) / 1e3
            got = self.mode.published(run, rec)
        # Writing the trace is the profiler's time, not the program's.
        written = sum(stretch["write_s"] for stretch in traced)
        return {
            "reports": len(blobs),
            "wall_s": t_end - first_put - written,
            "upload_s": last_ack - first_put,
            "ingest_s": first_round - last_ack,
            "rounds_s": t_end - first_round - written,
            "traced": traced,
            "compile_s": compile_s,
            "node_evals": node_evals,
            "faults": faults,
            "got": got,
        }


    def _traced_steps(self, trace_dir: str, run) -> tuple:
        """Service steps under the profiler until `TRACE_ROUNDS` more
        rounds are done (or the collection is), inside a `bench.traced`
        span.  A whole collection is not traced: writing its trace
        takes longer than the collection.  Returns (more, what was
        traced)."""
        import jax

        done = len(run.metrics)
        more = True
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=profile_options())
        try:
            with jax.profiler.TraceAnnotation("bench.traced"):
                while more and len(run.metrics) < done + TRACE_ROUNDS:
                    with jax.profiler.TraceAnnotation("bench.step"):
                        more = self.svc.step()
        finally:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            write_s = time.perf_counter() - t0
        levels = [done, len(run.metrics) - 1]
        say(f"traced levels {levels[0]}-{levels[1]}; trace written in "
            f"{write_s:.1f}s")
        return (more, {"path": trace_dir, "levels": levels,
                       "node_evals": sum(r.node_evals
                                         for r in run.metrics[done:]),
                       "write_s": write_s})


# -- one run ------------------------------------------------------------

def benchmark_spec() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def cell_metrics(spec: dict, workload: str, kind: str) -> list:
    return [m for m in spec[kind]
            if "workloads" not in m or workload in m["workloads"]]


def device_info(chips: int, require_tpu: bool) -> tuple:
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise BenchFailure(f"no TPU: JAX found {devices[0].platform} "
                           f"devices")
    if len(devices) < chips:
        raise BenchFailure(f"the cell needs {chips} chip(s), JAX found "
                           f"{len(devices)}")
    return (devices[:chips], {"platform": devices[0].platform,
                              "kind": devices[0].device_kind,
                              "count": chips})


def profile_options():
    """Host spans from `TraceAnnotation` and device ops only: no Python
    function tracing and no HLO protos."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def load_cell(workload_name: str, overrides: dict = None) -> tuple:
    """(workload, configuration), with `overrides` replacing keys of the
    configuration (the checks at small sizes)."""
    workload = load_json(HERE, "workloads", f"{workload_name}.json")
    cfg = load_json(HERE, "configs", f"{workload['config']}.json")
    cfg.update(overrides or {})
    return (workload, cfg)


def judge(mode, cfg: dict, rec: dict) -> dict:
    """One collection's compared numbers, each held to 0: its fault
    counts, and what it published against the plain reference."""
    t = rec.pop("traffic")
    counts = dict(rec["faults"])
    ref = mode.reference(cfg, t["alphas"], t["weights"])
    if rec["got"] is None:
        counts["missing_results"] = 1
        got = {k: [] for k in ref}
    else:
        counts["missing_results"] = 0
        got = rec["got"]
    counts.update(mode.compare(got, ref))
    return {k: int(v) for (k, v) in counts.items()}


def fill_cache(workload_name: str, seed: int, require_tpu: bool,
               overrides: dict, marker: str) -> dict:
    """In this process: one collection of the cell (the seed's first
    stream), held to the reference; where it is correct, the marker is
    written.  Returns its compared numbers."""
    (workload, cfg) = load_cell(workload_name, overrides)
    device_info(workload["chips"], require_tpu)
    with tempfile.TemporaryDirectory(prefix="mastic-bench-") as tmp:
        cell = Cell(workload, cfg, seed, tmp)
        try:
            t = cell.traffic(1)
            rec = cell.collect(t["blobs"])
            rec["traffic"] = t
        finally:
            cell.close()
    say(f"cache-filling collection: {rec['wall_s']:.1f}s")
    counts = judge(cell.mode, cfg, rec)
    if not any(counts.values()):
        os.makedirs(os.path.dirname(marker), exist_ok=True)
        with open(marker, "w"):
            pass
    return counts


def end_with_parent() -> None:
    """Have the kernel end this process when its parent ends, so that a
    run stopped from outside leaves no cache-filling child behind."""
    import ctypes
    import signal

    PR_SET_PDEATHSIG = 1
    ctypes.CDLL(None, use_errno=True).prctl(PR_SET_PDEATHSIG,
                                            signal.SIGKILL)


def fill_cache_child(workload_name: str, seed: int, require_tpu: bool,
                     overrides: dict) -> dict:
    """Run `fill_cache` in a child process, so that this process has
    not touched a device while the child holds it; returns the child's
    compared numbers."""
    cmd = [sys.executable, os.path.abspath(__file__),
           "--workload", workload_name, "--seed", str(seed),
           "--seconds", "0", "--fill-cache"]
    if not require_tpu:
        cmd.append("--allow-cpu")
    if overrides:
        cmd += ["--overrides", json.dumps(overrides)]
    proc = subprocess.run(cmd, stdout=subprocess.PIPE, text=True)
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        raise BenchFailure(f"the cache-filling collection failed "
                           f"(exit {proc.returncode})")
    return json.loads(lines[-1])["checks"]


def run_cell(workload_name: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, overrides: dict = None) -> dict:
    """One run of one cell; returns the result object.  `overrides`
    replaces keys of the configuration (the checks at small sizes)."""
    from mastic_tpu import compile_cache

    (workload, cfg) = load_cell(workload_name, overrides)
    chips = workload["chips"]
    cache_dir = compile_cache.configure()
    marker = warm_marker(cache_dir, workload_name, workload, cfg)
    checks: dict = {}
    if not os.path.exists(marker):
        say(f"{workload_name}: the compile cache lacks the cell; filling "
            f"it in a child process")
        checks = fill_cache_child(workload_name, seed, require_tpu,
                                  overrides)
    (devices, device) = device_info(chips, require_tpu)
    say(f"{workload_name}: {device['kind']} x{chips}, compile cache "
        f"{cache_dir}")

    collections = []
    with tempfile.TemporaryDirectory(prefix="mastic-bench-") as tmp:
        cell = Cell(workload, cfg, seed, tmp)
        try:
            # Stream 1 is the cache-filling collection's.
            stream = 1
            setup_s = time.perf_counter() - _T0
            trace_dir = None
            window_s = 0.0
            while not collections or window_s < seconds:
                stream += 1
                t = cell.traffic(stream)
                if trace and trace_dir is None:
                    trace_dir = os.path.join(tmp, "trace")
                    rec = cell.collect(t["blobs"], trace_dir)
                else:
                    rec = cell.collect(t["blobs"])
                rec["traffic"] = t
                window_s += rec["wall_s"]
                collections.append(rec)
                say(f"collection {len(collections)}: "
                    f"{rec['wall_s']:.1f}s "
                    f"(upload {rec['upload_s']:.1f}, ingest "
                    f"{rec['ingest_s']:.1f}, rounds {rec['rounds_s']:.1f})")
            peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in devices)
        finally:
            cell.close()
        mode = cell.mode
        del cell
        gc.collect()
        reduced = None
        stretches = [s for rec in collections for s in rec["traced"]]
        if stretches:
            tr = load_module(".", "trace")
            t0 = time.perf_counter()
            reduced = tr.combine([
                (f"levels {s['levels'][0]}-{s['levels'][1]}",
                 tr.reduce(tr.find_xplane(s["path"]), chips,
                           window="traced", top=None))
                for s in stretches])
            say(f"trace reduced in {time.perf_counter() - t0:.1f}s")
            shutil.rmtree(trace_dir, ignore_errors=True)

    # The comparison with the plain reference, every collection.
    for rec in collections:
        for (k, v) in judge(mode, cfg, rec).items():
            checks[k] = checks.get(k, 0) + v
    correct = all(v == 0 for v in checks.values())
    attempted = sum(r["reports"] for r in collections)
    failed = attempted if not correct else 0

    run = {"collections": collections, "trace": reduced,
           "setup_s": setup_s, "peak_bytes": peak, "cfg": cfg}
    spec = benchmark_spec()
    metrics = {}
    if trace:
        for m in cell_metrics(spec, workload_name, "per_layer"):
            value = load_module("metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell_metrics(spec, workload_name, "end_to_end"):
            metrics[m["name"]] = {"value": END_TO_END[m["name"]](run),
                                  "unit": m["unit"]}
    device["memory_peak_bytes"] = int(peak)
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
        out["traced_levels"] = [s["levels"] for s in stretches]
    out["checks"] = {k: {"value": v, "limit": 0}
                     for (k, v) in checks.items()}
    return out


END_TO_END = {
    "setup_s": lambda run: run["setup_s"],
    "collected_reports_per_s": lambda run: (
        sum(r["reports"] for r in run["collections"])
        / sum(r["wall_s"] for r in run["collections"])),
    "peak_device_gib": lambda run: run["peak_bytes"] / 2 ** 30,
}


def main() -> int:
    parser = argparse.ArgumentParser(
        description="run one benchmark cell once on the chip")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # The child that fills the compile cache, and what the checks at
    # small sizes on the CPU pass down to it.
    parser.add_argument("--fill-cache", action="store_true",
                        help=argparse.SUPPRESS)
    parser.add_argument("--allow-cpu", action="store_true",
                        help=argparse.SUPPRESS)
    parser.add_argument("--overrides", type=json.loads, default=None,
                        help=argparse.SUPPRESS)
    args = parser.parse_args()
    try:
        if args.fill_cache:
            end_with_parent()
            from mastic_tpu import compile_cache

            (workload, cfg) = load_cell(args.workload, args.overrides)
            marker = warm_marker(compile_cache.configure(), args.workload,
                                 workload, cfg)
            checks = fill_cache(args.workload, args.seed,
                                not args.allow_cpu, args.overrides, marker)
            print(json.dumps({"checks": checks}), flush=True)
            return 0
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), not args.allow_cpu,
                       args.overrides)
    except BenchFailure as exc:
        print(f"bench: {exc}", file=sys.stderr, flush=True)
        return 1
    for (k, v) in out["checks"].items():
        print(f"check {k} {v['value']} limit {v['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
