"""Benchmark: steady-state VIDPF evaluation throughput on one chip.

Prints ONE JSON line on stdout:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "configs": {...}}

The headline metric is the BASELINE.json north star — VIDPF node
evaluations per second per chip at 256-bit tree depth, where one node
evaluation is the full extend + correct + convert + node-proof
pipeline of /root/reference/poc/vidpf.py:281-325 (2 fixed-key-AES
blocks + 2 AES convert blocks + 1 TurboSHAKE-128 hash per node,
reference op model in BASELINE.md / PERF.md).  The reference publishes
no timing numbers, so vs_baseline compares against this repo's own
scalar CPU reference layer (the same byte-exact math the reference's
Python PoC runs), measured in-process.

Shapes mimic the heavy-hitters steady state: a pruned frontier of
constant width marching down a 256-level tree; each timed step is one
tree level over (reports x frontier) with a traced node binder so a
single compiled program serves every level.

`configs` carries the BASELINE.json per-config entries:
  incremental_round      full steady-state incremental round (tree
                         step + binder hashing + eval proof + masked
                         aggregation; backend/incremental.py) at the
                         headline shape — rounds/s and evals/s
  prep_round_p50_ms      p50 single-round latency of the same program
                         (includes host dispatch)
  histogram_f128_b64     MasticHistogram(16, 4) @ BITS=64 — Field128
                         limb kernels + device FLP weight check
  sumvec1024_f128_b128   MasticSumVec(1024, 1, 32) @ BITS=128 —
                         huge-payload convert; reported as payload
                         bytes/s next to evals/s

Every phase (import / scalar baseline / compile / warmup / measure /
each config) stamps progress to stderr.  A phase that fails or hangs
past the watchdog prints the JSON line with the failing phase named
in "error" and exits non-zero; no number is carried over from an
earlier run or a smaller shape, and every line names the platform it
was measured on.
"""

import argparse
import json
import os
import subprocess
import sys
import threading
import time

_T0 = time.time()

# Partial-result record, updated as phases complete; the watchdog and
# any exception handler print it so a hang/crash still yields data.
PARTIAL = {
    "metric": "vidpf_node_evals_per_sec_per_chip_256bit",
    "value": 0.0,
    "unit": "evals/s",
    "vs_baseline": 0.0,
    "phase": "start",
}


def stamp(phase: str, **info) -> None:
    """Progress line on stderr + phase update for the fail-open JSON."""
    PARTIAL["phase"] = phase
    extra = " ".join(f"{k}={v}" for (k, v) in info.items())
    print(f"[bench {time.time() - _T0:7.1f}s] {phase} {extra}".rstrip(),
          file=sys.stderr, flush=True)


def emit(error: str | None = None) -> None:
    out = dict(PARTIAL)
    phase = out.pop("phase")
    if error is not None:
        out["error"] = f"{error} (last phase: {phase})"
    print(json.dumps(out), flush=True)


def _watchdog(seconds: float):
    """Emit the partial result and hard-exit non-zero if any phase
    hangs."""

    def fire():
        emit(error=f"watchdog timeout after {seconds:.0f}s")
        os._exit(2)

    timer = threading.Timer(seconds, fire)
    timer.daemon = True
    timer.start()
    return timer


def scalar_rate(bits: int = 256, level: int = 3) -> float:
    """Node evals/sec of the scalar byte-exact reference layer."""
    from mastic_tpu.field import Field64
    from mastic_tpu.vidpf import Vidpf

    vidpf = Vidpf(Field64, bits, 2)
    alpha = tuple(bool(i % 2) for i in range(bits))
    beta = [Field64(1), Field64(1)]
    nonce = bytes(16)
    rand = bytes(range(32))
    (cws, keys) = vidpf.gen(alpha, beta, b"bench", nonce, rand)
    prefixes = tuple(
        tuple(bool((v >> (level - i)) & 1) for i in range(level + 1))
        for v in range(2 ** (level + 1)))
    t0 = time.perf_counter()
    (_, tree) = vidpf.eval_level_synchronous(
        0, cws, keys[0], level, prefixes, b"bench", nonce)
    dt = time.perf_counter() - t0
    nodes = sum(len(lvl) for lvl in tree.levels)
    return nodes / dt


class SteadyState:
    """The compiled one-level step at a given (reports, frontier)."""

    def __init__(self, bm, reports: int, frontier: int, bits: int):
        import numpy as np
        import jax
        import jax.numpy as jnp

        from mastic_tpu.backend.vidpf_jax import EvalState

        vid = bm.vidpf
        ctx = b"bench"
        rng = np.random.default_rng(0)
        nonces = jnp.asarray(rng.integers(0, 256, (reports, 16),
                                          dtype=np.uint8))
        (ext_rk, conv_rk) = jax.jit(
            lambda n: vid.roundkeys(ctx, n))(nonces)
        jax.block_until_ready(ext_rk)

        self.cw = (
            jnp.asarray(rng.integers(0, 256, (reports, 16), np.uint8)),
            jnp.asarray(rng.integers(0, 2, (reports, 2)).astype(bool)),
            jnp.asarray(rng.integers(
                0, 1 << 16,
                (reports, vid.VALUE_LEN, bm.spec.num_limbs),
                dtype=np.uint32)),
            jnp.asarray(rng.integers(0, 256, (reports, 32), np.uint8)),
        )
        # Binder is traced data so one compile serves every level (at
        # depth >= 248 of a 256-bit tree the path encoding is 32 B).
        self.binder = jnp.asarray(rng.integers(
            0, 256, (2 * frontier, 36), dtype=np.uint8))
        keep = np.arange(0, 2 * frontier, 2)

        def step(seed, ctrl, binder):
            parents = EvalState(
                seed=seed, ctrl=ctrl,
                w=jnp.zeros((reports, frontier, vid.VALUE_LEN,
                             bm.spec.num_limbs), jnp.uint32),
                proof=jnp.zeros((reports, frontier, 32), jnp.uint8))
            (child, ok) = vid.eval_step(ext_rk, conv_rk, parents,
                                        self.cw, ctx, binder)
            # Prune back to the frontier width (threshold survivors).
            return (child.seed[:, keep], child.ctrl[:, keep],
                    child.proof, ok)

        self.seed = jnp.asarray(rng.integers(
            0, 256, (reports, frontier, 16), dtype=np.uint8))
        self.ctrl = jnp.asarray(rng.integers(
            0, 2, (reports, frontier)).astype(bool))
        self.step = jax.jit(step)
        self.jax = jax
        self.evals_per_step = reports * 2 * frontier

    def compile(self) -> float:
        t0 = time.perf_counter()
        compiled = self.step.lower(self.seed, self.ctrl,
                                   self.binder).compile()
        dt = time.perf_counter() - t0
        self.step = compiled
        # XLA's compiled cost analysis: logical bytes accessed per
        # step — the roofline-position number PERF.md §3 tracks (the
        # megakernel's acceptance metric is this value dropping >= 3x
        # vs the scan path on the same platform).  Fail-open: some
        # backends return nothing.
        self.cost_bytes = None
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            val = ca.get("bytes accessed")
            if val is not None:
                self.cost_bytes = float(val)
        except Exception:
            pass
        return dt

    def run(self, steps: int) -> float:
        (seed, ctrl) = (self.seed, self.ctrl)
        t0 = time.perf_counter()
        for _ in range(steps):
            (seed, ctrl, _proof, _ok) = self.step(seed, ctrl, self.binder)
        self.jax.block_until_ready(seed)
        dt = time.perf_counter() - t0
        return self.evals_per_step * steps / dt


def _synth_batch(bm, num_reports: int, rng):
    """A synthetic ReportBatch with random bytes/limbs: the compute
    cost of a round is input-independent (constant-time lane selects),
    so throughput measured on garbage reports equals throughput on
    real ones — only `accept` differs, and aggregation is masked
    either way."""
    import jax.numpy as jnp
    import numpy as np

    from mastic_tpu.backend.mastic_jax import ReportBatch
    from mastic_tpu.backend.vidpf_jax import BatchedCorrectionWords

    m = bm.m
    bits = m.vidpf.BITS
    vl = m.vidpf.VALUE_LEN
    n = bm.spec.num_limbs

    def u8(*shape):
        return jnp.asarray(rng.integers(0, 256, shape, np.uint8))

    def limbs(*shape):
        return jnp.asarray(rng.integers(0, 1 << 16, shape,
                                        dtype=np.uint32))

    use_jr = m.flp.JOINT_RAND_LEN > 0
    return ReportBatch(
        nonces=u8(num_reports, 16),
        cws=BatchedCorrectionWords(
            seed=u8(num_reports, bits, 16),
            ctrl=jnp.asarray(rng.integers(0, 2, (num_reports, bits, 2))
                             .astype(bool)),
            w=limbs(num_reports, bits, vl, n),
            proof=u8(num_reports, bits, 32)),
        keys=u8(num_reports, 2, 16),
        leader_proofs=limbs(num_reports, m.flp.PROOF_LEN, n),
        helper_seeds=u8(num_reports, 32),
        leader_seeds=u8(num_reports, 32) if use_jr else None,
        peer_parts=tuple(u8(num_reports, 32) if use_jr else None
                         for _ in range(2)))


def bench_full_round(bm, num_reports: int, agg_param, steps: int,
                     latency_samples: int = 11):
    """Compile one full from-root round (both preps + checks + FLP on
    weight-check rounds + masked aggregation), then measure chained
    steady-state throughput and single-round p50 latency."""
    import time as _time

    import jax
    import numpy as np

    rng = np.random.default_rng(7)
    batch = _synth_batch(bm, num_reports, rng)
    vk = bytes(range(32))
    fn = jax.jit(lambda b: bm.round_device(vk, b"bench", agg_param, b))
    t0 = _time.perf_counter()
    compiled = fn.lower(batch).compile()
    compile_s = _time.perf_counter() - t0
    out = compiled(batch)
    jax.block_until_ready(out)

    # Chained throughput: feed a rotated nonce array back in so each
    # round depends on the last (defeats dispatch pipelining).
    t0 = _time.perf_counter()
    b = batch
    for _ in range(steps):
        (agg0, _agg1, _accept, _ok) = compiled(b)
        b = b._replace(nonces=b.nonces.at[0, 0].set(
            agg0[0, 0].astype("uint8")))
    jax.block_until_ready(b.nonces)
    per_round = (_time.perf_counter() - t0) / steps

    lat = []
    for _ in range(latency_samples):
        t0 = _time.perf_counter()
        out = compiled(batch)
        jax.block_until_ready(out)
        lat.append(_time.perf_counter() - t0)
    p50_ms = sorted(lat)[len(lat) // 2] * 1e3
    return (per_round, p50_ms, compile_s)


def bench_incremental_round(bm, num_reports: int, frontier: int,
                            bits: int, steps: int, mesh=None):
    """Steady-state *incremental* round at a deep level: tree step for
    both aggregators + binder hashing over the carried ancestor tree +
    eval proof + masked aggregation (backend/incremental.py).  Carry
    contents are random — cost is input-independent.

    With `mesh`, carries / batch / round keys place report-sharded and
    the masked aggregate's psum is the only cross-chip collective —
    the returned dict then carries the per-shard rate and the psum
    bytes per round next to the aggregate rate."""
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mastic_tpu.backend.incremental import (Carry,
                                                IncrementalMastic,
                                                RoundPlan,
                                                needed_paths,
                                                round_inputs)

    level = bits - 56  # deep steady state; any level compiles the same
    width = max(4, frontier)
    half = width // 2
    num_parents = frontier // 2
    # Parents: distinct level-bit paths; candidates: both children.
    parents = [
        tuple(bool((i >> b) & 1) for b in range(level))
        for i in range(num_parents)
    ]
    prefixes = tuple(p + (c,) for p in parents for c in (False, True))
    carried = needed_paths(parents, level - 1)
    plan = RoundPlan(prefixes, level, bits, width, carried)
    rnd = round_inputs(plan)

    engine = IncrementalMastic(bm, width)
    rng = np.random.default_rng(8)
    spec = bm.spec
    vl = bm.m.vidpf.VALUE_LEN

    def carry():
        return Carry(
            w=jnp.asarray(rng.integers(
                0, 1 << 16, (num_reports, bits, width, vl,
                             spec.num_limbs), dtype=np.uint32)),
            proof=jnp.asarray(rng.integers(
                0, 256, (num_reports, bits, width, 32), np.uint8)),
            seed=jnp.asarray(rng.integers(
                0, 256, (num_reports, width, 16), np.uint8)),
            ctrl=jnp.asarray(rng.integers(
                0, 2, (num_reports, width)).astype(bool)))

    batch = _synth_batch(bm, num_reports, rng)
    vk = bytes(range(32))
    (ext_rk, conv_rk) = jax.jit(
        lambda nn: bm.vidpf.roundkeys(b"bench", nn))(batch.nonces)

    cws = batch.cws
    jit_kwargs = {}
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from mastic_tpu.parallel import place_replicated, place_reports

        (ext_rk, conv_rk, cws) = place_reports(
            mesh, (ext_rk, conv_rk, cws))
        rnd = place_replicated(mesh, rnd)
        rep = NamedSharding(mesh, P("reports"))
        repl = NamedSharding(mesh, P())
        # Carries report-sharded in and out; aggregates replicated —
        # the psum over the sharded report axis is the round's only
        # collective (PERF.md §8's cost model).
        jit_kwargs["out_shardings"] = (rep, rep, repl, repl)

    def place(c):
        if mesh is None:
            return c
        from mastic_tpu.parallel import place_reports
        return place_reports(mesh, c)

    def both(c0, c1, r):
        (c0, p0, out0, ok0) = engine.agg_round(
            0, vk, b"bench", c0, r, ext_rk, conv_rk, cws)
        (c1, p1, out1, ok1) = engine.agg_round(
            1, vk, b"bench", c1, r, ext_rk, conv_rk, cws)
        accept = jnp.all(p0 == p1, axis=-1)
        return (c0, c1, bm.aggregate(out0, accept),
                bm.aggregate(out1, accept))

    # No donation under the persistent cache: the round runners' rule
    # (RoundPrograms._donation_safe) — a deserialized executable with
    # donated arguments corrupts the heap on the CPU backend.
    donate = () if jax.config.jax_compilation_cache_dir else (0, 1)
    fn = jax.jit(both, donate_argnums=donate, **jit_kwargs)
    t0 = _time.perf_counter()
    compiled = fn.lower(place(carry()), place(carry()), rnd).compile()
    compile_s = _time.perf_counter() - t0
    (c0, c1) = (place(carry()), place(carry()))
    (c0, c1, a0, a1) = compiled(c0, c1, rnd)
    jax.block_until_ready(a0)

    t0 = _time.perf_counter()
    for _ in range(steps):
        (c0, c1, a0, a1) = compiled(c0, c1, rnd)
    jax.block_until_ready(a0)
    per_round = (_time.perf_counter() - t0) / steps
    evals = num_reports * 2 * num_parents * 2  # both aggregators
    collective_bytes = (a0.nbytes + a1.nbytes if mesh is not None
                        else 0)
    return (per_round, evals / per_round, compile_s,
            collective_bytes)


def _bench_mesh(args):
    """The --mesh lever resolved to a Mesh (None when off).  `mesh_n`
    is resolved after the jax import in main ("all" -> device count).
    """
    n = getattr(args, "mesh_n", 1)
    if n <= 1:
        return None
    from mastic_tpu.parallel import make_mesh

    return make_mesh(n, nodes_axis=1)


def bench_chunked_round(args) -> dict:
    """The chunked PRODUCTION round on the pipelined executor
    (drivers/pipeline.py, `MASTIC_PIPELINE`): a small planted
    heavy-hitters run streamed through fixed-size chunks, measuring
    the per-phase timeline (upload / dispatch / compute-wait /
    download / host / compile) and the overlap efficiency — the
    numbers ISSUE 4 moves; the headline eval_step bench cannot see
    them because it never leaves the device."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from mastic_tpu import MasticCount
    from mastic_tpu.backend.mastic_jax import BatchedMastic
    from mastic_tpu.common import gen_rand
    from mastic_tpu.drivers.chunked import HostReportStore
    from mastic_tpu.drivers.heavy_hitters import HeavyHittersRun

    (bits, R, C) = (32, args.chunked_reports, args.chunked_reports // 4)
    m = MasticCount(bits)
    bm = BatchedMastic(m)
    rng = np.random.default_rng(5)
    # Three planted paths, no uniform tail: the frontier stays <= 6
    # wide for the whole depth, so the run is round-loop-bound (the
    # thing being measured), not node-eval-bound.
    paths = rng.integers(0, 2, (3, bits)).astype(bool)
    alphas = paths[rng.integers(0, 3, R)]
    beta = np.stack([bm.spec.int_to_limbs(el.int())
                     for el in [m.field(1)] + m.flp.encode(1)])
    betas = np.broadcast_to(beta, (R,) + beta.shape)
    shard_fn = jax.jit(
        lambda a, b, n, r: bm.shard_device(b"bench", a, b, n, r))
    (batch, ok) = shard_fn(
        jnp.asarray(alphas), jnp.asarray(betas),
        jnp.asarray(rng.integers(0, 256, (R, 16), dtype=np.uint8)),
        jnp.asarray(rng.integers(0, 256, (R, m.RAND_SIZE),
                                 dtype=np.uint8)))
    assert bool(np.all(np.asarray(ok)))
    store = HostReportStore.from_batch(batch, C)
    mesh = _bench_mesh(args)
    run = HeavyHittersRun(m, b"bench", {"default": R // 6}, None,
                          verify_key=gen_rand(m.VERIFY_KEY_SIZE),
                          store=store, mesh=mesh)
    # Same span schema as tools/serve.py epochs and tools/northstar.py
    # (one "collection" parent, "round"/"chunk.*" children), so a
    # bench trace and a live-service trace diff directly.
    from mastic_tpu.obs import trace as obs_trace
    tracer = obs_trace.get_tracer()
    coll_span = tracer.start_detached_span(
        "collection", tool="bench", reports=R, bits=bits)
    t0 = time.perf_counter()
    with tracer.use_parent(coll_span):
        while run.step():
            pass
    wall = time.perf_counter() - t0
    tracer.end_span(coll_span)

    pipes = [mx.extra["pipeline"] for mx in run.metrics]
    effs = sorted(p["overlap_efficiency"] for p in pipes)
    rounds = sorted(p["round_wall_ms"] for p in pipes)
    phases: dict = {}
    for mx in run.metrics:
        for rec in mx.extra["chunks"]:
            for (k, v) in rec["phases"].items():
                phases[k] = phases.get(k, 0.0) + v
    evals = sum(mx.node_evals for mx in run.metrics)
    shards = mesh.shape["reports"] if mesh is not None else 1
    mesh_block = None
    if mesh is not None:
        rounds_m = [mx.extra["mesh"] for mx in run.metrics
                    if "mesh" in mx.extra]
        skews = sorted(mr["shard_wait_skew_ms_max"] for mr in rounds_m)
        mesh_block = {
            "report_shards": shards,
            "device_rows_per_chunk":
                rounds_m[-1]["device_rows_per_chunk"],
            "psum_bytes_per_round_last":
                rounds_m[-1]["psum_bytes_per_round"],
            "psum_bytes_total": sum(mr["psum_bytes_per_round"]
                                    for mr in rounds_m),
            "shard_wait_skew_ms_p50": skews[len(skews) // 2],
            "shard_wait_skew_ms_max": skews[-1],
        }
    return {
        "instance": f"MasticCount({bits})",
        "reports": R, "chunk_size": C, "levels": len(run.metrics),
        "mesh_devices": shards,
        "mesh": mesh_block,
        "node_evals_per_sec_per_shard": round(evals / wall / shards, 1),
        "pipeline": pipes[-1]["mode"],
        "fallbacks": sorted({p["fallback"] for p in pipes
                             if p["fallback"]}),
        "wall_seconds": round(wall, 2),
        "round_ms_p50": round(rounds[len(rounds) // 2], 2),
        "node_evals_per_sec": round(evals / wall, 1),
        "overlap_efficiency_p50": effs[len(effs) // 2],
        "overlap_efficiency_max": effs[-1],
        "phase_ms": {k: round(v, 1) for (k, v) in sorted(
            phases.items())},
        "compile_inline_ms_total": round(
            sum(p["compile_inline_ms"] for p in pipes), 1),
        "aot_inline_compiles":
            run.runner.programs.stats["inline_compiles"],
        "aot_warm_compiles":
            run.runner.programs.stats["warm_compiles"],
    }


def bench_parties_wan(args) -> dict:
    """The `--parties-wan` config (ISSUE 11): the process-separated
    leader/helper session over the SHAPED network link
    (`MASTIC_NET_SHAPE`, mastic_tpu/net/transport.py), extending
    BASELINE's communication-only byte counts into a measured
    communication-vs-computation crossover.

    Method: one unshaped session is the compute baseline, then one
    session per bandwidth/RTT cell of the ladder.  Every session
    uploads the same seeded batch, pays one warm round (the parties'
    per-round trace/compile — identical across cells), then measures
    `--wan-rounds` rounds; the per-cell communication cost is the
    wall delta against the unshaped baseline, so the (large, equal)
    host/device work cancels.  Bit-identity across every cell is
    ASSERTED — a shaped link may slow the round, never change the
    aggregate.  The crossover stamp is the bandwidth at which the
    round's wire bytes take as long as the unshaped round computes:
    below it the session is communication-bound (the draft's
    deployment question, measured)."""
    import numpy as np

    from mastic_tpu.drivers.parties import AggregationSession
    from mastic_tpu.drivers.session import SessionConfig
    from mastic_tpu.mastic import MasticCount
    from mastic_tpu.metrics import RoundMetrics, count_round_bytes
    from mastic_tpu.net.transport import parse_shape

    bits = args.wan_bits
    n = args.wan_reports
    m = MasticCount(bits)
    spec = {"class": "MasticCount", "args": [bits]}
    ctx = b"bench parties wan"
    vk = bytes(range(m.VERIFY_KEY_SIZE))
    rng = np.random.default_rng(0)
    reports = []
    for i in range(n):
        value = 0 if i % 2 == 0 else (1 << bits) - 1
        alpha = m.vidpf.test_index_from_int(value, bits)
        nonce = bytes(rng.integers(0, 256, m.NONCE_SIZE,
                                   dtype="uint8"))
        rand = bytes(rng.integers(0, 256, m.RAND_SIZE,
                                  dtype="uint8"))
        (ps, shares) = m.shard(ctx, (alpha, True), nonce, rand)
        reports.append((nonce, ps, shares))
    param = (0, ((False,), (True,)), True)

    # The wire cost model (metrics.count_round_bytes — BASELINE's
    # communication-only numbers): per-round exchange bytes vs the
    # once-per-collection upload.
    model = RoundMetrics(level=0, frontier_width=2, padded_width=2,
                         reports_total=n)
    count_round_bytes(model, m, param, n)
    round_bytes = (model.bytes_prep_shares + model.bytes_prep_msgs
                   + model.bytes_agg_shares)
    upload_bytes_model = model.bytes_upload

    cfg = SessionConfig(connect_timeout=30.0, exchange_timeout=600.0,
                        ack_timeout=120.0, round_deadline=1200.0,
                        shutdown_timeout=5.0, retries=0, backoff=0.2)
    shapes = [None] + [s.strip() for s in args.wan_shapes.split(",")
                       if s.strip()]
    cells = []
    baseline = None
    reference = None
    for shape_text in shapes:
        if shape_text:
            os.environ["MASTIC_NET_SHAPE"] = shape_text
        else:
            os.environ.pop("MASTIC_NET_SHAPE", None)
        stamp("wan-cell", shape=shape_text or "unshaped")
        sess = AggregationSession(m, spec, ctx, vk, config=cfg)
        try:
            t0 = time.perf_counter()
            sess.upload(reports)
            upload_s = time.perf_counter() - t0
            upload_wire = sess.coll.wire_bytes()["sent"]
            sess.round(param)           # warm round (compile-bearing)
            walls = []
            for _ in range(max(1, args.wan_rounds)):
                t0 = time.perf_counter()
                (result, accept, shares) = sess.round(param)
                walls.append(time.perf_counter() - t0)
            wire_meas = sess.coll.wire_bytes()
        finally:
            sess.close()
        outcome = (result, [bool(x) for x in accept], shares)
        if reference is None:
            reference = outcome
        elif outcome != reference:
            raise RuntimeError(
                f"parties-wan: shaped link {shape_text!r} changed "
                f"the aggregate — bit-identity violated")
        cell = {
            "shape": shape_text or "unshaped",
            "upload_s": round(upload_s, 3),
            "round_wall_s": round(min(walls), 3),
            "round_walls_s": [round(w, 3) for w in walls],
            "collector_wire_bytes": wire_meas,
        }
        shape = parse_shape(shape_text)
        if shape is None:
            baseline = cell
        else:
            delta = min(walls) - baseline["round_wall_s"]
            cell["bandwidth_bytes_per_s"] = shape.bandwidth
            cell["rtt_s"] = shape.rtt
            # The upload leg is the CLEAN communication measurement
            # (no compute in it): measured wall vs the pipe model
            # over the collector's measured upload bytes validates
            # that the shaped link actually delivers its shape.
            cell["upload_model_s"] = round(
                (upload_wire / shape.bandwidth
                 if shape.bandwidth > 0 else 0.0) + shape.rtt, 3)
            cell["comm_delta_s"] = round(delta, 3)
            # Model: round bytes through the pipe + ~6 sequential
            # shaped sends on the critical path (agg params, prep
            # share, resolution, two agg shares), rtt/2 each.
            cell["comm_model_s"] = round(
                (round_bytes / shape.bandwidth
                 if shape.bandwidth > 0 else 0.0)
                + 6 * shape.rtt / 2, 3)
            cell["comm_fraction_of_round"] = round(
                max(0.0, delta) / max(1e-9, min(walls)), 3)
        cells.append(cell)

    compute_s = baseline["round_wall_s"]
    crossover = round_bytes / compute_s if compute_s > 0 else 0.0
    # The measured bracket around the crossover: the slowest shaped
    # cell still compute-bound and the fastest already comm-bound.
    above = [c for c in cells if c.get("comm_delta_s") is not None
             and c["comm_delta_s"] < compute_s]
    below = [c for c in cells if c.get("comm_delta_s") is not None
             and c["comm_delta_s"] >= compute_s]
    return {
        "bits": bits,
        "reports": n,
        "rounds_measured": max(1, args.wan_rounds),
        "round_bytes_model": round_bytes,
        "upload_bytes_model": upload_bytes_model,
        "compute_round_s": compute_s,
        "crossover_bandwidth_bytes_per_s": round(crossover, 1),
        "crossover_measured_bracket_bytes_per_s": [
            min((c["bandwidth_bytes_per_s"] for c in above),
                default=None),
            max((c["bandwidth_bytes_per_s"] for c in below),
                default=None),
        ],
        "cells": cells,
        "note": ("compute_round_s includes the parties' per-round "
                 "re-trace on this fabric; it cancels in every "
                 "comm_delta_s (equal work both sides of the delta) "
                 "but makes the crossover an upper bound"),
    }


def bench_service_overlap(args) -> dict:
    """The `--service-overlap` config (ISSUE 10): aggregate
    multi-tenant reports/s through the LIVE collector service —
    round-robin baseline (the r11 scheduler, in-process admission)
    vs the overlapped epoch executor + concurrent ingest front — with
    a freshly-baked AOT artifact store armed so steady-state epochs
    are trace-free in BOTH modes (fair fight: the r14 cold-start win
    is not conflated into the overlap number).

    Asserted, not just stamped: per-tenant epoch records bit-identical
    across the two modes, and zero inline compiles in every measured
    epoch (via the per-record compile accounting, which sums the
    timeline compile fields).  On a single-core fabric the wall clock
    is work-conserving — host work and XLA compute timeshare one core
    — so the speedup stamp is accompanied by the core count; the
    chip_session `serve-overlap` cell is where the device-overlap
    claim gets its hardware number (PERF.md §12)."""
    import tempfile
    import numpy as np

    from mastic_tpu.backend.mastic_jax import BatchedMastic
    from mastic_tpu.drivers import artifacts
    from mastic_tpu.drivers.heavy_hitters import \
        get_reports_from_measurements
    from mastic_tpu.drivers.service import (CollectorService,
                                            ServiceConfig, TenantSpec,
                                            encode_upload)
    from mastic_tpu.drivers.session import Deadline
    from mastic_tpu.mastic import MasticCount
    from mastic_tpu.obs.registry import get_registry

    bits = args.service_bits
    tenants_n = args.service_tenants
    reports_n = args.service_reports
    epochs_n = args.service_epochs
    hitters = 2
    ctx = b"bench service overlap"
    m = MasticCount(bits)
    vk = bytes(range(m.VERIFY_KEY_SIZE))

    # Bake the round-program family for exactly this config (rows =
    # the resident runner's report count), then arm the store.
    stamp("service-overlap-bake", bits=bits, rows=reports_n)
    store_dir = tempfile.mkdtemp(prefix="mastic_svc_overlap_")
    store = artifacts.default_store(store_dir)
    baker = artifacts.make_baker(BatchedMastic(m), ctx, width=8)
    bake_stats = artifacts.bake_trajectory(
        baker, store, reports_n,
        artifacts.trajectory(bits,
                             artifacts.planted_paths(bits, hitters)))
    os.environ["MASTIC_ARTIFACT_DIR"] = store_dir
    stamp("service-overlap-baked", **bake_stats)

    paths = artifacts.planted_paths(bits, hitters)
    meas = [(tuple(paths[i % hitters]), True)
            for i in range(reports_n)]
    reports = get_reports_from_measurements(m, ctx, meas)
    blobs = [encode_upload(m, r) for r in reports]
    expected_hitters = sorted("".join("1" if b else "0" for b in p)
                              for p in paths)

    def tenant_specs():
        return [
            TenantSpec(name=f"t{i}",
                       spec={"class": "MasticCount", "args": [bits]},
                       ctx=ctx, verify_key=vk,
                       thresholds={"default": 1})
            for i in range(tenants_n)
        ]

    def run_mode(overlapped: bool) -> dict:
        cfg = ServiceConfig(
            page_size=64, max_buffered=10 * reports_n * epochs_n,
            max_pending_epochs=epochs_n + 2, epoch_deadline=3600.0,
            overlap=(args.service_overlap_k if overlapped else 0),
            ingest_threads=(2 if overlapped else 0),
            ingest_queue=4 * reports_n)
        svc = CollectorService(tenant_specs(), config=cfg)
        deadline = Deadline(3600.0)

        def admit_epoch():
            for i in range(tenants_n):
                name = f"t{i}"
                for b in blobs:
                    svc.submit(name, b)
                svc.begin_epoch(name)

        # Warmup epoch: pays the once-per-process artifact loads +
        # probe rounds; excluded from the measured window.
        admit_epoch()
        while svc.step():
            if deadline.expired():
                raise RuntimeError("service-overlap warmup wedged")
        t0 = time.perf_counter()
        for _ in range(epochs_n):
            admit_epoch()
        while svc.step():
            if deadline.expired():
                raise RuntimeError("service-overlap drain wedged")
        wall = time.perf_counter() - t0
        svc.stop_ingest()
        mx = svc.metrics()["tenants"]
        records = {}
        inline = 0
        compile_ms = 0.0
        for (name, t) in mx.items():
            measured = t["epochs"][1:]
            for rec in measured:
                inline += rec.get("inline_compiles", 0)
                compile_ms += rec.get("compile_ms", 0.0)
                if sorted(rec["result"]) != [
                        [c == "1" for c in h]
                        for h in expected_hitters]:
                    raise RuntimeError(
                        f"service-overlap epoch wrong: {rec}")
            records[name] = [
                {k: v for (k, v) in rec.items()
                 if k not in ("wall_s", "compile_ms",
                              "inline_compiles")}
                for rec in measured
            ]
        eff = get_registry().gauge(
            "mastic_sched_overlap_efficiency").value()
        return {
            "wall_s": round(wall, 3),
            "reports_per_sec": round(
                tenants_n * reports_n * epochs_n / wall, 1),
            "records": records,
            "inline_compiles": inline,
            "compile_ms": round(compile_ms, 2),
            "overlap_efficiency": eff,
        }

    stamp("service-overlap-baseline")
    base = run_mode(False)
    stamp("service-overlap-overlapped",
          k=args.service_overlap_k)
    over = run_mode(True)

    bit_identical = over["records"] == base["records"]
    problems = []
    if not bit_identical:
        problems.append("per-tenant records diverge between modes")
    if base["inline_compiles"] or over["inline_compiles"]:
        problems.append(
            f"steady-state inline compiles nonzero: "
            f"baseline={base['inline_compiles']} "
            f"overlap={over['inline_compiles']}")
    if base["compile_ms"] or over["compile_ms"]:
        problems.append(
            f"steady-state timeline compile field nonzero: "
            f"baseline={base['compile_ms']}ms "
            f"overlap={over['compile_ms']}ms")
    if problems:
        raise RuntimeError("; ".join(problems))
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:
        cores = os.cpu_count() or 1
    rec = {
        "tenants": tenants_n,
        "bits": bits,
        "reports_per_epoch": reports_n,
        "epochs_measured": epochs_n,
        "overlap_k": args.service_overlap_k,
        "ingest_threads": 2,
        "store_entries": store.entry_count(),
        "baseline_reports_per_sec": base["reports_per_sec"],
        "overlap_reports_per_sec": over["reports_per_sec"],
        "speedup": round(over["reports_per_sec"]
                         / base["reports_per_sec"], 3),
        "bit_identical": bit_identical,
        "inline_compiles_measured": (base["inline_compiles"]
                                     + over["inline_compiles"]),
        "overlap_efficiency": over["overlap_efficiency"],
        "cores": cores,
    }
    if cores <= 1:
        # Physics stamp: one core timeshares host work and XLA
        # compute, so wall is work-conserving and the speedup here is
        # an overhead measurement, not the device-overlap claim —
        # that number comes from the serve-overlap chip cell.
        rec["note"] = ("single-core fabric: wall clock is "
                       "work-conserving; device-overlap speedup "
                       "requires the chip cell (PERF.md §12)")
    return rec


def run_cold_start_child(args) -> dict:
    """Fresh-process time-to-first-round of the PRODUCTION chunked
    incremental round (the runner path the AOT artifact store
    serves): build a deterministic planted-path collection, run it to
    completion, and report per-round compile fields + artifact stats
    + results — the payload both `bench.py --cold-start` and
    `tools/bake.py --smoke` compare across traced vs warm-store
    children.  Client-side report sharding is measured separately and
    excluded from the cold-start number (it is client work, not
    collector work)."""
    import jax

    from mastic_tpu.drivers import artifacts as artifacts_mod
    from mastic_tpu.drivers.heavy_hitters import (
        HeavyHittersRun, get_reports_from_measurements)
    from mastic_tpu.mastic import MasticCount

    bits = args.bits
    k = args.cold_start_hitters
    reports_n = args.chunked_reports
    ctx = args.cold_start_ctx.encode()
    m = MasticCount(bits)
    paths = artifacts_mod.planted_paths(bits, k)
    meas = [(tuple(paths[i % k]), True) for i in range(reports_n)]
    t_shard0 = time.time()
    reports = get_reports_from_measurements(m, ctx, meas)
    shard_s = time.time() - t_shard0
    stamp("cold-start-run", reports=reports_n, bits=bits,
          store=os.environ.get("MASTIC_ARTIFACT_DIR", ""))
    run = HeavyHittersRun(m, ctx, {"default": 1}, reports,
                          verify_key=bytes(range(m.VERIFY_KEY_SIZE)),
                          chunk_size=args.cold_start_chunk)
    more = run.step()   # the first round: the cold-start target
    t_first = time.time()
    while more:
        more = run.step()
    t_done = time.time()
    stats = run.runner.programs.stats
    round_compile = [
        round(sum(rec["phases"].get("compile_ms", 0.0)
                  for rec in mx.extra.get("chunks", ())), 3)
        for mx in run.metrics
    ]
    counters = [
        {"level": mx.level, "accepted": mx.accepted,
         "rejected_eval_proof": mx.rejected_eval_proof,
         "rejected_weight_check": mx.rejected_weight_check,
         "rejected_joint_rand": mx.rejected_joint_rand,
         "xof_fallbacks": mx.xof_fallbacks}
        for mx in run.metrics
    ]
    return {
        "mode": "cold-start-child",
        "platform": jax.devices()[0].platform,
        "bits": bits, "reports": reports_n,
        "chunk_size": args.cold_start_chunk, "hitters": k,
        "artifact_store": os.environ.get("MASTIC_ARTIFACT_DIR")
        or None,
        # Process start -> first completed round, client sharding
        # excluded: imports + backend init + runner construction
        # (incl. preload/compile) + round 0.
        "time_to_first_round_s": round(
            t_first - _T0 - shard_s, 2),
        "shard_seconds": round(shard_s, 2),
        "wall_s": round(t_done - _T0, 2),
        "levels": len(run.metrics),
        "inline_compiles": stats["inline_compiles"],
        "warm_compiles": stats["warm_compiles"],
        "artifact_hits": stats["artifact_hits"],
        "artifact_load_ms": round(stats["artifact_load_ms"], 1),
        "round_compile_ms": round_compile,
        "results": ["".join("1" if b else "0" for b in p)
                    for p in run.result()],
        "counters": counters,
    }


def run_cold_start_parent(args, timer) -> None:
    """`--cold-start`: the headline measurement of ISSUE 9 — fresh-
    subprocess time-to-first-round, traced vs warm artifact store,
    on the same fabric.  Bakes the store first (tools/bake.py, the
    same planted-path trajectory the children run) unless
    --artifact-dir already holds a manifest; stamps everything into
    one JSON line so the claim is reproducible from bench JSON
    alone."""
    import tempfile

    root = os.path.dirname(os.path.abspath(__file__))
    store = args.artifact_dir or os.path.join(
        tempfile.mkdtemp(prefix="mastic_cold_"), "store")

    def run_child(env_store: str | None) -> dict:
        env = dict(os.environ)
        env.pop("MASTIC_ARTIFACT_DIR", None)
        # A fresh process with no persistent compile cache: the
        # traced child's number is a true cold start.
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        if env_store is not None:
            env["MASTIC_ARTIFACT_DIR"] = env_store
        cmd = [sys.executable, os.path.join(root, "bench.py"),
               "--cold-start-child",
               "--bits", str(args.cold_start_bits),
               "--chunked-reports", str(args.cold_start_reports),
               "--cold-start-chunk", str(args.cold_start_chunk),
               "--cold-start-hitters", str(args.cold_start_hitters),
               "--cold-start-ctx", args.cold_start_ctx]
        if args.cpu:
            cmd.append("--cpu")
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=3600, env=env)
        if proc.returncode != 0:
            raise RuntimeError(
                f"cold-start child (store={env_store}) failed "
                f"rc={proc.returncode}: {proc.stderr[-2000:]}")
        return json.loads(proc.stdout.strip().splitlines()[-1])

    bake_s = 0.0
    bake_entries = None
    if not os.path.exists(os.path.join(store, "manifest.json")):
        stamp("cold-start-bake", out=store)
        t0 = time.time()
        cmd = [sys.executable, os.path.join(root, "tools", "bake.py"),
               "--out", store, "--bits", str(args.cold_start_bits),
               "--rows", str(args.cold_start_chunk),
               "--hitters", str(args.cold_start_hitters),
               "--ctx", args.cold_start_ctx]
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=7200, env=dict(os.environ))
        if proc.returncode != 0:
            timer.cancel()
            emit(error=f"cold-start bake failed: "
                 f"{proc.stderr[-1000:]}")
            sys.exit(2)
        bake_rec = json.loads(proc.stdout.strip().splitlines()[-1])
        bake_s = round(time.time() - t0, 1)
        bake_entries = bake_rec["entries"]
        stamp("cold-start-bake-done", entries=bake_entries,
              seconds=bake_s)

    stamp("cold-start-traced-child")
    traced = run_child(None)
    stamp("cold-start-warm-child", store=store)
    warm = run_child(store)
    (t_cold, t_warm) = (traced["time_to_first_round_s"],
                        warm["time_to_first_round_s"])
    PARTIAL["metric"] = "cold_start_time_to_first_round_seconds"
    PARTIAL["value"] = t_warm
    PARTIAL["unit"] = "s"
    PARTIAL["platform"] = warm["platform"]
    PARTIAL.pop("vs_baseline")
    PARTIAL["configs"] = {"incremental_round": {
        "instance": f"MasticCount({args.cold_start_bits})",
        "reports": args.cold_start_reports,
        "chunk_size": args.cold_start_chunk,
        "hitters": args.cold_start_hitters,
        # The attribution the r9..r13 bench JSON lacked: cold_start
        # is a FRESH PROCESS's time to its first completed round
        # (in-process `compile_seconds` elsewhere in this file can
        # read warm when the persistent XLA cache is armed on chip).
        "cold_start_seconds": t_cold,
        "warm_store_seconds": t_warm,
        "warm_over_cold": round(t_warm / t_cold, 3) if t_cold else None,
        "bake_seconds": bake_s,
        "store": store,
        "store_entries": bake_entries,
        "warm_inline_compiles": warm["inline_compiles"],
        "warm_artifact_hits": warm["artifact_hits"],
        "warm_round_compile_ms": warm["round_compile_ms"],
        "bit_identical": (warm["results"] == traced["results"]
                          and warm["counters"] == traced["counters"]),
    }}
    timer.cancel()
    stamp("done", cold=t_cold, warm=t_warm)
    emit()


def run_configs(args) -> dict:
    """The BASELINE.json per-config benches, into the shared record."""
    from mastic_tpu import MasticCount, MasticHistogram, MasticSumVec
    from mastic_tpu.backend.mastic_jax import BatchedMastic

    configs = PARTIAL.setdefault("configs", {})

    # 1. Full steady-state incremental round at the headline shape,
    # mesh-sharded over the report axis when --mesh asks for it (the
    # per-shard rate + psum bytes are the 8-chip scaling stamps).
    stamp("config-incremental-round", mesh=getattr(args, "mesh_n", 1))
    mesh = _bench_mesh(args)
    bm = BatchedMastic(MasticCount(args.bits))
    reports = args.reports // 2
    if mesh is not None:
        n = mesh.shape["reports"]
        reports = -(-reports // n) * n  # resident tile shards evenly
    (per_round, evals_s, compile_s, coll_bytes) = \
        bench_incremental_round(bm, reports, args.frontier, args.bits,
                                args.steps, mesh=mesh)
    configs["incremental_round"] = {
        "instance": f"MasticCount({args.bits})",
        "reports": reports, "frontier": args.frontier,
        "mesh_devices": (mesh.shape["reports"]
                         if mesh is not None else 1),
        "round_ms": round(per_round * 1e3, 2),
        "node_evals_per_sec": round(evals_s, 1),
        "node_evals_per_sec_per_shard": round(
            evals_s / (mesh.shape["reports"] if mesh is not None
                       else 1), 1),
        "collective_bytes_per_round": coll_bytes,
        "compile_seconds": round(compile_s, 1),
    }
    stamp("config-incremental-done", evals_s=f"{evals_s:.0f}")

    # 2. Histogram Field128 @ BITS=64: full round incl. device FLP.
    stamp("config-histogram-f128")
    bmh = BatchedMastic(MasticHistogram(64, 16, 4))
    agg_param = (0, ((False,), (True,)), True)
    (per_round, p50_ms, compile_s) = bench_full_round(
        bmh, 2048, agg_param, max(4, args.steps // 4))
    configs["histogram_f128_b64"] = {
        "instance": "MasticHistogram(bits=64, length=16, chunk=4)",
        "reports": 2048, "round": "level 0 + FLP weight check",
        "round_ms": round(per_round * 1e3, 2),
        "reports_per_sec": round(2048 / per_round, 1),
        "prep_round_p50_ms": round(p50_ms, 2),
        "compile_seconds": round(compile_s, 1),
    }
    stamp("config-histogram-done",
          rps=f"{2048 / per_round:.0f}")

    # 2b. Pipelined chunked production round: phase timeline +
    # overlap efficiency (drivers/pipeline.py).
    stamp("config-chunked-round",
          pipeline=os.environ.get("MASTIC_PIPELINE", "1"))
    configs["chunked_round"] = bench_chunked_round(args)
    stamp("config-chunked-round-done",
          eff=configs["chunked_round"]["overlap_efficiency_p50"])

    # 3. SumVec(1024) Field128 @ BITS=128: huge-payload convert.
    stamp("config-sumvec-f128")
    bmv = BatchedMastic(MasticSumVec(128, 1024, 1, 32))
    sv = SteadyState(bmv, 128, 8, 128)
    sv_compile = sv.compile()
    sv.run(1)
    rate = sv.run(max(4, args.steps // 4))
    payload = bmv.m.vidpf.VALUE_LEN * bmv.m.field.ENCODED_SIZE
    configs["sumvec1024_f128_b128"] = {
        "instance": "MasticSumVec(bits=128, length=1024, chunk=32)",
        "reports": 128, "frontier": 8,
        "node_evals_per_sec": round(rate, 1),
        "payload_bytes_per_sec": round(rate * payload, 1),
        "compile_seconds": round(sv_compile, 1),
    }
    stamp("config-sumvec-done", rate=f"{rate:.0f}")
    return configs


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--reports", type=int, default=4096)
    parser.add_argument("--frontier", type=int, default=64)
    parser.add_argument("--steps", type=int, default=16)
    parser.add_argument("--bits", type=int, default=256)
    parser.add_argument("--cpu", action="store_true",
                        help="force the CPU backend (local sanity)")
    parser.add_argument("--headline-only", action="store_true",
                        help="skip the per-config benches")
    parser.add_argument("--keccak-unroll", type=int, default=None,
                        help="Keccak round-scan unroll factor "
                        "(sets MASTIC_KECCAK_UNROLL; default 1 unless "
                        "the env var is already set; 1 = cheapest "
                        "compile)")
    parser.add_argument("--aes-pallas", action="store_true",
                        help="route the bitsliced AES through the "
                        "Pallas fused-VMEM kernel (MASTIC_AES_PALLAS)")
    parser.add_argument("--keccak-pallas", action="store_true",
                        help="route the Keccak permutation through "
                        "the Pallas fused-VMEM kernel "
                        "(MASTIC_KECCAK_PALLAS)")
    parser.add_argument("--level-pallas", action="store_true",
                        help="route the whole level step (extend -> "
                        "correct -> convert -> node proof) through "
                        "the fused-VMEM Pallas megakernel "
                        "(MASTIC_LEVEL_PALLAS) — the HBM-roofline "
                        "lever, PERF.md §3")
    parser.add_argument("--pipeline", choices=("on", "off"),
                        default=None,
                        help="set the MASTIC_PIPELINE lever for the "
                        "chunked-round config (drivers/pipeline.py: "
                        "double-buffered chunk streaming + "
                        "ahead-of-time bucket compile; default on)")
    parser.add_argument("--chunked-round-only", action="store_true",
                        help="run ONLY the chunked pipelined round "
                        "bench (per-phase timeline + "
                        "overlap_efficiency) — the MASTIC_PIPELINE "
                        "on/off comparison cell of "
                        "tools/chip_session.sh")
    parser.add_argument("--chunked-reports", type=int, default=1024,
                        help="report count for the chunked-round "
                        "config (4 chunks)")
    parser.add_argument("--service-overlap", action="store_true",
                        help="run ONLY the multi-tenant collector-"
                        "service bench: aggregate reports/s, "
                        "round-robin baseline vs the overlapped "
                        "epoch executor + concurrent ingest front, "
                        "bit-identity and zero-steady-state-compile "
                        "asserted (ISSUE 10; PERF.md §12)")
    parser.add_argument("--service-tenants", type=int, default=3)
    parser.add_argument("--service-reports", type=int, default=96,
                        help="reports per tenant per epoch for "
                        "--service-overlap")
    parser.add_argument("--service-epochs", type=int, default=3,
                        help="measured epochs per tenant (one warmup "
                        "epoch runs first, excluded)")
    parser.add_argument("--service-bits", type=int, default=6)
    parser.add_argument("--service-overlap-k", type=int, default=2,
                        help="in-flight tenant rounds for the "
                        "overlapped mode (MASTIC_SERVICE_OVERLAP)")
    parser.add_argument("--parties-wan", action="store_true",
                        help="run ONLY the network-separated "
                        "leader/helper session over the shaped link "
                        "ladder (MASTIC_NET_SHAPE): per-cell round "
                        "wall + comm delta, bit-identity asserted, "
                        "communication-vs-computation crossover "
                        "stamped (ISSUE 11; PERF.md §13)")
    parser.add_argument("--wan-bits", type=int, default=4)
    parser.add_argument("--wan-reports", type=int, default=256)
    parser.add_argument("--wan-rounds", type=int, default=2,
                        help="measured rounds per --parties-wan cell "
                        "(one warm round runs first, excluded)")
    parser.add_argument("--wan-shapes", type=str,
                        default="bw=1m:rtt=10ms,bw=128k:rtt=20ms,"
                                "bw=32k:rtt=40ms,bw=8k:rtt=80ms",
                        help="comma-separated MASTIC_NET_SHAPE cells "
                        "for --parties-wan (bw in bytes/s)")
    parser.add_argument("--cold-start", action="store_true",
                        help="measure fresh-process time-to-first-"
                        "round, traced vs warm AOT artifact store "
                        "(bakes via tools/bake.py unless "
                        "--artifact-dir holds a manifest) — the "
                        "ISSUE 9 headline; emits one JSON line")
    parser.add_argument("--cold-start-child", action="store_true",
                        help=argparse.SUPPRESS)  # internal: one
    # fresh-process collection run, JSON on stdout (parent + bake
    # --smoke drive it)
    parser.add_argument("--artifact-dir", type=str, default=None,
                        help="AOT artifact store for --cold-start "
                        "(reused when it has a manifest, baked "
                        "otherwise)")
    parser.add_argument("--cold-start-bits", type=int, default=8)
    parser.add_argument("--cold-start-reports", type=int, default=64)
    parser.add_argument("--cold-start-chunk", type=int, default=16)
    parser.add_argument("--cold-start-hitters", type=int, default=2)
    parser.add_argument("--cold-start-ctx", type=str,
                        default="bench cold-start")
    parser.add_argument("--mesh", type=str, default="1",
                        help="shard the report axis of the "
                        "incremental_round and chunked_round configs "
                        "over this many devices ('all' = every "
                        "attached device; 1 = off).  On CPU a numeric "
                        "value forces that many virtual host devices "
                        "(xla_force_host_platform_device_count)")
    parser.add_argument("--watchdog", type=float, default=1500.0)
    args = parser.parse_args()

    timer = _watchdog(args.watchdog)
    # The unroll lever must be in the environment before any
    # mastic_tpu.ops import (ops/keccak_jax.py reads it at import).
    # An explicit --keccak-unroll wins over an inherited env var; the
    # env var wins over the flag's default.
    if args.keccak_unroll is not None:
        os.environ["MASTIC_KECCAK_UNROLL"] = str(args.keccak_unroll)
    else:
        # unroll=1 was the best rate observed in the r5 chip lever
        # matrix (42.2M vs 37.5M warm at unroll=4 — single warm
        # measurements, so suggestive) and compiles quickest.
        os.environ.setdefault("MASTIC_KECCAK_UNROLL", "1")
    if args.keccak_pallas:
        os.environ["MASTIC_KECCAK_PALLAS"] = "1"
    if args.aes_pallas:
        os.environ["MASTIC_AES_PALLAS"] = "1"
    if args.level_pallas:
        os.environ["MASTIC_LEVEL_PALLAS"] = "1"
    if args.pipeline is not None:
        os.environ["MASTIC_PIPELINE"] = \
            "1" if args.pipeline == "on" else "0"

    if args.cold_start:
        # Pure subprocess orchestration: bake + two fresh children —
        # this process never imports jax (the children's cold start
        # must not inherit a warm runtime).
        run_cold_start_parent(args, timer)
        return

    if args.parties_wan:
        # Pure subprocess orchestration too: the parties are the
        # processes that touch jax; the parent only shards reports
        # (scalar layer) and drives the session.  Its own metric.
        PARTIAL["metric"] = "parties_wan_crossover_bandwidth"
        PARTIAL.pop("vs_baseline")
        # The parties are the processes that compute, and they run on
        # the CPU (drivers/parties.PARTY_PLATFORM).
        from mastic_tpu.drivers.parties import PARTY_PLATFORM

        PARTIAL["platform"] = PARTY_PLATFORM
        stamp("parties-wan", shapes=args.wan_shapes,
              reports=args.wan_reports)
        rec = bench_parties_wan(args)
        PARTIAL["value"] = rec["crossover_bandwidth_bytes_per_s"]
        PARTIAL["unit"] = "bytes/s"
        PARTIAL["configs"] = {"parties_wan": rec}
        timer.cancel()
        stamp("done",
              crossover=rec["crossover_bandwidth_bytes_per_s"],
              compute_s=rec["compute_round_s"])
        emit()
        return

    # A numeric --mesh > 1 must pin the virtual host device count
    # BEFORE the jax import (jax snapshots XLA_FLAGS then); on a chip
    # platform the flag only affects the unused host backend, so it is
    # always safe to set.  "all" resolves after the import.
    if args.mesh not in ("all",) and int(args.mesh) > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{int(args.mesh)}").strip()

    stamp("import-jax")
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if args.cold_start_child:
        # One fresh-process collection run, with no persistent compile
        # cache (a warm cache would fake the traced cold start).
        rec = run_cold_start_child(args)
        timer.cancel()
        print(json.dumps(rec), flush=True)
        return

    stamp("scalar-baseline")
    base = scalar_rate(bits=args.bits)
    PARTIAL["scalar_evals_per_sec"] = round(base, 1)

    stamp("device-attach")
    devices = jax.devices()
    stamp("device-up", devices=devices)
    # Resolve the --mesh lever now that the device set is known.
    args.mesh_n = (len(devices) if args.mesh == "all"
                   else int(args.mesh))
    if args.mesh_n > len(devices):
        timer.cancel()
        emit(error=f"--mesh {args.mesh_n} exceeds the "
             f"{len(devices)} attached device(s)")
        sys.exit(2)
    if args.mesh_n > 1:
        PARTIAL["mesh_devices"] = args.mesh_n
    # Stamped into every emit from here on, so a CPU-sim rate can
    # never be mistaken for a chip rate in a round artifact.
    PARTIAL["platform"] = devices[0].platform
    # With the persistent cache on, an in-process `compile_seconds`
    # below can read warm — the fresh-process cold start lives in
    # `--cold-start`'s `cold_start_seconds`.
    from mastic_tpu import compile_cache

    PARTIAL["compile_cache"] = compile_cache.configure()

    if args.service_overlap:
        # Multi-tenant serving throughput cell: round-robin baseline
        # vs overlapped executor + ingest front (ISSUE 10).  Its own
        # metric.
        PARTIAL["metric"] = "service_overlap_reports_per_sec"
        PARTIAL.pop("vs_baseline")
        stamp("service-overlap", tenants=args.service_tenants,
              reports=args.service_reports, k=args.service_overlap_k)
        rec = bench_service_overlap(args)
        PARTIAL["value"] = rec["overlap_reports_per_sec"]
        PARTIAL["unit"] = "reports/s"
        PARTIAL["speedup_vs_round_robin"] = rec["speedup"]
        PARTIAL["configs"] = {"service_overlap": rec}
        timer.cancel()
        stamp("done", rps=rec["overlap_reports_per_sec"],
              speedup=rec["speedup"])
        emit()
        return

    if args.chunked_round_only:
        # The MASTIC_PIPELINE on/off comparison cell: one JSON line
        # with the chunked production round's phase timeline and
        # overlap efficiency (a different metric than the headline).
        PARTIAL["metric"] = "chunked_round_node_evals_per_sec"
        PARTIAL["pipeline"] = \
            os.environ.get("MASTIC_PIPELINE", "1") != "0"
        PARTIAL.pop("vs_baseline")
        stamp("chunked-round", reports=args.chunked_reports,
              pipeline=PARTIAL["pipeline"])
        rec = bench_chunked_round(args)
        PARTIAL["value"] = rec["node_evals_per_sec"]
        PARTIAL["overlap_efficiency"] = rec["overlap_efficiency_p50"]
        PARTIAL["configs"] = {"chunked_round": rec}
        timer.cancel()
        stamp("done", rate=f"{rec['node_evals_per_sec']:.0f}",
              eff=rec["overlap_efficiency_p50"])
        emit()
        return

    from mastic_tpu import MasticCount
    from mastic_tpu.backend.mastic_jax import BatchedMastic
    bm = BatchedMastic(MasticCount(args.bits))

    stamp("full-compile", reports=args.reports, frontier=args.frontier)
    full = SteadyState(bm, args.reports, args.frontier, args.bits)
    compile_s = full.compile()
    stamp("warmup", compile_s=f"{compile_s:.1f}")
    full.run(2)
    stamp("measure")
    rate = full.run(args.steps)

    PARTIAL["value"] = round(rate, 1)
    PARTIAL["vs_baseline"] = round(rate / base, 1)
    PARTIAL["compile_seconds"] = round(compile_s, 1)
    PARTIAL["reports"] = args.reports
    PARTIAL["frontier"] = args.frontier
    PARTIAL["bits"] = args.bits
    PARTIAL["keccak_unroll"] = int(
        os.environ.get("MASTIC_KECCAK_UNROLL", "1"))
    PARTIAL["keccak_pallas"] = \
        os.environ.get("MASTIC_KECCAK_PALLAS", "0") == "1"
    PARTIAL["aes_pallas"] = \
        os.environ.get("MASTIC_AES_PALLAS", "0") == "1"
    PARTIAL["level_pallas"] = \
        os.environ.get("MASTIC_LEVEL_PALLAS", "0") == "1"
    if full.cost_bytes:
        # Logical bytes accessed per step / per eval (PERF.md §3: the
        # scan path measured 8.29 GB/step = 15.8 KB/eval on a v5e;
        # the megakernel acceptance target is < 5.3 KB/eval).
        PARTIAL["cost_bytes_per_step"] = round(full.cost_bytes, 1)
        PARTIAL["cost_bytes_per_eval"] = round(
            full.cost_bytes / full.evals_per_step, 1)

    if not args.headline_only:
        run_configs(args)
    timer.cancel()
    stamp("done", rate=f"{rate:.0f}")
    emit()


if __name__ == "__main__":
    try:
        main()
    except Exception as exc:  # report what was measured, then fail
        emit(error=f"{type(exc).__name__}: {exc}")
        raise
