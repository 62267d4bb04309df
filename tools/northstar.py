"""North-star-scale heavy hitters: stream a large report batch through
the chunked incremental runner end to end.

This is the flagship workload (reference driver semantics,
/root/reference/poc/examples.py:37-91 for Count and :94-170 for the
weighted Sum mode, scaled up): device-batched client sharding ->
HostReportStore -> chunked incremental rounds with per-chunk metrics
and memory accounting.  Run it on the chip for the real number, or on
CPU (JAX_PLATFORMS=cpu) as the memory-accounted simulation — the
execution model and the compiled programs are identical either way;
only the rate changes (the JSON's "platform" field says which one
produced it).

Planted heavy hitters are full-width bit paths; when two or more are
planted, the second shares a long prefix with the first (diverging at
3/4 of the tree depth), so the frontier stays >1 wide deep into the
tree — the shape that exercises the shared-ancestor carry layout at
depth.

Prints one JSON line:
  {"inst": "count"|"sum", "platform": ..., "reports": N, "bits": B,
   "chunk_size": C, "levels": B, "wall_seconds": ...,
   "node_evals_total": ..., "node_evals_per_sec": ...,
   "per_chunk_evals_per_sec_p50": ..., "memory": {...},
   "envelope": {...}, "heavy_hitters": [...so many...], "ok": true}

Examples (each shape has a recorded ok=true run, see NORTHSTAR_r05*):
  JAX_PLATFORMS=cpu python tools/northstar.py --reports 8192 --bits 256
      # full north-star depth, chunked; ~83 min on a 1-core CPU host
      # (per-level cost grows with depth - the binder hashes the
      # carried tree - so 20k reports at 256 bits is ~6 h there)
  JAX_PLATFORMS=cpu python tools/northstar.py --inst sum --reports 10000 \\
      --bits 32 --max-weight 255
  python tools/northstar.py --resident --reports 10000 --bits 256
      # device-resident carries: the fast path whenever the carry fits
      # one chip's HBM (chunked mode moves the full carry
      # host<->device every level)
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# -- checkpoint container ---------------------------------------------
#
# The run state blob (HeavyHittersRun.to_bytes) only binds the verify
# key / ctx / thresholds and tree shape; the synthetic reports are
# rebuilt from CLI args, so a resume with a different --seed /
# --planted / --inst silently continues carried state over mismatched
# reports and only surfaces as ok=false after the full remaining wall
# time (ADVICE r5).  The checkpoint therefore stamps every parameter
# the report rebuild depends on into its header, and --resume verifies
# them before touching the run state.

SHARD_PARAM_KEYS = ("inst", "reports", "bits", "seed", "planted",
                    "max_weight", "tail_weight")


def shard_params(args) -> dict:
    """The CLI parameters the synthetic report batch is a pure
    function of (plant_paths + weight assignment + shard RNG)."""
    return {k: getattr(args, k) for k in SHARD_PARAM_KEYS}


def write_checkpoint_bytes(vk: bytes, params: dict,
                           blob: bytes) -> bytes:
    """vk-length | vk | params-length | params-json | run blob."""
    header = json.dumps(params, sort_keys=True).encode()
    return (len(vk).to_bytes(2, "little") + vk
            + len(header).to_bytes(4, "little") + header + blob)


def read_checkpoint_bytes(raw: bytes) -> tuple:
    """Inverse of write_checkpoint_bytes -> (vk, params, blob)."""
    klen = int.from_bytes(raw[:2], "little")
    vk = raw[2:2 + klen]
    off = 2 + klen
    plen = int.from_bytes(raw[off:off + 4], "little")
    try:
        params = json.loads(raw[off + 4:off + 4 + plen])
    except ValueError:
        raise ValueError(
            "checkpoint has no shard-parameter header (written by an "
            "older tools/northstar.py) — re-run without --resume")
    return (vk, params, raw[off + 4 + plen:])


def verify_shard_params(saved: dict, current: dict) -> list:
    """Mismatched parameter names (resume must refuse on any)."""
    return sorted(k for k in set(saved) | set(current)
                  if saved.get(k) != current.get(k))


def plant_paths(rng, planted: int, bits: int):
    """Full-width planted heavy-hitter paths, (planted, bits) bool.

    Rows are pairwise distinct; when >= 2 are planted, row 1 copies
    row 0's first 3/4 of the tree and diverges exactly there, so the
    two survivors ride one shared ancestor chain for 3/4 of the run.
    """
    import numpy as np

    if planted > 2 ** bits:
        raise ValueError(
            f"cannot plant {planted} distinct paths in a "
            f"{bits}-bit tree ({2 ** bits} exist)")
    paths = rng.integers(0, 2, (planted, bits)).astype(bool)
    if planted >= 2:
        split = max(1, (3 * bits) // 4)
        if split >= bits:
            split = bits - 1
        paths[1, :split] = paths[0, :split]
        paths[1, split] = ~paths[0, split]
        paths[1, split + 1:] = rng.integers(
            0, 2, bits - split - 1).astype(bool)
    for r in range(planted):
        while any(np.array_equal(paths[r], paths[s]) for s in range(r)):
            paths[r] = rng.integers(0, 2, bits).astype(bool)
    return paths


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--inst", choices=("count", "sum"),
                        default="count")
    parser.add_argument("--reports", type=int, default=100_000)
    parser.add_argument("--bits", type=int, default=64)
    parser.add_argument("--chunk-size", type=int, default=4096)
    parser.add_argument("--planted", type=int, default=3,
                        help="number of heavy-hitter values planted")
    parser.add_argument("--max-weight", type=int, default=7,
                        help="MasticSum max_measurement; planted "
                             "reports carry this weight (sum mode)")
    parser.add_argument("--tail-weight", type=int, default=1,
                        help="weight of the uniform-tail reports "
                             "(sum mode)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--resident", action="store_true",
                        help="keep carries device-resident for the "
                             "whole run instead of streaming host "
                             "chunks — the fast path whenever the "
                             "full carry fits one chip's HBM "
                             "(chunked mode moves the full carry "
                             "host<->device every level)")
    parser.add_argument("--mesh", type=int, default=0,
                        help="shard the chunk's report axis over this "
                             "many devices (virtual CPU devices when "
                             "the platform is cpu)")
    parser.add_argument("--out", type=str, default=None,
                        help="also write the JSON artifact here")
    parser.add_argument("--checkpoint", type=str, default=None,
                        help="write the run state here every "
                             "--checkpoint-every levels (verify key "
                             "+ HeavyHittersRun.to_bytes, atomic "
                             "rename); with --resume, restore from "
                             "it and continue")
    parser.add_argument("--checkpoint-every", type=int, default=16)
    parser.add_argument("--resume", action="store_true",
                        help="resume from --checkpoint instead of "
                             "starting fresh (reports are rebuilt "
                             "deterministically from --seed, so only "
                             "the run state needs the file)")
    args = parser.parse_args()

    if args.checkpoint_every < 1:
        # A value of 0 used to crash with ZeroDivisionError at
        # `run.level % args.checkpoint_every` — after the first
        # (possibly long) level completed (ADVICE r5).
        parser.error(f"--checkpoint-every must be >= 1 "
                     f"(got {args.checkpoint_every})")

    # Read and verify the checkpoint BEFORE the jax import and the
    # multi-minute shard phase: a mismatched resume fails in
    # milliseconds, not after the full remaining wall time (ADVICE
    # r5 — the run state blob binds vk/ctx/thresholds but the
    # synthetic reports are rebuilt from these CLI args).
    resumed_from = None
    ckpt_blob = None
    vk = None
    if args.resume:
        if not args.checkpoint:
            parser.error("--resume needs --checkpoint PATH")
        with open(args.checkpoint, "rb") as f:
            raw = f.read()
        (vk, saved_params, ckpt_blob) = read_checkpoint_bytes(raw)
        mismatched = verify_shard_params(saved_params,
                                         shard_params(args))
        if mismatched:
            detail = ", ".join(
                f"{k}: checkpoint={saved_params.get(k)!r} "
                f"vs run={getattr(args, k, None)!r}"
                for k in mismatched)
            print(f"--resume refused: the checkpoint was written for "
                  f"different shard parameters ({detail}); the "
                  f"rebuilt reports would not match the carried "
                  f"state and the run would only fail at the end",
                  file=sys.stderr)
            sys.exit(2)

    if args.mesh:
        # Chunked mode shards ANY chunk_size: the runner pads each
        # chunk's device rows to the shard multiple and masks the dead
        # lanes (drivers/chunked.ChunkedIncrementalRunner._device_rows)
        # — the old parse-time divisibility refusal is gone.  Resident
        # mode's batch IS the device tile, so it still must divide;
        # fail before the multi-minute shard phase, not after it.
        if args.resident and args.reports % args.mesh:
            parser.error(
                f"--reports {args.reports} must be divisible by "
                f"--mesh {args.mesh} in --resident mode (the resident "
                f"batch shards without padding; chunked mode pads)")
        # Virtual device count must be pinned before jax import.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.mesh}").strip()

    t_start = time.time()

    def stamp(msg: str) -> None:
        print(f"[northstar {time.time() - t_start:8.1f}s] {msg}",
              file=sys.stderr, flush=True)

    import numpy as np
    import jax
    import jax.numpy as jnp

    from mastic_tpu import MasticCount, MasticSum, compile_cache
    from mastic_tpu.backend.mastic_jax import BatchedMastic
    from mastic_tpu.common import gen_rand
    from mastic_tpu.drivers.chunked import HostReportStore, memory_envelope
    from mastic_tpu.drivers.heavy_hitters import HeavyHittersRun

    (R, bits, C) = (args.reports, args.bits, args.chunk_size)
    if args.inst == "sum":
        m = MasticSum(bits, args.max_weight)
    else:
        m = MasticCount(bits)
    bm = BatchedMastic(m)
    rng = np.random.default_rng(args.seed)
    platform = jax.devices()[0].platform
    compile_cache.configure()
    if args.mesh and args.mesh > jax.device_count():
        print(f"--mesh {args.mesh} exceeds the {jax.device_count()} "
              f"available {platform} device(s)", file=sys.stderr)
        sys.exit(2)
    stamp(f"device={platform} inst={args.inst} reports={R} bits={bits} "
          f"chunk={C}")

    # Plant a few heavy paths (one pair colliding on a long prefix);
    # the rest is a uniform tail that the threshold prunes early.
    paths = plant_paths(rng, args.planted, bits)
    share_heavy = 0.6
    heavy_rows = int(R * share_heavy)
    choice = rng.integers(0, args.planted, heavy_rows)
    alphas = np.concatenate([
        paths[choice],
        rng.integers(0, 2, (R - heavy_rows, bits)).astype(bool)])

    # Per-report weights: heavy reports carry max weight, the tail
    # carries tail weight (Count: everyone weighs 1; the threshold is
    # in aggregate-weight units either way, reference examples.py:135).
    if args.inst == "sum":
        (w_heavy, w_tail) = (args.max_weight, args.tail_weight)
    else:
        (w_heavy, w_tail) = (1, 1)
    weights = np.concatenate([
        np.full(heavy_rows, w_heavy, np.int64),
        np.full(R - heavy_rows, w_tail, np.int64)])
    threshold = int(heavy_rows / args.planted * w_heavy * 0.5)

    def beta_limbs(weight: int) -> np.ndarray:
        beta = [m.field(1)] + m.flp.encode(int(weight))
        return np.stack([bm.spec.int_to_limbs(el.int()) for el in beta])

    beta_table = {int(w): beta_limbs(int(w))
                  for w in np.unique(weights)}
    betas = np.stack([beta_table[int(w)] for w in (w_heavy, w_tail)])
    beta_idx = (weights != w_heavy).astype(np.int64)  # 0=heavy, 1=tail

    # Device-batched client sharding, chunk by chunk, directly into
    # the host store (the client fleet axis; scalar clients would take
    # ~R seconds at 256 bits).
    stamp("shard: compiling client program")
    shard_fn = jax.jit(
        lambda a, b, n, r: bm.shard_device(b"northstar", a, b, n, r))
    num_chunks = -(-R // C)
    arrays = None
    chunk_batches = []
    shard_t0 = time.time()
    for i in range(num_chunks):
        (lo, hi) = (i * C, min((i + 1) * C, R))
        idx = np.arange(lo, hi)
        if hi - lo < C:  # pad the tail chunk (same compiled program)
            idx = np.concatenate([idx, np.full(C - (hi - lo), lo)])
        a = jnp.asarray(alphas[idx])
        b = jnp.asarray(betas[beta_idx[idx]])
        n = jnp.asarray(rng.integers(0, 256, (C, 16), dtype=np.uint8))
        r = jnp.asarray(rng.integers(0, 256, (C, m.RAND_SIZE),
                                     dtype=np.uint8))
        (batch, ok) = shard_fn(a, b, n, r)
        assert bool(np.all(np.asarray(ok))), \
            "XOF rejection fired during synthetic shard (p ~ 2^-32)"
        if args.resident:
            # Keep the (tail-trimmed) device arrays; no host store.
            chunk_batches.append(jax.tree_util.tree_map(
                lambda x: x[:hi - lo], batch))
            if i == 0:
                stamp(f"shard: chunk 0 done "
                      f"({time.time() - shard_t0:.1f}s incl compile)")
            continue
        chunk_store = HostReportStore.from_batch(batch, C)
        if arrays is None:
            arrays = {
                k: (np.zeros((R,) + v.shape[1:], v.dtype)
                    if isinstance(v, np.ndarray) else
                    tuple(np.zeros((R,) + p.shape[1:], p.dtype)
                          if isinstance(p, np.ndarray) else None
                          for p in v) if isinstance(v, tuple) else None)
                for (k, v) in chunk_store.arrays.items()}
        for (k, v) in chunk_store.arrays.items():
            if isinstance(v, np.ndarray):
                arrays[k][lo:hi] = v[:hi - lo]
            elif isinstance(v, tuple):
                for (dst, src) in zip(arrays[k], v):
                    if isinstance(src, np.ndarray):
                        dst[lo:hi] = src[:hi - lo]
        if i == 0:
            stamp(f"shard: chunk 0 done ({time.time() - shard_t0:.1f}s "
                  "incl compile)")
    shard_wall = time.time() - shard_t0
    stamp(f"shard: {R} reports in {shard_wall:.1f}s "
          f"({R / shard_wall:.0f} reports/s)")

    mesh = None
    if args.mesh:
        from mastic_tpu.parallel import make_mesh
        mesh = make_mesh(args.mesh, nodes_axis=1)
        stamp(f"mesh: report axis sharded over {args.mesh} devices")

    # Checkpoint file = vk + shard-parameter header + HeavyHittersRun
    # blob (write_checkpoint_bytes, read + verified at parse time
    # above).  The vk rides along because the blob's binding digest
    # pins it (a fresh key would silently reject every carried
    # report); the header pins the report rebuild.
    if vk is None:
        vk = gen_rand(m.VERIFY_KEY_SIZE)

    thresholds = {"default": threshold}
    if args.resident:
        full_batch = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *chunk_batches)
        chunk_batches.clear()  # don't hold 2x the batch in HBM
        if ckpt_blob is not None:
            run = HeavyHittersRun.from_bytes(
                m, b"northstar", thresholds, None, vk, ckpt_blob,
                batch=full_batch, mesh=mesh)
        else:
            run = HeavyHittersRun(m, b"northstar", thresholds,
                                  None, verify_key=vk,
                                  batch=full_batch, mesh=mesh)
    else:
        store = HostReportStore(arrays, R, C)
        if ckpt_blob is not None:
            run = HeavyHittersRun.from_bytes(
                m, b"northstar", thresholds, None, vk, ckpt_blob,
                store=store, mesh=mesh)
        else:
            run = HeavyHittersRun(m, b"northstar", thresholds,
                                  None, verify_key=vk, store=store,
                                  mesh=mesh)
    if ckpt_blob is not None:
        resumed_from = run.level
        stamp(f"resumed from checkpoint at level {run.level}")

    def save_checkpoint() -> None:
        tmp = args.checkpoint + ".tmp"
        with open(tmp, "wb") as f:
            f.write(write_checkpoint_bytes(vk, shard_params(args),
                                           run.to_bytes()))
        os.replace(tmp, args.checkpoint)

    stamp(f"rounds: threshold={threshold} planted={args.planted}")
    # The run's round/chunk spans nest under one "collection" span —
    # the same span schema tools/serve.py's epochs emit, so an offline
    # northstar trace and a live service trace diff directly
    # (MASTIC_TRACE_FILE=path captures both as JSONL).
    from mastic_tpu.obs import trace as obs_trace
    coll_span = obs_trace.get_tracer().start_detached_span(
        "collection", tool="northstar", inst=args.inst,
        reports=R, bits=bits,
        mode="resident" if args.resident else "chunked")
    agg_t0 = time.time()
    evals_total = 0
    chunk_rates: list = []
    level = 0
    more = True
    while more:
        # The deepest level's round runs inside the step() call that
        # returns False — consume metrics appended since the last
        # iteration, not just on True returns, or the final level's
        # evals vanish from the totals.
        with obs_trace.get_tracer().use_parent(coll_span):
            more = run.step()
        if args.checkpoint and more \
                and run.level % args.checkpoint_every == 0:
            save_checkpoint()
            stamp(f"checkpoint written at level {run.level}")
        for mx in run.metrics[level:]:
            evals_total += mx.node_evals
            if "chunks" in mx.extra:
                rates = [c["node_evals_per_sec"]
                         for c in mx.extra["chunks"]]
            else:  # resident: one device round, rate from its wall
                wall_ms = mx.extra.get("round_wall_ms", 0.0)
                rates = ([mx.node_evals / (wall_ms / 1e3)]
                         if wall_ms else [])
            chunk_rates += rates
            if level % 8 == 0 or level == bits - 1 or not more:
                p50 = (sorted(rates)[len(rates) // 2]
                       if rates else 0.0)
                stamp(f"level {mx.level}: frontier={mx.frontier_width}"
                      f" accepted={mx.accepted}/{mx.reports_total} "
                      f"evals/s p50={p50:.0f}")
            level += 1
    agg_wall = time.time() - agg_t0
    obs_trace.get_tracer().end_span(coll_span)

    hitters = run.result()
    expected = {tuple(bool(b) for b in row) for row in paths}
    got = set(hitters)
    mem = run.runner.memory_accounting()
    # Pipelined-executor summary (drivers/pipeline.py): overlap
    # efficiency is a measured number in the artifact, and a
    # degrade-to-serial fallback is named, never silent.
    pipe_rounds = [mx.extra["pipeline"] for mx in run.metrics
                   if "pipeline" in mx.extra]
    pipeline_out = None
    if pipe_rounds:
        effs = sorted(p["overlap_efficiency"] for p in pipe_rounds)
        pipeline_out = {
            "mode": pipe_rounds[-1]["mode"],
            "rounds_pipelined": sum(
                p["mode"] == "pipelined" for p in pipe_rounds),
            "rounds_total": len(pipe_rounds),
            "overlap_efficiency_p50": effs[len(effs) // 2],
            "compile_inline_ms_total": round(
                sum(p["compile_inline_ms"] for p in pipe_rounds), 1),
            "fallbacks": sorted({p["fallback"] for p in pipe_rounds
                                 if p["fallback"]}),
        }
    # Mesh summary (drivers/chunked.py stamps extra["mesh"]): psum
    # bytes and shard skew per round, so the collective overhead at
    # scale is a recorded number, not an inference.
    mesh_rounds = [mx.extra["mesh"] for mx in run.metrics
                   if "mesh" in mx.extra]
    mesh_out = None
    if mesh_rounds:
        skews = sorted(mr["shard_wait_skew_ms_max"]
                       for mr in mesh_rounds)
        mesh_out = {
            "report_shards": mesh_rounds[-1]["report_shards"],
            "device_rows_per_chunk":
                mesh_rounds[-1]["device_rows_per_chunk"],
            "rows_per_shard": mesh_rounds[-1]["rows_per_shard"],
            "psum_bytes_total": sum(mr["psum_bytes_per_round"]
                                    for mr in mesh_rounds),
            "psum_bytes_per_round_last":
                mesh_rounds[-1]["psum_bytes_per_round"],
            "shard_wait_skew_ms_p50": skews[len(skews) // 2],
            "shard_wait_skew_ms_max": skews[-1],
        }
    # Envelope at the FINAL width — a frontier that forced _grow must
    # be reflected next to the measured accounting.  Resident mode's
    # "chunk" is the entire batch.
    envelope = memory_envelope(bm, R if args.resident else C,
                               run.runner.width, R,
                               n_device_shards=args.mesh or 1)
    p50 = (sorted(chunk_rates)[len(chunk_rates) // 2]
           if chunk_rates else 0.0)
    out = {
        "inst": args.inst, "platform": platform,
        "mode": "resident" if args.resident else "chunked",
        "mesh_devices": args.mesh or 1,
        "reports": R, "bits": bits,
        "chunk_size": 0 if args.resident else C,
        "levels": len(run.metrics),
        "threshold": threshold,
        "shard_seconds": round(shard_wall, 1),
        "wall_seconds": round(agg_wall, 1),
        "node_evals_total": evals_total,
        "node_evals_per_sec": round(evals_total / agg_wall, 1),
        "per_chunk_evals_per_sec_p50": round(p50, 1),
        # Per-shard twin of the p50 (live rate / report shards): the
        # number to hold against the single-chip roofline (PERF.md §8).
        "per_chunk_evals_per_sec_per_shard_p50": round(
            p50 / (args.mesh or 1), 1),
        "memory": mem,
        "envelope": envelope,
        "heavy_hitters_found": len(hitters),
        "heavy_hitters_expected": len(expected),
        # Tracer state: how many spans this run emitted, where the
        # JSONL (if any) went — so an artifact names its own trace.
        "obs": obs_trace.get_tracer().snapshot(),
        "ok": got == expected,
    }
    if pipeline_out is not None:
        out["pipeline"] = pipeline_out
    if mesh_out is not None:
        out["mesh"] = mesh_out
    if args.inst == "sum":
        out["max_weight"] = args.max_weight
    if resumed_from is not None:
        # wall/evals cover only this process's rounds.
        out["resumed_from_level"] = resumed_from
    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    if not out["ok"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
