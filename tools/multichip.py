"""Multi-chip proof run: a REAL pipelined chunked heavy-hitters
collection on an n-device mesh, asserted bit-identical to the
single-device serial run.

This graduates `__graft_entry__.dryrun_multichip` (one jitted round on
tiny shapes) to the production execution model end to end: chunked
store -> pipelined double-buffered executor -> mesh-sharded chunk
uploads -> device-side accept combine -> psum-only aggregation, with
the uneven tail chunk padded to the shard multiple and masked.  On a
CPU host the mesh is forced via `--xla_force_host_platform_device_count`
(set before the jax import below); on a real multi-chip attachment the
same code runs over the physical devices.

Prints one JSON line and exits nonzero unless ALL of:
  * mesh-run aggregates, accept masks, rejection counters, fallback
    (quarantine-union) masks and checkpoint state arrays equal the
    serial run's bit for bit;
  * every multi-chunk round ran mode="pipelined" with fallback=None
    (the r9 `("serial", "mesh")` degrade is gone);
  * steady-state rounds after the first paid ZERO inline compile
    (the AOT predictor works sharded).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--devices", type=int, default=8,
                        help="report-axis mesh size (virtual CPU "
                             "devices are forced when the platform "
                             "is cpu)")
    parser.add_argument("--bits", type=int, default=3)
    parser.add_argument("--chunk-size", type=int, default=4,
                        help="deliberately NOT a multiple of "
                             "--devices by default: exercises the "
                             "pad-to-shard-multiple path")
    parser.add_argument("--out", type=str, default=None)
    args = parser.parse_args()

    # Pin the virtual device count before jax imports (config
    # snapshot); harmless on a real multi-chip attachment.
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.devices}").strip()

    import numpy as np
    import jax

    from mastic_tpu import MasticCount
    from mastic_tpu.common import gen_rand
    from mastic_tpu.drivers.heavy_hitters import (
        HeavyHittersRun, get_reports_from_measurements)
    from mastic_tpu.parallel import make_mesh

    if jax.device_count() < args.devices:
        print(json.dumps({"ok": False,
                          "error": f"need {args.devices} devices, "
                                   f"have {jax.device_count()}"}))
        sys.exit(2)

    m = MasticCount(args.bits)
    ctx = b"multichip"
    # Steady one-child-per-parent frontier (the AOT predictor's fixed
    # point) with one tampered report, so both the zero-inline-compile
    # claim and the rejection attribution are exercised; 10 reports /
    # chunk 4 = 3 chunks with a padded tail.
    meas = [(m.vidpf.test_index_from_int(v, args.bits), True)
            for v in (0, 0, 0, 7, 7, 7, 3, 1, 6, 6)]
    reports = get_reports_from_measurements(m, ctx, meas)
    (nonce, ps, shares) = reports[6]
    (key, proof, seed, part) = shares[0]
    reports[6] = (nonce, ps, [
        (bytes([key[0] ^ 1]) + key[1:], proof, seed, part), shares[1]])
    vk = gen_rand(m.VERIFY_KEY_SIZE)
    thresholds = {"default": 2}

    def collect(mesh):
        run = HeavyHittersRun(m, ctx, thresholds, reports,
                              verify_key=vk,
                              chunk_size=args.chunk_size, mesh=mesh)
        t0 = time.time()
        while run.step():
            pass
        return (run, time.time() - t0)

    (serial, serial_s) = collect(None)
    mesh = make_mesh(args.devices, nodes_axis=1)
    (meshed, meshed_s) = collect(mesh)

    failures = []

    def check(name, cond):
        if not cond:
            failures.append(name)

    check("result", serial.result() == meshed.result())
    check("levels", len(serial.metrics) == len(meshed.metrics))
    for (a, b) in zip(serial.metrics, meshed.metrics):
        check(f"counters_l{a.level}",
              (a.accepted, a.rejected_eval_proof,
               a.rejected_weight_check, a.rejected_joint_rand,
               a.rejected_fallback, a.xof_fallbacks) ==
              (b.accepted, b.rejected_eval_proof,
               b.rejected_weight_check, b.rejected_joint_rand,
               b.rejected_fallback, b.xof_fallbacks))
    check("quarantine_union_mask",
          np.array_equal(serial.runner.fallback,
                         meshed.runner.fallback))
    (sa, sb) = (serial.runner.state_arrays(),
                meshed.runner.state_arrays())
    check("state_keys", sorted(sa) == sorted(sb))
    for k in sa:
        check(f"state_{k}", np.array_equal(sa[k], sb[k]))

    pipes = [mx.extra["pipeline"] for mx in meshed.metrics]
    check("pipelined", all(p["mode"] == "pipelined" for p in pipes))
    check("no_fallback", all(p["fallback"] is None for p in pipes))
    check("zero_inline_after_first",
          all(p["compile_inline_ms"] == 0.0 for p in pipes[1:]))
    check("aot_predicted",
          all(p["aot"]["predicted"] for p in pipes[1:]))

    mesh_rounds = [mx.extra["mesh"] for mx in meshed.metrics]
    out = {
        "n_devices": args.devices,
        "platform": jax.devices()[0].platform,
        "bits": args.bits,
        "reports": len(reports),
        "chunk_size": args.chunk_size,
        "levels": len(meshed.metrics),
        "serial_seconds": round(serial_s, 1),
        "mesh_seconds": round(meshed_s, 1),
        "device_rows_per_chunk":
            mesh_rounds[0]["device_rows_per_chunk"],
        "rows_per_shard": mesh_rounds[0]["rows_per_shard"],
        "psum_bytes_total": sum(mr["psum_bytes_per_round"]
                                for mr in mesh_rounds),
        "pipeline_modes": sorted({p["mode"] for p in pipes}),
        "compile_inline_ms_after_first": round(
            sum(p["compile_inline_ms"] for p in pipes[1:]), 2),
        "hitters": len(meshed.result()),
        "failures": failures,
        "ok": not failures,
    }
    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
