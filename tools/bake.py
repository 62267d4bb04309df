"""Offline AOT artifact baker (`drivers/artifacts.py`, ROADMAP item
4): enumerate the round-program family for a collection config —
pow2 buckets × growth path × mesh shape — trace + compile each
program once, and seal the executables into a digest-sealed store a
collector process loads in seconds instead of re-paying the ~100 s
trace+XLA bill (`bench.py`'s `compile_seconds` on the incremental
round).

    # bake the family for a 32-bit Count collection streamed in
    # 256-report chunks, hitters up to 4, into ./artifacts/aot:
    python tools/bake.py --out artifacts/aot --bits 32 --rows 256 \
        --ctx "my collection" --hitters 1,2,3,4

    # the serving process then starts trace-free:
    python tools/serve.py --artifact-dir artifacts/aot ...
    # (or MASTIC_ARTIFACT_DIR=artifacts/aot for any driver)

The trajectory model: a heavy-hitters run's program shapes are a
pure function of the per-level frontier, which the planted-path
model (`artifacts.planted_paths` / `artifacts.trajectory`) makes
deterministic — `--hitters k` bakes the steady-k frontier family,
`--grow-frontier N` adds the threshold-prunes-nothing growth phase
(incl. the padded-width growth programs the runtime predictor
deliberately compiles inline).  A frontier the bake did not cover
simply compiles inline at runtime, attributed in
`extra["artifacts"]` — never wrong, only slower.

``--smoke`` is the `make artifacts-smoke` gate: bake a tiny config,
run the collection in-process against the freshly-traced programs
(the inline reference), then re-run it in a FRESH subprocess that
may only use the baked store — asserting zero inline compiles, a
zero compile field in every round timeline, and bit-identical
hitters + per-round counters.  That last comparison is the PERF.md
§7 soundness criterion: a deserialized executable must reproduce the
traced program's outputs exactly, and the per-artifact probe round
gates every load the same way.
"""

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Shared by --smoke and bench.py --cold-start: one tiny deterministic
# planted-path config both sides can reproduce exactly.
SMOKE_CONFIG = {"bits": 4, "reports": 16, "chunk": 8, "hitters": 2,
                "ctx": "artifact smoke"}


def bake(args) -> dict:
    from mastic_tpu.backend.mastic_jax import BatchedMastic
    from mastic_tpu.drivers import artifacts
    from mastic_tpu.drivers.parties import instantiate

    if args.mesh:
        from mastic_tpu.parallel import make_mesh
        mesh = make_mesh(args.mesh, nodes_axis=1)
    else:
        mesh = None

    spec = (json.loads(args.spec) if args.spec
            else {"class": "MasticCount", "args": [args.bits]})
    m = instantiate(spec)
    bm = BatchedMastic(m)
    ctx = args.ctx.encode()
    store = artifacts.default_store(args.out)
    bits = m.vidpf.BITS

    totals = {"compiled": 0, "skipped": 0, "seconds": 0.0}
    t0 = time.time()
    for rows in args.rows:
        for k in args.hitters:
            baker = artifacts.make_baker(bm, ctx, width=args.width,
                                         mesh=mesh)
            stats = artifacts.bake_trajectory(
                baker, store, rows,
                artifacts.trajectory(
                    bits, artifacts.planted_paths(bits, k)),
                with_stablehlo=not args.no_stablehlo)
            for (key, v) in stats.items():
                totals[key] += v
            print(f"[bake] rows={rows} hitters={k}: {stats}",
                  file=sys.stderr, flush=True)
        if args.grow_frontier:
            baker = artifacts.make_baker(bm, ctx, width=args.width,
                                         mesh=mesh)
            stats = artifacts.bake_trajectory(
                baker, store, rows,
                artifacts.growth_trajectory(bits, args.grow_frontier),
                with_stablehlo=not args.no_stablehlo)
            for (key, v) in stats.items():
                totals[key] += v
            print(f"[bake] rows={rows} grow<={args.grow_frontier}: "
                  f"{stats}", file=sys.stderr, flush=True)
        if args.attributes:
            # The attribute-metrics round program (ISSUE 10: the
            # from-root round now rides the artifact tier too) —
            # baked per (attribute set, rows, mesh shape), preloaded
            # by the service at tenant admission like every other
            # family member.
            baker = artifacts.make_baker(bm, ctx, width=args.width,
                                         mesh=mesh)
            stats = artifacts.bake_attribute_round(
                baker, store, rows, args.attributes,
                with_stablehlo=not args.no_stablehlo)
            for (key, v) in stats.items():
                totals[key] += v
            print(f"[bake] rows={rows} attributes="
                  f"{','.join(args.attributes)}: {stats}",
                  file=sys.stderr, flush=True)
    return {
        "mode": "bake",
        "out": store.path,
        "runtime": artifacts.runtime_tag(),
        "instance": spec,
        "ctx": args.ctx,
        "rows": args.rows,
        "hitters": args.hitters,
        "attributes": args.attributes,
        "mesh_devices": args.mesh or 1,
        "entries": store.entry_count(),
        "store_bytes": store.store_bytes(),
        "compiled": totals["compiled"],
        "skipped": totals["skipped"],
        "compile_seconds": round(totals["seconds"], 1),
        "wall_seconds": round(time.time() - t0, 1),
    }


def _smoke_child(store_dir: str, expect_store: bool) -> dict:
    """Run the smoke collection in a fresh subprocess (bench.py
    --cold-start-child), with or without the baked store armed."""
    cfg = SMOKE_CONFIG
    env = dict(os.environ)
    env.pop("MASTIC_ARTIFACT_DIR", None)
    if expect_store:
        env["MASTIC_ARTIFACT_DIR"] = store_dir
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "bench.py"),
         "--cold-start-child", "--cpu", "--bits", str(cfg["bits"]),
         "--chunked-reports", str(cfg["reports"]),
         "--cold-start-chunk", str(cfg["chunk"]),
         "--cold-start-hitters", str(cfg["hitters"]),
         "--cold-start-ctx", cfg["ctx"]],
        capture_output=True, text=True, timeout=1800, env=env)
    if proc.returncode != 0:
        raise SystemExit(
            f"bake --smoke: child (store={expect_store}) failed "
            f"rc={proc.returncode}:\n{proc.stderr[-4000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def smoke(args) -> dict:
    """The artifacts-smoke gate (acceptance criteria of ISSUE 9)."""
    import tempfile

    t0 = time.time()
    cfg = SMOKE_CONFIG
    tmp = tempfile.mkdtemp(prefix="mastic_aot_smoke_")
    bake_args = argparse.Namespace(
        out=tmp, spec=None, bits=cfg["bits"], ctx=cfg["ctx"],
        rows=[cfg["chunk"]], hitters=[cfg["hitters"]],
        grow_frontier=0, attributes=[], width=8, mesh=0,
        no_stablehlo=False)
    rec = bake(bake_args)
    print(f"[smoke] baked {rec['entries']} entries in "
          f"{rec['wall_seconds']}s", file=sys.stderr, flush=True)

    # The inline-traced reference: a fresh process with NO store.
    ref = _smoke_child(tmp, expect_store=False)
    if ref["inline_compiles"] == 0:
        raise SystemExit("smoke: reference child compiled nothing — "
                         "the comparison would be vacuous")
    # The warm-store run: a fresh process that may only load.
    warm = _smoke_child(tmp, expect_store=True)

    problems = []
    if warm["inline_compiles"] != 0:
        problems.append(f"warm child paid "
                        f"{warm['inline_compiles']} inline compiles")
    if warm["artifact_hits"] == 0:
        problems.append("warm child loaded no artifacts")
    if any(ms > 0.0 for ms in warm["round_compile_ms"]):
        problems.append(f"warm child's timeline compile field is "
                        f"nonzero: {warm['round_compile_ms']}")
    if warm["results"] != ref["results"]:
        problems.append(f"results diverge: {warm['results']} != "
                        f"{ref['results']}")
    if warm["counters"] != ref["counters"]:
        problems.append(f"per-round counters diverge: "
                        f"{warm['counters']} != {ref['counters']}")
    if problems:
        for p in problems:
            print(f"smoke: FAIL: {p}", file=sys.stderr, flush=True)
        sys.exit(1)
    return {
        "mode": "smoke", "ok": True,
        "store": tmp,
        "entries": rec["entries"],
        "bake_seconds": rec["wall_seconds"],
        "traced_first_round_s": ref["time_to_first_round_s"],
        "warm_first_round_s": warm["time_to_first_round_s"],
        "warm_artifact_hits": warm["artifact_hits"],
        "results": warm["results"],
        "wall_seconds": round(time.time() - t0, 1),
    }


def main() -> None:
    parser = argparse.ArgumentParser(
        description="bake AOT round-program artifacts "
                    "(USAGE.md 'AOT artifacts')")
    parser.add_argument("--out", type=str, default="artifacts/aot",
                        help="store directory (MASTIC_ARTIFACT_DIR / "
                             "--artifact-dir at serve time)")
    parser.add_argument("--spec", type=str, default=None,
                        help="instantiation record, e.g. "
                             '\'{"class": "MasticHistogram", '
                             '"args": [64, 16, 4]}\'')
    parser.add_argument("--bits", type=int, default=32,
                        help="MasticCount tree depth when --spec is "
                             "not given")
    parser.add_argument("--ctx", type=str, default="bench",
                        help="collection context (baked into the "
                             "programs' domain-separation tags — must "
                             "match the serving config)")
    parser.add_argument("--rows", type=str, default="256",
                        help="comma-separated device row counts "
                             "(chunk sizes) to bake for")
    parser.add_argument("--hitters", type=str, default="1,2,3,4",
                        help="comma-separated planted-hitter counts: "
                             "each bakes that steady frontier "
                             "trajectory")
    parser.add_argument("--grow-frontier", type=int, default=0,
                        help="also bake the all-survive growth "
                             "trajectory up to this frontier width "
                             "(covers padded-width growth programs)")
    parser.add_argument("--attributes", type=str, default="",
                        help="comma-separated attribute list: also "
                             "bake the attribute-metrics round "
                             "program for it (must match the serving "
                             "config's list exactly — the hashed "
                             "prefixes are baked into the program)")
    parser.add_argument("--width", type=int, default=8,
                        help="initial padded node width (grown on "
                             "demand, as at runtime)")
    parser.add_argument("--mesh", type=int, default=0,
                        help="bake mesh-sharded programs for this "
                             "many report-axis devices (0 = single "
                             "device; on CPU forces virtual devices)")
    parser.add_argument("--no-stablehlo", action="store_true",
                        help="skip the portable jax.export StableHLO "
                             "form (native executables only)")
    parser.add_argument("--smoke", action="store_true",
                        help="the `make artifacts-smoke` gate: bake "
                             "a tiny config, then prove a fresh "
                             "subprocess runs it trace-free and "
                             "bit-identical to the inline path")
    args = parser.parse_args()
    args.rows = [int(x) for x in str(args.rows).split(",") if x]
    args.hitters = [int(x) for x in str(args.hitters).split(",") if x]
    args.attributes = [x for x in str(args.attributes).split(",")
                       if x]

    if args.mesh:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.mesh}").strip()

    if args.smoke:
        # The smoke's children load the store on the CPU, so the
        # parent bakes there too — and holds no chip while they run.
        os.environ["JAX_PLATFORMS"] = "cpu"
        print("[smoke] baking and both children run with "
              "JAX_PLATFORMS=cpu", file=sys.stderr, flush=True)

    from mastic_tpu import compile_cache

    compile_cache.configure()
    out = smoke(args) if args.smoke else bake(args)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
