"""AOT artifact store (ISSUE 9, `drivers/artifacts.py`): the three
load gates (digest / runtime / probe), the ProgramCache artifact
tier, the runtime-skew refusal, and — slow tier — full-round
bit-identity of reloaded executables vs freshly traced programs
(incl. mesh={1,2} and width growth) plus kill-9 resume over a warm
store.

Fast-tier tests use trivial jitted programs (sub-second compiles);
the real round-program family is exercised by `make artifacts-smoke`
(tools/bake.py --smoke: bake -> fresh-subprocess load -> probe ->
bit-identity) and the slow tests here.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mastic_tpu.drivers import artifacts
from mastic_tpu.drivers.pipeline import ProgramCache
from mastic_tpu.obs.registry import configure as configure_registry


@pytest.fixture
def store(tmp_path):
    return artifacts.ArtifactStore(str(tmp_path / "store"))


def _trivial(tag: int = 1):
    """A compiled trivial program plus its call args."""
    fn = jax.jit(lambda a, b: (a + b * tag, (a * b).sum()))
    args = (jnp.arange(4, dtype=jnp.uint32),
            jnp.full((4,), 2, jnp.uint32))
    return (fn, args, fn.lower(*args).compile())


def _key(fam="famA", rows=4):
    return ("eval", rows, 0, 8, 2, 1, 2, artifacts.runtime_tag(), fam)


def _manifest(store):
    with open(os.path.join(store.path, "manifest.json")) as fh:
        return json.load(fh)


def _write_manifest(store, man):
    with open(os.path.join(store.path, "manifest.json"), "w") as fh:
        json.dump(man, fh)


# -- store mechanics --------------------------------------------------


def test_save_load_round_trip_bit_identical(store):
    (fn, args, compiled) = _trivial()
    entry = store.save(_key(), compiled,
                       stablehlo=artifacts.export_stablehlo(fn, args))
    assert entry["bytes"] > 0
    assert os.path.exists(os.path.join(store.path, entry["blob"]))
    assert os.path.exists(os.path.join(store.path, entry["stablehlo"]))
    # A fresh store object (no in-memory memo) pays the real disk
    # load + probe; outputs must be bit-identical to the traced
    # program's.
    fresh = artifacts.ArtifactStore(store.path)
    loaded = fresh.load(_key())
    assert loaded is not None
    for (a, b) in zip(jax.tree_util.tree_leaves(compiled(*args)),
                      jax.tree_util.tree_leaves(loaded(*args))):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("mesh_n", [1, 2])
def test_mesh_program_loads_on_multi_device_host(store, mesh_n):
    """A program compiled over the first `mesh_n` devices of the
    8-device test fabric (`--mesh 1` is the single-device case) loads
    from a fresh store onto its own devices and passes the probe."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    assert len(jax.devices()) == 8
    mesh = Mesh(np.asarray(jax.devices()[:mesh_n]), ("reports",))
    sharding = NamedSharding(mesh, P("reports"))
    fn = jax.jit(lambda a, b: (a + b, (a * b).sum()),
                 out_shardings=(sharding, NamedSharding(mesh, P())))
    args = tuple(jax.device_put(x, sharding)
                 for x in (jnp.arange(8, dtype=jnp.uint32),
                           jnp.full((8,), 3, jnp.uint32)))
    compiled = fn.lower(*args).compile()
    assert store.save(_key(), compiled)["devices"] == mesh_n
    fresh = artifacts.ArtifactStore(store.path)
    name = artifacts.key_name(_key())
    (loaded, outcome) = fresh._gated_load(
        name, fresh.manifest["entries"][name])
    assert outcome == artifacts.HIT
    for (a, b) in zip(jax.tree_util.tree_leaves(compiled(*args)),
                      jax.tree_util.tree_leaves(loaded(*args))):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_miss_and_memoization(store):
    assert store.load(("absent", 1)) is None
    (_fn, _args, compiled) = _trivial()
    store.save(_key(), compiled)
    # The saving store serves the traced object from memory — the
    # bake process never runs a reload of its own programs.
    assert store.load(_key()) is compiled


def test_corrupt_blob_detected_before_unpickle(store):
    (_fn, _args, compiled) = _trivial()
    entry = store.save(_key(), compiled)
    blob = os.path.join(store.path, entry["blob"])
    data = bytearray(open(blob, "rb").read())
    data[len(data) // 2] ^= 0xFF
    with open(blob, "wb") as fh:
        fh.write(bytes(data))
    fresh = artifacts.ArtifactStore(store.path)
    assert fresh.load(_key()) is None
    assert artifacts.CORRUPT in fresh._failed.values()


def test_version_skew_refused(store):
    (_fn, _args, compiled) = _trivial()
    store.save(_key(), compiled)
    man = _manifest(store)
    man["runtime"] = "jax-9.9.9-neverland"
    _write_manifest(store, man)
    fresh = artifacts.ArtifactStore(store.path)
    assert fresh.load(_key()) is None
    assert artifacts.VERSION_SKEW in fresh._failed.values()


def test_probe_failure_detected(store):
    """The PERF.md §7 failure mode: a reload that produces different
    outputs must be refused.  Simulated by doctoring the recorded
    probe digest — the load-side probe run then mismatches."""
    (_fn, _args, compiled) = _trivial()
    store.save(_key(), compiled)
    man = _manifest(store)
    name = artifacts.key_name(_key())
    man["entries"][name]["probe_digest"] = "0" * 64
    _write_manifest(store, man)
    fresh = artifacts.ArtifactStore(store.path)
    assert fresh.load(_key()) is None
    assert fresh._failed[name] == artifacts.PROBE_FAIL


def test_load_outcomes_land_in_registry(store, tmp_path):
    reg = configure_registry()
    (_fn, _args, compiled) = _trivial()
    entry = store.save(_key(), compiled)
    fresh = artifacts.ArtifactStore(store.path)
    fresh.load(_key())           # hit
    fresh.load(("absent", 1))    # miss
    blob = os.path.join(store.path, entry["blob"])
    with open(blob, "wb") as fh:
        fh.write(b"garbage")
    fresh2 = artifacts.ArtifactStore(store.path)
    fresh2.load(_key())          # corrupt
    get = lambda outcome: reg.counter(  # noqa: E731
        "mastic_artifact_loads_total", outcome=outcome).value()
    assert get("hit") == 1.0
    assert get("miss") == 1.0
    assert get("corrupt") == 1.0
    configure_registry()


# -- ProgramCache artifact tier ---------------------------------------


def test_cache_artifact_tier_skips_compile(store):
    (_fn, _args, compiled) = _trivial()
    store.save(_key(), compiled)
    cache = ProgramCache(store=artifacts.ArtifactStore(store.path))

    def must_not_build():
        raise AssertionError("store hit must not compile")

    (prog, wait) = cache.get(_key(), must_not_build)
    assert prog is not None and wait > 0.0
    assert cache.stats == {**cache.stats, "artifact_hits": 1,
                           "inline_compiles": 0}
    # Second get: in-process tier, zero wait.
    (prog2, wait2) = cache.get(_key(), must_not_build)
    assert prog2 is prog and wait2 == 0.0


def test_cache_warm_prefetches_from_store(store):
    (_fn, _args, compiled) = _trivial()
    store.save(_key(), compiled)
    cache = ProgramCache(store=artifacts.ArtifactStore(store.path))
    spent = cache.warm(_key(), lambda: pytest.fail("must prefetch"))
    assert spent > 0.0
    assert cache.stats["artifact_hits"] == 1
    assert cache.stats["warm_compiles"] == 0
    assert cache.contains(_key())


def test_cache_preload_filters_by_family(store):
    (_fn, _args, c1) = _trivial(1)
    (_fn2, _args2, c2) = _trivial(2)
    store.save(_key("famA"), c1)
    store.save(_key("famB"), c2)
    cache = ProgramCache(store=artifacts.ArtifactStore(store.path))
    n = cache.preload(lambda key: key[-1] == "famA")
    assert n == 1
    assert cache.contains(_key("famA"))
    assert not cache.contains(_key("famB"))


def test_cache_refuses_foreign_runtime_key():
    """Satellite regression: an in-process cache can never serve (or
    store) a program keyed for a different runtime — the refusal is
    loud, not a silent miss."""
    cache = ProgramCache()
    skewed = ("eval", 4, 0, 8, "jax-0.0.1-elsewhere", "fam")
    with pytest.raises(RuntimeError, match="refusing to serve"):
        cache.get(skewed, lambda: None)
    with pytest.raises(RuntimeError, match="refusing to serve"):
        cache.warm(skewed, lambda: None)
    # The matching runtime passes through to the build path.
    ok_key = ("k", artifacts.runtime_tag())
    (prog, _wait) = cache.get(
        ok_key, lambda: jax.jit(lambda: jnp.zeros(1)).lower())
    assert prog is not None


def test_store_from_env_lever(monkeypatch, tmp_path):
    monkeypatch.delenv("MASTIC_ARTIFACT_DIR", raising=False)
    assert artifacts.store_from_env() is None
    monkeypatch.setenv("MASTIC_ARTIFACT_DIR", str(tmp_path / "s"))
    store = artifacts.store_from_env()
    assert store is not None
    # Singleton per path: the in-memory memo is process-wide.
    assert artifacts.store_from_env() is store


# -- schema + key plumbing --------------------------------------------


def test_artifacts_extra_block_schema():
    from mastic_tpu.obs import schema

    good = {"artifacts": {"store": None, "hits": 0,
                          "inline_compiles": 2}}
    assert schema.validate_extra(good) == []
    assert schema.validate_extra(
        {"artifacts": {"store": "/s", "hits": 1,
                       "inline_compiles": 0}}) == []
    bad = schema.validate_extra({"artifacts": {"hits": 1}})
    assert any("missing" in p for p in bad)
    bad = schema.validate_extra(
        {"artifacts": {"store": 7, "hits": 0, "inline_compiles": 0}})
    assert any("artifacts.store" in p for p in bad)


def test_planted_trajectory_is_deterministic():
    paths = artifacts.planted_paths(4, 2)
    assert paths == artifacts.planted_paths(4, 2)
    levels = list(artifacts.trajectory(4, paths))
    assert [lvl for (lvl, _p) in levels] == [0, 1, 2, 3]
    # Steady-2: every frontier after level 0 is the 2 ancestors'
    # children (width 4).
    assert all(len(p) == 4 for (lvl, p) in levels[1:])
    grow = list(artifacts.growth_trajectory(4, 8))
    assert [len(p) for (_lvl, p) in grow] == [2, 4, 8]


def test_runner_keys_carry_runtime_and_family():
    """Every program key a runner builds ends with (runtime tag,
    family id) — the store namespace AND the in-process refusal
    hook."""
    from mastic_tpu.backend.mastic_jax import BatchedMastic
    from mastic_tpu.mastic import MasticCount

    m = MasticCount(4)
    bm = BatchedMastic(m)
    baker = artifacts.make_baker(bm, b"ctx A")
    plan = baker._plan(((False,), (True,)), 0)
    tag = artifacts.runtime_tag()
    fam = artifacts.family_id(bm, b"ctx A")
    for key in (baker._eval_key(8, plan), baker._agg_key(8, 4),
                baker._wc_key(8, 0), baker._rk_key(8)):
        assert key[-2:] == (tag, fam)
    # A different ctx is a different family: its programs can never
    # be served to this collection.
    assert artifacts.family_id(bm, b"ctx B") != fam
    assert artifacts.family_id(
        BatchedMastic(MasticCount(8)), b"ctx A") != fam


def test_struct_signatures_match_concrete_args():
    """The bake-side abstract signatures must mirror the runners'
    concrete arrays exactly — a drifted struct would bake programs
    the runtime cache can never hit."""
    from mastic_tpu.backend.mastic_jax import BatchedMastic
    from mastic_tpu.mastic import MasticCount

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import _synth_batch

    m = MasticCount(4)
    bm = BatchedMastic(m)
    baker = artifacts.make_baker(bm, b"sig")
    rows = 8
    batch = _synth_batch(bm, rows, np.random.default_rng(0))
    structs = baker._batch_structs(rows)
    concrete = jax.tree_util.tree_map(
        lambda x: (x.shape, str(x.dtype)), batch)
    abstract = jax.tree_util.tree_map(
        lambda s: (s.shape, str(s.dtype)), structs)
    assert concrete == abstract
    plan = baker._plan(((False,), (True,)), 0)
    ev = baker._eval_structs(rows, plan)
    assert ev[1].w.shape == (rows, 4, baker.width,
                             m.vidpf.VALUE_LEN, bm.spec.num_limbs)
    (erk, crk) = jax.eval_shape(
        lambda nn: bm.vidpf.roundkeys(b"sig", nn),
        jax.ShapeDtypeStruct((rows, 16), jnp.uint8))
    assert ev[4].shape == erk.shape and ev[5].shape == crk.shape


# -- slow tier: the real round programs -------------------------------


def _planted_run(m, ctx, chunk_size, mesh=None, reports=None):
    from mastic_tpu.drivers.heavy_hitters import (
        HeavyHittersRun, get_reports_from_measurements)

    bits = m.vidpf.BITS
    paths = artifacts.planted_paths(bits, 2)
    if reports is None:
        meas = [(tuple(paths[i % 2]), True) for i in range(10)]
        reports = get_reports_from_measurements(m, ctx, meas)
    run = HeavyHittersRun(m, ctx, {"default": 1}, reports,
                          verify_key=bytes(range(m.VERIFY_KEY_SIZE)),
                          chunk_size=chunk_size, mesh=mesh)
    while run.step():
        pass
    return (run, reports)


def _assert_identical(a, b):
    assert a.result() == b.result()
    assert len(a.metrics) == len(b.metrics)
    for (ma, mb) in zip(a.metrics, b.metrics):
        assert (ma.accepted, ma.rejected_eval_proof,
                ma.rejected_weight_check, ma.rejected_joint_rand,
                ma.xof_fallbacks) == \
            (mb.accepted, mb.rejected_eval_proof,
             mb.rejected_weight_check, mb.rejected_joint_rand,
             mb.xof_fallbacks)


@pytest.mark.slow
@pytest.mark.parametrize("mesh_n", [0, 2])
def test_round_trip_bit_identity_full_rounds(tmp_path, monkeypatch,
                                             mesh_n):
    """Traced reference run vs the same collection served purely from
    a baked store (fresh store objects, so every load comes from
    disk through all three gates): identical hitters and per-round
    counters, single-device and mesh=2.  (The fresh-SUBPROCESS
    variant is `make artifacts-smoke`.)"""
    from mastic_tpu.backend.mastic_jax import BatchedMastic
    from mastic_tpu.mastic import MasticCount

    monkeypatch.delenv("MASTIC_ARTIFACT_DIR", raising=False)
    mesh = None
    if mesh_n:
        from mastic_tpu.parallel import make_mesh
        mesh = make_mesh(mesh_n, nodes_axis=1)
    m = MasticCount(3)
    ctx = b"artifact rt"
    (ref, reports) = _planted_run(m, ctx, 4, mesh=mesh)
    assert ref.runner.programs.stats["inline_compiles"] > 0

    store = artifacts.default_store(str(tmp_path / f"s{mesh_n}"))
    baker = artifacts.make_baker(BatchedMastic(m), ctx, mesh=mesh)
    rows = ref.runner._device_rows()
    stats = artifacts.bake_trajectory(
        baker, store, rows,
        artifacts.trajectory(3, artifacts.planted_paths(3, 2)),
        with_stablehlo=False)
    assert stats["compiled"] > 0
    # Drop the in-memory memo so loads come from disk, then run the
    # same collection against the store only.
    artifacts._stores.pop(store.path, None)
    monkeypatch.setenv("MASTIC_ARTIFACT_DIR", store.path)
    (warm, _r) = _planted_run(m, ctx, 4, mesh=mesh, reports=reports)
    warm_stats = warm.runner.programs.stats
    assert warm_stats["inline_compiles"] == 0, warm_stats
    assert warm_stats["artifact_hits"] > 0
    _assert_identical(ref, warm)
    for mx in warm.metrics:
        assert mx.extra["artifacts"]["inline_compiles"] == 0
        assert mx.extra["artifacts"]["store"] == store.path


@pytest.mark.slow
def test_attribute_round_rides_artifact_tier(tmp_path, monkeypatch):
    """ISSUE 10 satellite: the attribute-metrics round program (a
    bare per-(ctx, agg_param) jit before r15) rides the AOT tier —
    baked via artifacts.bake_attribute_round, loaded through all
    three gates, zero inline compiles and a bit-identical aggregate
    on the warm path."""
    from mastic_tpu.backend.mastic_jax import BatchedMastic
    from mastic_tpu.drivers.attribute_metrics import \
        aggregate_by_attribute
    from mastic_tpu.drivers.heavy_hitters import \
        get_reports_from_measurements
    from mastic_tpu.mastic import MasticCount

    monkeypatch.delenv("MASTIC_ARTIFACT_DIR", raising=False)
    m = MasticCount(4)   # small tree keeps the from-root compile cheap
    ctx = b"attr artifact"
    attrs = ["checkout.html", "landing.html"]  # distinct 4-bit hashes
    from mastic_tpu.drivers.attribute_metrics import hash_attribute

    alpha = hash_attribute(m, attrs[0])
    val = int("".join("1" if b else "0" for b in alpha), 2)
    meas = [(m.vidpf.test_index_from_int(v, 4), True)
            for v in (val, val, 0)]
    reports = get_reports_from_measurements(m, ctx, meas)
    vk = bytes(range(m.VERIFY_KEY_SIZE))
    mx_ref: list = []
    ref = aggregate_by_attribute(m, ctx, attrs, reports,
                                 verify_key=vk, metrics_out=mx_ref)
    assert mx_ref[0].extra["artifacts"]["inline_compiles"] > 0

    store = artifacts.default_store(str(tmp_path / "attr"))
    baker = artifacts.make_baker(BatchedMastic(m), ctx)
    stats = artifacts.bake_attribute_round(
        baker, store, len(reports), attrs, with_stablehlo=False)
    assert stats["compiled"] == 1
    # Re-baking is a skip, not a recompile.
    assert artifacts.bake_attribute_round(
        baker, store, len(reports), attrs,
        with_stablehlo=False)["skipped"] == 1
    # Drop the in-memory memo so the load comes from disk through
    # the digest/runtime/probe gates.
    artifacts._stores.pop(store.path, None)
    monkeypatch.setenv("MASTIC_ARTIFACT_DIR", store.path)
    mx_warm: list = []
    warm = aggregate_by_attribute(m, ctx, attrs, reports,
                                  verify_key=vk, metrics_out=mx_warm)
    assert warm == ref
    art = mx_warm[0].extra["artifacts"]
    assert art["inline_compiles"] == 0, art
    assert art["hits"] >= 1
    assert art["store"] == store.path


def test_save_refuses_donating_executable():
    """The memory-safety guard behind the donation-free bake rule: a
    deserialized executable with input-output aliasing double-frees
    its donated buffers on this fabric (found by the artifacts-smoke
    gate), so sealing one is refused outright."""
    f = jax.jit(lambda a, b: a + b, donate_argnums=(0,))
    compiled = f.lower(jnp.ones(4), jnp.ones(4)).compile()
    import tempfile

    store = artifacts.ArtifactStore(tempfile.mkdtemp())
    with pytest.raises(ValueError, match="donated"):
        store.save(("k", artifacts.runtime_tag()), compiled)


@pytest.mark.slow
def test_bake_trajectory_covers_growth(tmp_path, monkeypatch):
    """A store baked over the growth trajectory serves a run whose
    width actually grows — the grow rounds load instead of paying the
    inline compile the runtime predictor deliberately skips."""
    from mastic_tpu.backend.mastic_jax import BatchedMastic
    from mastic_tpu.mastic import MasticCount

    monkeypatch.delenv("MASTIC_ARTIFACT_DIR", raising=False)
    m = MasticCount(4)
    ctx = b"grow bake"
    bm = BatchedMastic(m)
    store = artifacts.default_store(str(tmp_path / "grow"))
    baker = artifacts.make_baker(bm, ctx)
    stats = artifacts.bake_trajectory(
        baker, store, 4, artifacts.growth_trajectory(4, 16),
        with_stablehlo=False)
    assert stats["compiled"] > 0
    assert baker.width == 16  # the walk grew the padded width
    widths = {k[3] for k in store.keys() if k[0] == "eval"}
    assert widths >= {8, 16}

    # An all-survive run (threshold 0 keeps everything) over the
    # same family: the width-growth round — which the runtime
    # predictor deliberately never warms — loads from the store
    # instead of compiling inline.
    from mastic_tpu.drivers.heavy_hitters import (
        HeavyHittersRun, get_reports_from_measurements)

    artifacts._stores.pop(store.path, None)
    monkeypatch.setenv("MASTIC_ARTIFACT_DIR", store.path)
    meas = [(m.vidpf.test_index_from_int(v, 4), True)
            for v in range(8)]
    reports = get_reports_from_measurements(m, ctx, meas)
    run = HeavyHittersRun(m, ctx, {"default": 0}, reports,
                          verify_key=bytes(range(m.VERIFY_KEY_SIZE)),
                          chunk_size=4)
    while run.step():
        pass
    stats = run.runner.programs.stats
    assert run.runner.width == 16
    assert stats["inline_compiles"] == 0, stats
    assert sorted(len(r) for r in run.result()) == [4] * 16


@pytest.mark.slow
def test_kill9_resume_with_warm_store(tmp_path):
    """Crash-resume composes with the artifact store: a serve.py
    process killed mid-run resumes from its snapshot with
    --artifact-dir armed and finishes bit-identically to an unfaulted
    run — the restart path is exactly the cold start the store
    exists to kill."""
    import signal
    import time as _time

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MASTIC_ARTIFACT_DIR", None)
    snap = str(tmp_path / "svc.snap")
    store = str(tmp_path / "store")

    def serve(extra, timeout=900, check=True, **kw):
        proc = subprocess.run(
            [sys.executable, os.path.join(root, "tools", "serve.py"),
             "--bits", "2", "--reports", "6", "--page-size", "3",
             "--seed", "7", "--snapshot", snap] + extra,
            capture_output=True, text=True, timeout=timeout, env=env,
            **kw)
        if check:
            assert proc.returncode == 0, proc.stderr[-3000:]
        return proc

    # Reference: unfaulted run (also the trajectory the bake needs —
    # bake the store from a bake.py family walk for the same config).
    ref = serve([])
    ref_line = json.loads(ref.stdout.strip().splitlines()[-1])

    bake = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "bake.py"),
         "--out", store, "--bits", "2", "--rows", "6",
         "--hitters", "1,2,3", "--ctx", "serve count",
         "--no-stablehlo"],
        capture_output=True, text=True, timeout=900, env=env)
    assert bake.returncode == 0, bake.stderr[-3000:]

    # Kill -9 a fresh run mid-flight, then resume WITH the store.
    os.unlink(snap)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(root, "tools", "serve.py"),
         "--bits", "2", "--reports", "6", "--page-size", "3",
         "--seed", "7", "--snapshot", snap,
         "--artifact-dir", store],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        cwd=root, env=env)
    deadline = _time.time() + 600
    while not os.path.exists(snap) and _time.time() < deadline:
        _time.sleep(0.25)
    assert os.path.exists(snap), "no snapshot before the kill"
    proc.send_signal(signal.SIGKILL)
    proc.wait()

    resumed = serve(["--resume", "--artifact-dir", store])
    res_line = json.loads(resumed.stdout.strip().splitlines()[-1])
    assert res_line["ok"]
    # The count tenant's epoch results match the unfaulted run's.
    assert res_line["results"]["count"] == \
        ref_line["results"]["count"]
