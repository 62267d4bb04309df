"""Mesh-sharded production rounds (ISSUE 5): the pipelined chunked
executor over a report-axis device mesh must be bit-identical to the
serial single-device path — aggregates, accept masks, rejection
counters, quarantine-union (fallback) masks and checkpoint state
arrays — across 1/2/3-chunk stores including the padded tail and
UNEVEN shards (chunk_size not a multiple of the mesh), with
`("serial", "mesh")` gone as a degrade reason and steady-state rounds
compiling zero inline on the mesh.

Fast tier: envelope/padding/key units plus the per-device allocation
parity (`make multichip` runs these and tools/multichip.py — the real
8-device pipelined proof run).  The full mesh={1,2,8} x chunk-layout
matrix, growth-under-mesh, attribute-metrics and checkpoint-resume
compositions are slow tier (each is a pair of full collection runs).
"""

import numpy as np
import pytest

import jax

from mastic_tpu.backend.mastic_jax import BatchedMastic
from mastic_tpu.common import gen_rand
from mastic_tpu.drivers.chunked import (HostReportStore,
                                        _carry_to_device, _pad_rows,
                                        memory_envelope)
from mastic_tpu.drivers.heavy_hitters import (
    HeavyHittersRun, get_reports_from_measurements)
from mastic_tpu.mastic import MasticCount, MasticHistogram
from mastic_tpu.parallel import make_mesh, place_reports

CTX = b"mesh pipeline test"


def _reports(m):
    """10 reports over a 3-bit tree, one tampered (eval-proof reject
    at report 6): hitters {0, 6, 7} at threshold 2 with a steady
    one-child-per-parent frontier from level 1 — the AOT predictor's
    fixed point, so the zero-inline-compile claim is assertable."""
    meas = [(m.vidpf.test_index_from_int(v, 3), True)
            for v in (0, 0, 0, 7, 7, 7, 3, 1, 6, 6)]
    reports = get_reports_from_measurements(m, CTX, meas)
    (nonce, ps, shares) = reports[6]
    (key, proof, seed, part) = shares[0]
    reports[6] = (nonce, ps, [
        (bytes([key[0] ^ 1]) + key[1:], proof, seed, part), shares[1]])
    return reports


def _run_all(run):
    while run.step():
        pass
    return run


def _assert_bit_identical(ser, mesh_run):
    assert ser.result() == mesh_run.result()
    assert len(ser.metrics) == len(mesh_run.metrics)
    for (a, b) in zip(ser.metrics, mesh_run.metrics):
        assert (a.accepted, a.rejected_eval_proof,
                a.rejected_weight_check, a.rejected_joint_rand,
                a.rejected_fallback, a.xof_fallbacks,
                a.node_evals) == \
            (b.accepted, b.rejected_eval_proof,
             b.rejected_weight_check, b.rejected_joint_rand,
             b.rejected_fallback, b.xof_fallbacks, b.node_evals)
    # Quarantine-union (scalar-fallback) masks agree lane for lane.
    assert np.array_equal(ser.runner.fallback, mesh_run.runner.fallback)
    # Checkpoint state arrays (every chunk's both carries) bit-equal.
    (sa, sb) = (ser.runner.state_arrays(),
                mesh_run.runner.state_arrays())
    assert sorted(sa) == sorted(sb)
    for k in sa:
        assert np.array_equal(sa[k], sb[k]), f"state array {k}"


# -- fast tier: units + per-device allocation parity -----------------


def test_envelope_per_shard_fields():
    """Per-shard residency = device term / report shards, priced at
    the padded device rows (uneven chunks pad up to the shard
    multiple)."""
    m = MasticCount(3)
    bm = BatchedMastic(m)
    base = memory_envelope(bm, 8, 8, 16)
    env = memory_envelope(bm, 8, 8, 16, n_device_shards=4)
    assert base["report_shards"] == 1
    assert base["device_bytes_per_chunk_per_shard"] == \
        base["device_bytes_per_chunk"]
    assert env["report_shards"] == 4
    assert env["device_rows_per_chunk"] == 8
    assert env["rows_per_shard"] == 2
    assert env["device_bytes_per_chunk_per_shard"] == \
        env["device_bytes_per_chunk"] // 4
    assert env["device_bytes_per_chunk_pipelined_per_shard"] == \
        env["device_bytes_per_chunk_pipelined"] // 4
    assert env["max_chunk_size_at_width_sharded"] == \
        4 * env["max_chunk_size_at_width"]
    # Uneven: chunk 6 over 4 shards pads to 8 device rows, and the
    # per-shard price covers the padded rows (2 each), not 6/4.
    uneven = memory_envelope(bm, 6, 8, 16, n_device_shards=4)
    assert uneven["device_rows_per_chunk"] == 8
    assert uneven["rows_per_shard"] == 2
    assert uneven["device_bytes_per_chunk_per_shard"] == \
        env["device_bytes_per_chunk_per_shard"]


def test_envelope_prices_output_carries_without_donation(monkeypatch):
    """Without carry donation (the compile cache on) a round holds its
    output carries beside its inputs: the round, peak and max terms
    price that second copy, the resident terms stay what is
    allocated, and the guards refuse on the round's footprint."""
    from mastic_tpu.drivers import chunked

    bm = BatchedMastic(MasticCount(3))
    donated = memory_envelope(bm, 8, 8, 16, n_device_shards=2)
    assert donated["carry_copies"] == 1
    assert donated["device_round_bytes_per_chunk"] == \
        donated["device_bytes_per_chunk"]
    monkeypatch.setattr(chunked, "carries_donated", lambda: False)
    env = memory_envelope(bm, 8, 8, 16, n_device_shards=2)
    carry = env["per_report_bytes"]["carry"]
    assert env["carry_copies"] == 2
    assert env["device_bytes_per_chunk"] == \
        donated["device_bytes_per_chunk"]
    assert env["device_round_bytes_per_chunk"] == \
        env["device_bytes_per_chunk"] + 8 * carry
    assert env["device_round_bytes_per_chunk_per_shard"] == \
        env["device_round_bytes_per_chunk"] // 2
    assert env["device_peak_bytes_per_chunk"] == \
        donated["device_peak_bytes_per_chunk"] + 8 * carry
    assert env["device_peak_bytes_per_chunk_pipelined"] == \
        donated["device_peak_bytes_per_chunk_pipelined"] + 8 * carry
    per_round = env["device_round_bytes_per_chunk"] // 8
    assert env["max_chunk_size_at_width"] == \
        env["device_budget_bytes"] // per_round
    assert env["max_chunk_size_at_width"] < \
        donated["max_chunk_size_at_width"]
    assert chunked.round_peak_bytes(bm, 4, 4, 8, 1000, 2,
                                    carry_bytes=600) == \
        chunked.round_peak_bytes(bm, 4, 4, 8, 1600, 2)
    # A budget that holds the resident chunk but not the round's
    # second carry copy is refused.
    monkeypatch.setenv("MASTIC_DEVICE_BUDGET_BYTES",
                       str(env["device_bytes_per_chunk_per_shard"]))
    with pytest.raises(ValueError, match="feasible chunk_size"):
        chunked.check_envelope(bm, 8, 8, 16, n_device_shards=2)
    with pytest.raises(ValueError, match="output carries"):
        chunked.check_round_peak(bm, 4, 4, 8, 16, 0, 2,
                                 carry_bytes=2 ** 40)


def test_pad_rows_rule_and_device_chunk():
    """Device-tile padding repeats row 0 (the host_slice rule), and
    the live mask excludes every padded lane — dead lanes compute the
    same garbage serial and meshed, so trimmed carries stay
    bit-identical."""
    a = np.arange(6).reshape(3, 2)
    padded = _pad_rows(a, 5)
    assert padded.shape == (5, 2)
    assert np.array_equal(padded[3], a[0])
    assert np.array_equal(padded[4], a[0])
    assert _pad_rows(a, 3) is a  # no-op when nothing to pad

    m = MasticCount(3)
    bm = BatchedMastic(m)
    reports = _reports(m)[:5]
    store = HostReportStore.from_batch(bm.marshal_reports(reports), 4)
    # Tail chunk: 1 live row, chunk_size 4, device rows 8 (mesh of 8).
    (batch, live) = store.device_chunk(1, rows=8)
    assert batch.nonces.shape[0] == 8
    assert live.tolist() == [True] + [False] * 7
    row0 = np.asarray(batch.nonces[0])
    for lane in range(1, 8):
        assert np.array_equal(np.asarray(batch.nonces[lane]), row0)


def test_program_keys_carry_mesh_shape():
    """The AOT ProgramCache keys include the mesh's report-axis size
    (and the padded device rows), so serial and sharded programs can
    never collide — the invalidation-free growth argument extended
    one axis up."""
    m = MasticCount(3)
    bm = BatchedMastic(m)
    reports = _reports(m)
    store = HostReportStore.from_batch(bm.marshal_reports(reports), 4)
    mesh = make_mesh(8, nodes_axis=1)
    run = HeavyHittersRun(m, CTX, {"default": 2}, reports,
                          verify_key=gen_rand(m.VERIFY_KEY_SIZE),
                          store=store, mesh=mesh)
    runner = run.runner
    assert runner.mesh is mesh
    assert runner._report_shards() == 8
    assert runner._device_rows() == 8  # chunk 4 padded to the multiple
    plan = runner._plan(((False,), (True,)), 0)
    assert runner._eval_key(8, plan)[:3] == ("eval", 8, 8)
    assert runner._agg_key(8, 4)[:3] == ("agg", 8, 8)
    # Serial twin: shards=0 in the key, device rows = chunk size.
    ser = HeavyHittersRun(m, CTX, {"default": 2}, reports,
                          verify_key=gen_rand(m.VERIFY_KEY_SIZE),
                          chunk_size=4)
    assert ser.runner._eval_key(4, plan)[:3] == ("eval", 4, 0)
    assert ser.runner._device_rows() == 4


def test_envelope_per_shard_parity_real_allocations():
    """test_memory_envelope_guard-style parity, one axis up: the
    analytic per-shard price equals what ONE device actually holds
    when a chunk's state is placed exactly as the pipelined stage
    phase places it (joint-rand family, padded tail chunk)."""
    m = MasticHistogram(4, 4, 2)
    bm = BatchedMastic(m)
    meas = [(m.vidpf.test_index_from_int(v % 16, 4), v % 4)
            for v in range(6)]
    reports = get_reports_from_measurements(m, CTX, meas)
    store = HostReportStore.from_batch(bm.marshal_reports(reports), 4)
    mesh = make_mesh(2, nodes_axis=1)
    run = HeavyHittersRun(m, CTX, {"default": 1}, reports,
                          verify_key=gen_rand(m.VERIFY_KEY_SIZE),
                          store=store, mesh=mesh)
    runner = run.runner
    env = memory_envelope(bm, 4, runner.width, 6, n_device_shards=2)
    assert env["device_rows_per_chunk"] == runner._device_rows() == 4

    for chunk in range(store.num_chunks):
        cs = runner.chunks[chunk]
        (batch, _live) = store.device_chunk(chunk, rows=4)
        dev_c0 = _carry_to_device(cs.carries[0], 4)
        dev_c1 = _carry_to_device(cs.carries[1], 4)
        ext_rk = jax.numpy.asarray(_pad_rows(cs.ext_rk, 4))
        conv_rk = jax.numpy.asarray(_pad_rows(cs.conv_rk, 4))
        placed = place_reports(
            mesh, (batch, dev_c0, dev_c1, ext_rk, conv_rk))
        dev0 = sum(x.addressable_shards[0].data.nbytes
                   for x in jax.tree_util.tree_leaves(placed))
        assert dev0 == env["device_bytes_per_chunk_per_shard"], \
            f"chunk {chunk}"


# -- slow tier: full bit-identity matrix -----------------------------


@pytest.mark.slow
@pytest.mark.parametrize("mesh_n,chunk_size,num_chunks", [
    (1, 4, 3),    # 1-device mesh == serial layout, collective-free
    (2, 5, 2),    # even shards, no tail padding
    (2, 4, 3),    # padded tail chunk (2 live of 4)
    (8, 4, 3),    # UNEVEN: chunk 4 pads to 8 device rows per chunk
    (8, 12, 1),   # single chunk (serial fallback named, still sharded)
], ids=["mesh1-3chunk", "mesh2-2chunk", "mesh2-3chunk-tail",
        "mesh8-uneven", "mesh8-1chunk"])
def test_mesh_pipelined_matches_serial(monkeypatch, mesh_n,
                                       chunk_size, num_chunks):
    monkeypatch.setenv("MASTIC_PIPELINE", "1")
    m = MasticCount(3)
    reports = _reports(m)
    vk = gen_rand(m.VERIFY_KEY_SIZE)
    thresholds = {"default": 2}

    ser = _run_all(HeavyHittersRun(m, CTX, thresholds, reports,
                                   verify_key=vk,
                                   chunk_size=chunk_size))
    mesh = make_mesh(mesh_n, nodes_axis=1)
    meshed = _run_all(HeavyHittersRun(m, CTX, thresholds, reports,
                                      verify_key=vk,
                                      chunk_size=chunk_size,
                                      mesh=mesh))
    assert meshed.runner.store.num_chunks == num_chunks
    _assert_bit_identical(ser, meshed)

    pipes = [mx.extra["pipeline"] for mx in meshed.metrics]
    if num_chunks > 1:
        # The tentpole: mesh rounds PIPELINE — ("serial", "mesh") is
        # gone as a degrade reason.
        assert all(p["mode"] == "pipelined" for p in pipes)
        assert all(p["fallback"] is None for p in pipes)
    else:
        assert all(p["fallback"] == "single-chunk" for p in pipes)
    # Steady-state rounds after the first pay zero inline compile on
    # the mesh (sharded AOT warm predicted them).
    for p in pipes[1:]:
        assert p["compile_inline_ms"] == 0.0
        assert p["aot"]["predicted"]
    for mx in meshed.metrics:
        blk = mx.extra["mesh"]
        assert blk["report_shards"] == mesh_n
        assert blk["device_rows_per_chunk"] % mesh_n == 0
        if mesh_n > 1:
            assert blk["psum_bytes_per_round"] > 0
    # Per-shard rate honesty on every chunk record (live AND padded).
    for rec in meshed.metrics[-1].extra["chunks"]:
        assert rec["node_evals_per_sec_per_shard"] == pytest.approx(
            rec["node_evals_per_sec"] / mesh_n, rel=0.01)
        assert rec["node_evals_per_sec_padded_per_shard"] == \
            pytest.approx(rec["node_evals_per_sec_padded"] / mesh_n,
                          rel=0.01)


@pytest.mark.slow
def test_grow_under_mesh(monkeypatch):
    """Width growth under a mesh: the grown carries re-place with the
    same report sharding and the shape+mesh-keyed programs recompile
    for the new width — bit-identical to the serial grown run (the
    satellite regression for heavy_hitters/_grow threading)."""
    monkeypatch.setenv("MASTIC_PIPELINE", "1")
    m = MasticCount(5)
    meas = [(m.vidpf.test_index_from_int(v * 4, 5), True)
            for v in range(8)]
    reports = get_reports_from_measurements(m, CTX, meas)
    vk = gen_rand(m.VERIFY_KEY_SIZE)

    ser = _run_all(HeavyHittersRun(m, CTX, {"default": 1}, reports,
                                   verify_key=vk, chunk_size=4))
    mesh = make_mesh(2, nodes_axis=1)
    meshed = _run_all(HeavyHittersRun(m, CTX, {"default": 1}, reports,
                                      verify_key=vk, chunk_size=4,
                                      mesh=mesh))
    assert ser.runner.width == meshed.runner.width == 16
    _assert_bit_identical(ser, meshed)
    # Every compiled eval program key carries the mesh shape next to
    # the width it closed over.
    eval_keys = [k for k in meshed.runner.programs._programs
                 if k[0] == "eval"]
    assert eval_keys and all(k[2] == 2 for k in eval_keys)
    assert {k[3] for k in eval_keys} >= {8, 16}


@pytest.mark.slow
def test_checkpoint_resume_under_mesh(monkeypatch):
    """Kill after level 0, restore WITH the mesh, finish: identical to
    the uninterrupted serial run (from_bytes threads the mesh into the
    restored chunked runner)."""
    monkeypatch.setenv("MASTIC_PIPELINE", "1")
    m = MasticCount(3)
    reports = _reports(m)
    vk = gen_rand(m.VERIFY_KEY_SIZE)
    thresholds = {"default": 2}

    ref = _run_all(HeavyHittersRun(m, CTX, thresholds, reports,
                                   verify_key=vk, chunk_size=4))
    mesh = make_mesh(8, nodes_axis=1)
    victim = HeavyHittersRun(m, CTX, thresholds, reports,
                             verify_key=vk, chunk_size=4, mesh=mesh)
    victim.step()
    blob = victim.to_bytes()
    del victim

    resumed = HeavyHittersRun.from_bytes(m, CTX, thresholds, reports,
                                         vk, blob, mesh=mesh)
    assert resumed.level == 1
    assert resumed.runner.mesh is mesh
    _run_all(resumed)
    assert resumed.result() == ref.result()
    (sa, sb) = (ref.runner.state_arrays(),
                resumed.runner.state_arrays())
    for k in sa:
        assert np.array_equal(sa[k], sb[k]), k


@pytest.mark.slow
def test_attribute_round_mesh(monkeypatch):
    """aggregate_by_attribute over a mesh, uneven chunk (5 reports,
    chunk 3, 2 shards): padded+masked lanes never reach the psum —
    result identical to the whole-batch single-device round."""
    from mastic_tpu.drivers.attribute_metrics import (
        aggregate_by_attribute, hash_attribute)

    monkeypatch.setenv("MASTIC_PIPELINE", "1")
    m = MasticCount(8)
    attrs = ["checkout", "landing"]
    meas = [(hash_attribute(m, "checkout"), True)] * 3 + \
        [(hash_attribute(m, "landing"), True)] * 2
    reports = get_reports_from_measurements(m, CTX, meas)
    vk = gen_rand(m.VERIFY_KEY_SIZE)

    whole = aggregate_by_attribute(m, CTX, attrs, reports,
                                   verify_key=vk)
    out_m: list = []
    mesh = make_mesh(2, nodes_axis=1)
    meshed = aggregate_by_attribute(m, CTX, attrs, reports,
                                    verify_key=vk, chunk_size=3,
                                    mesh=mesh, metrics_out=out_m)
    assert whole == meshed == [("checkout", 3), ("landing", 2)]
    blk = out_m[0].extra["mesh"]
    assert blk["report_shards"] == 2
    assert blk["psum_bytes_per_round"] > 0
    assert out_m[0].extra["pipeline"]["mode"] == "pipelined"
