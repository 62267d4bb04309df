"""Chunked at-scale execution: batched client shard bit-exact vs the
scalar client, and the report-chunked incremental runner bit-identical
to the unchunked one (same aggregates, same verdicts, same
checkpoints)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mastic_tpu.backend.mastic_jax import BatchedMastic
from mastic_tpu.common import gen_rand
from mastic_tpu.drivers.heavy_hitters import (
    HeavyHittersRun, get_reports_from_measurements)
from mastic_tpu.mastic import MasticCount, MasticHistogram

pytestmark = pytest.mark.slow

CTX = b"chunk test"


def _shard_inputs(m, bm, measurements, seed=7):
    rng = np.random.default_rng(seed)
    num = len(measurements)
    nonces = rng.integers(0, 256, (num, 16), dtype=np.uint8)
    rand = rng.integers(0, 256, (num, m.RAND_SIZE), dtype=np.uint8)
    (alphas, betas) = bm.encode_measurements(measurements)
    return (nonces, rand, alphas, betas)


@pytest.mark.parametrize("inst,weight", [
    (MasticCount(4), True),
    (MasticHistogram(4, 4, 2), 2),   # joint-rand family
], ids=["count", "histogram-jr"])
def test_shard_device_matches_scalar(inst, weight) -> None:
    m = inst
    bm = BatchedMastic(m)
    meas = [(m.vidpf.test_index_from_int(v % 16, 4), weight)
            for v in (0, 3, 9, 9, 15)]
    (nonces, rand, alphas, betas) = _shard_inputs(m, bm, meas)

    (batch, ok) = jax.jit(
        lambda a, b, n, r: bm.shard_device(CTX, a, b, n, r))(
        jnp.asarray(alphas), jnp.asarray(betas),
        jnp.asarray(nonces), jnp.asarray(rand))
    assert bool(np.all(np.asarray(ok)))

    for r in range(len(meas)):
        (cws, shares) = m.shard(CTX, meas[r], bytes(nonces[r]),
                                bytes(rand[r]))
        got_cws = bm.vidpf.cws_to_host(batch.cws, r)
        for (got, want) in zip(got_cws, cws):
            assert got[0] == want[0]            # seed cw
            assert got[1] == list(want[1])      # ctrl cw
            assert [x.int() for x in got[2]] == \
                [x.int() for x in want[2]]      # payload cw
            assert got[3] == want[3]            # proof cw
        assert np.asarray(batch.keys[r, 0]).tobytes() == shares[0][0]
        assert np.asarray(batch.keys[r, 1]).tobytes() == shares[1][0]
        got_proof = [bm.spec.limbs_to_int(np.asarray(
            batch.leader_proofs[r, j]))
            for j in range(m.flp.PROOF_LEN)]
        assert got_proof == [x.int() for x in shares[0][1]]
        assert np.asarray(batch.helper_seeds[r]).tobytes() == \
            shares[1][2]
        if m.flp.JOINT_RAND_LEN > 0:
            assert np.asarray(batch.leader_seeds[r]).tobytes() == \
                shares[0][2]
            assert np.asarray(
                batch.peer_parts[0][r]).tobytes() == shares[0][3]
            assert np.asarray(
                batch.peer_parts[1][r]).tobytes() == shares[1][3]


def _tampered_reports(m):
    meas = [((bool(v >> 2 & 1), bool(v >> 1 & 1), bool(v & 1)), True)
            for v in [0, 0, 0, 5, 5, 5, 3, 1, 6, 6]]
    reports = get_reports_from_measurements(m, CTX, meas)
    # Report 4: VIDPF key tamper -> fails the eval-proof check.
    (nonce, ps, shares) = reports[4]
    (key, proof, seed, part) = shares[0]
    reports[4] = (nonce, ps, [
        (bytes([key[0] ^ 1]) + key[1:], proof, seed, part), shares[1]])
    # Report 7: FLP proof-share tamper -> passes the eval proof,
    # fails the weight check (attribution must survive chunking).
    (nonce, ps, shares) = reports[7]
    (key, proof, seed, part) = shares[0]
    bad_proof = [proof[0] + m.field(1)] + proof[1:]
    reports[7] = (nonce, ps, [(key, bad_proof, seed, part), shares[1]])
    return reports


def test_chunked_matches_unchunked() -> None:
    m = MasticCount(3)
    reports = _tampered_reports(m)
    vk = gen_rand(m.VERIFY_KEY_SIZE)
    thresholds = {"default": 2}

    runs = [
        HeavyHittersRun(m, CTX, thresholds, reports, verify_key=vk),
        HeavyHittersRun(m, CTX, thresholds, reports, verify_key=vk,
                        chunk_size=4),   # 10 reports -> 4+4+2 (pad)
    ]
    while True:
        more = [run.step() for run in runs]
        assert more[0] == more[1]
        for (m0, m1) in zip(runs[0].metrics, runs[1].metrics):
            assert m0.accepted == m1.accepted
            assert m0.rejected_eval_proof == m1.rejected_eval_proof
            assert m0.rejected_weight_check == m1.rejected_weight_check
            assert m0.rejected_joint_rand == m1.rejected_joint_rand
            assert m0.node_evals == m1.node_evals
        if not more[0]:
            break
    # Level 0 attributes one reject to each check, in both runners.
    assert runs[0].metrics[0].rejected_eval_proof == 1
    assert runs[0].metrics[0].rejected_weight_check == 1
    assert runs[0].result() == runs[1].result()
    assert runs[1].result()  # nonempty: the honest hitters survive

    # Per-chunk metrics and memory accounting are present.
    extra = runs[1].metrics[-1].extra
    assert len(extra["chunks"]) == 3
    assert sum(c["reports"] for c in extra["chunks"]) == len(reports)
    mem = extra["memory"]
    assert mem["num_chunks"] == 3 and mem["chunk_size"] == 4
    assert mem["device_bytes_per_chunk"] < mem["host_bytes_total"]


def test_chunked_checkpoint_roundtrip() -> None:
    m = MasticCount(3)
    reports = _tampered_reports(m)
    vk = gen_rand(m.VERIFY_KEY_SIZE)
    thresholds = {"default": 2}

    ref = HeavyHittersRun(m, CTX, thresholds, reports, verify_key=vk,
                          chunk_size=4)
    ref.step()
    ref.step()
    blob = ref.to_bytes()
    resumed = HeavyHittersRun.from_bytes(m, CTX, thresholds, reports,
                                         vk, blob)
    assert resumed.level == ref.level
    assert resumed.prefixes == ref.prefixes
    while True:
        (a, b) = (ref.step(), resumed.step())
        assert a == b
        if not a:
            break
    assert ref.result() == resumed.result()


def test_checkpoint_runner_kind_mismatch_refused() -> None:
    """Restoring a resident checkpoint with a store (or a chunked one
    with neither store nor reports) must fail descriptively, not with
    a KeyError on missing carry arrays (ADVICE r4)."""
    from mastic_tpu.drivers.chunked import HostReportStore

    m = MasticCount(3)
    reports = _tampered_reports(m)
    vk = gen_rand(m.VERIFY_KEY_SIZE)
    thresholds = {"default": 2}

    resident = HeavyHittersRun(m, CTX, thresholds, reports,
                               verify_key=vk)
    resident.step()
    resident_blob = resident.to_bytes()
    chunked = HeavyHittersRun(m, CTX, thresholds, reports,
                              verify_key=vk, chunk_size=4)
    chunked.step()
    chunked_blob = chunked.to_bytes()

    bm = BatchedMastic(m)
    store = HostReportStore.from_batch(bm.marshal_reports(reports), 4)
    with pytest.raises(ValueError, match="resident"):
        HeavyHittersRun.from_bytes(m, CTX, thresholds, None, vk,
                                   resident_blob, store=store)
    with pytest.raises(ValueError, match="report store"):
        HeavyHittersRun.from_bytes(m, CTX, thresholds, None, vk,
                                   chunked_blob)


def test_chunked_width_growth_matches_resident() -> None:
    """A frontier that outgrows the initial padded width: 8 distinct
    3-bit prefixes in a 5-bit tree with threshold 1 force _grow at
    level 3 (8 ancestors > width 8 / 2), and level 4 then runs on the
    grown carries.  Both runners cross the growth boundary and must
    stay bit-identical."""
    m = MasticCount(5)
    meas = [(m.vidpf.test_index_from_int(v * 4, 5), True)
            for v in range(8)]
    reports = get_reports_from_measurements(m, CTX, meas)
    vk = gen_rand(m.VERIFY_KEY_SIZE)
    thresholds = {"default": 1}

    runs = [
        HeavyHittersRun(m, CTX, thresholds, reports, verify_key=vk),
        HeavyHittersRun(m, CTX, thresholds, reports, verify_key=vk,
                        chunk_size=4),
    ]
    assert all(run.runner.width == 8 for run in runs)
    while True:
        more = [run.step() for run in runs]
        assert more[0] == more[1]
        if not more[0]:
            break
    # Both runners actually grew (the point of the test), at the same
    # level, and agree on everything downstream of the boundary.
    assert all(run.runner.width == 16 for run in runs)
    for (m0, m1) in zip(runs[0].metrics, runs[1].metrics):
        assert (m0.accepted, m0.padded_width, m0.node_evals) == \
            (m1.accepted, m1.padded_width, m1.node_evals)
    assert runs[0].metrics[3].padded_width == 16  # grew entering L3
    assert sorted(runs[0].result()) == sorted(runs[1].result()) == \
        sorted(m.vidpf.test_index_from_int(v * 4, 5) for v in range(8))


def test_memory_envelope_guard(monkeypatch) -> None:
    """The feasibility guard refuses shapes outside the device/host
    budget with an actionable message, and the analytic envelope
    matches the measured accounting byte-for-byte."""
    from mastic_tpu.drivers.chunked import (HostReportStore,
                                            memory_envelope)

    m = MasticHistogram(4, 4, 2)     # joint-rand family: widest rows
    bm = BatchedMastic(m)
    meas = [(m.vidpf.test_index_from_int(v % 16, 4), v % 4)
            for v in range(6)]
    (nonces, rand, alphas, betas) = _shard_inputs(m, bm, meas, seed=3)
    (batch, ok) = jax.jit(
        lambda a, b, n, r: bm.shard_device(CTX, a, b, n, r))(
        jnp.asarray(alphas), jnp.asarray(betas),
        jnp.asarray(nonces), jnp.asarray(rand))
    assert bool(np.all(np.asarray(ok)))
    # chunk_size 4 does NOT divide 6 reports: the parity below must
    # hold through the padded tail chunk (carries/round keys allocate
    # padded rows, the store exact rows).
    store = HostReportStore.from_batch(batch, chunk_size=4)
    vk = gen_rand(m.VERIFY_KEY_SIZE)

    run = HeavyHittersRun(m, CTX, {"default": 1}, None, verify_key=vk,
                          store=store)
    env = memory_envelope(bm, 4, run.runner.width, 6)
    mem = run.runner.memory_accounting()
    assert env["device_bytes_per_chunk"] == mem["device_bytes_per_chunk"]
    assert env["host_bytes_total"] == mem["host_bytes_total"]
    # The pipelined-residency term is exactly two chunks in flight.
    assert env["device_bytes_per_chunk_pipelined"] == \
        2 * mem["device_bytes_per_chunk"]

    # A budget below even one report's footprint: the width itself is
    # infeasible and the message must say so (not "shrink to 0").
    monkeypatch.setenv("MASTIC_DEVICE_BUDGET_BYTES", "1000")
    with pytest.raises(ValueError, match="width itself is infeasible"):
        HeavyHittersRun(m, CTX, {"default": 1}, None,
                        verify_key=vk, store=store)
    # A budget that fits one report but not the chunk: actionable
    # largest-feasible-chunk message.
    per = env["device_bytes_per_chunk"] // 4
    monkeypatch.setenv("MASTIC_DEVICE_BUDGET_BYTES", str(per * 2))
    with pytest.raises(ValueError, match="feasible chunk_size"):
        HeavyHittersRun(m, CTX, {"default": 1}, None,
                        verify_key=vk, store=store)
    monkeypatch.delenv("MASTIC_DEVICE_BUDGET_BYTES")
    monkeypatch.setenv("MASTIC_HOST_BUDGET_BYTES", "1000")
    with pytest.raises(ValueError, match="hosts"):
        HeavyHittersRun(m, CTX, {"default": 1}, None,
                        verify_key=vk, store=store)
    monkeypatch.delenv("MASTIC_HOST_BUDGET_BYTES")

    # Per-round binder-peak gate (the term a 20k x 256 resident run
    # OOMed on in r5): construction passes — the envelope cannot know
    # the live buckets up front — but the round refuses at the actual
    # buckets with the level named and everything before it
    # checkpointable.  Applies to both runners; exercised here on the
    # resident one (its whole batch is the "chunk").
    run2 = HeavyHittersRun(m, CTX, {"default": 1}, None,
                           verify_key=vk, batch=batch)
    resident = run2.runner.memory_accounting()["device_bytes_total"]
    monkeypatch.setenv("MASTIC_DEVICE_BUDGET_BYTES",
                       str(resident + 1))
    with pytest.raises(ValueError, match="binder buckets"):
        run2.step()


def test_round_peak_per_bucket_model(monkeypatch) -> None:
    """check_round_peak prices the proof staging at the onehot bucket
    and the payload staging at the payload bucket, SUMMED — not
    max(onehot, payload) applied to both (ADVICE r5: the shared cap
    overstated the peak whenever the two pow2 buckets diverge, which
    is the common case — payload rows trail onehot rows — and
    refused runs that actually fit the budget)."""
    from mastic_tpu.drivers.chunked import (_binder_staging_bytes,
                                            check_round_peak)

    m = MasticCount(8)
    bm = BatchedMastic(m)
    limb_bytes = m.vidpf.VALUE_LEN * bm.spec.num_limbs * 4
    (onehot_cap, payload_cap, rows, resident) = (64, 16, 100, 1 << 20)

    per_row = _binder_staging_bytes(bm, onehot_cap, payload_cap)
    assert per_row == 4 * (onehot_cap * 32 + payload_cap * limb_bytes)
    old_model = 4 * max(onehot_cap, payload_cap) * (32 + limb_bytes)
    assert per_row < old_model  # diverging buckets: model tightened

    # A budget between the tightened peak and the old overstated one:
    # the old model refused this shape; the per-bucket model admits it.
    peak = resident + per_row * rows
    monkeypatch.setenv(
        "MASTIC_DEVICE_BUDGET_BYTES",
        str((resident + old_model * rows + peak) // 2))
    check_round_peak(bm, onehot_cap, payload_cap, rows, resident, 3)

    # Still a real gate: a budget below the tightened peak refuses,
    # naming both buckets and the level.
    monkeypatch.setenv("MASTIC_DEVICE_BUDGET_BYTES", str(peak - 1))
    with pytest.raises(ValueError) as err:
        check_round_peak(bm, onehot_cap, payload_cap, rows, resident, 3)
    assert "64 (onehot)" in str(err.value)
    assert "16 (payload)" in str(err.value)
    assert "level 3" in str(err.value)


def test_shard_device_feeds_chunked_run() -> None:
    """The at-scale path end to end: device-sharded reports (no scalar
    client at all) -> HostReportStore -> chunked heavy hitters."""
    from mastic_tpu.drivers.chunked import HostReportStore

    m = MasticCount(3)
    bm = BatchedMastic(m)
    meas = [((bool(v >> 2 & 1), bool(v >> 1 & 1), bool(v & 1)), True)
            for v in [0, 0, 0, 5, 5, 5, 3, 6]]
    (nonces, rand, alphas, betas) = _shard_inputs(m, bm, meas, seed=11)
    (batch, ok) = jax.jit(
        lambda a, b, n, r: bm.shard_device(CTX, a, b, n, r))(
        jnp.asarray(alphas), jnp.asarray(betas),
        jnp.asarray(nonces), jnp.asarray(rand))
    assert bool(np.all(np.asarray(ok)))

    store = HostReportStore.from_batch(batch, chunk_size=4)
    vk = gen_rand(m.VERIFY_KEY_SIZE)
    run = HeavyHittersRun(m, CTX, {"default": 3}, None, verify_key=vk,
                          store=store)
    while run.step():
        pass
    expected = [
        m.vidpf.test_index_from_int(v, 3) for v in (0, 5)]
    assert sorted(run.result()) == sorted(expected)
