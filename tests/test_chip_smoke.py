"""chip_smoke.py's pieces at a CPU size: the batch-to-wire upload
encoder against the scalar client, and one whole collection through
the upload front, WAL and service epochs against the plain reference.
The TPU check lives only in chip_smoke.main()."""

import numpy as np
import pytest

import chip_smoke
from mastic_tpu import MasticCount, MasticSum


@pytest.mark.parametrize("make,weight", [
    (lambda: MasticSum(8, 255), 200),
    (lambda: MasticCount(16), True),
], ids=["MasticSum(8,255)", "MasticCount(16)"])
def test_encode_upload_batch_matches_scalar_client(make, weight):
    import jax

    from mastic_tpu.backend.mastic_jax import BatchedMastic
    from mastic_tpu.drivers.service import encode_upload
    from mastic_tpu.net.loadgen import encode_upload_batch

    m = make()
    bm = BatchedMastic(m)
    rng = np.random.default_rng(5)
    num = 3
    bits = m.vidpf.BITS
    alphas = rng.integers(0, 2, (num, bits)).astype(bool)
    nonces = rng.integers(0, 256, (num, 16), dtype=np.uint8)
    rand = rng.integers(0, 256, (num, m.RAND_SIZE), dtype=np.uint8)
    meas = [(tuple(bool(b) for b in alphas[r]), weight)
            for r in range(num)]
    (_, betas) = bm.encode_measurements(meas)
    (batch, ok) = jax.jit(
        lambda a, b, n, r: bm.shard_device(b"enc", a, b, n, r))(
            alphas, betas, nonces, rand)
    assert np.asarray(ok).all()
    blobs = encode_upload_batch(bm, batch)
    for r in range(num):
        nonce = nonces[r].tobytes()
        want = encode_upload(
            m, (nonce, *m.shard(b"enc", meas[r], nonce,
                                rand[r].tobytes())))
        assert blobs[r] == want


def test_reference_walk_counts_weights():
    alphas = np.array([[0, 0], [0, 0], [0, 1], [1, 1]], bool)
    weights = np.array([3, 3, 5, 1])
    (hitters, widths, sums) = chip_smoke.reference_walk(alphas, weights,
                                                        5)
    assert hitters == [[False, False], [False, True]]
    assert widths == [2, 2]
    assert sums == [[11, 1], [6, 5]]
    assert chip_smoke.reference_walk(alphas, weights, 12) == \
        ([], [2], [[11, 1]])


def test_check_refuses_a_wrong_aggregate():
    """The reference check compares every level's weights, not only
    which side of the threshold each candidate falls on."""
    wl = chip_smoke.make_workload(8, 64, seed=3)
    (hitters, widths, sums) = chip_smoke.reference_walk(
        wl["alphas"], wl["weights"], wl["threshold"])
    got = {"hitters": hitters,
           "levels": [(lv, w, 64) for (lv, w) in enumerate(widths)],
           "aggregates": [list(s) for s in sums]}
    assert chip_smoke.check_against_reference(got, wl) == hitters
    # One tail report's weight lost under a hitter at the last level:
    # every candidate stays on its side of the threshold.
    top = max(range(len(sums[-1])), key=lambda i: sums[-1][i])
    got["aggregates"][-1][top] -= 1
    assert got["aggregates"][-1][top] > wl["threshold"]
    with pytest.raises(chip_smoke.SmokeFailure, match="aggregates"):
        chip_smoke.check_against_reference(got, wl)


def test_resident_reports_fit_without_donation(monkeypatch):
    """The smoke's report count: the headline's 4096 halved until a
    round's worst-case peak, output carries included, fits a chip."""
    from mastic_tpu.backend.mastic_jax import BatchedMastic
    from mastic_tpu.drivers import chunked

    monkeypatch.setattr(chunked, "carries_donated", lambda: False)
    bm = BatchedMastic(MasticSum(chip_smoke.BITS, chip_smoke.MAX_WEIGHT))
    reports = chip_smoke.resident_reports(bm)
    assert reports == 2048
    env = chunked.memory_envelope(bm, reports, chip_smoke.WIDTH, reports)
    assert env["device_peak_bytes_per_chunk"] <= env["device_budget_bytes"]
    env = chunked.memory_envelope(bm, 2 * reports, chip_smoke.WIDTH,
                                  2 * reports)
    assert env["device_peak_bytes_per_chunk"] > env["device_budget_bytes"]


def test_collection_matches_reference():
    """One collection at 8 bits x 64 reports through the HTTP front,
    the admission WAL and the service epoch (one run: compiling
    dominates on the CPU)."""
    wl = chip_smoke.make_workload(8, 64, seed=3)
    blobs = chip_smoke.shard_blobs(MasticSum(8, chip_smoke.MAX_WEIGHT),
                                   wl)
    got = chip_smoke.run_collection(blobs, 8, wl["threshold"],
                                    wl["verify_key"])
    expected = chip_smoke.check_against_reference(got, wl)
    assert expected, "the seeded workload plants no hitter"
    assert got["hitters"] == expected
    # The published aggregates are what the check compared: every
    # level's weights, summing to the admitted weight at level 0.
    assert len(got["aggregates"]) == len(got["levels"])
    assert sum(got["aggregates"][0]) == int(wl["weights"].sum())
    assert len(chip_smoke.digest(got)) == 16
    assert [a for (_, _, a) in got["levels"]] == [64] * len(got["levels"])
    assert got["warm_errors"] == 0
    assert got["node_evals"] > 0
    assert {"compute_wait_ms", "warm_ms", "scheduler_ms"} \
        <= set(got["round_phases_s"])
    chip_smoke.report("collection", got)
