"""Differential tests: batched Keccak/TurboSHAKE128 vs scalar reference."""

import numpy as np

from mastic_tpu.keccak import turbo_shake128
from mastic_tpu.ops.keccak_jax import turbo_shake128 as ts_jax


def test_turbo_shake128_matches_scalar():
    rng = np.random.default_rng(0)
    # Lengths straddling the 168-byte rate boundary, both domains used
    # by the VDAF XOFs, single- and multi-block squeezes.
    cases = [
        (0, 1, 16), (1, 2, 32), (42, 1, 32), (167, 1, 168),
        (168, 2, 169), (169, 1, 16), (336, 2, 32), (901, 1, 345),
    ]
    for (msg_len, domain, out_len) in cases:
        batch = rng.integers(0, 256, size=(3, msg_len), dtype=np.uint8)
        got = np.asarray(ts_jax(batch, domain, out_len))
        for b in range(batch.shape[0]):
            want = turbo_shake128(bytes(batch[b]), domain, out_len)
            assert bytes(got[b]) == want, (msg_len, domain, out_len, b)


def test_turbo_shake128_nd_batch():
    rng = np.random.default_rng(1)
    batch = rng.integers(0, 256, size=(2, 3, 50), dtype=np.uint8)
    got = np.asarray(ts_jax(batch, 1, 32))
    assert got.shape == (2, 3, 32)
    for i in range(2):
        for j in range(3):
            assert bytes(got[i, j]) == turbo_shake128(bytes(batch[i, j]), 1, 32)


import pytest  # noqa: E402  (module tail: only the pallas test below)


@pytest.mark.slow
@pytest.mark.parametrize("flat", [5, 600])
def test_keccak_pallas_call_plumbing(flat):
    """The pallas_call plumbing (lane-major transpose, padding, grid —
    incl. a batch whose lane-padded size is not a _BLOCK_B multiple)
    is bit-exact vs the scan path for a single round in interpret
    mode.  The round math itself is the scan path's _keccak_round,
    shared by construction; a full 12-round unrolled kernel takes
    minutes of interpret compile on the CPU fabric, so one round
    suffices here."""
    pytest.importorskip("jax.experimental.pallas")
    import jax.numpy as jnp

    from mastic_tpu.ops.keccak_jax import keccak_p1600
    from mastic_tpu.ops.keccak_pallas import keccak_p1600_pallas

    rng = np.random.default_rng(3)
    lo = jnp.asarray(rng.integers(0, 1 << 32, (flat, 25),
                                  dtype=np.uint32))
    hi = jnp.asarray(rng.integers(0, 1 << 32, (flat, 25),
                                  dtype=np.uint32))
    (alo, ahi) = keccak_p1600(lo, hi, 1)
    (blo, bhi) = keccak_p1600_pallas(lo, hi, 1, interpret=True)
    np.testing.assert_array_equal(np.asarray(alo), np.asarray(blo))
    np.testing.assert_array_equal(np.asarray(ahi), np.asarray(bhi))


@pytest.mark.slow
def test_keccak_pallas_chained_rounds_match_scan():
    """All 12 rounds through the pallas boundary, one single-round
    kernel per round (round_range pins each round's constant), must
    equal the 12-round scan path.  This validates the multi-round
    state handoff and the ROUND_CONSTANTS start offset that the
    single kernel's unrolled form bakes in — without the >1 h
    interpret compile of that form."""
    pytest.importorskip("jax.experimental.pallas")
    import jax.numpy as jnp

    from mastic_tpu.ops.keccak_jax import keccak_p1600
    from mastic_tpu.ops.keccak_pallas import keccak_p1600_pallas

    rng = np.random.default_rng(5)
    lo = jnp.asarray(rng.integers(0, 1 << 32, (7, 25), dtype=np.uint32))
    hi = jnp.asarray(rng.integers(0, 1 << 32, (7, 25), dtype=np.uint32))
    (want_lo, want_hi) = keccak_p1600(lo, hi, 12)
    (got_lo, got_hi) = (lo, hi)
    for r in range(12, 24):
        (got_lo, got_hi) = keccak_p1600_pallas(
            got_lo, got_hi, interpret=True, round_range=(r, r + 1))
    np.testing.assert_array_equal(np.asarray(want_lo),
                                  np.asarray(got_lo))
    np.testing.assert_array_equal(np.asarray(want_hi),
                                  np.asarray(got_hi))
