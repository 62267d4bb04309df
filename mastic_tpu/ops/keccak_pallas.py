"""Pallas fused Keccak-p[1600] kernel: all rounds resident in VMEM.

The XLA path (ops/keccak_jax.keccak_p1600) runs the round loop under
lax.scan — correct and portable, but the 50-array scan carry round-
trips through HBM between rounds unless XLA fuses the unrolled form
(PERF.md §3: the last ~2x to the VPU ceiling).  This kernel keeps the
whole 1600-bit state in VMEM for all 12 rounds: one HBM read of the
state, 12 rounds of pure VPU work, one HBM write.

Layout: lane-major planes (50, B) uint32 — lane half i of A[x+5y] is
row i, the batch rides the 128-wide vector lanes (the same layout the
XLA path uses internally, so adoption is a transpose at the call
boundary, already present there).  B is padded to the 128-lane tile.

Gated by MASTIC_KECCAK_PALLAS=1 (read in ops/keccak_jax at import):
the interpret-mode equivalence suite (tests/test_ops_keccak.py) locks
bit-exactness against the scan path on CPU, and
tests/test_tpu_compile.py compiles the kernel for a v5e at headline
widths.
"""

import jax
import jax.numpy as jnp
import numpy as np

from ..keccak import ROUND_CONSTANTS

_U32 = jnp.uint32
_LANE = 128   # TPU vector lane width (uint32 tile: 8 x 128)
_BLOCK_B = 512  # max batch elements per grid step (100 KB VMEM)


def _make_kernel(start: int, end: int):
    def kernel(state_ref, out_ref):
        # state: (50, B_block) — rows 0..24 = lo halves, 25..49 = hi.
        # The round math is the scan path's _keccak_round verbatim
        # (pallas refs load as ordinary jax arrays, so the shared
        # definition applies unchanged).  Rows are kept as (1, block)
        # 2-D tiles — Mosaic's vector lowering expects >= 2-D; the
        # round ops are all elementwise, so the leading unit axis
        # broadcasts through unchanged.
        from .keccak_jax import _keccak_round

        a = [(state_ref[i:i + 1, :], state_ref[25 + i:26 + i, :])
             for i in range(25)]
        for r in range(start, end):  # unrolled: state stays in VMEM
            rc = ROUND_CONSTANTS[r]
            a = _keccak_round(a, _U32(rc & 0xFFFFFFFF), _U32(rc >> 32))
        for i in range(25):
            out_ref[i:i + 1, :] = a[i][0]
            out_ref[25 + i:26 + i, :] = a[i][1]

    return kernel


_CALL_CACHE: dict = {}


def _pallas_permute(state: jax.Array, rounds: tuple,
                    interpret: bool, block: int) -> jax.Array:
    """state (50, B) uint32, B a multiple of `block`; `rounds` is the
    half-open [start, end) range into ROUND_CONSTANTS."""
    from jax.experimental import pallas as pl

    B = state.shape[1]
    assert B % block == 0, (B, block)
    key = (rounds, B, block, interpret)
    call = _CALL_CACHE.get(key)
    if call is None:
        # mastic-allow: PL004 — the 50-row block equals the full
        # array dim (25 lo + 25 hi lane halves, never tiled), the
        # case Mosaic accepts for a non-multiple-of-8 sublane dim
        call = pl.pallas_call(
            _make_kernel(*rounds),
            out_shape=jax.ShapeDtypeStruct((50, B), jnp.uint32),
            grid=(B // block,),
            in_specs=[pl.BlockSpec((50, block), lambda i: (0, i))],
            out_specs=pl.BlockSpec((50, block), lambda i: (0, i)),
            interpret=interpret,
        )
        _CALL_CACHE[key] = call
    return call(state)


def keccak_p1600_pallas(lo: jax.Array, hi: jax.Array,
                        num_rounds: int = 12,
                        interpret: bool = False,
                        round_range: tuple = None):
    """Drop-in twin of ops/keccak_jax.keccak_p1600: lo/hi (..., 25)
    uint32 -> permuted (lo, hi).  Batch is flattened, transposed to
    lane-major planes, padded to the 128-lane tile, and run through
    the fused VMEM kernel.

    `round_range` overrides the usual last-`num_rounds` window with an
    explicit [start, end) into ROUND_CONSTANTS — the chained
    equivalence test applies the 12 rounds one kernel at a time, which
    is what pins each round's constant offset without the >1 h
    interpret compile of the fully unrolled kernel."""
    batch_shape = lo.shape[:-1]
    flat = int(np.prod(batch_shape)) if batch_shape else 1
    state = jnp.concatenate([
        lo.reshape(flat, 25).T, hi.reshape(flat, 25).T], axis=0)
    # Pad to a multiple of the block size so the grid covers every
    # column (the block is the largest power-of-2 <= _BLOCK_B that
    # divides the lane-padded batch — no dropped remainder).
    lanes = -(-flat // _LANE) * _LANE
    block = _BLOCK_B
    while lanes % block:
        block //= 2
    pad = lanes - flat
    if pad:
        state = jnp.pad(state, ((0, 0), (0, pad)))
    rounds = (round_range if round_range is not None
              else (24 - num_rounds, 24))
    out = _pallas_permute(state, rounds, interpret, block)
    out = out[:, :flat]
    return (out[:25].T.reshape(batch_shape + (25,)),
            out[25:].T.reshape(batch_shape + (25,)))
