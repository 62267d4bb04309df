"""Pallas fused bitsliced-AES kernel: all 10 rounds resident in VMEM.

The XLA path (ops/aes_jax.aes128_encrypt_bitsliced) runs the middle
rounds under lax.scan — correct and portable, but the 128 plane arrays
round-trip through HBM between rounds unless XLA fuses aggressively.
This kernel keeps the whole bitsliced state in VMEM for the full
whiten -> 9 full rounds -> final round pipeline: one HBM read of the
state planes, ~3k gate-ops of pure VPU work per 128 packed blocks, one
HBM write.  Same boolean circuit (ops/sbox_tower shared by import), so
constant-time discipline is preserved.

Layout: the (8, 16, M, W) plane stack flattens to (128, M, W) — plane
rows on the sublane axis, the packed-word axis W riding the 128-wide
vector lanes, the block axis M gridded.  Round-key planes (11, 8, 16,
W) flatten to (1408, 1, W) and broadcast over M inside the kernel.

Gated by MASTIC_AES_PALLAS=1 (read in ops/aes_jax at import): the
chained interpret-mode suite (tests/test_ops_aes.py) locks every stage
bit-exact against the scan path on CPU, and tests/test_tpu_compile.py
compiles the kernel for a v5e at headline widths.
"""

import jax
import jax.numpy as jnp
import numpy as np

_U32 = jnp.uint32
_ONES32 = np.uint32(0xFFFFFFFF)
_LANE = 128    # TPU vector lane width
# Blocks-per-grid-step.  Mosaic requires the second-to-last block dim
# to be a multiple of 8 (sublane tile) unless it equals the array dim,
# so the block axis is padded up to a multiple of 8 below; VMEM stays
# ~6 MB of live planes per grid step.
_BLOCK_M = 8

# ShiftRows permutation over the 16-byte axis (ops/aes_jax._SHIFT_ROWS).
from .aes_jax import _SHIFT_ROWS


def _shift_rows(p: jax.Array) -> jax.Array:
    """Static-slice permutation of the 16-byte axis — a fancy-index
    gather would capture its index array as a pallas kernel constant,
    which pallas_call rejects."""
    return jnp.concatenate([p[i:i + 1] for i in _SHIFT_ROWS], axis=0)


def _xtime_list(planes: list) -> list:
    """xtime on a list of 8 plane arrays (aes_jax._xtime_planes on a
    stack): planes shift up one, the top plane folds into the 0x1B
    taps (bits 1, 3, 4) and becomes bit 0."""
    hi = planes[7]
    out = [hi] + list(planes[:7])
    out[1] = out[1] ^ hi
    out[3] = out[3] ^ hi
    out[4] = out[4] ^ hi
    return out


def _mix_list(planes: list) -> list:
    """MixColumns on 8 x (16, ...) plane arrays (byte index = 4*col +
    row, so axis 1 of the (4, 4, ...) reshape is the row axis —
    aes_jax._mix_columns_planes with the plane axis as a list)."""
    c = [p.reshape((4, 4) + p.shape[1:]) for p in planes]
    r1 = [jnp.roll(x, -1, axis=1) for x in c]
    r2 = [jnp.roll(x, -2, axis=1) for x in c]
    r3 = [jnp.roll(x, -3, axis=1) for x in c]
    xt_c = _xtime_list(c)
    xt_r1 = _xtime_list(r1)
    out = [xt_c[i] ^ xt_r1[i] ^ r1[i] ^ r2[i] ^ r3[i]
           for i in range(8)]
    return [o.reshape((16,) + o.shape[2:]) for o in out]


def _make_kernel(start: int, end: int):
    """Stages 0..10: stage 0 = key whitening, 1..9 = full rounds,
    10 = final round (no MixColumns).  [start, end) is half-open."""

    def kernel(kp_ref, state_ref, out_ref):
        from .sbox_tower import sbox_planes_tower

        planes = [state_ref[b * 16:(b + 1) * 16] for b in range(8)]

        def key(r: int) -> list:
            return [kp_ref[(r * 8 + b) * 16:(r * 8 + b + 1) * 16]
                    for b in range(8)]

        for stage in range(start, end):  # unrolled: state stays in VMEM
            if stage == 0:
                k = key(0)
                planes = [planes[b] ^ k[b] for b in range(8)]
                continue
            planes = sbox_planes_tower(planes, _ONES32)
            planes = [_shift_rows(p) for p in planes]
            if stage < 10:
                planes = _mix_list(planes)
            k = key(stage)
            planes = [planes[b] ^ k[b] for b in range(8)]
        for b in range(8):
            out_ref[b * 16:(b + 1) * 16] = planes[b]

    return kernel


_CALL_CACHE: dict = {}


def aes128_encrypt_bitsliced_pallas(key_planes: jax.Array,
                                    planes: jax.Array,
                                    interpret: bool = False,
                                    stage_range: tuple = None):
    """Drop-in twin of ops/aes_jax.aes128_encrypt_bitsliced:
    key_planes (11, 8, 16, W), planes (8, 16, N..., W) -> encrypted
    planes, middle dims broadcasting against the keys.

    `stage_range` overrides the full [0, 11) pipeline with an explicit
    half-open stage window — the chained equivalence test applies the
    11 stages one kernel at a time, pinning each round key and the
    final round's missing MixColumns without the interpret compile of
    the fully unrolled kernel."""
    from jax.experimental import pallas as pl

    (rounds, eight, sixteen, w) = key_planes.shape
    assert (rounds, eight, sixteen) == (11, 8, 16), key_planes.shape
    mid_shape = planes.shape[2:-1]
    m = int(np.prod(mid_shape)) if mid_shape else 1
    state = planes.reshape(8 * 16, m, planes.shape[-1])
    kp = key_planes.reshape(11 * 8 * 16, 1, w)

    # Pad the lane axis to the 128-wide tile and the block axis to the
    # grid block (dead lanes/blocks are sliced back off).
    w_pad = -(-w // _LANE) * _LANE - w
    m_block = _BLOCK_M  # never narrower: Mosaic's 8-sublane tile rule
    m_pad = -(-m // m_block) * m_block - m
    if w_pad:
        state = jnp.pad(state, ((0, 0), (0, 0), (0, w_pad)))
        kp = jnp.pad(kp, ((0, 0), (0, 0), (0, w_pad)))
    if m_pad:
        state = jnp.pad(state, ((0, 0), (0, m_pad), (0, 0)))
    (stages, wp) = (stage_range or (0, 11), w + w_pad)
    mp = m + m_pad

    key = (stages, mp, m_block, wp, interpret)
    call = _CALL_CACHE.get(key)
    if call is None:
        # Grid over BOTH the block axis and the lane axis: packed
        # lanes are independent (round keys included), and an
        # un-gridded W would scale the VMEM-resident key block
        # linearly with the report count (~18 MB at 100k reports).
        call = pl.pallas_call(
            _make_kernel(*stages),
            out_shape=jax.ShapeDtypeStruct((128, mp, wp), jnp.uint32),
            grid=(mp // m_block, wp // _LANE),
            in_specs=[
                pl.BlockSpec((11 * 128, 1, _LANE),
                             lambda i, j: (0, 0, j)),
                pl.BlockSpec((128, m_block, _LANE),
                             lambda i, j: (0, i, j)),
            ],
            out_specs=pl.BlockSpec((128, m_block, _LANE),
                                   lambda i, j: (0, i, j)),
            interpret=interpret,
        )
        _CALL_CACHE[key] = call
    out = call(kp, state)
    out = out[:, :m, :w]
    return out.reshape(planes.shape[:2] + mid_shape
                       + planes.shape[-1:])
