"""Batched AES-128 (encrypt-only) in JAX, bitsliced per byte.

TPUs have no AES instructions and data-dependent table lookups are both
slow (gathers) and timing-leaky, so SubBytes is computed as a boolean
circuit over the 8 bit-planes of each byte: GF(2^8) inversion by the
addition chain x^254 (4 multiplies + 8 squarings on bit-planes)
followed by the affine map.  This is constant-time by construction —
the TPU-native reading of the reference's side-channel notes
(/root/reference/poc/vidpf.py:116-119).

The circuit functions are generic over the array type (anything with
&, ^): at import they are run on numpy over all 256 byte values and
asserted equal to the generated S-box table of the scalar reference
(mastic_tpu.aes.SBOX), so the JAX path and the scalar path cannot
drift.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np

from ..aes import SBOX, _gf_mul
from .sbox_tower import sbox_planes_tower

_U8 = jnp.uint8

# Route the bitsliced encrypt through the Pallas fused-VMEM kernel
# (ops/aes_pallas.py).  Off by default: bit-exact by the chained
# interpret suite, but unmeasured on real hardware.
USE_PALLAS = os.environ.get("MASTIC_AES_PALLAS", "0") == "1"


def _planes(x):
    """Split bytes into 8 bit-planes (LSB first), values 0/1."""
    return [(x >> i) & 1 for i in range(8)]


def _unplanes(planes):
    out = planes[0]
    for i in range(1, 8):
        out = out ^ (planes[i] << i)
    return out


def _gf_mul_planes(a, b):
    """Schoolbook GF(2^8) multiply on bit-planes, reduced mod 0x11B."""
    t: list = [None] * 15
    for i in range(8):
        for j in range(8):
            p = a[i] & b[j]
            k = i + j
            t[k] = p if t[k] is None else t[k] ^ p
    # x^8 == x^4 + x^3 + x + 1: fold degrees 14..8 downward so cascades
    # into still-unprocessed degrees are picked up.
    for k in range(14, 7, -1):
        c = t[k]
        t[k - 4] = t[k - 4] ^ c
        t[k - 5] = t[k - 5] ^ c
        t[k - 7] = t[k - 7] ^ c
        t[k - 8] = t[k - 8] ^ c
    return t[:8]


def _gf_square_planes(a):
    """Squaring is linear: sum a_i x^(2i), then fold."""
    zero = a[0] ^ a[0]
    t = [zero] * 15
    for i in range(8):
        t[2 * i] = a[i]
    for k in range(14, 7, -1):
        c = t[k]
        t[k - 4] = t[k - 4] ^ c
        t[k - 5] = t[k - 5] ^ c
        t[k - 7] = t[k - 7] ^ c
        t[k - 8] = t[k - 8] ^ c
    return t[:8]


def _gf_inv_planes(x):
    """x^254 = x^-1 (and 0 -> 0) via an addition chain."""
    x2 = _gf_square_planes(x)
    x3 = _gf_mul_planes(x2, x)
    x6 = _gf_square_planes(x3)
    x12 = _gf_square_planes(x6)
    x15 = _gf_mul_planes(x12, x3)
    x30 = _gf_square_planes(x15)
    x60 = _gf_square_planes(x30)
    x120 = _gf_square_planes(x60)
    x240 = _gf_square_planes(x120)
    x252 = _gf_mul_planes(x240, x12)
    return _gf_mul_planes(x252, x2)


def _sbox_planes(x, one=1):
    """The affine-constant term `one` is 1 for 0/1-valued byte planes
    and all-ones for bit-packed uint32 planes (the circuit itself is
    representation-agnostic: only &, ^ between planes)."""
    inv = _gf_inv_planes(x)
    out = []
    for i in range(8):
        bit = inv[i] ^ inv[(i + 4) % 8] ^ inv[(i + 5) % 8] \
            ^ inv[(i + 6) % 8] ^ inv[(i + 7) % 8]
        if (0x63 >> i) & 1:
            bit = bit ^ one
        out.append(bit)
    return out


def sub_bytes(x):
    """Apply the AES S-box elementwise to a uint8 array (tower-field
    circuit, ops/sbox_tower.py — ~4x fewer gates than the x^254
    chain above, which is kept as independent documentation of the
    inversion)."""
    return _unplanes(sbox_planes_tower(_planes(x), 1))


# Lock BOTH circuits against the table at import (numpy path).
for _circuit in (
        lambda p: _sbox_planes(p),
        lambda p: sbox_planes_tower(p, 1),
):
    _check = _unplanes(_circuit(_planes(np.arange(256, dtype=np.uint8))))
    assert bytes(_check) == SBOX, "S-box circuit diverges from table"
del _check, _circuit


def _xtime(v):
    # mastic-allow: DT002 — the uint8 truncation IS the GF(2^8)
    # reduction: bit 8 of (v << 1) is exactly what the 0x1B term
    # folds back in, so dropping it is the field multiply by x
    return ((v << 1) ^ ((v >> 7) * _U8(0x1B))).astype(_U8)


# ShiftRows: byte i of the new state comes from byte (i + 4*(i%4)) % 16
# (column-major state; scalar reference mastic_tpu/aes.py:97).
_SHIFT_ROWS = tuple((i + 4 * (i % 4)) % 16 for i in range(16))

_RCON = []
_r = 1
for _ in range(10):
    _RCON.append(_r)
    _r = _gf_mul(_r, 2)


def aes128_key_schedule(keys: jax.Array) -> jax.Array:
    """Batched key expansion: (..., 16) uint8 -> (..., 11, 16).

    The 10 expansion rounds run under lax.scan — each round contains a
    full bitsliced S-box circuit, and unrolling all of them dominated
    XLA compile time."""
    words = keys.reshape(keys.shape[:-1] + (4, 4))

    def body(words, rcon):
        s = sub_bytes(words[..., 3, :])
        temp = jnp.stack([s[..., 1] ^ rcon, s[..., 2], s[..., 3],
                          s[..., 0]], axis=-1)
        w0 = words[..., 0, :] ^ temp
        w1 = words[..., 1, :] ^ w0
        w2 = words[..., 2, :] ^ w1
        w3 = words[..., 3, :] ^ w2
        new = jnp.stack([w0, w1, w2, w3], axis=-2)
        return (new, new)

    (_, rounds) = jax.lax.scan(body, words,
                               jnp.asarray(_RCON, dtype=_U8))
    rounds = jnp.moveaxis(rounds, 0, -3)  # (..., 10, 4, 4)
    all_rounds = jnp.concatenate([words[..., None, :, :], rounds],
                                 axis=-3)
    return all_rounds.reshape(keys.shape[:-1] + (11, 16))


def _sub_shift(state: jax.Array) -> jax.Array:
    return sub_bytes(state)[..., _SHIFT_ROWS]


def _mix_columns(state: jax.Array) -> jax.Array:
    cols = state.reshape(state.shape[:-1] + (4, 4))
    rot1 = jnp.roll(cols, -1, axis=-1)
    mixed = _xtime(cols) ^ _xtime(rot1) ^ rot1 \
        ^ jnp.roll(cols, -2, axis=-1) ^ jnp.roll(cols, -3, axis=-1)
    return mixed.reshape(state.shape)


def aes128_encrypt(round_keys: jax.Array, blocks: jax.Array) -> jax.Array:
    """Batched ECB encrypt: round_keys (..., 11, 16) and blocks
    (..., 16) uint8, with broadcasting between the batch shapes.
    Middle rounds run under lax.scan (one S-box circuit compiled, not
    nine)."""
    state = blocks ^ round_keys[..., 0, :]
    mid = jnp.moveaxis(round_keys[..., 1:10, :], -2, 0)
    mid = jnp.broadcast_to(mid, (9,) + state.shape)

    def body(state, rk):
        return (_mix_columns(_sub_shift(state)) ^ rk, None)

    (state, _) = jax.lax.scan(body, state, mid)
    return _sub_shift(state) ^ round_keys[..., 10, :]


# -- batch-bitsliced path ---------------------------------------------
#
# The byte path above stores one 0/1 plane value per array element, so
# every VPU lane carries a single data bit (uint8 elementwise ops run
# in 32-bit lanes on TPU).  For large batches the state is instead
# bit-transposed along the batch axis: bit j of the uint32 word at
# packed index w is batch element 32*w + j, and each of the 128
# (byte, bit) state positions becomes a dense word vector.  The
# boolean circuit is unchanged — its arrays are 32x smaller, which is
# the difference between the VPU spending lanes on padding and
# spending them on data.  Constant-time discipline is preserved (same
# gates, no lookups).

_U32 = jnp.uint32
# numpy scalar on purpose: a jnp constant at module scope would
# initialize the JAX backend at import time (see _RC_LO note in
# ops/keccak_jax.py), which a process that merely imports this module
# must not do.
_ONES32 = np.uint32(0xFFFFFFFF)
_SHIFT_ROWS_ARR = np.asarray(_SHIFT_ROWS)


def bitslice_pack(x: jax.Array) -> jax.Array:
    """uint8 (M, ..., K) with M % 32 == 0 -> planes (8, K, ..., M//32)
    uint32, where bit j of word w is element 32*w + j of the leading
    axis."""
    m = x.shape[0]
    assert m % 32 == 0
    rest = x.shape[1:-1]
    xr = x.reshape((m // 32, 32) + rest + x.shape[-1:]).astype(_U32)
    shifts = jnp.arange(32, dtype=_U32).reshape(
        (1, 32) + (1,) * (len(rest) + 1))
    planes = []
    for b in range(8):
        bits = (xr >> b) & _U32(1)
        planes.append(jnp.sum(bits << shifts, axis=1, dtype=_U32))
    p = jnp.stack(planes)          # (8, W, ..., K)
    p = jnp.moveaxis(p, -1, 1)     # (8, K, W, ...)
    return jnp.moveaxis(p, 2, -1)  # (8, K, ..., W)


def bitslice_unpack(planes: jax.Array) -> jax.Array:
    """Inverse of bitslice_pack: (8, K, ..., W) -> (32*W, ..., K)."""
    p = jnp.moveaxis(planes, -1, 2)  # (8, K, W, ...)
    p = jnp.moveaxis(p, 1, -1)       # (8, W, ..., K)
    shifts = jnp.arange(32, dtype=_U32).reshape(
        (1, 32) + (1,) * (p.ndim - 2))
    acc = None
    for b in range(8):
        bits = ((p[b][:, None] >> shifts) & _U32(1)) << b
        acc = bits if acc is None else acc | bits
    out = acc.astype(_U8)            # (W, 32, ..., K)
    return out.reshape((-1,) + out.shape[2:])


def bitslice_keys(round_keys: jax.Array) -> jax.Array:
    """Key schedules (R, 11, 16) uint8 -> key planes (11, 8, 16, R//32)
    uint32 (R % 32 == 0)."""
    return jnp.moveaxis(bitslice_pack(round_keys), 2, 0)


def pack_mask(bits: jax.Array) -> jax.Array:
    """Pack a bool array (M, ...) along its leading axis:
    -> (..., M//32) uint32 select-mask words (bit j of word w = element
    32*w + j), for plane-domain lane selects (x ^ (planes & mask))."""
    m = bits.shape[0]
    assert m % 32 == 0
    xr = bits.reshape((m // 32, 32) + bits.shape[1:]).astype(_U32)
    shifts = jnp.arange(32, dtype=_U32).reshape(
        (1, 32) + (1,) * (bits.ndim - 1))
    words = jnp.sum(xr << shifts, axis=1, dtype=_U32)  # (W, ...)
    return jnp.moveaxis(words, 0, -1)


def unpack_mask(words: jax.Array, m: int) -> jax.Array:
    """Inverse of pack_mask: (..., W) uint32 -> (m, ...) bool."""
    shifts = jnp.arange(32, dtype=_U32).reshape(
        (1,) * (words.ndim - 1) + (1, 32))
    bits = (words[..., None] >> shifts) & _U32(1)   # (..., W, 32)
    bits = bits.reshape(words.shape[:-1] + (-1,))   # (..., 32W)
    return jnp.moveaxis(bits, -1, 0)[:m].astype(bool)


def block_index_planes(num_blocks: int) -> np.ndarray:
    """le128(i) for i < num_blocks as plane masks: (num_blocks, 8, 16)
    uint32, each entry 0 or 0xFFFFFFFF (XOR-constant in plane form)."""
    out = np.zeros((num_blocks, 8, 16), np.uint32)
    for i in range(num_blocks):
        le = i.to_bytes(16, "little")
        for b in range(8):
            for k in range(16):
                if (le[k] >> b) & 1:
                    out[i, b, k] = 0xFFFFFFFF
    return out


def _xtime_planes(v: jax.Array) -> jax.Array:
    """xtime on a (8, ...) plane stack: shift planes up one, fold the
    top plane into the 0x1B taps (bits 1, 3, 4; bit 0 is the rolled-in
    top plane itself)."""
    out = jnp.roll(v, 1, axis=0)
    hi = v[7]
    out = out.at[1].set(out[1] ^ hi)
    out = out.at[3].set(out[3] ^ hi)
    return out.at[4].set(out[4] ^ hi)


def _mix_columns_planes(s: jax.Array) -> jax.Array:
    c = s.reshape((8, 4, 4) + s.shape[2:])  # (planes, col, row, ...)
    rot1 = jnp.roll(c, -1, axis=2)
    mixed = _xtime_planes(c) ^ _xtime_planes(rot1) ^ rot1 \
        ^ jnp.roll(c, -2, axis=2) ^ jnp.roll(c, -3, axis=2)
    return mixed.reshape(s.shape)


def _sub_shift_planes(s: jax.Array) -> jax.Array:
    sb = jnp.stack(sbox_planes_tower([s[b] for b in range(8)],
                                     _ONES32))
    return sb[:, _SHIFT_ROWS_ARR]


def aes128_encrypt_bitsliced(key_planes: jax.Array,
                             planes: jax.Array) -> jax.Array:
    """Bitsliced ECB encrypt.

    key_planes: (11, 8, 16, W) from bitslice_keys — one schedule per
    packed batch element.  planes: (8, 16, ..., W) state planes whose
    middle dims broadcast against the keys (many blocks per batch
    element, e.g. every tree node of a report)."""
    if USE_PALLAS:
        from .aes_pallas import aes128_encrypt_bitsliced_pallas
        return aes128_encrypt_bitsliced_pallas(key_planes, planes)
    extra = planes.ndim - 3
    kp = key_planes.reshape(
        (11, 8, 16) + (1,) * extra + key_planes.shape[-1:])

    def body(state, rk):
        return (_mix_columns_planes(_sub_shift_planes(state)) ^ rk, None)

    state = planes ^ kp[0]
    (state, _) = jax.lax.scan(body, state, kp[1:10])
    return _sub_shift_planes(state) ^ kp[10]
