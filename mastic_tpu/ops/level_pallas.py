"""Pallas fused level-step megakernel: the whole VIDPF node-eval
pipeline — extend (1 fixed-key AES block per child) -> correct/select
-> convert (`convert_blocks` AES blocks) -> node proof
(Keccak-p[1600,12]) — resident in VMEM for a (report x frontier) tile.

PERF.md §3: the headline `eval_step` is HBM-bandwidth-bound (8.29 GB
logical bytes per step, 15.8 KB per node eval, 84-92% of a v5e chip's
HBM at the measured rate), and per-stage kernels (Keccak r4, AES r5)
only tie the XLA scan because each stage's VMEM residency is repaid by
its own HBM carries.  This kernel is the lever PERF.md names: the
~16 KB of per-eval intermediates (expanded seeds, bitsliced AES
planes, Keccak state planes) never leave VMEM — only the level's
input carries (parent seed planes, ctrl words, correction words, round
keys) and its output rows (next seeds, ctrl, payload limbs, proofs)
cross the HBM boundary, ~100 B per eval against the scan path's
15.8 KB.

Round math is shared by import with the hardware-validated per-stage
kernels: the tower-field bitsliced S-box (ops/sbox_tower), ShiftRows /
MixColumns plane helpers (ops/aes_pallas) and the lane-major 12-round
permutation body (ops/keccak_jax._keccak_round), so the megakernel
cannot drift from the paths the chip already ran.

Layouts keep the r5 tiling lessons: every ref block is 2-D+, uint32,
the lane axis is 128-wide (packed words W for the AES phase, dense
reports R = 32*W for the Keccak phase), and every second-to-last block
dim is a multiple of 8 or equals the array dim.  The child/column axes
are tiled by `_block_parents` so the per-grid-step working set stays a
few MB of VMEM.

Two call forms, one stage table:

* fused (`chain=False`): ONE pallas_call running all stages with the
  intermediates in VMEM scratch — the hardware form.  Its interpret
  compile is the known >1 h wall, so it is never traced on the CPU
  fabric.
* chained (`chain=True`, the default whenever `interpret=True`): one
  pallas_call per stage with the intermediate state in explicit
  buffers — the r5 technique that pins every AES round key, every
  Keccak round constant and the final AES round's missing MixColumns
  bit-exactly on CPU without the interpret compile of the fused form
  (tests/test_ops_level_pallas.py).

Gated by MASTIC_LEVEL_PALLAS=1 (read in backend/vidpf_jax at import):
bit-exact by the chained interpret suite.  The fused form does not
compile for a v5e: Mosaic refuses the packed-word to dense-bit
relayout of the Keccak phase ("unsupported shape cast"), so on a TPU
the lever raises that compiler error at the first level step.
tests/test_tpu_compile.py holds the refusal.
"""

import jax
import jax.numpy as jnp
import numpy as np

from ..keccak import ROUND_CONSTANTS
from .aes_pallas import _mix_list, _shift_rows

_U32 = jnp.uint32
_ONES32 = np.uint32(0xFFFFFFFF)
_LANE = 128     # TPU vector lane width: packed words per lane tile
_RATE = 168     # TurboSHAKE128 rate (bytes); proof messages are one
                # absorb block (the wrapper refuses longer binders)
_PROOF_WORDS = 8   # 32-byte node proof = 8 uint32 lane halves

# Stage table (half-open ranges; NUM_STAGES total):
#   0            extend sigma build (seed ^ le128(i), Davies-Meyer in)
#   1..11        extend AES stages 0..10 (whiten, 9 rounds, final)
#   12           extend finish + correct/select + convert sigma build
#   13..23       convert AES stages 0..10
#   24           convert finish: next-seed bit-transpose, payload
#                sample + field correction, ct unpack, ok mask
#   25           node-proof message build + absorb (single block)
#   26..37       Keccak-p rounds 12..23
#   38           squeeze + proof correction
NUM_STAGES = 39

_CONSTS = ("ekp", "ckp", "pseed", "pctrl", "cwsd", "cwct", "wcw",
           "pcw", "bnd")
_OUTS = ("seedb", "ctd", "wlb", "okd", "prf")
_SCRATCH = ("planes", "sigma", "ctp", "klo", "khi")
_STATE = _OUTS + _SCRATCH


def _block_parents(m: int) -> int:
    """Parents per grid step: the smallest count whose convert column
    block (2 children x m blocks each) is a multiple of the 8-sublane
    tile — the r5 Mosaic rule that failed the first AES kernel."""
    return 2 if m % 2 == 0 else 4


def _sigma_rows(x: jax.Array) -> jax.Array:
    """sigma(lo||hi) = hi || hi^lo on a (128, ...) plane-row stack
    (row = 16*bit + byte): pure row shuffling + XOR, the plane-index
    form of xof_jax.fixed_key_blocks_planes' byte moves."""
    rows = []
    for b in range(8):
        lo = x[b * 16:b * 16 + 8]
        hi = x[b * 16 + 8:b * 16 + 16]
        rows.append(hi)
        rows.append(hi ^ lo)
    return jnp.concatenate(rows, axis=0)


def _flip_index_bits(x: jax.Array, i: int) -> jax.Array:
    """XOR le128(i) into a plane-row stack: block indices are < 256,
    so only byte 0's bit planes (rows 16*b) flip — scalar XORs, no
    captured constant arrays (pallas rejects those).  Empty slices are
    left out of the concatenation: Mosaic refuses zero-sized vectors."""
    out = x
    for b in range(8):
        if (i >> b) & 1:
            pieces = [out[:b * 16], out[b * 16:b * 16 + 1] ^ _ONES32,
                      out[b * 16 + 1:]]
            out = jnp.concatenate([p for p in pieces if p.shape[0]],
                                  axis=0)
    return out


def _aes_stage(planes: list, key: list, stage: int) -> list:
    """One AES stage on 8 plane arrays of shape (16, cols, lanes):
    stage 0 = whitening, 1..9 = full rounds, 10 = final round (no
    MixColumns) — identical math to ops/aes_pallas._make_kernel."""
    from .sbox_tower import sbox_planes_tower

    if stage == 0:
        return [planes[b] ^ key[b] for b in range(8)]
    planes = sbox_planes_tower(planes, _ONES32)
    planes = [_shift_rows(p) for p in planes]
    if stage < 10:
        planes = _mix_list(planes)
    return [planes[b] ^ key[b] for b in range(8)]


def _unpack_words(words: jax.Array) -> jax.Array:
    """(rows, W) packed words -> (rows, 32*W) dense bits (report
    r = 32*w + j, the bitslice_pack convention), values 0/1."""
    iota = jax.lax.broadcasted_iota(_U32, (1, 1, 32), 2)
    bits = (words[:, :, None] >> iota) & _U32(1)
    return bits.reshape(words.shape[0], words.shape[1] * 32)


class _Meta:
    """Static kernel parameters (hashable cache key via `key`)."""

    def __init__(self, m, n_limbs, value_len, enc_size, p_limbs,
                 prefix, blen, num_parents_pad, w_pad, lane):
        self.m = m                      # convert blocks per child
        self.n = n_limbs                # 16-bit limbs per element
        self.vl = value_len
        self.enc = enc_size
        self.p = tuple(int(v) for v in p_limbs)
        self.prefix = bytes(prefix)     # static TurboSHAKE prefix
        self.blen = blen                # binder bytes per child
        self.msg_len = len(prefix) + 16 + blen
        self.bn = _block_parents(m)     # parents per grid step
        self.np_ = num_parents_pad      # padded parent count
        self.w = w_pad                  # padded packed-word count
        self.lane = lane                # words per lane tile
        self.tnb = 2 * self.bn          # children per grid step
        self.cb = self.tnb * m          # convert columns per step
        self.tn = 2 * num_parents_pad
        self.c = self.tn * m
        self.r = 32 * w_pad             # dense report lanes
        self.rl = 32 * lane             # dense reports per lane tile

    def key(self):
        return (self.m, self.n, self.vl, self.enc, self.p, self.prefix,
                self.blen, self.np_, self.w, self.lane)


# -- in-kernel field arithmetic (plain 16-bit limbs in uint32) --------

def _limb_lt(a: list, b: list):
    """Borrow out of a - b over matched limb lists (the
    field_jax._sub_limbs borrow chain with static constants)."""
    borrow = None
    for (ai, bi) in zip(a, b):
        need = bi + borrow if borrow is not None else bi
        bor = (ai < need).astype(_U32)
        borrow = bor
    return borrow


def _field_add(a: list, b: list, p: tuple) -> list:
    """(a + b) mod p on limb lists — byte-exact twin of FieldSpec.add
    (propagate to n+1 limbs, one conditional subtract of p)."""
    n = len(p)
    s = []
    carry = None
    for i in range(n):
        v = a[i] + b[i]
        if carry is not None:
            v = v + carry
        s.append(v & _U32(0xFFFF))
        carry = v >> 16
    s.append(carry)
    p_ext = tuple(p) + (0,)
    d = []
    borrow = None
    for i in range(n + 1):
        need = _U32(p_ext[i])
        if borrow is not None:
            need = need + borrow
        bor = (s[i] < need).astype(_U32)
        d.append((s[i] + (bor << 16) - need) & _U32(0xFFFF))
        borrow = bor
    keep = _U32(0) - borrow     # all-ones where a + b < p
    return [(s[i] & keep) | (d[i] & ~keep) for i in range(n)]


# -- the stage bodies -------------------------------------------------

def _run_stages(meta: _Meta, refs: dict, start: int, end: int) -> None:
    mt = meta
    for stage in range(start, end):
        if stage == 0:
            _stage_extend_sigma(mt, refs)
        elif stage <= 11:
            _stage_aes(mt, refs, "ekp", stage - 1, 2 * mt.bn)
        elif stage == 12:
            _stage_correct(mt, refs)
        elif stage <= 23:
            _stage_aes(mt, refs, "ckp", stage - 13, mt.cb)
        elif stage == 24:
            _stage_convert_finish(mt, refs)
        elif stage == 25:
            _stage_absorb(mt, refs)
        elif stage <= 37:
            _stage_keccak(mt, refs, stage - 26 + 12)
        else:
            _stage_proof(mt, refs)


def _stage_extend_sigma(mt: _Meta, refs) -> None:
    ps = jnp.moveaxis(refs["pseed"][...], 0, 1)   # (128, BN, L)
    sigs = [_sigma_rows(_flip_index_bits(ps, i)) for i in (0, 1)]
    # Column = 2*parent + block: left/right extend blocks interleaved.
    s = jnp.stack(sigs, axis=2).reshape(128, mt.tnb, mt.lane)
    refs["planes"][:, :mt.tnb, :] = s
    refs["sigma"][:, :mt.tnb, :] = s


def _stage_aes(mt: _Meta, refs, kp_name: str, aes_stage: int,
               cols: int) -> None:
    st = refs["planes"][:, :cols, :]
    planes = [st[b * 16:(b + 1) * 16] for b in range(8)]
    kp = refs[kp_name]
    key = [kp[(aes_stage * 8 + b) * 16:(aes_stage * 8 + b + 1) * 16]
           for b in range(8)]
    planes = _aes_stage(planes, key, aes_stage)
    refs["planes"][:, :cols, :] = jnp.concatenate(planes, axis=0)


def _stage_correct(mt: _Meta, refs) -> None:
    """Extend finish (Davies-Meyer), ctrl-bit extraction, seed/ctrl
    corrections (mask ANDs on packed words — vidpf_jax.
    _level_core_planes' constant-time discipline), then the convert
    sigma build for all m blocks of every child."""
    enc = refs["planes"][:, :mt.tnb, :] ^ refs["sigma"][:, :mt.tnb, :]
    t = enc[0:1]                       # plane (bit 0, byte 0): ctrl
    seeds = jnp.concatenate(
        [jnp.zeros_like(enc[0:1]), enc[1:]], axis=0)

    # Parent ctrl replicated per child (col = 2*parent + side).
    pc = jnp.moveaxis(refs["pctrl"][...], 0, 1)     # (1, BN, L)
    pcc = jnp.broadcast_to(pc[:, :, None, :],
                           (1, mt.bn, 2, mt.lane)).reshape(
                               1, mt.tnb, mt.lane)
    seeds = seeds ^ (refs["cwsd"][...] & pcc)
    ccw = jnp.moveaxis(refs["cwct"][...], 0, 1)     # (1, 2, L)
    ilv = jnp.broadcast_to(ccw[:, None, :, :],
                           (1, mt.bn, 2, mt.lane)).reshape(
                               1, mt.tnb, mt.lane)
    t = t ^ (pcc & ilv)
    refs["ctp"][...] = jnp.moveaxis(t, 1, 0)        # (2BN, 1, L)

    sigs = [_sigma_rows(_flip_index_bits(seeds, j))
            for j in range(mt.m)]
    s = jnp.stack(sigs, axis=2).reshape(128, mt.cb, mt.lane)
    refs["planes"][...] = s
    refs["sigma"][...] = s


def _stage_convert_finish(mt: _Meta, refs) -> None:
    """Davies-Meyer finish on the convert stream, then the in-VMEM
    plane->byte bit-transpose: next-seed bytes (block 0) feed the
    node-proof message, payload bytes (blocks 1..m-1) become field
    limbs with the in-range mask and the w correction word applied."""
    enc = refs["planes"][...] ^ refs["sigma"][...]
    st = enc.reshape(128, mt.tnb, mt.m, mt.lane)

    def dense_byte(j: int, k: int) -> jax.Array:
        """Byte k of stream block j per (child, report): unpack the 8
        bit planes of one byte position to report-dense values."""
        acc = None
        for b in range(8):
            bits = _unpack_words(st[b * 16 + k, :, j, :]) << b
            acc = bits if acc is None else acc | bits
        return acc                       # (2BN, RL) values 0..255

    for k in range(16):
        refs["seedb"][:, k, :] = dense_byte(0, k)

    ctd = _unpack_words(refs["ctp"][:, 0, :])
    refs["ctd"][:, 0, :] = ctd
    mask = _U32(0) - ctd                 # select mask per (child, r)

    byte_cache: dict = {}

    def payload_byte(pos: int) -> jax.Array:
        if pos not in byte_cache:
            byte_cache[pos] = dense_byte(pos // 16 + 1, pos % 16)
        return byte_cache[pos]

    ok_all = None
    for e in range(mt.vl):
        limbs = []
        for li in range(mt.n):
            p0 = e * mt.enc + 2 * li
            limbs.append(payload_byte(p0)
                         | (payload_byte(p0 + 1) << 8))
        # In-range: value < p (the XOF rejection predicate).
        ok_e = _limb_lt(limbs, [_U32(v) for v in mt.p])
        ok_all = ok_e if ok_all is None else ok_all & ok_e
        # w correction: w + w_cw mod p where the child holds ctrl.
        cw = [refs["wcw"][e * mt.n + li:e * mt.n + li + 1, 0, :]
              for li in range(mt.n)]
        corrected = _field_add(limbs, cw, mt.p)
        for li in range(mt.n):
            sel = (limbs[li] & ~mask) | (corrected[li] & mask)
            refs["wlb"][:, e * mt.n + li, :] = sel
    refs["okd"][:, 0, :] = ok_all


def _stage_absorb(mt: _Meta, refs) -> None:
    """Build the padded TurboSHAKE128 message lanes (prefix | next
    seed | binder, domain 1, pad10*1) and absorb into the zero state:
    message fits one rate block by the wrapper's gate."""
    bnd = refs["bnd"][...]               # (2BN, 1, B_pad) byte values

    def msg_byte(p: int):
        """Static message byte p: scalar, (2BN, RL) seed byte, or
        (2BN, 1) binder column (broadcast over reports)."""
        lp = len(mt.prefix)
        val = 0
        if p < lp:
            val = mt.prefix[p]
        elif p < lp + 16:
            return refs["seedb"][:, p - lp, :]
        elif p < mt.msg_len:
            return bnd[:, 0, p - lp - 16:p - lp - 15]
        if p == mt.msg_len:
            val ^= 0x01                  # domain byte
        if p == _RATE - 1:
            val ^= 0x80                  # pad10*1 final bit
        return val

    for i in range(25):
        for (half, ref) in ((0, refs["klo"]), (1, refs["khi"])):
            if i >= 21:                  # capacity lanes stay zero
                ref[:, i, :] = jnp.zeros((mt.tnb, mt.rl), _U32)
                continue
            base = 8 * i + 4 * half
            scalar = 0
            arr = None
            for t in range(4):
                b = msg_byte(base + t)
                if isinstance(b, int):
                    scalar |= b << (8 * t)
                else:
                    part = (b if b.ndim == 2 and b.shape[1] == mt.rl
                            else jnp.broadcast_to(b, (mt.tnb, 1)))
                    part = part.astype(_U32) << (8 * t)
                    arr = part if arr is None else arr | part
            word = jnp.full((mt.tnb, mt.rl), scalar, _U32)
            if arr is not None:
                word = word | arr        # byte fields are disjoint
            ref[:, i, :] = word


def _stage_keccak(mt: _Meta, refs, r: int) -> None:
    from .keccak_jax import _keccak_round

    a = [(refs["klo"][:, i, :], refs["khi"][:, i, :])
         for i in range(25)]
    rc = ROUND_CONSTANTS[r]
    a = _keccak_round(a, _U32(rc & 0xFFFFFFFF), _U32(rc >> 32))
    for i in range(25):
        refs["klo"][:, i, :] = a[i][0]
        refs["khi"][:, i, :] = a[i][1]


def _stage_proof(mt: _Meta, refs) -> None:
    """Squeeze the 32 proof bytes (lanes 0..3) and fold in proof_cw
    where the child holds the ctrl bit, at uint32-word granularity."""
    mask = _U32(0) - refs["ctd"][:, 0, :]
    for t in range(_PROOF_WORDS):
        src = refs["klo"] if t % 2 == 0 else refs["khi"]
        cw = refs["pcw"][t:t + 1, 0, :]
        refs["prf"][:, t, :] = src[:, t // 2, :] ^ (cw & mask)


# -- pallas_call assembly ---------------------------------------------

def _shapes(mt: _Meta) -> dict:
    """Full-array shape per buffer (blocks in _specs slice these)."""
    return {
        "ekp": (11 * 128, 1, mt.w), "ckp": (11 * 128, 1, mt.w),
        "pseed": (mt.np_, 128, mt.w), "pctrl": (mt.np_, 1, mt.w),
        "cwsd": (128, 1, mt.w), "cwct": (2, 1, mt.w),
        "wcw": (mt.vl * mt.n, 1, mt.r), "pcw": (_PROOF_WORDS, 1, mt.r),
        "bnd": (mt.tn, 1, _LANE),
        "planes": (128, mt.c, mt.w), "sigma": (128, mt.c, mt.w),
        "ctp": (mt.tn, 1, mt.w),
        "seedb": (mt.tn, 16, mt.r), "ctd": (mt.tn, 1, mt.r),
        "wlb": (mt.tn, mt.vl * mt.n, mt.r), "okd": (mt.tn, 1, mt.r),
        "prf": (mt.tn, _PROOF_WORDS, mt.r),
        "klo": (mt.tn, 25, mt.r), "khi": (mt.tn, 25, mt.r),
    }


def _specs(mt: _Meta) -> dict:
    """BlockSpec per buffer over the (lane-tile j, parent-tile i)
    grid.  Node-major leading axes keep every second-to-last block dim
    either a multiple of 8 or equal to the array dim (the r5 Mosaic
    tiling rule); lane axes are `lane` packed words or 32*lane dense
    reports."""
    from jax.experimental import pallas as pl

    (bn, tnb, cb, l, rl) = (mt.bn, mt.tnb, mt.cb, mt.lane, mt.rl)
    # mastic-allow: PL004 — the klo/khi 25-row blocks equal the full
    # Keccak lane-axis dim (25 lanes, never tiled), the case Mosaic
    # accepts for a non-multiple-of-8 sublane dim
    return {
        "ekp": pl.BlockSpec((11 * 128, 1, l), lambda j, i: (0, 0, j)),
        "ckp": pl.BlockSpec((11 * 128, 1, l), lambda j, i: (0, 0, j)),
        "pseed": pl.BlockSpec((bn, 128, l), lambda j, i: (i, 0, j)),
        "pctrl": pl.BlockSpec((bn, 1, l), lambda j, i: (i, 0, j)),
        "cwsd": pl.BlockSpec((128, 1, l), lambda j, i: (0, 0, j)),
        "cwct": pl.BlockSpec((2, 1, l), lambda j, i: (0, 0, j)),
        "wcw": pl.BlockSpec((mt.vl * mt.n, 1, rl),
                            lambda j, i: (0, 0, j)),
        "pcw": pl.BlockSpec((_PROOF_WORDS, 1, rl),
                            lambda j, i: (0, 0, j)),
        "bnd": pl.BlockSpec((tnb, 1, _LANE), lambda j, i: (i, 0, 0)),
        "planes": pl.BlockSpec((128, cb, l), lambda j, i: (0, i, j)),
        "sigma": pl.BlockSpec((128, cb, l), lambda j, i: (0, i, j)),
        "ctp": pl.BlockSpec((tnb, 1, l), lambda j, i: (i, 0, j)),
        "seedb": pl.BlockSpec((tnb, 16, rl), lambda j, i: (i, 0, j)),
        "ctd": pl.BlockSpec((tnb, 1, rl), lambda j, i: (i, 0, j)),
        "wlb": pl.BlockSpec((tnb, mt.vl * mt.n, rl),
                            lambda j, i: (i, 0, j)),
        "okd": pl.BlockSpec((tnb, 1, rl), lambda j, i: (i, 0, j)),
        "prf": pl.BlockSpec((tnb, _PROOF_WORDS, rl),
                            lambda j, i: (i, 0, j)),
        "klo": pl.BlockSpec((tnb, 25, rl), lambda j, i: (i, 0, j)),
        "khi": pl.BlockSpec((tnb, 25, rl), lambda j, i: (i, 0, j)),
    }


_CALL_CACHE: dict = {}


def _chained_call(mt: _Meta, start: int, end: int, interpret: bool):
    """One pallas_call covering stages [start, end) with the full
    intermediate state in explicit HBM buffers (in AND out), so stages
    chain across calls — the r5 per-stage validation technique."""
    from jax.experimental import pallas as pl

    cache_key = ("chain", mt.key(), start, end, interpret)
    call = _CALL_CACHE.get(cache_key)
    if call is not None:
        return call
    shapes = _shapes(mt)
    specs = _specs(mt)

    def kernel(*refs):
        named = dict(zip(_CONSTS + tuple("in_" + s for s in _STATE)
                         + _STATE, refs))
        for s in _STATE:   # carry untouched state through this stage
            named[s][...] = named["in_" + s][...]
        _run_stages(mt, named, start, end)

    grid = (mt.w // mt.lane, mt.np_ // mt.bn)
    call = pl.pallas_call(
        kernel,
        out_shape=tuple(jax.ShapeDtypeStruct(shapes[s], jnp.uint32)
                        for s in _STATE),
        grid=grid,
        in_specs=[specs[s] for s in _CONSTS]
        + [specs[s] for s in _STATE],
        out_specs=tuple(specs[s] for s in _STATE),
        interpret=interpret,
    )
    _CALL_CACHE[cache_key] = call
    return call


def _fused_call(mt: _Meta, interpret: bool):
    """The production form: ONE pallas_call, all stages, intermediates
    in VMEM scratch — nothing but the level's inputs and outputs
    crosses HBM.  Never traced in interpret mode by the wrapper (the
    unrolled pipeline is the known >1 h interpret compile)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    cache_key = ("fused", mt.key(), interpret)
    call = _CALL_CACHE.get(cache_key)
    if call is not None:
        return call
    shapes = _shapes(mt)
    specs = _specs(mt)
    scratch = {
        "planes": (128, mt.cb, mt.lane), "sigma": (128, mt.cb, mt.lane),
        "ctp": (mt.tnb, 1, mt.lane),
        "klo": (mt.tnb, 25, mt.rl), "khi": (mt.tnb, 25, mt.rl),
    }

    def kernel(*refs):
        named = dict(zip(_CONSTS + _OUTS + _SCRATCH, refs))
        _run_stages(mt, named, 0, NUM_STAGES)

    grid = (mt.w // mt.lane, mt.np_ // mt.bn)
    call = pl.pallas_call(
        kernel,
        out_shape=tuple(jax.ShapeDtypeStruct(shapes[s], jnp.uint32)
                        for s in _OUTS),
        grid=grid,
        in_specs=[specs[s] for s in _CONSTS],
        out_specs=tuple(specs[s] for s in _OUTS),
        scratch_shapes=[pltpu.VMEM(scratch[s], jnp.uint32)
                        for s in _SCRATCH],
        interpret=interpret,
    )
    _CALL_CACHE[cache_key] = call
    return call


# -- host-facing wrapper ----------------------------------------------

def _pad_axis(x: jax.Array, axis: int, size: int) -> jax.Array:
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def supports(convert_blocks: int, prefix_len: int,
             binder_bytes: int) -> bool:
    """Shapes the megakernel serves; callers fall back to the scan
    path otherwise.  The message must fit one absorb block and the
    convert column block must stay a small multiple of the VMEM tile
    (huge-payload instantiations like SumVec(1024) stream hundreds of
    blocks and belong on the scan path)."""
    return (convert_blocks <= 8
            and prefix_len + 16 + binder_bytes <= _RATE - 1)


def level_step_pallas(spec, convert_blocks: int, ext_rk: jax.Array,
                      conv_rk: jax.Array, parent_seed: jax.Array,
                      parent_ctrl: jax.Array, cw_slice,
                      prefix: bytes, node_binder,
                      interpret: bool = False, chain=None):
    """Run one full VIDPF level in the megakernel.

    spec: ops/field_jax.FieldSpec; ext_rk/conv_rk (R, 11, 16) uint8;
    parent_seed (R, N, 16) uint8; parent_ctrl (R, N) bool; cw_slice =
    (seed_cw (R,16), ctrl_cw (R,2), w_cw (R,VL,n), proof_cw (R,32));
    prefix = the static TurboSHAKE node-proof message prefix;
    node_binder (2N, blen) uint8 (static or traced — same for every
    report).  Returns (next_seed (R,2N,16) u8, ct (R,2N) bool, w
    (R,2N,VL,n) u32 plain limbs, ok (R,2N) bool, proof (R,2N,32) u8),
    byte-exact vs vidpf_jax's scan-path eval_step.

    `chain` selects per-stage kernel calls — one pallas_call per
    pipeline stage with the intermediate state in explicit buffers,
    which is what pins each AES round key, each Keccak round constant
    and the final AES round's missing MixColumns individually.  The
    default follows `interpret`, keeping the CPU fabric off the fused
    form's interpret-compile wall.
    """
    from ..ops.aes_jax import bitslice_keys, bitslice_pack, pack_mask

    (seed_cw, ctrl_cw, w_cw, proof_cw) = cw_slice
    (num_reports, num_parents) = parent_ctrl.shape
    binder = jnp.asarray(node_binder)
    blen = int(binder.shape[-1])
    assert supports(convert_blocks, len(prefix), blen), \
        "shape outside the megakernel envelope (caller must gate)"
    if chain is None:
        chain = interpret

    # Pad reports to the packed-word lane tile and parents to the
    # grid block; dead lanes carry zeros and are sliced off below.
    # The chained (CPU validation) form shrinks the lane tile to the
    # batch so small differential shapes stay small; the fused
    # (hardware) form always uses the full 128-lane tile.
    r32 = -(-num_reports // 32) * 32
    w_words = r32 // 32
    lane = (min(_LANE, 1 << (w_words - 1).bit_length()) if chain
            else _LANE)
    w_pad = -(-w_words // lane) * lane
    bn = _block_parents(convert_blocks)
    np_pad = max(bn, -(-num_parents // bn) * bn)
    mt = _Meta(convert_blocks, spec.num_limbs, w_cw.shape[-2],
               spec.encoded_size, spec.P, prefix, blen, np_pad,
               w_pad, lane)

    def planes_in(x, mid):
        """uint8 (R, ..., 16) -> padded plane rows (mid, 128, w_pad)
        node-major (mid = middle-axis size after padding)."""
        p = bitslice_pack(_pad_axis(x, 0, 32 * w_pad))
        p = p.reshape((128,) + p.shape[2:])
        if p.ndim == 2:
            p = p[:, None, :]
        p = _pad_axis(p, 1, mid)
        return jnp.moveaxis(p, 1, 0)

    pseed = planes_in(parent_seed, np_pad)
    cwsd = jnp.moveaxis(planes_in(seed_cw, 1), 0, 1)   # (128, 1, W)
    pctrl = _pad_axis(
        pack_mask(_pad_axis(parent_ctrl, 0, 32 * w_pad)),
        0, np_pad)[:, None, :]
    cwct = pack_mask(_pad_axis(ctrl_cw, 0, 32 * w_pad))[:, None, :]
    ekp = bitslice_keys(
        _pad_axis(ext_rk, 0, 32 * w_pad)).reshape(11 * 128, 1, w_pad)
    ckp = bitslice_keys(
        _pad_axis(conv_rk, 0, 32 * w_pad)).reshape(11 * 128, 1, w_pad)
    wcw = jnp.moveaxis(
        _pad_axis(w_cw, 0, mt.r).reshape(mt.r, -1).astype(_U32),
        0, 1)[:, None, :]
    shifts = (jnp.arange(4, dtype=_U32) * 8)[None, None, :]
    pcw = jnp.sum(
        _pad_axis(proof_cw, 0, mt.r).reshape(mt.r, 8, 4).astype(_U32)
        << shifts, axis=-1, dtype=_U32)
    pcw = jnp.moveaxis(pcw, 0, 1)[:, None, :]
    bnd = _pad_axis(_pad_axis(binder.astype(_U32), 0, mt.tn),
                    1, _LANE)[:, None, :]

    consts = (ekp, ckp, pseed, pctrl, cwsd, cwct, wcw, pcw, bnd)
    if chain:
        shapes = _shapes(mt)
        state = tuple(jnp.zeros(shapes[s], _U32) for s in _STATE)
        for stage in range(NUM_STAGES):
            state = _chained_call(mt, stage, stage + 1,
                                  interpret)(*consts, *state)
        outs = state[:len(_OUTS)]
    else:
        outs = _fused_call(mt, interpret)(*consts)
    (seedb, ctd, wlb, okd, prf) = outs

    tn = 2 * num_parents
    next_seed = jnp.moveaxis(
        seedb[:tn, :, :num_reports], 2, 0).astype(jnp.uint8)
    ct = jnp.moveaxis(ctd[:tn, 0, :num_reports], 1, 0).astype(bool)
    w = jnp.moveaxis(
        wlb[:tn, :, :num_reports].reshape(
            tn, mt.vl, mt.n, num_reports), 3, 0)
    ok = jnp.moveaxis(okd[:tn, 0, :num_reports], 1, 0).astype(bool)
    byte_sh = (jnp.arange(4, dtype=_U32) * 8)[None, None, :, None]
    prf_bytes = ((prf[:tn, :, None, :num_reports] >> byte_sh)
                 & _U32(0xFF)).reshape(tn, 32, num_reports)
    proof = jnp.moveaxis(prf_bytes, 2, 0).astype(jnp.uint8)
    return (next_seed, ct, w, ok, proof)
