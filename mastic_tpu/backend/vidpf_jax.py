"""Batched VIDPF: dense level-synchronous gen / eval over JAX arrays.

Byte-exact twin of the scalar mastic_tpu.vidpf (itself conformance-
locked against /root/reference/test_vec/mastic/), with the per-report
pointer tree replaced by (reports x nodes) arrays:

* one fixed-key AES key schedule per (report, usage), reused for every
  node of that report's tree (see mastic_tpu/backend/xof_jax.py);
* within a level, all nodes extend / correct / convert / hash in one
  fused batch; the level loop is the only sequential axis (it is a PRG
  chain, reference vidpf.py:250-258);
* every secret-dependent choice is a lane select (jnp.where) — the
  constant-time discipline the reference asks for (vidpf.py:116-119)
  holds by construction.

Field payloads are carried as plain (non-Montgomery) 16-bit limbs:
the VIDPF only ever adds/subtracts payloads, so no domain conversion
is needed until the FLP (which multiplies) takes over.
"""

import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common import to_le_bytes
from ..dst import USAGE_CONVERT, USAGE_EXTEND, USAGE_NODE_PROOF, dst
from ..field import Field
from ..ops.aes_jax import (bitslice_keys, bitslice_pack,
                           bitslice_unpack, pack_mask, unpack_mask)
from ..ops.field_jax import FieldSpec, spec_for
from ..ops.keccak_jax import turbo_shake128_dynamic
from ..vidpf import PROOF_SIZE, CorrectionWord
from .schedule import LevelSchedule
from .xof_jax import (fixed_key_blocks, fixed_key_blocks_planes,
                      fixed_key_schedule, sample_vec, ts_prefix,
                      turboshake_xof)

_U8 = jnp.uint8

KEY_SIZE = 16

# Third backend path: route the whole level step (extend -> correct ->
# convert -> node proof) through the fused-VMEM Pallas megakernel
# (ops/level_pallas.py) instead of chaining scan-path stages.  Read
# once at import like the per-stage levers (MASTIC_KECCAK_PALLAS /
# MASTIC_AES_PALLAS in ops/); interpret mode is selected per call from
# the active backend so the CPU fabric exercises the kernel path
# bit-exactly via chained per-stage calls.
USE_LEVEL_PALLAS = os.environ.get("MASTIC_LEVEL_PALLAS", "0") == "1"


class BatchedCorrectionWords(NamedTuple):
    """Correction words for a report batch, one slice per tree level.

    seed  (R, BITS, 16) uint8
    ctrl  (R, BITS, 2) bool       [left, right]
    w     (R, BITS, VALUE_LEN, n) uint32 plain limbs
    proof (R, BITS, 32) uint8
    """
    seed: jax.Array
    ctrl: jax.Array
    w: jax.Array
    proof: jax.Array


class EvalState(NamedTuple):
    """One level's node states for a report batch: the resumable carry
    of the level loop (the reference's cache-across-rounds note,
    vidpf.py:243-245, made explicit)."""
    seed: jax.Array   # (R, N, 16) uint8
    ctrl: jax.Array   # (R, N) bool
    w: jax.Array      # (R, N, VALUE_LEN, n) uint32 plain limbs
    proof: jax.Array  # (R, N, 32) uint8


def pack_path_bits(bits_arr: jax.Array) -> jax.Array:
    """MSB-first bit packing of (..., L) bools -> (..., ceil(L/8))
    uint8 (device twin of common.pack_bits)."""
    length = bits_arr.shape[-1]
    nbytes = (length + 7) // 8
    padded = jnp.zeros(bits_arr.shape[:-1] + (nbytes * 8,), jnp.int32)
    padded = padded.at[..., :length].set(bits_arr.astype(jnp.int32))
    weights = (1 << (7 - np.arange(8))).astype(np.int32)
    grouped = padded.reshape(padded.shape[:-1] + (nbytes, 8))
    return jnp.sum(grouped * weights, axis=-1).astype(_U8)


class BatchedVidpf:
    """Batched VIDPF over `field` with input length `bits` and payload
    length `value_len` (scalar twin: mastic_tpu.vidpf.Vidpf)."""

    def __init__(self, field: type[Field], bits: int, value_len: int):
        self.field = field
        self.spec: FieldSpec = spec_for(field)
        self.BITS = bits
        self.VALUE_LEN = value_len
        # Convert reads a 16-byte next seed then VALUE_LEN elements.
        payload_bytes = value_len * self.spec.encoded_size
        self.convert_blocks = 1 + (payload_bytes + 15) // 16
        # Optional mesh-sharding hook: applied to every level's
        # EvalState so the (reports x nodes) grid stays distributed
        # (set by mastic_tpu.parallel.mesh).
        self.constrain_state = None

    # -- per-report key schedules ----------------------------------

    def roundkeys(self, ctx: bytes, nonces: jax.Array):
        """The two fixed-key AES schedules per report: (extend rk,
        convert rk), each (R, 11, 16)."""
        batch = nonces.shape[:-1]
        ext = fixed_key_schedule(dst(ctx, USAGE_EXTEND), nonces, batch)
        conv = fixed_key_schedule(dst(ctx, USAGE_CONVERT), nonces, batch)
        return (ext, conv)

    # -- the three per-node primitives -----------------------------

    def extend(self, ext_rk: jax.Array, seeds: jax.Array):
        """Extend seeds (R, N..., 16) into left/right child seeds and
        control bits (the LSB of byte 0, then cleared — reference
        vidpf.py:330-350)."""
        blocks = fixed_key_blocks(ext_rk, seeds, 2)
        (s_l, s_r) = (blocks[..., :16], blocks[..., 16:])
        t_l = (s_l[..., 0] & 1).astype(bool)
        t_r = (s_r[..., 0] & 1).astype(bool)
        mask = _U8(0xFE)
        s_l = s_l.at[..., 0].set(s_l[..., 0] & mask)
        s_r = s_r.at[..., 0].set(s_r[..., 0] & mask)
        return ((s_l, s_r), (t_l, t_r))

    def convert(self, conv_rk: jax.Array, seeds: jax.Array):
        """Convert seeds (R, N..., 16) -> (next seed, payload limbs,
        in-range mask per node) (reference vidpf.py:352-364)."""
        stream = fixed_key_blocks(conv_rk, seeds, self.convert_blocks)
        next_seed = stream[..., :16]
        (w, ok) = sample_vec(self.spec, stream, self.VALUE_LEN, offset=16)
        return (next_seed, w, ok)

    def node_proof(self, ctx: bytes, seeds: jax.Array, binder,
                   batch_shape: tuple) -> jax.Array:
        """TurboSHAKE node proof over (seed, BITS, level, path); the
        (BITS, level, path) binder is passed in pre-encoded (static for
        eval schedules, device-packed for gen)."""
        return turboshake_xof(dst(ctx, USAGE_NODE_PROOF), seeds,
                              (binder,), PROOF_SIZE, batch_shape)

    # -- key generation (client side; reference vidpf.py:103-211) --

    def _node_proof_dynamic(self, ctx: bytes, seeds: jax.Array,
                            path: jax.Array, i: jax.Array) -> jax.Array:
        """Node proof with the level index traced: the message is
        prefix | seed | BITS | le16(i) | packed path, hashed over its
        runtime length (path bytes = i//8 + 1).  Byte-exact vs the
        static node_proof for every level (the dynamic sponge masks
        the capacity tail)."""
        num_reports = seeds.shape[0]
        prefix = np.frombuffer(
            ts_prefix(dst(ctx, USAGE_NODE_PROOF), KEY_SIZE), np.uint8)
        bits_le = np.frombuffer(to_le_bytes(self.BITS, 2), np.uint8)
        i_le = jnp.stack([i & 0xFF, (i >> 8) & 0xFF]).astype(_U8)
        msg = jnp.concatenate([
            jnp.broadcast_to(jnp.asarray(prefix),
                             (num_reports, prefix.shape[0])),
            seeds,
            jnp.broadcast_to(jnp.asarray(bits_le), (num_reports, 2)),
            jnp.broadcast_to(i_le, (num_reports, 2)),
            path,
        ], axis=-1)
        length = prefix.shape[0] + KEY_SIZE + 4 + i // 8 + 1
        return turbo_shake128_dynamic(msg, jnp.int32(length), 1,
                                      PROOF_SIZE)

    def gen(self, alphas: jax.Array, betas: jax.Array, ctx: bytes,
            nonces: jax.Array, rand: jax.Array):
        """Batched VIDPF key generation.

        alphas (R, BITS) bool; betas (R, VALUE_LEN, n) plain limbs;
        nonces (R, 16); rand (R, 32) uint8.
        Returns (BatchedCorrectionWords, keys (R, 2, 16), ok (R,)).

        The level loop runs under lax.scan — the per-level body is
        identical and every shape is level-independent (the one
        varying quantity, the node-proof binder's packed on-path
        prefix, is precomputed per level and hashed with the
        runtime-length sponge), so the compiled program is O(1) in
        BITS rather than a BITS-times-unrolled graph (a 64-bit client
        program previously took minutes of XLA compile; the chain
        itself is sequential either way, reference vidpf.py:136-209).
        """
        (num_reports, bits) = alphas.shape
        assert bits == self.BITS
        (ext_rk, conv_rk) = self.roundkeys(ctx, nonces)

        keys = jnp.stack([rand[:, :KEY_SIZE], rand[:, KEY_SIZE:]], axis=1)

        # Per-level packed on-path prefixes: row i equals
        # pack_path_bits(alphas[:, :i+1]) zero-extended to capacity
        # (MSB-first packing => masking trailing bytes/bits of the
        # full packing).
        path_cap = (bits + 7) // 8
        packed_full = pack_path_bits(alphas)            # (R, cap)
        lvl = jnp.arange(bits, dtype=jnp.int32)[:, None]
        byte_idx = jnp.arange(path_cap, dtype=jnp.int32)[None, :]
        keep = jnp.left_shift(0xFF, 7 - (lvl % 8)) & 0xFF
        byte_mask = jnp.where(
            byte_idx * 8 + 7 <= lvl, 0xFF,
            jnp.where(byte_idx * 8 <= lvl, keep, 0)).astype(_U8)
        level_paths = packed_full[None] & byte_mask[:, None, :]

        def body(carry, xs):
            (s0, s1, t0, t1, ok) = carry
            (bit, path, i) = xs

            ((s0l, s0r), (t0l, t0r)) = self.extend(ext_rk, s0)
            ((s1l, s1r), (t1l, t1r)) = self.extend(ext_rk, s1)

            # The losing child's seeds are forced to collide; control
            # corrections make on-path ctrl bits shares of 1.
            sel = bit[:, None]
            seed_cw = jnp.where(sel, s0l ^ s1l, s0r ^ s1r)
            ctrl_cw_l = t0l ^ t1l ^ ~bit
            ctrl_cw_r = t0r ^ t1r ^ bit

            s0k = jnp.where(sel, s0r, s0l)
            s1k = jnp.where(sel, s1r, s1l)
            t0k = jnp.where(bit, t0r, t0l)
            t1k = jnp.where(bit, t1r, t1l)
            ctrl_cw_keep = jnp.where(bit, ctrl_cw_r, ctrl_cw_l)

            s0k = jnp.where(t0[:, None], s0k ^ seed_cw, s0k)
            t0k = t0k ^ (t0 & ctrl_cw_keep)
            s1k = jnp.where(t1[:, None], s1k ^ seed_cw, s1k)
            t1k = t1k ^ (t1 & ctrl_cw_keep)

            (seed0, w0, ok0) = self.convert(conv_rk, s0k)
            (seed1, w1, ok1) = self.convert(conv_rk, s1k)
            ok = ok & ok0 & ok1

            # Payload correction: on-path shares must sum to beta.
            w_cw = self.spec.add(self.spec.sub(betas, w0), w1)
            w_cw = jnp.where(t1k[:, None, None],
                             self.spec.neg(w_cw), w_cw)

            # Node-proof correction, binding the on-path prefix.
            proof_cw = \
                self._node_proof_dynamic(ctx, seed0, path, i) ^ \
                self._node_proof_dynamic(ctx, seed1, path, i)

            ys = (seed_cw,
                  jnp.stack([ctrl_cw_l, ctrl_cw_r], axis=-1),
                  w_cw, proof_cw)
            return ((seed0, seed1, t0k, t1k, ok), ys)

        init = (keys[:, 0], keys[:, 1],
                jnp.zeros(num_reports, bool),
                jnp.ones(num_reports, bool),
                jnp.ones(num_reports, bool))
        ((_s0, _s1, _t0, _t1, ok), ys) = jax.lax.scan(
            body, init,
            (alphas.T, level_paths, jnp.arange(bits, dtype=jnp.int32)))

        (cw_seed, cw_ctrl, cw_w, cw_proof) = ys
        cws = BatchedCorrectionWords(
            seed=jnp.moveaxis(cw_seed, 0, 1),
            ctrl=jnp.moveaxis(cw_ctrl, 0, 1),
            w=jnp.moveaxis(cw_w, 0, 1),
            proof=jnp.moveaxis(cw_proof, 0, 1),
        )
        return (cws, keys, ok)

    # -- evaluation (aggregator side; reference vidpf.py:213-325) --

    def root_state(self, agg_id: int, keys: jax.Array) -> EvalState:
        """The pre-level-0 carry: root seed = the party's key, root
        ctrl = agg_id."""
        num_reports = keys.shape[0]
        return EvalState(
            seed=keys[:, None, :],
            ctrl=jnp.full((num_reports, 1), bool(agg_id)),
            w=jnp.zeros((num_reports, 1, self.VALUE_LEN,
                         self.spec.num_limbs), jnp.uint32),
            proof=jnp.zeros((num_reports, 1, PROOF_SIZE), _U8),
        )

    def level_core(self, ext_rk: jax.Array, conv_rk: jax.Array,
                   parents: EvalState, cw_slice):
        """extend + correct + convert for one level (everything except
        the node proof): returns (next_seed (R, 2N, 16), ct (R, 2N)
        bool, w plain limbs, ok per child).  Children are interleaved
        (left0, right0, left1, right1, ...), preserving lexicographic
        order.

        Large report batches run entirely in the bitsliced plane
        domain — parent-seed pack to next-seed unpack with no byte
        round-trips in between (corrections are mask ANDs on packed
        words).  Small batches use the byte path."""
        (num_reports, num_parents) = parents.ctrl.shape
        if num_reports >= 32 and num_reports % 32 == 0:
            return self._level_core_planes(ext_rk, conv_rk, parents,
                                           cw_slice)
        (seed_cw, ctrl_cw, w_cw, _proof_cw) = cw_slice

        ((s_l, s_r), (t_l, t_r)) = self.extend(ext_rk, parents.seed)

        # Correct where the parent holds the control bit.
        sel = parents.ctrl[..., None]
        s_l = jnp.where(sel, s_l ^ seed_cw[:, None, :], s_l)
        s_r = jnp.where(sel, s_r ^ seed_cw[:, None, :], s_r)
        t_l = t_l ^ (parents.ctrl & ctrl_cw[:, None, 0])
        t_r = t_r ^ (parents.ctrl & ctrl_cw[:, None, 1])

        cs = jnp.stack([s_l, s_r], axis=2).reshape(
            num_reports, 2 * num_parents, KEY_SIZE)
        ct = jnp.stack([t_l, t_r], axis=2).reshape(
            num_reports, 2 * num_parents)

        (next_seed, w, ok) = self.convert(conv_rk, cs)
        w = jnp.where(ct[..., None, None],
                      self.spec.add(w, w_cw[:, None]), w)
        return (next_seed, ct, w, ok)

    def _level_core_planes(self, ext_rk: jax.Array, conv_rk: jax.Array,
                           parents: EvalState, cw_slice):
        """Plane-domain level core: one bitslice_pack of the parent
        seeds in, one bitslice_unpack of the next seeds + payload out."""
        (seed_cw, ctrl_cw, w_cw, _proof_cw) = cw_slice
        (num_reports, num_parents) = parents.ctrl.shape

        ext_kp = bitslice_keys(ext_rk)          # (11, 8, 16, W)
        conv_kp = bitslice_keys(conv_rk)
        sp = bitslice_pack(parents.seed)        # (8, 16, N, W)
        pctrl = pack_mask(parents.ctrl)         # (N, W)

        ext = fixed_key_blocks_planes(ext_kp, sp, 2)  # (8,16,N,2,W)
        s_l = ext[..., 0, :]
        s_r = ext[..., 1, :]
        # Control bits are plane (0, byte 0); clear them in the seeds.
        t_l = s_l[0, 0]                         # (N, W) packed bits
        t_r = s_r[0, 0]
        s_l = s_l.at[0, 0].set(jnp.zeros_like(t_l))
        s_r = s_r.at[0, 0].set(jnp.zeros_like(t_r))

        # Corrections: secret-dependent selects become mask ANDs on
        # packed words (the same constant-time discipline, denser).
        cw_planes = bitslice_pack(seed_cw)      # (8, 16, W)
        sel = cw_planes[:, :, None, :] & pctrl[None, None, :, :]
        s_l = s_l ^ sel
        s_r = s_r ^ sel
        cw_ctrl = pack_mask(ctrl_cw)            # (2, W)
        t_l = t_l ^ (pctrl & cw_ctrl[0])
        t_r = t_r ^ (pctrl & cw_ctrl[1])

        cs = jnp.stack([s_l, s_r], axis=3).reshape(
            (8, 16, 2 * num_parents) + sp.shape[-1:])
        ct_words = jnp.stack([t_l, t_r], axis=1).reshape(
            2 * num_parents, -1)

        stream = fixed_key_blocks_planes(conv_kp, cs,
                                         self.convert_blocks)
        next_seed = bitslice_unpack(stream[..., 0, :])[:num_reports]
        # Unpack payload blocks (8, 16, 2N, m-1, W) -> bytes
        # (R, 2N, (m-1)*16), block-major per node.
        tail = stream[..., 1:, :]
        tail = bitslice_unpack(
            tail.reshape(tail.shape[:2] + (-1,) + tail.shape[-1:]))
        tail = tail[:num_reports].reshape(
            num_reports, 2 * num_parents, self.convert_blocks - 1, 16)
        stream_bytes = tail.reshape(num_reports, 2 * num_parents, -1)
        (w, ok) = sample_vec(self.spec, stream_bytes, self.VALUE_LEN)

        ct = unpack_mask(ct_words, num_reports)  # (R, 2N)
        w = jnp.where(ct[..., None, None],
                      self.spec.add(w, w_cw[:, None]), w)
        return (next_seed, ct, w, ok)

    def eval_step(self, ext_rk: jax.Array, conv_rk: jax.Array,
                  parents: EvalState, cw_slice, ctx: bytes,
                  node_binder: np.ndarray):
        """One level of the tree: extend every parent, correct, convert
        and hash both children (see level_core).  Returns (EvalState
        for the children, ok (R,)).

        With MASTIC_LEVEL_PALLAS=1 and a supported shape, the whole
        level runs in the fused-VMEM megakernel (ops/level_pallas.py):
        same byte-exact outputs, but the per-eval intermediates never
        round-trip HBM (PERF.md §3's roofline lever).  Unsupported
        shapes (tiny batches, huge-payload converts, binders past one
        sponge block) keep the scan path.  The fused form does not
        compile for a v5e yet (tests/test_tpu_compile.py): on a TPU the
        lever raises the compiler's error at the first level step."""
        (_seed_cw, _ctrl_cw, _w_cw, proof_cw) = cw_slice
        (num_reports, num_parents) = parents.ctrl.shape

        if USE_LEVEL_PALLAS and num_reports >= 32:
            from ..ops.level_pallas import supports
            prefix = ts_prefix(dst(ctx, USAGE_NODE_PROOF), KEY_SIZE)
            binder = np.asarray(node_binder) \
                if isinstance(node_binder, np.ndarray) else node_binder
            if supports(self.convert_blocks, len(prefix),
                        int(binder.shape[-1])):
                (child, ok) = self._eval_step_level_pallas(
                    ext_rk, conv_rk, parents, cw_slice, prefix, binder)
                if self.constrain_state is not None:
                    child = self.constrain_state(child)
                return (child, ok)

        (next_seed, ct, w, ok) = self.level_core(ext_rk, conv_rk,
                                                 parents, cw_slice)

        proof = self.node_proof(
            ctx, next_seed, jnp.asarray(node_binder),
            (num_reports, 2 * num_parents))
        proof = jnp.where(ct[..., None], proof ^ proof_cw[:, None, :],
                          proof)

        child = EvalState(seed=next_seed, ctrl=ct, w=w, proof=proof)
        if self.constrain_state is not None:
            child = self.constrain_state(child)
        return (child, jnp.all(ok, axis=-1))

    def _eval_step_level_pallas(self, ext_rk: jax.Array,
                                conv_rk: jax.Array,
                                parents: EvalState, cw_slice,
                                prefix: bytes, node_binder):
        """The megakernel level step (ops/level_pallas.py): one fused
        VMEM-resident kernel on hardware, chained per-stage kernel
        calls on the CPU fabric (the r5 interpret-validation
        technique)."""
        from ..ops.level_pallas import level_step_pallas

        (seed_cw, ctrl_cw, w_cw, proof_cw) = cw_slice
        # mastic-allow: TS004 — deliberate trace-time constant:
        # interpret mode is baked per backend and jax retraces per
        # backend, so the frozen value can never go stale
        (next_seed, ct, w, ok, proof) = level_step_pallas(
            self.spec, self.convert_blocks, ext_rk, conv_rk,
            parents.seed, parents.ctrl,
            (seed_cw, ctrl_cw, w_cw, proof_cw), prefix, node_binder,
            interpret=jax.default_backend() == "cpu")
        child = EvalState(seed=next_seed, ctrl=ct, w=w, proof=proof)
        return (child, jnp.all(ok, axis=-1))

    def eval_full(self, agg_id: int, cws: BatchedCorrectionWords,
                  keys: jax.Array, sched: LevelSchedule, ctx: bytes,
                  nonces: jax.Array):
        """Evaluate the whole grid of `sched` from the root.

        Returns (levels: list[EvalState] per depth, out_w
        (R, P, VALUE_LEN, n) payload shares in the caller's prefix
        order (negated for aggregator 1), ok (R,)).
        """
        (ext_rk, conv_rk) = self.roundkeys(ctx, nonces)
        state = self.root_state(agg_id, keys)
        ok = jnp.ones(keys.shape[0], bool)
        levels: list[EvalState] = []
        for d in range(sched.level + 1):
            pidx = sched.parent_index[d]
            if pidx is not None:
                state = EvalState(
                    seed=state.seed[:, pidx], ctrl=state.ctrl[:, pidx],
                    w=state.w[:, pidx], proof=state.proof[:, pidx])
            cw_slice = (cws.seed[:, d], cws.ctrl[:, d], cws.w[:, d],
                        cws.proof[:, d])
            (state, step_ok) = self.eval_step(
                ext_rk, conv_rk, state, cw_slice, ctx,
                sched.node_binder[d])
            ok = ok & step_ok
            levels.append(state)

        out_w = levels[sched.level].w[:, sched.out_index]
        if agg_id == 1:
            out_w = self.spec.neg(out_w)
        return (levels, out_w, ok)

    def get_beta_share(self, agg_id: int, cws: BatchedCorrectionWords,
                       keys: jax.Array, ctx: bytes, nonces: jax.Array):
        """Each party's beta share: sum of the two depth-1 payloads
        (reference vidpf.py:263-279).  Returns (share, ok)."""
        sched = LevelSchedule([(False,), (True,)], 0, self.BITS)
        (levels, _, ok) = self.eval_full(agg_id, cws, keys, sched, ctx,
                                         nonces)
        share = self.spec.add(levels[0].w[:, 0], levels[0].w[:, 1])
        if agg_id == 1:
            share = self.spec.neg(share)
        return (share, ok)

    # -- host <-> device converters (test/wire boundary) -----------

    def cws_to_host(self, cws: BatchedCorrectionWords,
                    report: int) -> list[CorrectionWord]:
        """One report's correction words as scalar-layer objects."""
        out: list[CorrectionWord] = []
        seed = np.asarray(cws.seed[report])
        ctrl = np.asarray(cws.ctrl[report])
        w = np.asarray(cws.w[report])
        proof = np.asarray(cws.proof[report])
        for d in range(self.BITS):
            w_vec = [self.field(self.spec.limbs_to_int(w[d, j]))
                     for j in range(self.VALUE_LEN)]
            out.append((seed[d].tobytes(),
                        [bool(ctrl[d, 0]), bool(ctrl[d, 1])],
                        w_vec, proof[d].tobytes()))
        return out

    def cws_from_host(self,
                      batches: list[list[CorrectionWord]],
                      ) -> BatchedCorrectionWords:
        """Scalar correction words (one list per report) -> arrays."""
        num_reports = len(batches)
        seed = np.zeros((num_reports, self.BITS, KEY_SIZE), np.uint8)
        ctrl = np.zeros((num_reports, self.BITS, 2), bool)
        w = np.zeros((num_reports, self.BITS, self.VALUE_LEN,
                      self.spec.num_limbs), np.uint32)
        proof = np.zeros((num_reports, self.BITS, PROOF_SIZE), np.uint8)
        for (r, cws) in enumerate(batches):
            for (d, (s, c, wv, p)) in enumerate(cws):
                seed[r, d] = np.frombuffer(s, np.uint8)
                ctrl[r, d] = c
                for (j, el) in enumerate(wv):
                    w[r, d, j] = self.spec.int_to_limbs(el.int())
                proof[r, d] = np.frombuffer(p, np.uint8)
        return BatchedCorrectionWords(
            seed=jnp.asarray(seed), ctrl=jnp.asarray(ctrl),
            w=jnp.asarray(w), proof=jnp.asarray(proof))

    def w_to_host(self, w: jax.Array) -> list:
        """(..., VALUE_LEN, n) plain limbs -> nested lists of scalar
        field elements."""
        # mastic-allow: TS003 — host-boundary converter: runs on
        # concrete device arrays outside any jit trace, where
        # np.asarray is the device-to-host transfer
        arr = np.asarray(w)
        if arr.ndim == 2:
            return [self.field(self.spec.limbs_to_int(arr[j]))
                    for j in range(arr.shape[0])]
        return [self.w_to_host(arr[i]) for i in range(arr.shape[0])]
