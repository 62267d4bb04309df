"""Compiles for a described TPU v5e, at headline widths.

The installed TPU compiler compiles for a chip that is described and
not attached, so these catch what interpret mode cannot (tiling,
VMEM, unsupported vector shapes) without a chip.  Nothing runs: a
pass says the program compiles, not that it is right or fast.

The topology is described inside a fixture, never at import: only
one process at a time may load the TPU library, and every test
worker imports every test file.
"""

import jax
import jax.numpy as jnp
import pytest

from mastic_tpu import MasticCount
from mastic_tpu.backend.mastic_jax import BatchedMastic

REPORTS = 4096      # bench headline tile: reports x frontier x bits
FRONTIER = 64
BITS = 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def bm():
    return BatchedMastic(MasticCount(BITS))


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _level_args(sharding, bm):
    """Abstract inputs of one level step at the headline tile."""
    vid = bm.vidpf
    (r, n) = (REPORTS, FRONTIER)
    return (_sds(sharding, (r, 11, 16), jnp.uint8),
            _sds(sharding, (r, 11, 16), jnp.uint8),
            _sds(sharding, (r, n, 16), jnp.uint8),
            _sds(sharding, (r, n), jnp.bool_),
            _sds(sharding, (r, 16), jnp.uint8),
            _sds(sharding, (r, 2), jnp.bool_),
            _sds(sharding, (r, vid.VALUE_LEN, bm.spec.num_limbs),
                 jnp.uint32),
            _sds(sharding, (r, 32), jnp.uint8),
            _sds(sharding, (2 * n, 36), jnp.uint8))


def _node_proof_prefix():
    from mastic_tpu.backend.vidpf_jax import KEY_SIZE, ts_prefix
    from mastic_tpu.dst import USAGE_NODE_PROOF, dst

    return ts_prefix(dst(b"bench", USAGE_NODE_PROOF), KEY_SIZE)


def test_scan_eval_step_compiles(one_chip, bm):
    """The main-path level step (the XLA scan form)."""
    from mastic_tpu.backend.vidpf_jax import EvalState

    vid = bm.vidpf

    def step(erk, crk, seed, ctrl, s_cw, c_cw, w_cw, p_cw, binder):
        parents = EvalState(
            seed=seed, ctrl=ctrl,
            w=jnp.zeros(ctrl.shape + (vid.VALUE_LEN, bm.spec.num_limbs),
                        jnp.uint32),
            proof=jnp.zeros(ctrl.shape + (32,), jnp.uint8))
        (child, ok) = vid.eval_step(erk, crk, parents,
                                    (s_cw, c_cw, w_cw, p_cw), b"bench",
                                    binder)
        return (child.seed, child.ctrl, child.proof, ok)

    compiled = jax.jit(step).lower(*_level_args(one_chip, bm)).compile()
    assert "tpu_custom_call" not in compiled.as_text()


def test_keccak_pallas_compiles(one_chip):
    from mastic_tpu.ops.keccak_pallas import keccak_p1600_pallas

    state = _sds(one_chip, (REPORTS * 128, 25), jnp.uint32)
    compiled = jax.jit(keccak_p1600_pallas).lower(state, state).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_aes_pallas_compiles(one_chip):
    from mastic_tpu.ops.aes_pallas import aes128_encrypt_bitsliced_pallas

    words = REPORTS // 32
    keys = _sds(one_chip, (11, 8, 16, words), jnp.uint32)
    planes = _sds(one_chip, (8, 16, 2 * FRONTIER, words), jnp.uint32)
    compiled = jax.jit(aes128_encrypt_bitsliced_pallas).lower(
        keys, planes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_level_megakernel_is_refused(one_chip, bm):
    """The fused level megakernel (`MASTIC_LEVEL_PALLAS=1`) does not
    compile for the chip: Mosaic refuses the packed-word to dense-bit
    relayout of its Keccak phase.  On a TPU the lever therefore raises
    this compiler error at the first level step instead of running.
    When a change makes it compile, this test fails and is replaced
    by the compile itself."""
    from mastic_tpu.ops.level_pallas import level_step_pallas

    prefix = _node_proof_prefix()

    def fused(erk, crk, seed, ctrl, s_cw, c_cw, w_cw, p_cw, binder):
        return level_step_pallas(bm.spec, bm.vidpf.convert_blocks, erk,
                                 crk, seed, ctrl,
                                 (s_cw, c_cw, w_cw, p_cw), prefix,
                                 binder, interpret=False, chain=False)

    lowered = jax.jit(fused).lower(*_level_args(one_chip, bm))
    with pytest.raises(Exception, match="unsupported shape cast"):
        lowered.compile()
