"""The main path's mixing kernels compile for a TPU v5e.

Interpret mode accepts kernels that Mosaic refuses (a scalar stored to
VMEM, a uint32 -> float32 cast), so every kernel the training step can
reach is compiled here, with interpret off, at pga-lm-100m widths (4
stacked nodes) for one chip of a described ``v5e:2x2``.  Nothing runs: a
compile that passes says the chip's compiler accepts the kernel, not that
its numbers are right (chip_smoke.py checks those on the chip).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import compress
from repro.configs import get_model_config
from repro.kernels import mixing_pallas as mp
from repro.models.model import make_model

N = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    # a compile for a described chip is written to the cache but cannot be
    # read back without one
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture(scope="module")
def stacked(one_chip, no_persistent_cache):
    """Shapes of the 4-node stacked pga-lm-100m params, on one chip."""
    model = make_model(get_model_config("pga-lm-100m"))
    params = jax.eval_shape(lambda k: model.init(k)[0],
                            jax.random.PRNGKey(0))
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (N,) + a.shape, a.dtype, sharding=one_chip), params)


def _compressed(kind):
    comp = compress.make_compressor(kind)
    return lambda t: mp.compressed_step_mix(
        t, compressor=comp, ef_state=t, seed=jnp.uint32(3), phase="gossip",
        topology="one_peer_exp", n_nodes=N, interpret=False)


def _collective(kind):
    comp = compress.make_compressor(kind)
    return lambda t: mp.collective_step_mix(
        t, compressor=comp, ef_state=t, seed=jnp.uint32(3), phase="global",
        n_nodes=N, interpret=False)


KERNELS = {
    "fused_step_mix-gossip": lambda t: mp.fused_step_mix(
        t, phase="gossip", topology="one_peer_exp", n_nodes=N, step=1,
        interpret=False),
    "fused_step_mix-global-bf16-wire": lambda t: mp.fused_step_mix(
        t, phase="global", n_nodes=N, comm_dtype=jnp.bfloat16,
        interpret=False),
    "mix_residual": lambda t: mp.mix_residual(
        t, phase="gossip", topology="one_peer_exp", n_nodes=N, step=1,
        interpret=False),
    "compressed_step_mix-int8": _compressed("int8"),
    "compressed_step_mix-fp8": _compressed("fp8"),
    "collective_step_mix-int8": _collective("int8"),
    "collective_step_mix-fp8": _collective("fp8"),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_stacked_kernel_compiles_for_v5e(name, stacked):
    compiled = jax.jit(KERNELS[name]).lower(stacked).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_shard_mix_block_with_residual_compiles_for_v5e(stacked, one_chip):
    """The per-shard kernel of the sharded path: one node per chip, the
    whole packed parameter row, self plus one one_peer_exp neighbor."""
    d = sum(a.size // N for a in jax.tree.leaves(stacked))

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = jax.jit(lambda x, xs, w, m: mp.shard_mix_block(
        x, xs, w, m, with_residual=True, interpret=False)).lower(
        sds(1, d), sds(2, d), sds(1, 1), sds(1, 2)).compile()
    assert "tpu_custom_call" in compiled.as_text()
