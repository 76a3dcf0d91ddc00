"""The main path's mixing kernels compile for a TPU v5e.

Interpret mode accepts kernels that Mosaic refuses (a scalar stored to
VMEM, a uint32 -> float32 cast), so every kernel the training step can
reach is compiled here, with interpret off, at pga-lm-100m widths (4
stacked nodes) for one chip of a described ``v5e:2x2``.  Nothing runs: a
compile that passes says the chip's compiler accepts the kernel, not that
its numbers are right (chip_smoke.py checks those on the chip).
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
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


@pytest.fixture(scope="module")
def gpt2_stacked(one_chip, no_persistent_cache):
    """The 4-node stacked pga-lm-100m params at GPT-2's vocabulary (50257
    rows: no multiple of 8), as the benchmark's configuration runs it."""
    cfg = dataclasses.replace(get_model_config("pga-lm-100m"),
                              vocab_size=50257)
    params = jax.eval_shape(lambda k: make_model(cfg).init(k)[0],
                            jax.random.PRNGKey(0))
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (N,) + a.shape, a.dtype, sharding=one_chip), params)


_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\(?[a-z0-9]+\[[0-9,]*\])\S*"
                    r".*? ([a-z-]+)\((.*?)\)")


def _entry(hlo: str):
    """name -> (first result shape, opcode, operand names) of the entry
    computation's instructions."""
    body = hlo[hlo.index("\nENTRY"):]
    body = body[:body.index("\n}")]
    out = {}
    for line in body.splitlines()[1:]:
        m = _INSTR.match(line)
        if m:
            name, shape, op, args = m.groups()
            out[name] = (shape.lstrip("("), op,
                         re.findall(r"%([\w.\-]+)", args))
    return out


@pytest.mark.parametrize("phase,step", [("gossip", 0), ("gossip", 1),
                                        ("global", 0)])
def test_round_mixes_each_large_leaf_in_place(phase, step, gpt2_stacked,
                                              topo, monkeypatch):
    """The train step's round (consensus residual, no x̄) at pga-lm-100m's
    full leaf tree on a v5e: every leaf at or above the dispatch threshold
    reaches its kernel as a bitcast of the parameter, with no copy,
    transpose or loop between them, and each kernel's first result is the
    mixed leaf, ``f32[4,…]`` (the prefix ``mix_round_roofline`` reads).
    The one large leaf whose memory holds its nodes inside each tile (the
    embedding, laid out as ``(50257, 4, 768)``) comes back node-leading and
    takes one copy into its parameter's layout; every other kernel mixes
    its leaf in place.  The leaves take the chip's default layouts, so the
    program asks the described chip for them."""
    dev = topo.devices[0]
    monkeypatch.setattr(
        mp, "_memory_order", lambda shape, dtype: tuple(
            dev.client.get_default_layout(np.dtype(dtype), shape, dev)
            ._xla_layout().minor_to_major()[::-1]))
    compiled = jax.jit(lambda t: mp.mix_residual(
        t, phase=phase, topology="one_peer_exp", n_nodes=N, step=step,
        with_xbar=False, interpret=False), donate_argnums=0).lower(
        gpt2_stacked).compile()
    hlo = compiled.as_text()
    instrs = _entry(hlo)
    large = N * mp.LEAF_DISPATCH_THRESHOLD

    def elems(shape):
        return int(np.prod([int(d) for d in
                            re.findall(r"\d+", shape.split("[")[1])]))

    def source(name):
        # back through bitcasts, tuple elements and XLA's memory-space
        # prefetches (copy-start/-done: the same layout in another memory)
        while instrs[name][1] in ("bitcast", "get-tuple-element",
                                  "copy-start", "copy-done"):
            name = instrs[name][2][0]
        return name

    kernels = {k: v for k, v in instrs.items() if v[1] == "custom-call"}
    n_large = sum(a.size >= large for a in jax.tree.leaves(gpt2_stacked))
    assert len(kernels) == n_large + 1          # one more: the small leaves
    fed = 0
    for shape, _, args in kernels.values():
        assert shape.startswith(f"f32[{N},"), shape
        fed += instrs[source(args[-1])][1] == "parameter"
    assert fed == n_large
    in_place = hlo.count("output_to_operand_aliasing={{0}:")
    assert in_place == len(kernels) - 1
    moved = [(k, op) for k, (shape, op, args) in instrs.items()
             if op in ("copy", "transpose", "while") and elems(shape) >= large]
    assert len(moved) == 1 and moved[0][1] == "copy", moved
    assert source(instrs[moved[0][0]][2][0]) in kernels
