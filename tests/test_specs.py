"""Sharding-spec resolution + a subprocess mini dry-run (8 forced host
devices) exercising specs → lower → compile end-to-end on a reduced arch."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.core import mixing
from repro.models.sharding import logical_to_spec


def _mesh(shape=(16, 16), axes=("data", "model")):
    return AbstractMesh(shape, axes)


def test_divisible_dims_are_sharded():
    spec = logical_to_spec(("node", "embed", "heads", None), "train_data",
                           _mesh(), shape=(16, 1024, 16, 64))
    assert spec == P("data", None, "model", None)


def test_non_divisible_dims_stay_replicated():
    # kv_heads=8 on model=16 -> replicated
    spec = logical_to_spec(("embed", "kv_heads", None), "train_data",
                           _mesh(), shape=(1024, 8, 64))
    assert spec == P(None, None, None)


def test_mesh_axis_never_used_twice():
    spec = logical_to_spec(("heads", "ffn"), "train_data", _mesh(),
                           shape=(16, 64))
    # both map to "model": only the first dim gets it
    assert spec == P("model", None)


def test_multi_pod_node_axis_flattens_pod_and_data():
    spec = logical_to_spec(("node", None), "train_data",
                           _mesh((2, 16, 16), ("pod", "data", "model")),
                           shape=(32, 7))
    assert spec == P(("pod", "data"), None)


def test_serve_tp_seq_shards_sequence_not_kv_heads():
    spec = logical_to_spec(("batch", "kv_seq", "kv_heads", None),
                           "serve_tp_seq", _mesh(),
                           shape=(128, 32768, 8, 256))
    assert spec == P("data", "model", None, None)


def test_comm_dtype_bf16_mixing_close_to_f32():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    f32 = mixing.mix_pytree(x, "ring", 8)
    bf16 = mixing.mix_pytree(x, "ring", 8, comm_dtype=jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(bf16), np.asarray(f32),
                               atol=2e-2, rtol=2e-2)
    # mean preservation holds to wire precision
    np.testing.assert_allclose(np.asarray(bf16.mean(0)),
                               np.asarray(x.mean(0)), atol=2e-2)


_DRYRUN_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    from repro.configs import DistConfig, get_model_config
    from repro.configs.base import InputShape
    from repro.launch.specs import serve_specs, train_specs
    from repro.launch.dryrun import _compile_train, _compile_serve

    mesh = jax.make_mesh((4, 2), ("data", "model"))
    cfg = get_model_config("qwen3-0.6b", reduced=True)
    shape = InputShape("t", 64, 8, "train")
    compiled, specs = _compile_train(
        cfg, shape, mesh, dist=DistConfig(topology="ring"), phase="gossip")
    assert compiled.cost_analysis() is not None
    text = compiled.as_text()
    assert "collective-permute" in text, "gossip must lower to permutes"
    compiled2, _ = _compile_train(
        cfg, shape, mesh, dist=DistConfig(topology="ring"), phase="global")
    assert "all-reduce" in compiled2.as_text()
    dshape = InputShape("d", 128, 8, "decode")
    compiled3, _ = _compile_serve(cfg, dshape, mesh, param_sharding="tp")
    print("MINI_DRYRUN_OK")
""")


def test_mini_dryrun_subprocess():
    """Gossip lowers to collective-permute, global averaging to all-reduce,
    decode compiles — on a real (4,2) device mesh in a subprocess."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", _DRYRUN_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=570)
    assert "MINI_DRYRUN_OK" in out.stdout, out.stderr[-3000:]
