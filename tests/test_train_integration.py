"""End-to-end training integration: every algorithm runs; PGA learns; the
checkpoint roundtrip is exact; parallel == PGA(full topology) on the real
model train step."""
import os
import subprocess
import sys
import tempfile
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.configs import (DataConfig, DistConfig, OptimizerConfig,
                           TrainConfig, get_model_config)
from repro.train import Trainer

CFG = get_model_config("pga-lm-100m", reduced=True)


def _tcfg(algorithm="gossip_pga", topology="ring", H=4, opt="adamw",
          lr=3e-3):
    return TrainConfig(
        model=CFG,
        dist=DistConfig(algorithm=algorithm, topology=topology, H=H),
        optimizer=OptimizerConfig(name=opt, lr=lr, schedule="constant",
                                  warmup_steps=0, grad_clip=1.0),
        data=DataConfig(non_iid=True), global_batch=8, seq_len=32,
        log_every=0)


@pytest.mark.repro_guards
@pytest.mark.parametrize("algorithm", ["parallel", "gossip", "local",
                                       "gossip_pga", "gossip_aga", "slowmo"])
def test_every_algorithm_runs(algorithm):
    """Guarded suite: under ``--repro-guards`` the whole run executes with
    the transfer guard + leak checking on, proving the log_every=0 hot
    path of every algorithm never implicitly syncs (assertions below use
    explicit ``jax.device_get`` only)."""
    tr = Trainer(_tcfg(algorithm), n_nodes=4)
    state = tr.init_state(jax.random.PRNGKey(0))
    state = tr.run(state, steps=5, log_every=0)
    host = jax.device_get((state.step, state.params))
    assert int(host[0]) == 5
    for leaf in jax.tree.leaves(host[1]):
        assert np.all(np.isfinite(np.asarray(leaf, np.float32)))


def test_pga_learns():
    tr = Trainer(_tcfg(), n_nodes=4, with_consensus=True)
    state = tr.init_state(jax.random.PRNGKey(0))
    tr.run(state, steps=30, log_every=29)
    assert tr.history[-1]["loss"] < tr.history[0]["loss"] - 0.2


def test_parallel_equals_pga_full_topology_exactly():
    """W = J reduction on the full train step (paper §3: Gossip-PGA with
    W = (1/n)𝟙𝟙ᵀ *is* parallel SGD)."""
    out = {}
    for alg, topology in [("parallel", "full"), ("gossip_pga", "full")]:
        tr = Trainer(_tcfg(alg, topology=topology, H=1, opt="sgd", lr=0.05),
                     n_nodes=4)
        state = tr.init_state(jax.random.PRNGKey(7))
        state = tr.run(state, steps=4, log_every=0)
        out[alg] = jax.tree.leaves(state.params)[0]
    np.testing.assert_allclose(np.asarray(out["parallel"], np.float32),
                               np.asarray(out["gossip_pga"], np.float32),
                               atol=1e-5)


def test_nodes_stay_identical_under_parallel():
    tr = Trainer(_tcfg("parallel"), n_nodes=4, with_consensus=True)
    state = tr.init_state(jax.random.PRNGKey(0))
    tr.run(state, steps=3, log_every=2)
    assert tr.history[-1]["consensus"] < 1e-8


def test_checkpoint_roundtrip():
    tr = Trainer(_tcfg(), n_nodes=2)
    state = tr.init_state(jax.random.PRNGKey(0))
    state = tr.run(state, steps=2, log_every=0)
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, state, 2)
        restored = restore_checkpoint(d, state)
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gossip_nodes_diverge_then_global_resyncs():
    """Consensus grows between global averages and collapses at the sync —
    the mechanism PGA exploits (paper §4 Intuition)."""
    tcfg = _tcfg("gossip_pga", topology="disconnected", H=5)
    tr = Trainer(tcfg, n_nodes=4, with_consensus=True)
    state = tr.init_state(jax.random.PRNGKey(0))
    cons = []
    for k in range(5):
        state = tr.run(state, steps=1, log_every=0)
        from repro.train.state import consensus_distance
        cons.append(float(consensus_distance(state.params)))
    # steps 1-4: disconnected gossip (=no comm) -> consensus grows
    assert cons[3] > cons[0] * 0.9 and cons[3] > 0
    # step 5 = global averaging -> consensus ~0
    assert cons[4] < 1e-8


def _run_script(script: str, env_extra=None, timeout: int = 600):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


_MESH4_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from repro.configs import (DataConfig, DistConfig, OptimizerConfig,
                               TrainConfig, get_model_config)
    from repro.launch.mesh import node_mesh
    from repro.train import Trainer

    def tcfg(backend):
        return TrainConfig(
            model=get_model_config("pga-lm-100m", reduced=True),
            dist=DistConfig(algorithm="gossip_pga", topology="one_peer_exp",
                            H=2, comm_backend=backend),
            optimizer=OptimizerConfig(name="adamw", lr=3e-3,
                                      schedule="constant", warmup_steps=0),
            data=DataConfig(non_iid=True), global_batch=8, seq_len=16,
            log_every=0)

    try:
        node_mesh(6)
        raise AssertionError("6 nodes cannot split over 4 devices")
    except ValueError:
        pass
    # launch/train's mesh (Auto axes) and jax.make_mesh's default
    # (Explicit axes) must both work
    meshes = {"reference": node_mesh(4),
              "pallas": jax.make_mesh((4,), ("data",))}
    final = {}
    for backend, mesh in meshes.items():
        tr = Trainer(tcfg(backend), n_nodes=4, mesh=mesh,
                     with_consensus=True)
        state = tr.run(tr.init_state(jax.random.PRNGKey(0)), steps=3)
        for tree in (state.params, state.opt_state):
            for a in jax.tree.leaves(tree):
                if a.ndim and a.shape[0] == 4:
                    assert len(a.sharding.device_set) == 4, a.sharding
                    assert a.sharding.spec[0] == "data", a.sharding
        final[backend] = jax.device_get(state.params)
    for a, b in zip(jax.tree.leaves(final["reference"]),
                    jax.tree.leaves(final["pallas"])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    print("MESH4_OK")
""")


def test_trainer_places_state_over_four_devices():
    """With a 4-device ("data",) mesh the state's node axis is split over
    the devices on both comm backends, stays split across global rounds,
    and the two backends agree."""
    out = _run_script(_MESH4_SCRIPT)
    assert "MESH4_OK" in out.stdout, out.stdout[-2000:] + out.stderr[-4000:]


_CACHE_SCRIPT = textwrap.dedent("""
    import os, sys
    import jax, jax.numpy as jnp
    from repro.launch import mesh
    assert mesh.use_compile_cache() == os.environ["JAX_COMPILATION_CACHE_DIR"]
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
    del os.environ["JAX_COMPILATION_CACHE_DIR"]
    path = mesh.use_compile_cache()
    assert path == str(mesh.CHECKOUT / ".jax_cache"), path
    assert jax.config.jax_compilation_cache_dir == path
    print("CACHE_OK")
""")


def test_compile_cache_follows_the_environment(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, entries land there; unset, the
    entry points point JAX at the checkout's fixed .jax_cache."""
    cache = tmp_path / "cache"
    out = _run_script(_CACHE_SCRIPT, {
        "JAX_COMPILATION_CACHE_DIR": str(cache),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"})
    assert "CACHE_OK" in out.stdout, out.stdout[-2000:] + out.stderr[-4000:]
    assert any(cache.iterdir())
