"""Pallas mixing-kernel parity: the fused backend must match the roll-based
reference (itself proven ≡ dense W in test_mixing.py) for every phase ×
topology × shape, including the bf16 wire-cast path, the fused residual
outputs, per-leaf dispatch, and the shard_map-aware sharded path (run in a
subprocess with 8 forced host devices, launch/dryrun.py convention).  All
kernels run in interpret mode on CPU (kernels/ops.py convention), so these
tests exercise the exact code that compiles to Mosaic on TPU."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mixing, topology as topo
from repro.kernels import mixing_pallas as mp

TOPOLOGIES = ["ring", "exp", "full", "grid", "one_peer_exp", "disconnected"]
# deliberately odd/ragged shapes: exercises block-padding and multi-leaf concat
SHAPES = [(5, 3), (7,), ()]


def _tree(key, n, dtype=jnp.float32):
    keys = jax.random.split(key, len(SHAPES))
    return {f"leaf{i}": jax.random.normal(k, (n,) + s).astype(dtype)
            for i, (k, s) in enumerate(zip(keys, SHAPES))}


def _assert_tree_close(got, want, atol):
    got_l, want_l = jax.tree.leaves(got), jax.tree.leaves(want)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(got_l, want_l):
        assert g.dtype == w.dtype
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), atol=atol)


# ---------------------------------------------------------------------------
# Phase parity: gossip / global / pod_avg, fp32 and bf16 wire
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("t", TOPOLOGIES)
@pytest.mark.parametrize("n", [4, 8])
@pytest.mark.parametrize("comm_dtype", [None, jnp.bfloat16])
def test_gossip_parity(t, n, comm_dtype, rng_key):
    tree = _tree(rng_key, n)
    want = mixing.mix_pytree(tree, t, n, step=3, comm_dtype=comm_dtype)
    got = mixing.mix_pytree(tree, t, n, step=3, comm_dtype=comm_dtype,
                            backend="pallas")
    _assert_tree_close(got, want, atol=1e-5 if comm_dtype is None else 3e-2)


@pytest.mark.parametrize("comm_dtype", [None, jnp.bfloat16])
def test_global_parity(comm_dtype, rng_key):
    tree = _tree(rng_key, 8)
    want = mixing.global_average_pytree(tree, comm_dtype=comm_dtype)
    got = mixing.global_average_pytree(tree, comm_dtype=comm_dtype,
                                       backend="pallas")
    _assert_tree_close(got, want, atol=1e-5 if comm_dtype is None else 3e-2)


@pytest.mark.parametrize("n_pods", [2, 4])
@pytest.mark.parametrize("comm_dtype", [None, jnp.bfloat16])
def test_pod_avg_parity(n_pods, comm_dtype, rng_key):
    tree = _tree(rng_key, 8)
    want = mixing.pod_average_pytree(tree, n_pods, comm_dtype=comm_dtype)
    got = mixing.pod_average_pytree(tree, n_pods, comm_dtype=comm_dtype,
                                    backend="pallas")
    _assert_tree_close(got, want, atol=1e-5 if comm_dtype is None else 3e-2)


@pytest.mark.parametrize("phase", ["gossip", "global", "pod_avg"])
def test_communicate_dispatch_parity(phase, rng_key):
    """The selector on mixing.communicate reaches the same numbers."""
    tree = _tree(rng_key, 8)
    spec = mixing.CommSpec(topology="one_peer_exp", n_nodes=8, n_pods=2)
    want = mixing.communicate(tree, spec, phase=phase, step=2)
    got = mixing.communicate(tree, spec.replace(backend="pallas"),
                             phase=phase, step=2)
    _assert_tree_close(got, want, atol=1e-5)


def test_one_peer_exp_time_varying_steps(rng_key):
    """Shift step must select the right one-peer graph in the kernel too."""
    n = 8
    x = jax.random.normal(rng_key, (n, 6))
    for step in range(4):
        W = jnp.asarray(topo.mixing_matrix("one_peer_exp", n, step=step))
        got = mp.fused_step_mix(x, phase="gossip", topology="one_peer_exp",
                                n_nodes=n, step=step)
        np.testing.assert_allclose(np.asarray(got), np.asarray(W @ x),
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# Fused SGD half-step and residual outputs
# ---------------------------------------------------------------------------
def test_fused_half_step(rng_key):
    n, gamma = 8, 0.37
    k1, k2 = jax.random.split(rng_key)
    x, g = _tree(k1, n), _tree(k2, n)
    want = mixing.mix_pytree(
        jax.tree.map(lambda p, q: p - gamma * q, x, g), "ring", n)
    got = mp.fused_step_mix(x, g, gamma, phase="gossip", topology="ring",
                            n_nodes=n)
    _assert_tree_close(got, want, atol=1e-5)


@pytest.mark.parametrize("phase", ["gossip", "global", "pod_avg"])
def test_mix_residual_outputs(phase, rng_key):
    n = 8
    tree = _tree(rng_key, n)
    mixed, xbar, resid = mp.mix_residual(tree, phase=phase, topology="ring",
                                         n_nodes=n, n_pods=2)
    want = mixing.communicate(
        tree, mixing.CommSpec(topology="ring", n_nodes=n, n_pods=2),
        phase=phase)
    _assert_tree_close(mixed, want, atol=1e-5)
    # x̄ = node average of the mixed iterate, leaves without the node axis
    want_bar = jax.tree.map(lambda p: jnp.mean(p, axis=0), want)
    _assert_tree_close(xbar, want_bar, atol=1e-5)
    # residual = Σ_i ‖x_i − x̄‖² over every leaf of the mixed iterate
    want_r = sum(float(jnp.sum((p - jnp.mean(p, 0, keepdims=True)) ** 2))
                 for p in jax.tree.leaves(want))
    np.testing.assert_allclose(float(resid), want_r, rtol=1e-4, atol=1e-6)


def test_residual_zero_after_global(rng_key):
    """Global averaging leaves all nodes identical ⇒ residual ≈ 0."""
    _, _, resid = mp.mix_residual(_tree(rng_key, 8), phase="global",
                                  n_nodes=8)
    assert float(resid) < 1e-6


# ---------------------------------------------------------------------------
# Invariants and plumbing
# ---------------------------------------------------------------------------
def test_preserves_bf16_storage_dtype(rng_key):
    tree = _tree(rng_key, 4, dtype=jnp.bfloat16)
    out = mp.fused_step_mix(tree, phase="gossip", topology="ring", n_nodes=4)
    want = mixing.mix_pytree(tree, "ring", 4)
    # kernel accumulates in fp32 (reference accumulates in bf16): bf16 tol
    _assert_tree_close(out, want, atol=3e-2)


def test_gossip_preserves_node_average(rng_key):
    """𝟙ᵀW = 𝟙ᵀ must survive the kernelization."""
    x = jax.random.normal(rng_key, (8, 33))
    mixed = mp.fused_step_mix(x, phase="gossip", topology="exp", n_nodes=8)
    np.testing.assert_allclose(np.asarray(mixed.mean(0)),
                               np.asarray(x.mean(0)), atol=1e-5)


def test_block_boundary_independence(rng_key):
    """Numbers must not depend on the grid block size (padding masked)."""
    x = jax.random.normal(rng_key, (8, 37))
    outs = [np.asarray(mp.fused_step_mix(x, phase="gossip", topology="ring",
                                         n_nodes=8, block_d=bd))
            for bd in (1, 8, 64, 2048)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=1e-6)


def test_simulate_backend_parity(rng_key):
    """Whole-trajectory check: simulate() with backend='pallas' (fused
    half-step + eval residual) tracks the reference trajectory."""
    from repro.core.algorithms import simulate
    d = 6
    A = np.asarray(np.random.default_rng(0).normal(size=(d, d)))
    A = jnp.asarray(A @ A.T / d + np.eye(d), jnp.float32)

    def grad_fn(xs, key, k):
        return xs @ A + jax.random.normal(key, xs.shape) * 0.01

    outs = {b: simulate(algorithm="gossip_pga", grad_fn=grad_fn,
                        loss_fn=lambda x: 0.5 * x @ A @ x,
                        x0=jnp.ones((d,), jnp.float32), n=8, steps=20,
                        lr=0.05, topology="ring", H=4, eval_every=5,
                        backend=b)
            for b in ("reference", "pallas")}
    np.testing.assert_allclose(outs["reference"]["loss"],
                               outs["pallas"]["loss"], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(outs["reference"]["consensus"],
                               outs["pallas"]["consensus"], rtol=1e-3,
                               atol=1e-5)


def test_pallas_backend_rejects_nonzero_axis(rng_key):
    x = jax.random.normal(rng_key, (3, 8))
    with pytest.raises(ValueError, match="axis"):
        mixing.mix_pytree(x, "ring", 8, axis=1, backend="pallas")


def test_unknown_backend_rejected(rng_key):
    x = jax.random.normal(rng_key, (8, 4))
    with pytest.raises(ValueError, match="backend"):
        mixing.mix_pytree(x, "ring", 8, backend="cuda")


def test_backend_error_names_entry_point(rng_key):
    """The axis/backend raise must name the public entry point that reached
    the check, so a failure routed through simulate()/Decentralized is
    attributable (previously the message carried no caller)."""
    x = jax.random.normal(rng_key, (3, 8))
    with pytest.raises(ValueError, match=r"mixing\.mix_pytree.*axis=1"):
        mixing.mix_pytree(x, "ring", 8, axis=1, backend="pallas")
    with pytest.raises(ValueError, match=r"mixing\.communicate.*axis=2"):
        mixing.communicate(
            x, mixing.CommSpec(topology="ring", n_nodes=8,
                               backend="pallas"), phase="gossip", axis=2)
    with pytest.raises(ValueError, match=r"mixing\.communicate.*cuda"):
        mixing.communicate(
            x, mixing.CommSpec(topology="ring", n_nodes=8,
                               backend="cuda"), phase="gossip")


def test_backend_validated_before_noop_early_returns(rng_key):
    """n == 1 / disconnected rounds are no-ops, but a bogus backend or axis
    must still raise instead of silently dropping to the reference path."""
    x = jax.random.normal(rng_key, (1, 4))
    with pytest.raises(ValueError, match="backend"):
        mixing.mix_pytree(x, "ring", 1, backend="cuda")
    with pytest.raises(ValueError, match="axis"):
        mixing.mix_pytree(x, "disconnected", 8, axis=1, backend="pallas")


# ---------------------------------------------------------------------------
# Per-leaf dispatch and the aliasing contract
# ---------------------------------------------------------------------------
def test_leaf_dispatch_threshold_independence(rng_key):
    """Numbers must not depend on how leaves are grouped into dispatches:
    all-in-one staging buffer, every-leaf-its-own-kernel, and mixed."""
    tree = _tree(rng_key, 8)
    base = mp.fused_step_mix(tree, phase="gossip", topology="ring", n_nodes=8)
    for thresh in (1, 8, 10**9):  # all big / split / all small
        got = mp.fused_step_mix(tree, phase="gossip", topology="ring",
                                n_nodes=8, leaf_threshold=thresh)
        _assert_tree_close(got, base, atol=0)  # per-column math is identical


def test_leaf_dispatch_residual_combines_exactly(rng_key):
    tree = _tree(rng_key, 8)
    m0, x0, r0 = mp.mix_residual(tree, phase="gossip", topology="exp",
                                 n_nodes=8)
    m1, x1, r1 = mp.mix_residual(tree, phase="gossip", topology="exp",
                                 n_nodes=8, leaf_threshold=1)
    _assert_tree_close(m1, m0, atol=0)
    _assert_tree_close(x1, x0, atol=1e-6)
    np.testing.assert_allclose(float(r1), float(r0), rtol=1e-5)


def test_aliasing_does_not_clobber_caller_input(rng_key):
    """input_output_aliases is an in-place contract on the *packed staging
    buffer*; the caller's arrays must come back untouched."""
    x = jax.random.normal(rng_key, (8, 37))
    before = np.asarray(x).copy()
    mp.fused_step_mix(x, phase="gossip", topology="ring", n_nodes=8)
    np.testing.assert_array_equal(np.asarray(x), before)


# ---------------------------------------------------------------------------
# Large leaves in their own layout: ragged edges, sub-tile minor dims
# ---------------------------------------------------------------------------
# (per-node shape, block budget in bytes): the two small leaves take a budget
# that cuts them into tiles with a ragged edge; the third sits exactly at the
# dispatch threshold and takes the default budget
NATIVE_LEAVES = [((131, 96), 80 << 10), ((2, 24, 3, 64), 80 << 10),
                 ((512, 512), mp._BLOCK_BYTES)]
# the memory order XLA:TPU gives these leaves' default layouts on a v5e
# (node axis second-minor for the first: its 131 rows are no multiple of 8)
V5E_ORDER = {(131, 96): (1, 0, 2), (2, 24, 3, 64): (0, 1, 3, 2, 4)}


def _native_round(tree, n, phase, comm_dtype, **kw):
    return mp.mix_residual(tree, phase=phase, topology="one_peer_exp",
                           n_nodes=n, step=1, comm_dtype=comm_dtype,
                           n_pods=2, **kw)


@pytest.mark.parametrize("wire", [False, True])
@pytest.mark.parametrize("phase", ["gossip", "global", "pod_avg"])
@pytest.mark.parametrize("n", [4, 8])
@pytest.mark.parametrize("leaf,budget", NATIVE_LEAVES,
                         ids=["ragged_rows", "subtile_minor", "at_threshold"])
def test_native_leaf_round(leaf, budget, n, phase, wire, rng_key,
                           monkeypatch):
    """A leaf at or above the threshold, mixed in its own view, against
    the reference round and (n = 4) the packed staging path; x̄ and the
    residual exact up to summation order, so the ragged edge's rows
    count nowhere.  Run twice: as the CPU interprets it (dot of the node
    mix, leaf as laid out on the host) and as the chip runs it (VPU node
    sum, the leaf in v5e's memory order)."""
    monkeypatch.setattr(mp, "_BLOCK_BYTES", budget)
    cd = jnp.bfloat16 if wire else None
    x = {"w": jax.random.normal(rng_key, (n,) + leaf)}
    thresh = min(mp.LEAF_DISPATCH_THRESHOLD, int(np.prod(leaf)))
    spec = mixing.CommSpec(topology="one_peer_exp", n_nodes=n, n_pods=2,
                           comm_dtype=cd)
    want = mixing.communicate(x, spec, phase=phase, step=1)["w"]
    want_bar = jnp.mean(want, axis=0)
    want_r = float(jnp.sum((want - want_bar) ** 2))
    atol = 1e-5 if cd is None else 3e-2
    packed = _native_round(x, n, phase, cd, leaf_threshold=10**9)

    runs = [_native_round(x, n, phase, cd, leaf_threshold=thresh)]
    monkeypatch.setattr(mp, "_dot_chunk", lambda interpret, block_d: 0)
    monkeypatch.setattr(
        mp, "_memory_order",
        lambda shape, dtype: V5E_ORDER.get(shape[1:],
                                           tuple(range(len(shape)))))
    runs.append(_native_round(x, n, phase, cd, leaf_threshold=thresh))
    for mixed, xbar, resid in runs:
        assert mixed["w"].shape == x["w"].shape
        np.testing.assert_allclose(mixed["w"], want, atol=atol)
        np.testing.assert_allclose(xbar["w"], jnp.mean(mixed["w"], 0),
                                   atol=1e-6)
        got_r = float(jnp.sum((mixed["w"] - xbar["w"]) ** 2))
        np.testing.assert_allclose(float(resid), got_r, rtol=1e-5)
        if cd is None:
            np.testing.assert_allclose(xbar["w"], want_bar, atol=1e-5)
            np.testing.assert_allclose(float(resid), want_r, rtol=1e-4,
                                       atol=1e-5)
        if n == 4:
            np.testing.assert_allclose(mixed["w"], packed[0]["w"],
                                       atol=1e-6)
            np.testing.assert_allclose(xbar["w"], packed[1]["w"], atol=1e-6)
            np.testing.assert_allclose(float(resid), float(packed[2]),
                                       rtol=1e-5)


def test_round_without_xbar_writes_none(rng_key):
    """``with_xbar=False`` (the train step's round) returns no x̄ and the
    same mixed tree and residual."""
    tree = {"big": jax.random.normal(rng_key, (4, 131, 96)),
            "small": jax.random.normal(rng_key, (4, 7))}
    kw = dict(phase="gossip", topology="ring", n_nodes=4, leaf_threshold=64)
    m0, x0, r0 = mp.mix_residual(tree, **kw)
    m1, x1, r1 = mp.mix_residual(tree, with_xbar=False, **kw)
    assert x0 is not None and x1 is None
    _assert_tree_close(m1, m0, atol=0)
    assert float(r1) == float(r0)


def test_leaf_block_follows_the_shape():
    """Blocks come from the view and the budget: whole minor dims, the
    node axis whole, about the budget's bytes a step."""
    budget = mp._BLOCK_BYTES
    # pga-lm-100m's leaves at n = 4, in v5e's memory order
    assert mp._leaf_block((4, 12, 768, 3072), 0, 4) == (4, 1, 40, 3072)
    assert mp._leaf_block((4, 144, 64, 768), 0, 4) == (4, 2, 64, 768)
    # the node axis tiled in memory: the rows are tiled in the kernel's
    # node-leading output, so they are cut to a multiple of 8
    assert mp._leaf_block((50257, 4, 768), 1, 4) == (80, 4, 768)
    for view, ax in (((4, 12, 768, 3072), 0), ((4, 144, 64, 768), 0),
                     ((50257, 4, 768), 1)):
        block = mp._leaf_block(view, ax, 4)
        # a node axis in the sublane position pads to 8 rows in VMEM
        pad = 8 // block[ax] if ax == len(view) - 2 else 1
        nbytes = 4 * int(np.prod(block)) * pad
        assert budget // 2 < nbytes <= budget
    assert mp._leaf_view((1, 0, 2), (4, 50257, 768)) == ((50257, 4, 768), 1)
    assert mp._leaf_view((0, 1, 3, 4, 2), (4, 12, 768, 12, 64)) == \
        ((4, 144, 64, 768), 0)
    assert mp._leaf_view((0, 1, 2, 3), (4, 12, 768, 3072)) == \
        ((4, 12, 768, 3072), 0)


@pytest.mark.parametrize("n", [4, 8, 32])
def test_chip_round_mixes_packed_nodes_on_the_mxu(n, monkeypatch):
    """Compiled for the chip, the packed group (nodes in a tile's
    sublanes) mixes with the ``(n, n) @ (n, block_d)`` dot and a large
    leaf's view (node axis untiled) with the VPU sum, at every n."""
    seen = []
    real = mp._mix_nodes

    def spy(x, *args, **kw):
        seen.append((x.ndim, kw["dot_chunk"]))
        return real(x, *args, **kw)

    monkeypatch.setattr(mp, "_mix_nodes", spy)
    tree = {"big": jax.ShapeDtypeStruct((n, 4, 64, 128), jnp.float32),
            "small": jax.ShapeDtypeStruct((n, 96), jnp.float32)}
    jax.eval_shape(lambda t: mp.fused_step_mix(
        t, phase="global", n_nodes=n, block_d=512, interpret=False,
        leaf_threshold=4 * 64 * 128), tree)
    assert sorted(seen) == [(2, 512), (4, 0)]


# ---------------------------------------------------------------------------
# shard_map-aware sharded path (8 forced host devices, subprocess)
# ---------------------------------------------------------------------------
_SHARDED_PARITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import mixing

    mesh = jax.make_mesh((8,), ("data",))
    SHAPES = [(5, 3), (7,), ()]

    def tree(key, n):
        ks = jax.random.split(key, len(SHAPES))
        return {f"leaf{i}": jax.random.normal(k, (n,) + s)
                for i, (k, s) in enumerate(zip(ks, SHAPES))}

    def close(got, want, atol):
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert g.dtype == w.dtype
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(w, np.float32), atol=atol)

    key, n = jax.random.PRNGKey(0), 16
    CASES = ([("gossip", t, 1) for t in
              ("ring", "exp", "one_peer_exp", "grid", "disconnected")]
             + [("global", "ring", 1), ("pod_avg", "ring", 2),
                ("pod_avg", "ring", 4)])
    for phase, topol, n_pods in CASES:
        for cd in (None, jnp.bfloat16):
            t = tree(key, n)
            kw = dict(phase=phase, topology=topol, n_nodes=n, step=3,
                      comm_dtype=cd, n_pods=n_pods)
            want = mixing.communicate(t, **kw)
            got = mixing.communicate(t, backend="pallas", mesh=mesh, **kw)
            close(got, want, 1e-5 if cd is None else 3e-2)
            print(f"PARITY_OK {phase}/{topol}/p{n_pods}/"
                  f"{'fp32' if cd is None else 'bf16'}")

    # fused residual: psum-combined consensus matches the direct form
    t = tree(key, n)
    mixed, xbar, resid = mixing.communicate_sharded(
        t, phase="gossip", topology="ring", n_nodes=n, mesh=mesh,
        with_residual=True)
    want = mixing.communicate(t, phase="gossip", topology="ring", n_nodes=n)
    close(mixed, want, 1e-5)
    close(xbar, jax.tree.map(lambda p: jnp.mean(p, 0), want), 1e-5)
    want_r = sum(float(jnp.sum((p - jnp.mean(p, 0, keepdims=True)) ** 2))
                 for p in jax.tree.leaves(want))
    np.testing.assert_allclose(float(resid), want_r, rtol=1e-4, atol=1e-6)
    print("RESIDUAL_OK")

    # fused SGD half-step before the halo exchange
    g = tree(jax.random.PRNGKey(1), n)
    got = mixing.communicate_sharded(t, phase="gossip", topology="ring",
                                     n_nodes=n, mesh=mesh, grads=g,
                                     gamma=0.37)
    want = mixing.communicate(jax.tree.map(lambda p, q: p - 0.37 * q, t, g),
                              phase="gossip", topology="ring", n_nodes=n)
    close(got, want, 1e-5)
    print("HALFSTEP_OK")

    # flattened (pod, data) node axis — DistConfig.node_axis="data" semantics
    mesh2 = jax.make_mesh((2, 4), ("pod", "data"))
    got = mixing.communicate(t, phase="gossip", topology="exp", n_nodes=n,
                             backend="pallas", mesh=mesh2)
    close(got, mixing.communicate(t, phase="gossip", topology="exp",
                                  n_nodes=n), 1e-5)
    print("POD_DATA_OK")

    # shard_mode="stacked" forces the local kernels even under a mesh
    got = mixing.communicate(t, phase="gossip", topology="ring", n_nodes=n,
                             backend="pallas", mesh=mesh,
                             shard_mode="stacked")
    close(got, mixing.communicate(t, phase="gossip", topology="ring",
                                  n_nodes=n, backend="pallas"), 1e-6)
    print("STACKED_OVERRIDE_OK")

    # constant state is a fixed point under sharding too
    c = jax.tree.map(lambda p: jnp.full_like(p, 1.5), t)
    got = mixing.communicate(c, phase="gossip", topology="ring", n_nodes=n,
                             backend="pallas", mesh=mesh)
    close(got, c, 1e-6)
    print("CONSTANT_OK")
""")


def _run_forced_device_script(script: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, (out.stdout[-2000:] + out.stderr[-4000:])
    return out.stdout


def test_sharded_pallas_parity_8dev():
    """backend='pallas' under a mesh whose node axis is sharded: the
    shard_map wrapper (ppermute halo + per-shard fused kernel) must match
    the roll-based oracle for every phase × topology × wire dtype, plus the
    fused residual, half-step, flattened (pod, data) axis, and the
    shard_mode override — all on 8 forced host devices."""
    stdout = _run_forced_device_script(_SHARDED_PARITY_SCRIPT)
    assert stdout.count("PARITY_OK") == 16, stdout
    for marker in ("RESIDUAL_OK", "HALFSTEP_OK", "POD_DATA_OK",
                   "STACKED_OVERRIDE_OK", "CONSTANT_OK"):
        assert marker in stdout, stdout


def test_node_axis_pod_without_pod_axis_is_unsharded():
    """node_axis='pod' (DistConfig's hierarchical mode) on a single-pod mesh
    — no 'pod' axis — means one gossip node and no shards, not a KeyError."""
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    assert mixing.node_axis_names(mesh, "pod") == ()
    assert mixing.node_shard_count(mesh, "pod") == 1
    assert not mixing.use_sharded_backend("pallas", mesh, "pod", "auto")
    with pytest.raises(ValueError, match="no axis"):
        mixing.communicate_sharded(
            jnp.ones((4, 2)),
            mixing.CommSpec(topology="ring", n_nodes=4, mesh=mesh,
                            node_axis="pod"), phase="gossip")


def test_shard_mode_sharded_requires_sharded_mesh(rng_key):
    """comm_shard_mode='sharded' with no mesh (or an unsharded node axis)
    must raise, not silently fall back to the stacked kernels."""
    x = jax.random.normal(rng_key, (8, 4))
    with pytest.raises(ValueError, match="sharded"):
        mixing.communicate(
            x, mixing.CommSpec(topology="ring", n_nodes=8,
                               backend="pallas", mesh=None,
                               shard_mode="sharded"), phase="gossip")
    with pytest.raises(ValueError, match="shard_mode"):
        mixing.communicate(
            x, mixing.CommSpec(topology="ring", n_nodes=8,
                               backend="pallas", shard_mode="bogus"),
            phase="gossip")
