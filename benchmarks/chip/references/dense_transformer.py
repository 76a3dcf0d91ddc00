"""Plain reference of the dense transformer trainer, in float32.

Written from the architecture's description and imports nothing of the
program under test.  One node's model: token embedding (a masked-LM
replaces masked positions by a learned mask vector), ``n_layers`` pre-norm
blocks of RMSNorm, multi-head attention with rotate-half RoPE (causal for
a decoder, full for an encoder) and a gated MLP (SiLU for a decoder, tanh
GELU for an encoder), a final RMSNorm and the output head (the tied
embedding, or its own matrix).  The loss is the mean cross-entropy over
the positions trained on: every position of an LM, the masked ones of an
MLM.

One training step of ``n`` nodes, as Gossip-PGA states it: each node's
gradient of its own loss; one clip of all nodes' gradients by their
global norm; a per-node AdamW or LAMB update; then the communication
round: gossip over the one-peer
exponential graph (node ``i`` averages with node ``i + 2^(s mod log2 n)``)
or the exact global average.

One departure from the LAMB paper, noted because the program makes it
too: LAMB's trust ratio is taken per parameter array, and the layers of a
stack are one array, so the ratio is shared by a stack's layers where the
paper takes it per layer.  ``lamb_per_layer=True`` takes it per layer, to
measure what that departure moves.

Matrix products run at ``Precision.HIGHEST``.  ``precision="fp8"`` runs
every product of the forward and backward passes on operands rounded to
float8 e4m3 with one scale per tensor: the benchmark's control.  The
parameter tree has the program's layout, so the two can be compared leaf
by leaf.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
NEG = -2.3819763e38


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def param_shapes(cfg: Dict) -> Dict:
    """Shapes of one node's parameters (leading ``layers`` axis on the
    per-layer leaves)."""
    d, nh, nkv, hd = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                      cfg["head_dim"])
    ff, L, v = cfg["d_ff"], cfg["n_layers"], cfg["vocab_size"]
    embed = {"embedding": (v, d)}
    if not cfg["tie_embeddings"]:
        embed["unembed"] = (d, v)
    layer = {
        "ln1": (L, d),
        "mixer": {"w_q": (L, d, nh, hd), "w_k": (L, d, nkv, hd),
                  "w_v": (L, d, nkv, hd), "w_o": (L, nh, hd, d)},
        "ln2": (L, d),
        "ffn": {"w_gate": (L, d, ff), "w_up": (L, d, ff),
                "w_down": (L, ff, d)},
    }
    tree = {"embed": embed, "stack": {"scan": {"entry_0": layer}},
            "final_norm": (d,)}
    if not cfg["causal"]:
        tree["mask_emb"] = (d,)
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def _init_std(path: Sequence[str], shape: Tuple[int, ...]) -> Optional[float]:
    """None for a norm weight (ones); else the normal's deviation: 0.02
    for embeddings, 1/sqrt(fan in) for a projection."""
    name = path[-1]
    if name.startswith("ln") or name == "final_norm":
        return None
    if name in ("embedding", "mask_emb"):
        return 0.02
    if name == "unembed":
        fan_in = shape[0]
    elif name == "w_o":
        fan_in = shape[1] * shape[2]
    elif name in ("w_q", "w_k", "w_v", "w_gate", "w_up", "w_down"):
        fan_in = shape[1]
    else:
        raise KeyError(f"no initializer for parameter {'/'.join(path)}")
    return 1.0 / math.sqrt(fan_in)


def init_params(cfg: Dict, key: jax.Array) -> Dict:
    """One node's parameters from ``key``, float32 (run under ``jit``)."""
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)
    leaves = []
    for i, (path, shape) in enumerate(flat):
        names = [p.key for p in path]
        std = _init_std(names, shape)
        if std is None:
            leaves.append(jnp.ones(shape, jnp.float32))
        else:
            leaves.append(std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32))
    return jax.tree.unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Matrix products
# ---------------------------------------------------------------------------
def _q8(x: jax.Array) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _fp8_einsum(eq: str):
    ins, out = eq.split("->")
    a_s, b_s = ins.split(",")

    @jax.custom_vjp
    def f(a, b):
        return jnp.einsum(eq, _q8(a), _q8(b), precision=HIGHEST)

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        qa, qb, qg = _q8(a), _q8(b), _q8(g)
        da = jnp.einsum(f"{out},{b_s}->{a_s}", qg, qb, precision=HIGHEST)
        db = jnp.einsum(f"{a_s},{out}->{b_s}", qa, qg, precision=HIGHEST)
        return da, db

    f.defvjp(fwd, bwd)
    return f


def make_einsum(precision: str):
    if precision == "highest":
        return lambda eq, a, b: jnp.einsum(eq, a, b, precision=HIGHEST)
    if precision == "fp8":
        cache = {}

        def ein(eq, a, b):
            if eq not in cache:
                cache[eq] = _fp8_einsum(eq)
            return cache[eq](a, b)

        return ein
    raise ValueError(f"unknown reference precision {precision!r}")


# ---------------------------------------------------------------------------
# One node's loss
# ---------------------------------------------------------------------------
def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _rope(x, positions, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs       # (S, half)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]  # (S,1,half)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def node_loss(params: Dict, batch: Dict, cfg: Dict, ein) -> jax.Array:
    """Mean cross-entropy of one node over its ``(b, S)`` batch."""
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    nh, nkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    causal = cfg["causal"]
    tokens = batch["inputs"]
    S = tokens.shape[1]
    h = params["embed"]["embedding"][tokens]
    if not causal:
        h = jnp.where(batch["mask"][..., None], params["mask_emb"], h)
    pos = jnp.arange(S)
    allowed = (pos[None, :] <= pos[:, None]) if causal else \
        jnp.ones((S, S), bool)
    act = jax.nn.silu if causal else _gelu_tanh

    def layer(h, p):
        a = _rms_norm(h, p["ln1"], eps)
        m = p["mixer"]
        q = _rope(ein("bsd,dhk->bshk", a, m["w_q"]), pos, theta)
        k = _rope(ein("bsd,dhk->bshk", a, m["w_k"]), pos, theta)
        v = ein("bsd,dhk->bshk", a, m["w_v"])
        if nkv != nh:
            k = jnp.repeat(k, nh // nkv, axis=2)
            v = jnp.repeat(v, nh // nkv, axis=2)
        s = ein("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        s = jnp.where(allowed, s, NEG)
        o = ein("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        h = h + ein("bshk,hkd->bsd", o, m["w_o"])
        a = _rms_norm(h, p["ln2"], eps)
        f = p["ffn"]
        g = act(ein("bsd,df->bsf", a, f["w_gate"])) * \
            ein("bsd,df->bsf", a, f["w_up"])
        return h + ein("bsf,fd->bsd", g, f["w_down"]), None

    h, _ = jax.lax.scan(jax.checkpoint(layer), h,
                        params["stack"]["scan"]["entry_0"])
    h = _rms_norm(h, params["final_norm"], eps)
    if cfg["tie_embeddings"]:
        logits = ein("bsd,vd->bsv", h, params["embed"]["embedding"])
    else:
        logits = ein("bsd,dv->bsv", h, params["embed"]["unembed"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["targets"][..., None],
                               axis=-1)[..., 0]
    w = (batch["mask"].astype(jnp.float32) if not causal
         else jnp.ones(nll.shape, jnp.float32))
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


# ---------------------------------------------------------------------------
# The training step of n nodes
# ---------------------------------------------------------------------------
def _per_node(fn, tree, groups: int):
    """Apply ``fn`` to each node's slice: ``vmap`` over ``groups`` (one per
    device, so each device computes its own nodes) and a sequential
    ``lax.map`` over the nodes inside a group, so that one node's
    activations are live at a time."""
    def split(x):
        return x.reshape((groups, x.shape[0] // groups) + x.shape[1:])

    out = jax.vmap(lambda t: jax.lax.map(fn, t))(jax.tree.map(split, tree))
    return jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), out)


def _trust_norms(x: jax.Array, per_layer: bool) -> jax.Array:
    """LAMB's norms per node, and per layer where ``per_layer`` (the
    second axis of a stacked leaf), broadcastable against ``x``."""
    axes = tuple(range(2 if per_layer else 1, x.ndim))
    return jnp.sqrt(jnp.sum(x * x, axis=axes, keepdims=True))


def make_step(cfg: Dict, opt: Dict, n_nodes: int, groups: int,
              precision: str = "highest", fault: Optional[str] = None,
              lamb_per_layer: bool = False):
    """``step(state, batch, lr, hop) -> (state, losses)``; ``hop`` 0 is
    the global average, else the gossip peer's distance.  ``fault``
    plants one of the faults the benchmark must catch: "half_batch"
    (each node's loss over the first half of its rows, or of its one
    row's positions), "no_exchange" (no communication round),
    "no_gossip" (no round in the gossip steps; the global average kept)
    or "frozen" (the state comes back unchanged)."""
    ein = make_einsum(precision)
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    wd, clip = opt["weight_decay"], opt["grad_clip"]
    lamb = opt["name"] == "lamb"
    if opt["name"] not in ("adamw", "lamb"):
        raise ValueError(f"reference has no optimizer {opt['name']!r}")

    def loss_and_grad(pb):
        params, batch = pb
        if fault == "half_batch":
            # half the rows; a single row keeps its first half of positions
            rows, seq = batch["inputs"].shape
            batch = jax.tree.map(
                (lambda x: x[:rows // 2]) if rows > 1 else
                (lambda x: x[:, :seq // 2]), batch)
        return jax.value_and_grad(node_loss)(params, batch, cfg, ein)

    def step(state, batch, lr, hop):
        params, m, v, count = (state["params"], state["m"], state["v"],
                               state["count"])
        losses, grads = _per_node(loss_and_grad, (params, batch), groups)
        if clip:
            gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
            scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12))
            grads = jax.tree.map(lambda g: g * scale, grads)
        count = count + 1
        m = jax.tree.map(lambda mi, g: b1 * mi + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda vi, g: b2 * vi + (1 - b2) * g * g, v, grads)
        bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count

        def update(path, p, mi, vi):
            u = (mi / bc1) / (jnp.sqrt(vi / bc2) + eps) + wd * p
            if lamb:
                per_layer = lamb_per_layer and any(
                    getattr(k, "key", None) == "stack" for k in path)
                wn, un = (_trust_norms(p, per_layer),
                          _trust_norms(u, per_layer))
                u = u * jnp.where((wn > 0) & (un > 0),
                                  wn / jnp.maximum(un, 1e-12), 1.0)
            return p - lr * u

        new = jax.tree_util.tree_map_with_path(update, params, m, v)
        if fault != "no_exchange" and not (fault == "no_gossip" and hop):
            if hop == 0:
                new = jax.tree.map(
                    lambda x: jnp.broadcast_to(jnp.mean(x, axis=0), x.shape),
                    new)
            else:
                new = jax.tree.map(
                    lambda x: 0.5 * x + 0.5 * jnp.roll(x, -hop, axis=0), new)
        out = {"params": new, "m": m, "v": v, "count": count}
        if fault == "frozen":
            out = dict(state)
        return out, losses

    return step


def init_state(params0: Dict, n_nodes: int) -> Dict:
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_nodes,) + x.shape), params0)
    zeros = jax.tree.map(jnp.zeros_like, stacked)
    return {"params": stacked, "m": zeros,
            "v": jax.tree.map(jnp.zeros_like, stacked),
            "count": jnp.zeros((), jnp.float32)}


def hop_of(phase: str, shift: int, n_nodes: int) -> int:
    """The gossip peer distance at schedule shift ``shift`` (0: global)."""
    if phase == "global":
        return 0
    p = int(round(math.log2(n_nodes)))
    return 2 ** (shift % p)
