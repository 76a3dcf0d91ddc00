"""input_specs: ShapeDtypeStruct stand-ins + shardings for every
(architecture × input shape × mesh) combination — weak-type-correct,
shardable, zero allocation.

Three step kinds:
  train   — ``train_step(state, batch, lr)`` (TrainState via eval_shape)
  prefill — ``forward(params, batch)`` full-sequence with cache out
  decode  — ``serve_step(params, caches, tokens, pos)`` ONE new token against
            a full ``seq_len`` cache (the brief's decode semantics)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import (DataConfig, DistConfig, InputShape,
                                ModelConfig, OptimizerConfig, TrainConfig)
from repro.launch.mesh import n_gossip_nodes
from repro.models import sharding as shd
from repro.models.model import Model, make_model
from repro.optim import make_optimizer
from repro.train.state import (TrainState, stack_for_nodes, stacked_axes,
                               state_axes)

PyTree = Any


def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dtype)


# ---------------------------------------------------------------------------
# Batch specs (train / prefill)
# ---------------------------------------------------------------------------
def batch_specs(cfg: ModelConfig, n_nodes: Optional[int], batch: int,
                seq_len: int) -> Tuple[Dict[str, jax.ShapeDtypeStruct],
                                       Dict[str, tuple]]:
    """n_nodes None => serving layout (B, S); else (n, B/n, S)."""
    if n_nodes is None:
        lead, lead_axes = (batch,), ("batch",)
    else:
        assert batch % n_nodes == 0, (batch, n_nodes)
        lead, lead_axes = (n_nodes, batch // n_nodes), ("node",
                                                        "per_node_batch")
    shapes: Dict[str, Any] = {}
    axes: Dict[str, Any] = {}
    if cfg.family == "encoder" and cfg.audio is not None:
        shapes["frames"] = _sds(lead + (seq_len, cfg.d_model), jnp.bfloat16)
        axes["frames"] = lead_axes + (None, None)
        shapes["mask"] = _sds(lead + (seq_len,), jnp.bool_)
        axes["mask"] = lead_axes + (None,)
        shapes["targets"] = _sds(lead + (seq_len,), jnp.int32)
        axes["targets"] = lead_axes + (None,)
        return shapes, axes
    shapes["inputs"] = _sds(lead + (seq_len,), jnp.int32)
    axes["inputs"] = lead_axes + (None,)
    shapes["targets"] = _sds(lead + (seq_len,), jnp.int32)
    axes["targets"] = lead_axes + (None,)
    if cfg.family == "encoder":
        shapes["mask"] = _sds(lead + (seq_len,), jnp.bool_)
        axes["mask"] = lead_axes + (None,)
    if cfg.family == "vlm" and cfg.vision is not None:
        n_img = cfg.vision.n_tiles * cfg.vision.patches_per_tile
        shapes["patches"] = _sds(lead + (n_img, cfg.d_model), jnp.bfloat16)
        axes["patches"] = lead_axes + (None, None)
    return shapes, axes


# ---------------------------------------------------------------------------
# Train specs
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class TrainSpecs:
    state_sds: TrainState
    state_shardings: TrainState
    batch_sds: Dict[str, jax.ShapeDtypeStruct]
    batch_shardings: Dict[str, NamedSharding]
    lr_sds: jax.ShapeDtypeStruct
    lr_sharding: NamedSharding
    n_nodes: int
    mode: str


def train_specs(cfg: ModelConfig, mesh: Mesh, shape: InputShape, *,
                dist: DistConfig = DistConfig(),
                optimizer: OptimizerConfig = OptimizerConfig()) -> TrainSpecs:
    model = make_model(cfg)
    n_nodes = n_gossip_nodes(mesh, dist.node_axis)
    mode = "train_data" if dist.node_axis == "data" else "train_pod"
    opt = make_optimizer(optimizer, per_node=True)
    axes_box: Dict[str, Any] = {}
    from repro.core import algo as algo_lib

    def build_state(key):
        params, axes = model.init(key)
        axes_box["axes"] = axes
        stacked = stack_for_nodes(params, n_nodes)
        opt_state = opt.init(stacked)
        extras = algo_lib.init_extras(dist, stacked, n_nodes)
        return TrainState(params=stacked, opt_state=opt_state,
                          step=jnp.zeros((), jnp.int32), extras=extras)

    state_sds = jax.eval_shape(build_state, jax.random.PRNGKey(0))
    axes = axes_box["axes"]
    st_axes = stacked_axes(axes)
    state_axes_tree = state_axes(
        st_axes, optimizer.name,
        extras=algo_lib.extras_axes(dist, st_axes, axes))
    state_sh = shd.shardings_for(state_axes_tree, mode, mesh, state_sds)

    b_sds, b_axes = batch_specs(cfg, n_nodes, shape.global_batch,
                                shape.seq_len)
    b_sh = shd.shardings_for(b_axes, mode, mesh, b_sds)
    repl = NamedSharding(mesh, P())
    return TrainSpecs(state_sds=state_sds, state_shardings=state_sh,
                      batch_sds=b_sds, batch_shardings=b_sh,
                      lr_sds=_sds((), jnp.float32), lr_sharding=repl,
                      n_nodes=n_nodes, mode=mode)


# ---------------------------------------------------------------------------
# Serve specs (prefill / decode)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ServeSpecs:
    params_sds: PyTree
    params_shardings: PyTree
    batch_sds: Optional[Dict[str, jax.ShapeDtypeStruct]]   # prefill
    batch_shardings: Optional[Dict[str, NamedSharding]]
    cache_sds: Optional[PyTree]                             # decode
    cache_shardings: Optional[PyTree]
    tokens_sds: Optional[jax.ShapeDtypeStruct]
    tokens_sharding: Optional[NamedSharding]
    pos_sds: Optional[jax.ShapeDtypeStruct]
    pos_sharding: Optional[NamedSharding]
    mode: str


def serve_specs(cfg: ModelConfig, mesh: Mesh, shape: InputShape, *,
                param_sharding: str = "tp",
                context_parallel: Optional[bool] = None) -> ServeSpecs:
    model = make_model(cfg)
    axes_box: Dict[str, Any] = {}

    def build_params(key):
        params, axes = model.init(key)
        axes_box["axes"] = axes
        return params

    params_sds = jax.eval_shape(build_params, jax.random.PRNGKey(0))
    axes = axes_box["axes"]
    data_size = dict(mesh.shape).get("data", 1)
    if context_parallel is None:
        context_parallel = (shape.kind == "decode"
                            and shape.global_batch < data_size)
    mode = ("serve_cp" if context_parallel
            else {"tp": "serve_tp", "2d": "serve_2d",
                  "tp_seq": "serve_tp_seq"}[param_sharding])
    params_sh = shd.shardings_for(axes, mode, mesh, params_sds)

    if shape.kind == "prefill":
        b_sds, b_axes = batch_specs(cfg, None, shape.global_batch,
                                    shape.seq_len)
        b_sds.pop("targets", None)
        b_axes.pop("targets", None)
        b_sh = shd.shardings_for(b_axes, mode, mesh, b_sds)
        return ServeSpecs(params_sds, params_sh, b_sds, b_sh,
                          None, None, None, None, None, None, mode)

    # decode: full-length cache, one new token
    B = shape.global_batch
    cache_sds = jax.eval_shape(
        lambda: model.init_cache(B, shape.seq_len))
    cache_axes = model.cache_axes()
    cache_sh = shd.shardings_for(cache_axes, mode, mesh, cache_sds)
    tok_axes = ("batch", None)
    pos_axes = ("batch",)
    return ServeSpecs(
        params_sds, params_sh, None, None, cache_sds, cache_sh,
        _sds((B, 1), jnp.int32),
        NamedSharding(mesh, shd.logical_to_spec(tok_axes, mode, mesh,
                                                shape=(B, 1))),
        _sds((B,), jnp.int32),
        NamedSharding(mesh, shd.logical_to_spec(pos_axes, mode, mesh,
                                                shape=(B,))),
        mode)
