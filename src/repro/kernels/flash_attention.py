"""Flash attention — Pallas TPU kernel with explicit BlockSpec VMEM tiling.

TPU mapping: grid (batch·q_heads, Sq/block_q, Sk/block_k); the innermost grid
dim iterates sequentially on a TensorCore, so the online-softmax running state
(m, l, acc) lives in VMEM scratch that persists across kv-block steps.
Blocks are MXU-aligned (block_q/block_k default 128; head_dim is the
contraction dim).  GQA is expressed through the k/v BlockSpec index maps
(q-head → kv-head), so kv blocks are never replicated into VMEM.

Supports: causal masking, sliding window, Gemma-2 attn-logit softcap.
Validated in interpret mode against repro.kernels.ref (CPU container);
the compiled path targets TPU.

VMEM budget per grid step ≈ (block_q + 2·block_k)·D·2B input tiles
+ block_q·D·4B f32 acc + block_q·block_k·4B scores — well under a v5e
core's ~16 MB VMEM for the default tiles at any supported head_dim.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: Optional[int],
                  softcap: Optional[float], block_q: int, block_k: int,
                  n_kv_blocks: int, sq: int, sk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0].astype(jnp.float32)            # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = (q_pos < sq) & (k_pos < sk)          # pad positions
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_scr[...] = m_new
    l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        lsum = l_scr[...]
        safe_l = jnp.where(lsum > 0.0, lsum, 1.0)
        o_ref[0] = (acc_scr[...] / safe_l[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Sq, H, D); k,v: (B, Sk, KH, D), H % KH == 0 → (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    assert H % KH == 0, (H, KH)
    group = H // KH
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    block_q_ = max(min(block_q, Sq), 8)
    block_k_ = max(min(block_k, Sk), 8)
    pad_q = (-Sq) % block_q_
    pad_k = (-Sk) % block_k_
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))

    # (B, S, H, D) -> (B*H, S, D) head-major layout
    qh = q.transpose(0, 2, 1, 3).reshape(B * H, Sq + pad_q, D)
    kh = k.transpose(0, 2, 1, 3).reshape(B * KH, Sk + pad_k, D)
    vh = v.transpose(0, 2, 1, 3).reshape(B * KH, Sk + pad_k, D)

    nq = (Sq + pad_q) // block_q_
    nk = (Sk + pad_k) // block_k_

    def q_index(bh, qi, ki):
        return (bh, qi, 0)

    def kv_index(bh, qi, ki):
        b, h = bh // H, bh % H
        return (b * KH + h // group, ki, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q_, block_k=block_k_, n_kv_blocks=nk,
        sq=Sq, sk=Sk)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q_, D), q_index),
            pl.BlockSpec((1, block_k_, D), kv_index),
            pl.BlockSpec((1, block_k_, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q_, D), q_index),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq + pad_q, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q_,), jnp.float32),       # m
            pltpu.VMEM((block_q_,), jnp.float32),       # l
            pltpu.VMEM((block_q_, D), jnp.float32),     # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qh, kh, vh)

    out = out[:, :Sq].reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    return out
