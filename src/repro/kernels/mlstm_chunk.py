"""Chunkwise mLSTM — Pallas TPU kernel.

The xLSTM matrix-memory recurrence in its chunkwise-parallel form
(repro.models.ssm._mlstm_chunk_scan): intra-chunk attention-style matmuls on
the MXU + a sequential inter-chunk state (C, n, m) carried in VMEM scratch.

Grid: (B·nh, S/chunk) — the chunk dim iterates sequentially per TensorCore so
the (d_k × d_v) matrix memory persists in scratch across chunk steps; one
(chunk × d) tile of q/k/v lives in VMEM per step.  Log-space gate
stabilization is identical to the reference (m carried per head).

VMEM per step ≈ 3·L·d·2B tiles + (d_k·d_v + L²)·4B scratch — with L=64,
d=128: well under 1 MB.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_BIG = -1e9


def _mlstm_kernel(q_ref, k_ref, v_ref, li_ref, lf_ref, h_ref,
                  C_scr, n_scr, m_scr, *, chunk: int, seq_len: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        C_scr[...] = jnp.zeros_like(C_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_BIG)

    q = q_ref[0].astype(jnp.float32)            # (L, dk)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)            # (L, dv)
    li = li_ref[0].astype(jnp.float32)          # (L,)
    lf = lf_ref[0].astype(jnp.float32)

    # mask pad positions beyond seq_len: forget=1 (log 0), input gate -inf
    pos = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk,), 0)
    valid = pos < seq_len
    li = jnp.where(valid, li, NEG_BIG)
    lf = jnp.where(valid, lf, 0.0)

    F = jnp.cumsum(lf)                          # inclusive (L,)
    w = F[:, None] - F[None, :] + li[None, :]   # (L, L): t rows, τ cols
    tril = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    w = jnp.where(tril, w, -jnp.inf)
    w_max = jnp.max(w, axis=1)                  # (L,)
    m_prev = m_scr[0]
    m_in = m_prev + F
    m_t = jnp.maximum(w_max, m_in)

    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (L,L)
    gates = jnp.where(tril, jnp.exp(w - m_t[:, None]), 0.0)
    probs = scores * gates
    h_intra = jax.lax.dot_general(probs, v, (((1,), (0,)), ((), ())))
    den_intra = jnp.sum(probs, axis=1)

    C = C_scr[...]                              # (dk, dv), stabilized
    n = n_scr[...]                              # (dk,)
    sgate = jnp.exp(m_in - m_t)
    h_state = jax.lax.dot_general(q, C, (((1,), (0,)), ((), ()))) \
        * sgate[:, None]
    den_state = (q @ n) * sgate
    den = jnp.maximum(jnp.abs(den_intra + den_state), jnp.exp(-m_t))
    h = (h_intra + h_state) / den[:, None]
    h_ref[0] = h.astype(h_ref.dtype)

    # ---- state update to end of chunk ----
    F_L = F[-1]
    w_end = F_L - F + li                        # (L,)
    m_end = jnp.maximum(jnp.max(w_end), m_prev + F_L)
    kg = jnp.exp(w_end - m_end)
    decay = jnp.exp(m_prev + F_L - m_end)
    C_scr[...] = C * decay + jax.lax.dot_general(
        k * kg[:, None], v, (((0,), (0,)), ((), ())))
    n_scr[...] = n * decay + jnp.sum(k * kg[:, None], axis=0)
    m_scr[0] = m_end


def mlstm_chunk(q: jax.Array, k: jax.Array, v: jax.Array, log_i: jax.Array,
                log_f: jax.Array, *, chunk: int = 64,
                interpret: bool = False) -> jax.Array:
    """q,k: (B,S,nh,dk); v: (B,S,nh,dv); log_i/log_f: (B,S,nh).
    Returns h: (B,S,nh,dv) — matches models.ssm._mlstm_chunk_scan outputs."""
    B, S, nh, dk = q.shape
    dv = v.shape[-1]
    L = max(min(chunk, S), 8)
    pad = (-S) % L

    def heads_major(t):
        # (B,S,nh,d) -> (B*nh, S+pad, d)
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        t = jnp.moveaxis(t, 2, 1)
        return t.reshape((B * nh, S + pad) + t.shape[3:])

    qh, kh, vh = heads_major(q), heads_major(k), heads_major(v)
    lih, lfh = heads_major(log_i), heads_major(log_f)
    nc = (S + pad) // L

    kernel = functools.partial(_mlstm_kernel, chunk=L, seq_len=S)
    out = pl.pallas_call(
        kernel,
        grid=(B * nh, nc),
        in_specs=[
            pl.BlockSpec((1, L, dk), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, L, dk), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, L, dv), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, L), lambda bh, ci: (bh, ci)),
            pl.BlockSpec((1, L), lambda bh, ci: (bh, ci)),
        ],
        out_specs=pl.BlockSpec((1, L, dv), lambda bh, ci: (bh, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((B * nh, S + pad, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((dk, dv), jnp.float32),
            pltpu.VMEM((dk,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qh, kh, vh, lih, lfh)
    out = out[:, :S].reshape(B, nh, S, dv)
    return jnp.moveaxis(out, 1, 2)
