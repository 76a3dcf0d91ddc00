"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU so the same call sites work in this CPU
container (kernel body executed in Python) and compile to Mosaic on TPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.mixing_pallas import _default_interpret
from repro.kernels.mlstm_chunk import mlstm_chunk as _mlstm
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "block_q", "block_k", "interpret"))
def flash_attention_op(q, k, v, *, causal: bool = True,
                       window: Optional[int] = None,
                       softcap: Optional[float] = None,
                       scale: Optional[float] = None,
                       block_q: int = 128, block_k: int = 128,
                       interpret: Optional[bool] = None):
    interp = _default_interpret() if interpret is None else interpret
    return _flash(q, k, v, causal=causal, window=window, softcap=softcap,
                  scale=scale, block_q=block_q, block_k=block_k,
                  interpret=interp)


@functools.partial(jax.jit, static_argnames=(
    "eps", "offset", "block_rows", "interpret"))
def rmsnorm_op(x, w, *, eps: float = 1e-6, offset: float = 0.0,
               block_rows: int = 256, interpret: Optional[bool] = None):
    interp = _default_interpret() if interpret is None else interpret
    return _rmsnorm(x, w, eps=eps, offset=offset, block_rows=block_rows,
                    interpret=interp)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunk_op(q, k, v, log_i, log_f, *, chunk: int = 64,
                   interpret: Optional[bool] = None):
    interp = _default_interpret() if interpret is None else interpret
    return _mlstm(q, k, v, log_i, log_f, chunk=chunk, interpret=interp)
