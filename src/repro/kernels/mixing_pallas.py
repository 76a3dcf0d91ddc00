"""Fused Pallas mixing kernels — the paper's communication primitive as a
first-class TPU kernel (DESIGN.md §2.1, "pallas backend").

The reference path in :mod:`repro.core.mixing` applies the gossip round as a
chain of unfused jnp ops: the SGD half-step ``x − γg`` is one pass over HBM,
then every circulant shift term ``w_s · roll(x, s)`` re-reads the parameters,
then the weighted sum writes them back — ``2 + |shifts|`` HBM round-trips per
round.  Here each leaf's round is one ``pallas_call`` that reads the leaf
and writes it back once:

* leaves *at or above* ``leaf_threshold`` per-node elements are mixed in
  their own layout.  The kernel takes a free view of the leaf: its dims
  in the order the device's default layout keeps them in memory, each
  run of dims above the two tiled minor ones merged.  On a v5e
  ``[n, 12, 768, 3072]`` stays as it is, ``[n, 12, 768, 12, 64]`` is
  mixed as ``(n, 144, 64, 768)`` and ``[n, 50257, 768]`` as
  ``(50257, n, 768)``, its node axis the second-minor dim.  No reshape to
  ``(n, D)``, pad, slice or relayout copy lies between a large leaf and
  its kernel.  The kernel returns the leaf node-leading: in place where
  the node axis leads in memory, else moved first in VMEM (XLA then
  copies it into the leaf's layout);
* leaves *below* the threshold are flattened and concatenated into one
  ``(n, D)`` node-major staging matrix, so one kernel covers the long tail
  of small parameters (``block_d`` columns a step);
* the block a grid step moves is derived from the leaf's shape: whole
  minor dims where they fit, about ``_BLOCK_BYTES`` a step, the grid a
  ``pl.cdiv`` of each blocked dim.  The edge block of a ragged dim is
  masked: its rows count neither in the residual nor in x̄ (their writes
  are dropped);
* every ``pallas_call`` whose x input is node-leading aliases it with
  the mixed output (``input_output_aliases``), so inside a jitted caller
  (train step, simulator) XLA updates the leaf or the staging buffer in
  place — the aliasing contract is that the kernel's input is consumed
  and must not be read again (DESIGN.md §2.1);
* the mix over the node axis is an ``(n, n) @ (n, block_d)`` MXU dot in
  the packed group, whose nodes sit in a tile's sublanes.  A large leaf's
  view keeps its node axis untiled, which Mosaic cannot contract on the
  MXU, so there it runs on the VPU: ``d`` and ``M`` are scalars in SMEM
  (n ≤ 32), each node's row is ``Σ_j M_ij · cast(x_j)`` summed in node
  order, plus the uncast self term ``d_i · x_i``, all in fp32
  (interpreted off the chip, it stays the dot: see ``_dot_chunk``).

Three public entry points, one kernel body:

``fused_step_mix``   — ``W · (x − γg)`` (γ, g optional → plain ``W·x``)
``global_average`` / ``pod_average`` — the same kernel with W = 𝟙𝟙ᵀ/n or its
                       pod-block-diagonal variant (the PGA / Hier-PGA rounds)
``mix_residual``     — additionally emits ``x̄`` (unless asked not to) and
                       the consensus distance ``Σ_i ‖x_i − x̄‖²`` of the
                       *mixed* iterate, so eval loops stop re-reading the
                       parameters they just wrote

Wire-dtype ("orthogonal quantization") semantics match the reference: for
gossip rounds the *self* term stays in the storage dtype and only neighbor
terms are cast to ``comm_dtype``; averaging rounds cast everything.  The grid
topology ignores ``comm_dtype`` exactly like the reference does.

``interpret`` defaults to True off-TPU (same convention as kernels/ops.py),
so the backend is exercised end-to-end in CPU CI and compiles to Mosaic on
TPU unchanged.

Scope: ``fused_step_mix`` / ``global_average`` / ``pod_average`` /
``mix_residual`` operate on the *local* stacked node axis — the simulator,
single-host training, and the per-chip tail of a sharded step.  For a mesh
whose node axis is sharded, :func:`shard_mix_block` is the per-shard kernel
behind ``repro.core.mixing.communicate_sharded``: each shard holds an
``(m, D)`` row-block of the stacked state, receives its neighbor blocks via
``jax.lax.ppermute`` halo exchange, and this kernel fuses the rectangular
mix ``d ⊙ x_local + M_r · xs`` (plus the consensus partial sums) in one
pass over the local block (DESIGN.md §2.1 dispatch table).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import topology as topo

PyTree = Any

KERNEL_PHASES = ("gossip", "global", "pod_avg")

# Per-node element count at or above which a leaf gets its own kernel
# dispatch instead of riding the concatenation staging buffer
# (DistConfig.pallas_leaf_threshold overrides per run).
LEAF_DISPATCH_THRESHOLD = 262_144

# Bytes of fp32 x a grid step of a large leaf's kernel loads, over all
# nodes (0.5 MiB a node at n = 4).  In and out, double-buffered, plus the
# body's fp32 temporaries stay inside v5e's 16 MiB default scoped VMEM.
_BLOCK_BYTES = 2 << 20


# Every kernel matmul is an fp32 mix like the reference backend's: Mosaic's
# default contraction precision for fp32 operands is not guaranteed to be fp32.
_FP32 = jax.lax.Precision.HIGHEST


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Phase → (self-weight diagonal, off/cast factor) decomposition
# ---------------------------------------------------------------------------
def phase_matrices(phase: str, topology: str, n: int, step: int = 0,
                   n_pods: int = 1) -> Tuple[np.ndarray, np.ndarray]:
    """Decompose one communication round into ``x ← d ⊙ x + M · cast(x)``.

    Returns ``(d, M)`` with ``d`` shape (n, 1), ``M`` shape (n, n):

    * gossip:  ``d = diag(W)``, ``M = W − diag(W)`` — the self term is kept
      out of ``M`` so the wire cast touches only neighbor traffic, matching
      ``mixing.mix_array``.
    * global:  ``d = 0``, ``M = 𝟙𝟙ᵀ/n`` — the reference all-reduce casts its
      whole operand, and with ``d = 0`` the cast-everything semantics fall
      out of the same ``d ⊙ x + M · cast(x)`` form.
    * pod_avg: ``d = 0``, ``M = blockdiag(𝟙𝟙ᵀ/per)`` — likewise.
    """
    if phase == "gossip":
        W = topo.mixing_matrix(topology, n, step=step)
        d = np.diag(W).copy()
        M = W - np.diag(d)
        return d.reshape(n, 1).astype(np.float32), M.astype(np.float32)
    if phase == "global":
        M = np.full((n, n), 1.0 / n)
        return np.zeros((n, 1), np.float32), M.astype(np.float32)
    if phase == "pod_avg":
        if n % n_pods != 0:
            raise ValueError(f"n={n} not divisible by n_pods={n_pods}")
        per = n // n_pods
        M = np.zeros((n, n))
        for p in range(n_pods):
            M[p * per:(p + 1) * per, p * per:(p + 1) * per] = 1.0 / per
        return np.zeros((n, 1), np.float32), M.astype(np.float32)
    raise ValueError(f"no kernel decomposition for phase {phase!r}")


# ---------------------------------------------------------------------------
# PyTree <-> (n, D) node-major matrix
# ---------------------------------------------------------------------------
def _pack_rows(leaves, n: int) -> jax.Array:
    """Concatenate leaves' non-node dims into one fp32 ``(n, D)`` matrix."""
    cols = [lf.reshape(n, -1).astype(jnp.float32) for lf in leaves]
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)


def flatten_nodes(tree: PyTree) -> Tuple[jax.Array, Callable]:
    """``(flat, unflatten)`` for a node-stacked pytree: ``flat`` is the fp32
    ``(n, D)`` node-major packing of every leaf;
    ``unflatten(flat2, drop_node=False)`` restores the original structure,
    shapes, and per-leaf dtypes.  With ``drop_node=True`` it maps a
    ``(1, D)`` row (e.g. the kernel's x̄ output) back to leaves without the
    node axis.  Shared by the stacked entry points and
    ``mixing.communicate_sharded`` — the packing layout must stay identical
    between them.
    """
    leaves, treedef = jax.tree.flatten(tree)
    n = leaves[0].shape[0]
    shapes = [lf.shape for lf in leaves]
    dtypes = [lf.dtype for lf in leaves]
    sizes = [int(np.prod(s[1:], dtype=np.int64)) for s in shapes]
    flat = _pack_rows(leaves, n)

    def unflatten(f: jax.Array, drop_node: bool = False) -> PyTree:
        out, off = [], 0
        for shape, dtype, size in zip(shapes, dtypes, sizes):
            piece = f[:, off:off + size]
            if drop_node:
                out.append(piece.reshape(shape[1:]).astype(dtype))
            else:
                out.append(piece.reshape((n,) + shape[1:]).astype(dtype))
            off += size
        return jax.tree.unflatten(treedef, out)

    return flat, unflatten


def flatten_nodes_sharded(tree: PyTree, k_model: int
                          ) -> Tuple[jax.Array, Callable]:
    """Model-sharded variant of :func:`flatten_nodes` (``k_model == 1``
    degenerates to it exactly, byte for byte).

    Each leaf's flattened columns are zero-padded to a multiple of
    ``k_model`` and split into ``k_model`` equal chunks; the packed matrix
    concatenates chunk ``j`` of *every* leaf contiguously, so a
    ``P(node_axes, model_axes)`` sharding hands model shard ``j`` exactly
    chunk ``j`` of every leaf — a per-leaf wire array sharded
    ``P(node_axes, model_axes)`` on its own column axis stays
    column-aligned with the packed matrix inside the shard_map body
    (``mixing._communicate_sharded_compressed``).  Zero padding is inert
    (same pad-to-multiple semantics as ``compress.collective.pad_cols``,
    inlined here to keep the kernels layer free of compress imports): pad
    columns mix to zero and quantize to zero codes, and ``unflatten``
    strips them per leaf.
    """
    if k_model <= 1:
        return flatten_nodes(tree)
    leaves, treedef = jax.tree.flatten(tree)
    n = leaves[0].shape[0]
    shapes = [lf.shape for lf in leaves]
    dtypes = [lf.dtype for lf in leaves]
    sizes = [int(np.prod(s[1:], dtype=np.int64)) for s in shapes]
    chunks = [-(-s // k_model) for s in sizes]       # per-shard leaf width
    width = sum(chunks)                              # columns per model shard
    x2 = [lf.reshape(n, -1).astype(jnp.float32) for lf in leaves]
    x2 = [jnp.pad(x, ((0, 0), (0, c * k_model - s))) if c * k_model != s
          else x for x, c, s in zip(x2, chunks, sizes)]
    cols = [x[:, j * c:(j + 1) * c]
            for j in range(k_model) for x, c in zip(x2, chunks)]
    flat = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)

    def unflatten(f: jax.Array, drop_node: bool = False) -> PyTree:
        out, off = [], 0
        for shape, dtype, size, c in zip(shapes, dtypes, sizes, chunks):
            parts = [f[:, j * width + off:j * width + off + c]
                     for j in range(k_model)]
            piece = jnp.concatenate(parts, axis=1)[:, :size]
            if drop_node:
                out.append(piece.reshape(shape[1:]).astype(dtype))
            else:
                out.append(piece.reshape((n,) + shape[1:]).astype(dtype))
            off += c
        return jax.tree.unflatten(treedef, out)

    return flat, unflatten


# ---------------------------------------------------------------------------
# Kernel body (shared by all entry points)
# ---------------------------------------------------------------------------
def _dot_chunk(interpret: bool, block_d: int) -> int:
    """Columns per ``jnp.dot`` of a large leaf's node mix, or 0 for the
    VPU node sum (the packed group always takes the dot).

    Compiled for the chip, the mix is the VPU sum (Mosaic has no matmul
    over a leading axis).  Interpreted on a CPU it stays the
    ``(n, n) @ (n, ≤ block_d)`` dot of the packed kernel: XLA:CPU picks a
    dot's summation order (and FMA use) by the operand shapes and the host
    CPU, so only the same dot on the same widths keeps the CPU goldens
    bitwise on every host.  No one VPU summation order stands in for it:
    on one AVX-512 host a node-order sum changes one pallas golden
    trajectory of 26 and a pairwise tree 21, while on another host the
    pairwise tree matched the dot and the node order did not."""
    return block_d if interpret else 0


def _mix_kernel(*refs, ax: int, with_g: bool, with_residual: bool,
                with_xbar: bool, wire: bool, dims: Tuple[int, ...],
                block: Tuple[int, ...], dot_chunk: int):
    """One grid step: load a tile, fuse half-step + mix (+ residual).

    The x (and g) tile has the node axis whole at position ``ax``; the
    mixed tile, x̄ and everything between are node-leading.  ``dims`` /
    ``block`` are the non-node dims of the array and of its tile.  A dim
    that the tile does not divide is ragged: its out-of-range rows are
    masked out of the residual, and Pallas drops their writes.

    Ref order: [gamma?, d, M, x, g?] then outputs [o, xbar?, r?].
    """
    refs = list(refs)
    gamma_ref = refs.pop(0) if with_g else None
    d_ref, m_ref, x_ref = refs.pop(0), refs.pop(0), refs.pop(0)
    g_ref = refs.pop(0) if with_g else None
    o_ref = refs.pop(0)
    xbar_ref = refs.pop(0) if with_residual and with_xbar else None
    r_ref = refs.pop(0) if with_residual else None
    n = x_ref.shape[ax]

    def nodes_first(ref):
        return jnp.moveaxis(ref[...].astype(jnp.float32), ax, 0)

    x = nodes_first(x_ref)                                   # (n, *block)
    if with_g:
        x = x - gamma_ref[0, 0] * nodes_first(g_ref)
    # wire-dtype cast applies to the M term only: neighbor traffic for gossip
    # (d carries the uncast self term), everything for averages (d = 0)
    onwire = x.astype(jnp.bfloat16).astype(jnp.float32) if wire else x
    if dot_chunk:
        # every dot as wide as the packed kernel's tiles were, zero padded
        w2, x2 = onwire.reshape(n, -1), x.reshape(n, -1)
        cols = w2.shape[1]
        c = min(dot_chunk, int(np.prod(dims, dtype=np.int64)))
        pad = ((0, 0), (0, -cols % c))
        w2, x2 = jnp.pad(w2, pad), jnp.pad(x2, pad)
        d2 = d_ref[...].reshape(n, 1)
        parts = [jnp.dot(m_ref[...], w2[:, k:k + c],
                         preferred_element_type=jnp.float32, precision=_FP32)
                 + d2 * x2[:, k:k + c]
                 for k in range(0, w2.shape[1], c)]
        mixed = jnp.concatenate(parts, axis=1)[:, :cols].reshape(x.shape)
    else:
        # row i = Σ_j M_ij · cast(x_j) in node order, then the self term
        rows = []
        for i in range(n):
            acc = m_ref[i, 0] * onwire[0:1]
            for j in range(1, n):
                acc = acc + m_ref[i, j] * onwire[j:j + 1]
            rows.append(acc + d_ref[i] * x[i:i + 1])
        mixed = jnp.concatenate(rows, axis=0)
    o_ref[...] = mixed.astype(o_ref.dtype)

    if with_residual:
        xbar = jnp.mean(mixed, axis=0, keepdims=True)        # (1, *block)
        if with_xbar:
            xbar_ref[...] = xbar.astype(xbar_ref.dtype)
        sq = jnp.square(mixed - xbar)
        inside = None
        for k, (size, b) in enumerate(zip(dims, block)):
            if size % b:
                idx = pl.program_id(k) * b + jax.lax.broadcasted_iota(
                    jnp.int32, xbar.shape, k + 1)
                ok = idx < size
                inside = ok if inside is None else inside & ok
        if inside is not None:
            sq = jnp.where(inside, sq, 0.0)

        @pl.when(functools.reduce(
            jnp.logical_and, [pl.program_id(k) == 0 for k in range(len(dims))]))
        def _init():
            r_ref[0, 0] = 0.0

        r_ref[0, 0] += jnp.sum(sq)


@functools.lru_cache(maxsize=None)
def _memory_order(shape: Tuple[int, ...], dtype) -> Tuple[int, ...]:
    """A leaf's dims, major first, in the default layout of the default
    device — the layout a jit's parameters and results take.  XLA:TPU
    permutes dims so that the two tiled minor ones pad least: a
    ``[4, 50257, 768]`` array lies in memory as ``(50257, 4, 768)``, a
    ``[4, 12, 768, 12, 64]`` one as ``(4, 12, 12, 64, 768)``."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return tuple(range(len(shape)))
    lay = dev.client.get_default_layout(np.dtype(dtype), shape, dev)
    return tuple(lay._xla_layout().minor_to_major()[::-1])


def _leaf_view(order: Tuple[int, ...], shape: Tuple[int, ...]
               ) -> Tuple[Tuple[int, ...], int]:
    """The view a large leaf is mixed in, and its node axis: the leaf's
    dims in memory ``order``, with each run of dims above the two tiled
    minor ones merged, the node axis kept apart.  Transposing to ``order``
    and merging those runs moves no byte, so the view is free."""
    mem = [shape[p] for p in order]
    node, nd = order.index(0), len(shape)
    view: list = []
    ax, prev_free = 0, False
    for k, size in enumerate(mem):
        free = k != node and k < nd - 2
        if free and prev_free:
            view[-1] *= size
        else:
            view.append(size)
        if k == node:
            ax = len(view) - 1
        prev_free = free
    return tuple(view), ax


def _leaf_block(view: Tuple[int, ...], ax: int, itemsize: int,
                budget: int = _BLOCK_BYTES) -> Tuple[int, ...]:
    """Tile of a view that loads about ``budget`` bytes of fp32: the node
    axis whole, then whole dims from the minor end while they fit, one dim
    cut to a multiple of its tile, the dims above it 1.  Dims count at
    their padded (8·4/itemsize, 128) tile sizes, as in VMEM; a dim that is
    tiled in the kernel's node-leading output is cut to that output's
    tile too."""
    def tiles(ndim):
        t = [1] * ndim
        t[-1] = 128
        if ndim > 1:
            t[-2] = 8 * 4 // itemsize
        return t

    nd = len(view)
    tile = tiles(nd)
    rest = [k for k in range(nd) if k != ax]
    for k, t in zip(rest, tiles(nd)[1:]):
        tile[k] = max(tile[k], t)
    room = budget // 4 // (-(-view[ax] // tile[ax]) * tile[ax])
    block = list(view)
    for k in reversed(rest):
        padded = -(-view[k] // tile[k]) * tile[k]
        if padded <= room:
            room //= padded
        else:
            block[k] = min(view[k], max(tile[k], room // tile[k] * tile[k]))
            room = 1
    return tuple(block)


@functools.partial(
    jax.jit,
    static_argnames=("ax", "block", "with_g", "with_residual", "with_xbar",
                     "wire", "dot_chunk", "interpret"))
def _mix_nodes(x: jax.Array, g: Optional[jax.Array],
               gamma: Optional[jax.Array], d: jax.Array, M: jax.Array, *,
               ax: int, block: Tuple[int, ...], with_g: bool,
               with_residual: bool, with_xbar: bool, wire: bool,
               dot_chunk: int, interpret: bool):
    """Run the fused kernel over an array whose axis ``ax`` is the node
    axis, in ``block`` tiles (the node axis whole).  Returns the mixed
    array node-leading (``x`` with its node axis moved first, ``x``'s
    dtype); with residual also x̄ (``(1, *rest)`` fp32; None without
    ``with_xbar``) and the scalar ``Σ_i ‖x_i − x̄‖²``.  With the node
    axis leading the mixed array aliases ``x``."""
    n = x.shape[ax]
    dims = x.shape[:ax] + x.shape[ax + 1:]
    tile = block[:ax] + block[ax + 1:]
    grid = tuple(pl.cdiv(s, b) for s, b in zip(dims, tile))

    def at_x(*ids):  # grid index -> x's block index, the node axis whole
        return ids[:ax] + (0,) + ids[ax:]

    def at_out(*ids):
        return (0,) + ids

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs, inputs = [], []
    if with_g:
        in_specs.append(smem)
        inputs.append(jnp.asarray(gamma, jnp.float32).reshape(1, 1))
    if dot_chunk:  # the dot reads d and M whole, from VMEM
        in_specs += [pl.BlockSpec((n, 1), lambda *ids: (0, 0)),
                     pl.BlockSpec((n, n), lambda *ids: (0, 0))]
    else:          # the VPU sum reads them as scalars
        in_specs += [smem, smem]
    in_specs.append(pl.BlockSpec(block, at_x))
    inputs += [jnp.asarray(d, jnp.float32).reshape((n, 1) if dot_chunk
                                                   else (n,)),
               jnp.asarray(M, jnp.float32), x]
    if with_g:
        in_specs.append(pl.BlockSpec(block, at_x))
        inputs.append(g)

    out_shape = [jax.ShapeDtypeStruct((n,) + dims, x.dtype)]
    out_specs = [pl.BlockSpec((n,) + tile, at_out)]
    if with_residual and with_xbar:
        out_shape.append(jax.ShapeDtypeStruct((1,) + dims, jnp.float32))
        out_specs.append(pl.BlockSpec((1,) + tile, at_out))
    if with_residual:
        # the residual is a scalar accumulated across the grid: Mosaic
        # stores scalars only to SMEM
        out_shape.append(jax.ShapeDtypeStruct((1, 1), jnp.float32))
        out_specs.append(smem)

    kernel = functools.partial(
        _mix_kernel, ax=ax, with_g=with_g, with_residual=with_residual,
        with_xbar=with_xbar, wire=wire, dims=dims, block=tile,
        dot_chunk=dot_chunk)
    # a node-leading x is consumed in place: the mixed output aliases it,
    # so jitted callers never allocate a second copy
    aliases = {3 if with_g else 2: 0} if ax == 0 else {}
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=tuple(out_specs) if with_residual else out_specs[0],
        out_shape=tuple(out_shape) if with_residual else out_shape[0],
        input_output_aliases=aliases,
        interpret=interpret,
    )(*inputs)
    if not with_residual:
        return out
    if with_xbar:
        return out[0], out[1], out[2][0, 0]
    return out[0], None, out[1][0, 0]


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------
def _split_leaves(leaves, threshold: int):
    """``(small, big)`` leaf indices: leaves below ``threshold`` per-node
    elements share the concatenation staging buffer, each leaf at or
    above it is mixed in its own layout."""
    sizes = [int(np.prod(lf.shape[1:], dtype=np.int64)) for lf in leaves]
    return ([i for i, s in enumerate(sizes) if s < threshold],
            [i for i, s in enumerate(sizes) if s >= threshold])


def staged_bytes(params: PyTree, leaf_threshold: Optional[int] = None
                 ) -> int:
    """Per-node bytes of a stacked fused round that go through the
    concatenation staging buffer (fp32): the leaves below the threshold."""
    leaves = jax.tree.leaves(params)
    thresh = LEAF_DISPATCH_THRESHOLD if leaf_threshold is None \
        else leaf_threshold
    small, _ = _split_leaves(leaves, thresh)
    return 4 * sum(int(np.prod(leaves[i].shape[1:], dtype=np.int64))
                   for i in small)


def _mix_tree(params: PyTree, grads: Optional[PyTree], gamma, dj, Mj, *,
              wire: bool, block_d: int, interpret: bool, threshold: int,
              with_residual: bool, with_xbar: bool):
    """One round over a node-stacked pytree: the small leaves packed into
    one ``(n, D)`` dispatch, each large leaf in its own view.  The
    residual sums over dispatches (the consensus sum decomposes over
    elements)."""
    with_g = grads is not None
    leaves, treedef = jax.tree.flatten(params)
    gleaves = jax.tree.flatten(grads)[0] if with_g else None
    n = leaves[0].shape[0]
    mixed_leaves: list = [None] * len(leaves)
    xbar_leaves: list = [None] * len(leaves)
    resid = None
    kw = dict(with_g=with_g, with_residual=with_residual,
              with_xbar=with_xbar, wire=wire, interpret=interpret)

    def run(x, g, ax, block, dot_chunk):
        nonlocal resid
        out = _mix_nodes(x, g, gamma if with_g else None, dj, Mj,
                         ax=ax, block=block, dot_chunk=dot_chunk, **kw)
        if not with_residual:
            return out, None
        mixed, xbar, r = out
        resid = r if resid is None else resid + r
        return mixed, xbar

    small, big = _split_leaves(leaves, threshold)
    if small:
        xf = _pack_rows([leaves[i] for i in small], n)
        gf = _pack_rows([gleaves[i] for i in small], n) if with_g else None
        # nodes in the tile's sublanes: the MXU dot, on the chip too
        mixed, xbar = run(xf, gf, 0, (n, max(1, min(block_d, xf.shape[1]))),
                          block_d)
        off = 0
        for i in small:
            shape = leaves[i].shape
            size = int(np.prod(shape[1:], dtype=np.int64))
            mixed_leaves[i] = (mixed[:, off:off + size].reshape(shape)
                               .astype(leaves[i].dtype))
            if xbar is not None:
                xbar_leaves[i] = (xbar[:, off:off + size].reshape(shape[1:])
                                  .astype(leaves[i].dtype))
            off += size
    for i in big:
        leaf = leaves[i].reshape(n, 1) if leaves[i].ndim == 1 else leaves[i]
        order = _memory_order(leaf.shape, leaf.dtype)
        view, ax = _leaf_view(order, leaf.shape)
        # the kernel returns the leaf node-leading, its other dims in
        # memory order
        rest = [p for p in order if p != 0]
        back = np.argsort([0] + rest)

        def to_view(a):
            return a.transpose(order).reshape(view)

        def from_out(a):
            return a.reshape((a.shape[0],) + tuple(leaf.shape[p] for p in rest)
                             ).transpose(back)

        block = _leaf_block(view, ax, leaf.dtype.itemsize,
                            _BLOCK_BYTES // 2 if with_g else _BLOCK_BYTES)
        g = to_view(gleaves[i].reshape(leaf.shape)) if with_g else None
        mixed, xbar = run(to_view(leaf), g, ax, block,
                          _dot_chunk(interpret, block_d))
        mixed_leaves[i] = from_out(mixed).reshape(leaves[i].shape)
        if xbar is not None:
            xbar_leaves[i] = (from_out(xbar).reshape(leaves[i].shape[1:])
                              .astype(leaf.dtype))
    mixed_tree = jax.tree.unflatten(treedef, mixed_leaves)
    if not with_residual:
        return mixed_tree
    xbar_tree = jax.tree.unflatten(treedef, xbar_leaves) if with_xbar \
        else None
    return mixed_tree, xbar_tree, resid


def fused_step_mix(params: PyTree, grads: Optional[PyTree] = None,
                   gamma: Optional[jax.Array] = None, *, phase: str,
                   topology: str = "ring", n_nodes: int, step: int = 0,
                   comm_dtype=None, n_pods: int = 1, block_d: int = 2048,
                   interpret: Optional[bool] = None,
                   with_residual: bool = False, with_xbar: bool = True,
                   leaf_threshold: Optional[int] = None):
    """Fused ``W · (params − γ·grads)`` for one communication round.

    With ``grads is None`` this is a plain mixing round (the production
    trainer's optimizer already produced the half-step iterate); with grads
    and γ it is the simulator's whole SGD+gossip step in one HBM pass.

    Leaves at or above ``leaf_threshold`` per-node elements are mixed in
    their own layout, each by its own kernel call; the rest share the
    concatenation staging buffer, walked ``block_d`` columns a step.

    Returns the mixed pytree; with ``with_residual=True`` returns
    ``(mixed, xbar, residual)`` where ``xbar`` is the node average (leaves
    without the node axis; None with ``with_xbar=False``, which writes no
    x̄) and ``residual = Σ_i ‖x_i − x̄‖²`` of the mixed iterate (divide by
    n for the paper's consensus distance).
    """
    if phase not in KERNEL_PHASES:
        raise ValueError(f"phase {phase!r} has no fused kernel "
                         f"(expected one of {KERNEL_PHASES})")
    interp = _default_interpret() if interpret is None else interpret
    thresh = LEAF_DISPATCH_THRESHOLD if leaf_threshold is None \
        else leaf_threshold
    d, M = phase_matrices(phase, topology, n_nodes, step=step, n_pods=n_pods)
    # grid mixing ignores comm_dtype in the reference path — mirror that
    wire = (comm_dtype is not None
            and not (phase == "gossip" and topology == "grid"))
    if grads is not None and gamma is None:
        raise ValueError("grads given without gamma")
    return _mix_tree(params, grads, gamma, jnp.asarray(d), jnp.asarray(M),
                     wire=wire, block_d=block_d, interpret=interp,
                     threshold=thresh, with_residual=with_residual,
                     with_xbar=with_xbar)


def fused_step_mix_dense(params: PyTree, W: jax.Array, *, n_nodes: int,
                         comm_dtype=None, block_d: int = 2048,
                         interpret: Optional[bool] = None,
                         leaf_threshold: Optional[int] = None) -> PyTree:
    """Fused mixing round for a **runtime** dense ``W`` (push-sum,
    DESIGN.md §2.5).

    The phase-based entry points bake W in at trace time from the
    ``(phase, topology, shift)`` triple — fine when the matrix repertoire
    is small and static.  Push-sum under faults draws a *different*
    column-stochastic W every step (drop renormalization, per-step
    resampling), so here W is an ``(n, n)`` jax array threaded through jit
    as a regular traced operand: one compiled kernel serves every failure
    pattern, zero recompiles.  The kernel already reads ``d``/``M`` as
    runtime data, so this is the same kernel body as
    :func:`fused_step_mix` — only the factor construction moves into the
    traced graph (``d = diag(W)``, ``M = W − diag(W)``).

    Gossip wire semantics: ``comm_dtype`` (bf16 only, like the other fused
    paths) casts the M (neighbor) term; the self term stays in the storage
    dtype.  The push-sum weight column rides the packed matrix as just
    another leaf — mixing x and w through the *same* kernel invocation is
    what keeps the de-bias ratio consistent (DESIGN.md §2.5).
    """
    interp = _default_interpret() if interpret is None else interpret
    thresh = LEAF_DISPATCH_THRESHOLD if leaf_threshold is None \
        else leaf_threshold
    if comm_dtype is not None \
            and jnp.dtype(comm_dtype) != jnp.dtype(jnp.bfloat16):
        raise ValueError(
            f"fused_step_mix_dense wire-casts to bfloat16 only (got "
            f"comm_dtype={jnp.dtype(comm_dtype)}); use backend='reference'")
    Wj = jnp.asarray(W, jnp.float32)
    dj = jnp.diagonal(Wj)
    Mj = Wj - jnp.diag(dj)
    return _mix_tree(params, None, None, dj, Mj, wire=comm_dtype is not None,
                     block_d=block_d, interpret=interp, threshold=thresh,
                     with_residual=False, with_xbar=False)


def global_average(params: PyTree, n_nodes: int, *, comm_dtype=None,
                   block_d: int = 2048, interpret: Optional[bool] = None,
                   with_residual: bool = False,
                   leaf_threshold: Optional[int] = None):
    """Fused periodic global averaging ``x ← (1/n)𝟙𝟙ᵀ x`` (PGA round)."""
    return fused_step_mix(params, phase="global", n_nodes=n_nodes,
                          comm_dtype=comm_dtype, block_d=block_d,
                          interpret=interpret, with_residual=with_residual,
                          leaf_threshold=leaf_threshold)


def pod_average(params: PyTree, n_nodes: int, n_pods: int, *,
                comm_dtype=None, block_d: int = 2048,
                interpret: Optional[bool] = None,
                with_residual: bool = False,
                leaf_threshold: Optional[int] = None):
    """Fused intra-pod exact averaging (Hier-PGA round, DESIGN.md §4)."""
    return fused_step_mix(params, phase="pod_avg", n_nodes=n_nodes,
                          n_pods=n_pods, comm_dtype=comm_dtype,
                          block_d=block_d, interpret=interpret,
                          with_residual=with_residual,
                          leaf_threshold=leaf_threshold)


def mix_residual(params: PyTree, grads: Optional[PyTree] = None,
                 gamma: Optional[jax.Array] = None, *, phase: str,
                 topology: str = "ring", n_nodes: int, step: int = 0,
                 comm_dtype=None, n_pods: int = 1, block_d: int = 2048,
                 interpret: Optional[bool] = None, with_xbar: bool = True,
                 leaf_threshold: Optional[int] = None):
    """``(W·x, x̄, Σ_i ‖x_i − x̄‖²)`` in one pass — eval without re-reading.
    A caller that discards x̄ passes ``with_xbar=False`` (x̄ is then None
    and the kernel writes none)."""
    return fused_step_mix(params, grads, gamma, phase=phase,
                          topology=topology, n_nodes=n_nodes, step=step,
                          comm_dtype=comm_dtype, n_pods=n_pods,
                          block_d=block_d, interpret=interpret,
                          with_residual=True, with_xbar=with_xbar,
                          leaf_threshold=leaf_threshold)


# ---------------------------------------------------------------------------
# Per-shard block kernel (the shard_map-aware path, DESIGN.md §2.1)
# ---------------------------------------------------------------------------
# ---------------------------------------------------------------------------
# Compressed rounds: fused quantize → mix → dequantize (DESIGN.md §2.3)
# ---------------------------------------------------------------------------
def _cmix_kernel(*refs, kind: str, with_ef: bool, wire: bool):
    """One grid step of the compensated compressed round
    ``o = x + (M·q − w ⊙ q)``.

    For the quantizer kinds ("int8", "fp8") the wire estimate ``q`` is
    computed **in-register** from the tile: random bits from the shared
    column hash (repro.compress.base), codes via the same element-wise
    math as the reference compressor (repro.compress.quantize), dequant,
    mix — the quantized payload never exists in HBM.  ``kind ==
    "precomputed"`` takes ``q`` as an input (sparsifier selections are
    data-dependent gathers, not tile-local ops) and fuses only the
    compensated mix.

    Ref order: [seed?, x, e?, scale?, q?, w, M] → [o, ef?]
    (seed/scale for quantizers, e with error feedback, q precomputed).
    ``wire=True`` (the global phase with a comm_dtype) additionally
    bf16-casts the estimate — both occurrences, preserving the constant
    fixed point — mirroring the reference collective's operand cast.
    """
    from repro.compress import base as cbase
    from repro.compress import quantize as cq

    quant = kind in ("int8", "fp8")
    idx = 0
    if quant:
        seed_ref = refs[idx]; idx += 1
    x_ref = refs[idx]; idx += 1
    if with_ef and quant:
        e_ref = refs[idx]; idx += 1
    if quant:
        scale_ref = refs[idx]; idx += 1
    else:
        q_ref = refs[idx]; idx += 1
    w_ref = refs[idx]; idx += 1
    m_ref = refs[idx]; idx += 1
    o_ref = refs[idx]; idx += 1
    if with_ef and quant:
        ef_ref = refs[idx]; idx += 1

    x = x_ref[...].astype(jnp.float32)                       # (n, bd)
    if quant:
        y = x + e_ref[...].astype(jnp.float32) if with_ef else x
        n, bd = x.shape
        base = (pl.program_id(0) * bd).astype(jnp.uint32)
        cols = base + jax.lax.broadcasted_iota(jnp.uint32, (n, bd), 1)
        scale = scale_ref[...]
        if kind == "int8":
            u = cbase.uniform_columns(seed_ref[0, 0], cols)
            q = cq.int8_dequant(cq.int8_codes(y, scale, u), scale)
        else:
            bits = cbase.column_bits(seed_ref[0, 0], cols)
            q = cq.fp8_dequant(cq.fp8_codes(y, scale, bits), scale)
        if with_ef:
            ef_ref[...] = (y - q).astype(ef_ref.dtype)
    else:
        q = q_ref[...].astype(jnp.float32)
    if wire:
        q = q.astype(jnp.bfloat16).astype(jnp.float32)
    corr = jnp.dot(m_ref[...], q, preferred_element_type=jnp.float32,
                   precision=_FP32) \
        - w_ref[...] * q
    o_ref[...] = (x + corr).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "with_ef", "wire", "block_d", "interpret"))
def _cmix_flat(xf: jax.Array, ef: Optional[jax.Array],
               qf: Optional[jax.Array], seed: Optional[jax.Array],
               scale: Optional[jax.Array], w: jax.Array, M: jax.Array, *,
               kind: str, with_ef: bool, wire: bool, block_d: int,
               interpret: bool):
    """Run the compressed-mix kernel over one flattened (n, D) leaf."""
    n, D = xf.shape
    bd = max(1, min(block_d, D))
    pad = (-D) % bd
    if pad:  # zero columns quantize to exact zero codes → contribute 0
        xf = jnp.pad(xf, ((0, 0), (0, pad)))
        if ef is not None:
            ef = jnp.pad(ef, ((0, 0), (0, pad)))
        if qf is not None:
            qf = jnp.pad(qf, ((0, 0), (0, pad)))
    Dp = D + pad
    quant = kind in ("int8", "fp8")

    def tile(i):
        return (0, i)

    def scalar(i):
        return (0, 0)

    in_specs, inputs = [], []
    if quant:
        in_specs.append(pl.BlockSpec((1, 1), scalar))
        inputs.append(jnp.asarray(seed).astype(jnp.uint32).reshape(1, 1))
    in_specs.append(pl.BlockSpec((n, bd), tile))
    inputs.append(xf)
    if with_ef and quant:
        in_specs.append(pl.BlockSpec((n, bd), tile))
        inputs.append(ef)
    if quant:
        in_specs.append(pl.BlockSpec((n, 1), scalar))
        inputs.append(scale)
    else:
        in_specs.append(pl.BlockSpec((n, bd), tile))
        inputs.append(qf)
    in_specs.append(pl.BlockSpec((n, 1), scalar))
    inputs.append(w)
    in_specs.append(pl.BlockSpec((n, n), scalar))
    inputs.append(M)

    out_shape = [jax.ShapeDtypeStruct((n, Dp), xf.dtype)]
    out_specs = [pl.BlockSpec((n, bd), tile)]
    if with_ef and quant:
        out_shape.append(jax.ShapeDtypeStruct((n, Dp), jnp.float32))
        out_specs.append(pl.BlockSpec((n, bd), tile))

    multi = with_ef and quant
    x_idx = 1 if quant else 0
    out = pl.pallas_call(
        functools.partial(_cmix_kernel, kind=kind, with_ef=with_ef,
                          wire=wire),
        grid=(Dp // bd,),
        in_specs=in_specs,
        out_specs=tuple(out_specs) if multi else out_specs[0],
        out_shape=tuple(out_shape) if multi else out_shape[0],
        input_output_aliases={x_idx: 0},
        interpret=interpret,
    )(*inputs)

    if multi:
        mixed, ef_out = out
        return mixed[:, :D], ef_out[:, :D]
    return out[:, :D], None


def compressed_step_mix(params: PyTree, *, compressor,
                        ef_state: Optional[PyTree] = None, seed=0,
                        phase: str, topology: str = "ring", n_nodes: int,
                        step: int = 0, n_pods: int = 1, block_d: int = 2048,
                        interpret: Optional[bool] = None, comm_dtype=None):
    """Fused compressed communication round (DESIGN.md §2.3):
    ``mixed = x + (M·q − (1−d)⊙q)`` with ``q`` the compressed-wire
    estimate of ``x (+ ef)``, one HBM pass per leaf.

    Quantizer compressors (int8/fp8) fuse quantize → mix → dequantize
    in-register (the per-leaf scale is the one extra cheap reduction);
    sparsifiers precompute ``q`` via the reference codec and fuse the
    compensated mix.  Dispatch is always per-leaf — the scales, salts,
    and (for sparsifiers) selections are per-leaf, so the concat staging
    buffer of the uncompressed path would mix scales across leaves.

    Returns ``(mixed, new_ef_state)`` (``new_ef_state`` is None when
    ``ef_state`` is None).  Consensus-residual fusion deliberately does
    not compose with compression — callers fall back to
    ``train.state.consensus_distance`` (DESIGN.md §2.3).
    """
    if phase not in KERNEL_PHASES:
        raise ValueError(f"phase {phase!r} has no fused kernel "
                         f"(expected one of {KERNEL_PHASES})")
    interp = _default_interpret() if interpret is None else interpret
    d, M = phase_matrices(phase, topology, n_nodes, step=step, n_pods=n_pods)
    w = (1.0 - d).astype(np.float32)
    wj, Mj = jnp.asarray(w), jnp.asarray(M)
    kind = compressor.name if compressor.name in ("int8", "fp8") \
        else "precomputed"
    with_ef = ef_state is not None
    # global phase: the collective operand is uncompressed fp32 sums, so
    # comm_dtype still wire-casts the estimate (both occurrences; matches
    # _compressed_round_reference and the sharded psum — DESIGN.md §2.3)
    wire = phase == "global" and comm_dtype is not None
    if wire and jnp.dtype(comm_dtype) != jnp.dtype(jnp.bfloat16):
        # the kernel's wire cast is bf16 like _mix_kernel's; other dtypes
        # would silently diverge from the reference backend
        raise ValueError(
            f"compressed_step_mix: the fused kernel wire-casts to bfloat16 "
            f"only (got comm_dtype={jnp.dtype(comm_dtype)}); use "
            f"backend='reference' for other wire dtypes")

    return _compressed_leaf_loop(params, compressor, ef_state, seed, wj, Mj,
                                 kind=kind, wire=wire, block_d=block_d,
                                 interp=interp)


def _compressed_leaf_loop(params: PyTree, compressor, ef_state, seed,
                          wj: jax.Array, Mj: jax.Array, *, kind: str,
                          wire: bool, block_d: int, interp: bool):
    """Per-leaf dispatch of the compensated compressed round — shared by
    the phase-based (:func:`compressed_step_mix`) and runtime-dense-W
    (:func:`compressed_step_mix_dense`) entry points.  Dispatch must stay
    per-leaf: scales, salts, and sparsifier selections are per-leaf."""
    from repro import compress as compress_mod
    from repro.compress import quantize as cq

    with_ef = ef_state is not None
    leaves, treedef = jax.tree.flatten(params)
    n = leaves[0].shape[0]
    ef_leaves = jax.tree.flatten(ef_state)[0] if with_ef \
        else [None] * len(leaves)

    if kind == "precomputed":
        q_tree, new_ef = compress_mod.apply_tree(compressor, params,
                                                 ef_state, seed)
        q_leaves = jax.tree.leaves(q_tree)
    mixed_leaves, new_ef_leaves = [], []
    for i, (leaf, e) in enumerate(zip(leaves, ef_leaves)):
        x2 = leaf.reshape(n, -1).astype(jnp.float32)
        e2 = e.reshape(n, -1).astype(jnp.float32) if e is not None else None
        if kind == "precomputed":
            q2 = q_leaves[i].reshape(n, -1).astype(jnp.float32)
            mixed, _ = _cmix_flat(x2, None, q2, None, None, wj, Mj,
                                  kind=kind, with_ef=False, wire=wire,
                                  block_d=block_d, interpret=interp)
        else:
            y2 = x2 if e2 is None else x2 + e2
            scale = cq.int8_scale(y2) if kind == "int8" else cq.fp8_scale(y2)
            seed_i = compress_mod.leaf_seed(seed, i)
            mixed, ef_out = _cmix_flat(x2, e2, None, seed_i, scale, wj, Mj,
                                       kind=kind, with_ef=with_ef,
                                       wire=wire, block_d=block_d,
                                       interpret=interp)
            if with_ef:
                new_ef_leaves.append(ef_out.reshape(e.shape).astype(e.dtype))
        mixed_leaves.append(mixed.reshape(leaf.shape).astype(leaf.dtype))
    mixed_tree = jax.tree.unflatten(treedef, mixed_leaves)
    if not with_ef:
        return mixed_tree, None
    if kind == "precomputed":
        return mixed_tree, new_ef
    return mixed_tree, jax.tree.unflatten(treedef, new_ef_leaves)


def compressed_step_mix_dense(params: PyTree, *, W: jax.Array, compressor,
                              ef_state: Optional[PyTree] = None, seed=0,
                              n_nodes: int, block_d: int = 2048,
                              interpret: Optional[bool] = None):
    """Compensated compressed gossip round for a runtime dense ``W``
    (push-sum under faults — the dense-W analogue of
    :func:`compressed_step_mix`, same kernel body, factors built in the
    traced graph).

    ``mixed = x + (M·q − (1−d)⊙q)`` with ``d = diag(W)``, ``M = W −
    diag(W)``.  The correction is a weighted combination of a *shared*
    per-node quantity q, so any column-stochastic W conserves push-sum
    mass exactly like the uncompressed round does — the caller mixes the
    weight column outside this lossy codec (DESIGN.md §2.5).  Returns
    ``(mixed, new_ef_state)``.
    """
    interp = _default_interpret() if interpret is None else interpret
    Wj = jnp.asarray(W, jnp.float32)
    dj = jnp.diagonal(Wj).reshape(n_nodes, 1)
    wj = 1.0 - dj
    Mj = Wj - jnp.diag(jnp.diagonal(Wj))
    kind = compressor.name if compressor.name in ("int8", "fp8") \
        else "precomputed"
    # gossip wire semantics only — the push-sum global phase is never
    # compressed (DistConfig forbids it), so no wire flag here
    return _compressed_leaf_loop(params, compressor, ef_state, seed, wj, Mj,
                                 kind=kind, wire=False, block_d=block_d,
                                 interp=interp)


def _collective_kernel(*refs, kind: str, with_ef: bool, n_pods: int):
    """One ``qblock`` tile of the compressed-collective averaging round
    (DESIGN.md §2.3 "Compressed collectives"):

        q₁ = Q₁(x + e);  m̄ = q₁[pod,0] + mean(q₁ − q₁[pod,0]);
        o  = x + (Q₂(m̄)[pod] − Q₂(q₁));  e' = (x + e) − q₁

    entirely in-register — stage-1 and stage-2 codes never exist in HBM on
    the stacked path.  The kernel tile *is* the scale block (the grid walks
    D in ``qblock`` columns), so the per-tile row absmax is exactly the
    per-(row, block) scale of the reference
    (repro.compress.collective.quantize_blocks), and the random bits come
    from the same column hash — bit-identical rounding decisions.

    Ref order: [s1, s2, x, e?] → [o, ef?].
    """
    from repro.compress import base as cbase
    from repro.compress import collective as ccol
    from repro.compress import quantize as cq

    s1_ref, s2_ref, x_ref = refs[0], refs[1], refs[2]
    idx = 3
    if with_ef:
        e_ref = refs[idx]; idx += 1
    o_ref = refs[idx]; idx += 1
    if with_ef:
        ef_ref = refs[idx]; idx += 1

    x = x_ref[...].astype(jnp.float32)                       # (n, bd)
    y = x + e_ref[...].astype(jnp.float32) if with_ef else x
    n, bd = x.shape
    base = (pl.program_id(0) * bd).astype(jnp.uint32)
    cols = base + jax.lax.broadcasted_iota(jnp.uint32, (n, bd), 1)

    # power-of-two block scales (ccol.pow2_block_scale): every codec op is
    # exact or single-rounded, so this in-kernel instance and the
    # reference/sharded instances are bit-identical on equal inputs — the
    # bitwise consensus fixed point does not depend on fusion decisions
    if kind == "int8":
        def enc(v, seed, c):
            scale = ccol.pow2_block_scale(v, 7)
            u = cbase.uniform_columns(seed, c)
            return cq.int8_dequant(cq.int8_codes(v, scale, u), scale)
    else:
        def enc(v, seed, c):
            scale = ccol.pow2_block_scale(v, 8)
            bits = cbase.column_bits(seed, c)
            return cq.fp8_dequant(cq.fp8_codes(v, scale, bits), scale)

    q1 = enc(y, s1_ref[0, 0], cols)
    if with_ef:
        ef_ref[...] = (y - q1).astype(ef_ref.dtype)
    per = n // n_pods
    qp = q1.reshape(n_pods, per, bd)
    anchor = qp[:, 0]
    # anchored accumulate: a consensus tile passes through bitwise
    mbar = anchor + jnp.mean(qp - anchor[:, None], axis=1)   # (p, bd)
    r = enc(mbar, s2_ref[0, 0], cols[:n_pods])
    rho = enc(q1, s2_ref[0, 0], cols)
    r_rows = jnp.broadcast_to(r[:, None], (n_pods, per, bd)).reshape(n, bd)
    o_ref[...] = (x + (r_rows - rho)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "with_ef", "n_pods", "qblock", "interpret"))
def _collective_flat(xf: jax.Array, ef: Optional[jax.Array],
                     s1: jax.Array, s2: jax.Array, *, kind: str,
                     with_ef: bool, n_pods: int, qblock: int,
                     interpret: bool):
    """Run the collective kernel over the packed (n, D) matrix; the grid
    tile equals the scale block, so padding to a ``qblock`` multiple keeps
    block boundaries identical to the reference."""
    from repro.compress import collective as ccol

    n, D = xf.shape
    xf = ccol.pad_cols(xf, qblock)
    ef = ccol.pad_cols(ef, qblock)
    Dp = xf.shape[1]

    def tile(i):
        return (0, i)

    def scalar(i):
        return (0, 0)

    in_specs = [pl.BlockSpec((1, 1), scalar), pl.BlockSpec((1, 1), scalar),
                pl.BlockSpec((n, qblock), tile)]
    inputs = [jnp.asarray(s1).astype(jnp.uint32).reshape(1, 1),
              jnp.asarray(s2).astype(jnp.uint32).reshape(1, 1), xf]
    if with_ef:
        in_specs.append(pl.BlockSpec((n, qblock), tile))
        inputs.append(ef)

    out_shape = [jax.ShapeDtypeStruct((n, Dp), xf.dtype)]
    out_specs = [pl.BlockSpec((n, qblock), tile)]
    if with_ef:
        out_shape.append(jax.ShapeDtypeStruct((n, Dp), jnp.float32))
        out_specs.append(pl.BlockSpec((n, qblock), tile))

    out = pl.pallas_call(
        functools.partial(_collective_kernel, kind=kind, with_ef=with_ef,
                          n_pods=n_pods),
        grid=(Dp // qblock,),
        in_specs=in_specs,
        out_specs=tuple(out_specs) if with_ef else out_specs[0],
        out_shape=tuple(out_shape) if with_ef else out_shape[0],
        input_output_aliases={2: 0},
        interpret=interpret,
    )(*inputs)

    if with_ef:
        mixed, ef_out = out
        return mixed[:, :D], ef_out[:, :D]
    return out[:, :D], None


def collective_step_mix(params: PyTree, *, compressor,
                        ef_state: Optional[PyTree] = None, seed=0,
                        phase: str, n_nodes: int, n_pods: int = 1,
                        qblock: Optional[int] = None,
                        interpret: Optional[bool] = None):
    """Fused compressed global/pod-averaging round (DESIGN.md §2.3
    "Compressed collectives"): the packed ``(n, D)`` state goes through
    quantize → anchored accumulate → re-quantize → compensate in one HBM
    pass; int8/fp8 codes never hit HBM.  Unlike ``compressed_step_mix``
    dispatch is the *packed* matrix, not per-leaf — collective scales are
    per ``qblock`` column block, so leaf boundaries don't carry salts.

    Returns ``(mixed, new_ef_state)`` (``new_ef_state`` None when
    ``ef_state`` is None).
    """
    from repro.compress import collective as ccol

    if phase not in ("global", "pod_avg"):
        raise ValueError(f"collective_step_mix: phase {phase!r} is not an "
                         f"averaging round (expected 'global' or 'pod_avg')")
    pods = n_pods if phase == "pod_avg" else 1
    if n_nodes % max(pods, 1) or pods < 1:
        raise ValueError(f"collective_step_mix: n_pods={pods} does not "
                         f"divide n_nodes={n_nodes}")
    kind = compressor.name
    qb = ccol.QBLOCK if qblock is None else qblock
    interp = _default_interpret() if interpret is None else interpret

    xf, unflatten = flatten_nodes(params)
    with_ef = ef_state is not None
    ef_unflatten = None
    ef2 = None
    if with_ef:
        ef2, ef_unflatten = flatten_nodes(ef_state)
    s1, s2 = ccol.stage_seeds(seed)
    mixed, ef_out = _collective_flat(xf, ef2, s1, s2, kind=kind,
                                     with_ef=with_ef, n_pods=pods,
                                     qblock=qb, interpret=interp)
    return (unflatten(mixed),
            ef_unflatten(ef_out) if with_ef else None)


def _shard_cmix_kernel(x_ref, q_ref, qs_ref, w_ref, m_ref, o_ref):
    """Per-shard compensated compressed mix: ``x + (M_r·qs − w ⊙ q_self)``
    where ``qs`` stacks the locally rebuilt neighbor estimates (the
    compressed wire arrays were what crossed the ICI — see
    ``mixing._communicate_sharded_compressed``)."""
    x = x_ref[...].astype(jnp.float32)                       # (m, bd)
    q = q_ref[...].astype(jnp.float32)                       # (m, bd)
    qs = qs_ref[...].astype(jnp.float32)                     # (K·m, bd)
    corr = jnp.dot(m_ref[...], qs, preferred_element_type=jnp.float32,
                   precision=_FP32) \
        - w_ref[...] * q
    o_ref[...] = (x + corr).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def shard_comp_mix_block(x: jax.Array, q_self: jax.Array, qs: jax.Array,
                         w: jax.Array, M: jax.Array, *, block_d: int = 2048,
                         interpret: Optional[bool] = None):
    """Compensated per-shard round over one ``(m, D)`` row-block (the
    compressed-wire analogue of :func:`shard_mix_block`; same aliasing
    contract on ``x``)."""
    interp = _default_interpret() if interpret is None else interpret
    m, D = x.shape
    K = qs.shape[0]
    bd = max(1, min(block_d, D))
    pad = (-D) % bd
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
        q_self = jnp.pad(q_self, ((0, 0), (0, pad)))
        qs = jnp.pad(qs, ((0, 0), (0, pad)))
    Dp = D + pad

    def tile(i):
        return (0, i)

    in_specs = [pl.BlockSpec((m, bd), tile),
                pl.BlockSpec((m, bd), tile),
                pl.BlockSpec((K, bd), tile),
                pl.BlockSpec((m, 1), lambda i: (0, 0)),
                pl.BlockSpec((m, K), lambda i: (0, 0))]
    out = pl.pallas_call(
        _shard_cmix_kernel,
        grid=(Dp // bd,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((m, bd), tile),
        out_shape=jax.ShapeDtypeStruct((m, Dp), x.dtype),
        input_output_aliases={0: 0},
        interpret=interp,
    )(x, q_self, qs, w, M)
    return out[:, :D]


def _shard_mix_kernel(x_ref, xs_ref, d_ref, m_ref, *out_refs,
                      with_residual: bool):
    """One grid step of the per-shard mix: ``d ⊙ x + M · xs`` where ``x`` is
    this shard's (m, bd) tile and ``xs`` stacks the halo-exchanged neighbor
    blocks (already wire-cast by the caller).  With residual, also emits
    the shard's column sums of the mixed tile — the caller psums them into
    x̄.  (The consensus residual itself cannot be fused here: it needs the
    cross-shard x̄, and the cancellation-free form Σ‖x − x̄‖² requires a
    second local pass once the psum lands — see communicate_sharded.)"""
    o_ref = out_refs[0]
    x = x_ref[...].astype(jnp.float32)                       # (m, bd)
    xs = xs_ref[...].astype(jnp.float32)                     # (K·m, bd)
    mixed = jnp.dot(m_ref[...], xs, preferred_element_type=jnp.float32,
                    precision=_FP32)
    mixed = mixed + d_ref[...] * x
    o_ref[...] = mixed.astype(o_ref.dtype)

    if with_residual:
        out_refs[1][...] = jnp.sum(mixed, axis=0,
                                   keepdims=True).astype(out_refs[1].dtype)


@functools.partial(jax.jit,
                   static_argnames=("with_residual", "block_d", "interpret"))
def shard_mix_block(x: jax.Array, xs: jax.Array, d: jax.Array, M: jax.Array,
                    *, with_residual: bool = False, block_d: int = 2048,
                    interpret: Optional[bool] = None):
    """Fused per-shard communication round over one ``(m, D)`` row-block.

    Called inside ``shard_map`` (repro.core.mixing.communicate_sharded):
    ``x`` is the shard's uncast local block, ``xs`` the ``(K·m, D)`` stack
    of halo blocks (self + ppermute-received neighbors, wire-cast), ``d``
    the shard's rows of the self-weight diagonal and ``M`` its
    ``(m, K·m)`` row-block of the mixing matrix restricted to the received
    blocks.  Returns the mixed ``(m, D)`` block; with residual also its
    ``(1, D)`` column sums (the shard-local partial of x̄).  The x input
    is aliased with the mixed output (same in-place contract as the
    stacked kernel).
    """
    interp = _default_interpret() if interpret is None else interpret
    m, D = x.shape
    K = xs.shape[0]
    bd = max(1, min(block_d, D))
    pad = (-D) % bd
    if pad:  # zero columns: contribute 0 to mix, column sums, and Σ‖·‖²
        x = jnp.pad(x, ((0, 0), (0, pad)))
        xs = jnp.pad(xs, ((0, 0), (0, pad)))
    Dp = D + pad

    def tile(i):
        return (0, i)

    in_specs = [pl.BlockSpec((m, bd), tile),
                pl.BlockSpec((K, bd), tile),
                pl.BlockSpec((m, 1), lambda i: (0, 0)),
                pl.BlockSpec((m, K), lambda i: (0, 0))]
    out_shape = [jax.ShapeDtypeStruct((m, Dp), x.dtype)]
    out_specs = [pl.BlockSpec((m, bd), tile)]
    if with_residual:
        out_shape.append(jax.ShapeDtypeStruct((1, Dp), jnp.float32))
        out_specs.append(pl.BlockSpec((1, bd), tile))

    out = pl.pallas_call(
        functools.partial(_shard_mix_kernel, with_residual=with_residual),
        grid=(Dp // bd,),
        in_specs=in_specs,
        out_specs=tuple(out_specs) if with_residual else out_specs[0],
        out_shape=tuple(out_shape) if with_residual else out_shape[0],
        input_output_aliases={0: 0},
        interpret=interp,
    )(x, xs, d, M)

    if with_residual:
        mixed, cs = out
        return mixed[:, :D], cs[:, :D]
    return out[:, :D]
