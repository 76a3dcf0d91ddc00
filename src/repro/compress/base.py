"""Compressed-gossip wire subsystem — protocol, shared randomness, EF algebra.

The third axis of wire-traffic reduction (DESIGN.md §2.3): after gossip
replaces the all-reduce and ``comm_dtype`` halves the payload, lossy
compression shrinks what crosses the ICI another 4–8×.  A ``Compressor``
maps a node-stacked value to a compact wire representation (``LeafWire``)
and back; the mixing layer (core/mixing.py) applies the round in the
**self-compensated form**

    mixed = x + (M · q − (1 − d) ⊙ q),      q = decompress(compress(x + e))

so the node's own state never loses precision, the global node average is
preserved to fp rounding for any compressor (column sums of M equal
``1 − d`` for a doubly-stochastic W), and — because every node draws the
*same* per-step random bits (`shared randomness`, :func:`uniform_columns`)
— a constant state is an exact fixed point of the round under every
compressor: identical inputs quantize to identical ``q`` rows and the
correction cancels.

Per-node **error feedback** (EF / EF21-style residual memory) threads the
compression error back into the next round instead of dropping it:
``y = x + e``, ``wire = compress(y)``, ``e' = y − decompress(wire)``.  The
EF state lives in ``train.state.TrainState.ef_state`` and is updated by
the same ``compress`` call that produces the wire payload, matching the
``compress(x, state) -> (wire, state)`` contract below.

Compressors operate on one **leaf row-block** at a time: a ``(rows, D)``
fp32 matrix whose rows are per-node flattened leaf values.  Pytree
plumbing (per-leaf salts, EF threading, reassembly) lives in
:func:`apply_tree`; the Pallas fast path (kernels/mixing_pallas.py)
reuses the same per-element math via the helpers in quantize.py so the
two backends make bit-identical rounding decisions.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


class LeafWire(NamedTuple):
    """Wire representation of one compressed leaf row-block.

    ``payload`` carries the bulk bytes (int8/fp8 codes, top-k values);
    ``aux`` the per-row metadata (scales, indices).  Both are pytrees of
    arrays with a leading node/row axis, so the sharded path can hand them
    straight to ``shard_map``/``ppermute`` — the payload bytes are exactly
    what crosses the ICI.
    """
    payload: Tuple[jax.Array, ...]
    aux: Tuple[jax.Array, ...]

    @property
    def nbytes(self) -> int:
        """Total bytes-on-wire of this leaf (payload + aux)."""
        return int(sum(int(np.prod(a.shape, dtype=np.int64))
                       * a.dtype.itemsize
                       for a in tuple(self.payload) + tuple(self.aux)))


@dataclasses.dataclass(frozen=True)
class Compressor:
    """Base compressor: subclasses override the leaf-level codec.

    ``lossy = False`` (identity) routes ``mixing.communicate`` to the
    exact pre-compression code path — bit-identical by construction.
    """
    name: str = "identity"
    lossy: bool = False

    # -- leaf-level codec ------------------------------------------------
    def compress_leaf(self, y2: jax.Array, seed: jax.Array) -> LeafWire:
        """``y2``: (rows, D) fp32; ``seed``: uint32 scalar (already salted
        per leaf).  Identity sends the values verbatim."""
        return LeafWire(payload=(y2,), aux=())

    def decompress_leaf(self, wire: LeafWire, d: int) -> jax.Array:
        """Reconstruct the (rows, d) fp32 estimate from the wire."""
        return wire.payload[0]

    # -- accounting ------------------------------------------------------
    def wire_bytes(self, rows: int, d: int) -> int:
        """Analytic bytes of one (rows, d) leaf's full wire representation
        (payload + all aux, matching ``LeafWire.nbytes``)."""
        return rows * d * 4

    def wire_bytes_per_send(self, rows: int, d: int) -> int:
        """Bytes that cross the interconnect per *transmission* of the
        leaf.  Differs from :meth:`wire_bytes` only when part of the wire
        is derivable on the receiver (randk's shared column indices) and
        so is never actually sent — the per-shift cost model
        (``round_wire_bytes``) uses this."""
        return self.wire_bytes(rows, d)

    # -- the ISSUE contract: compress(x, state) -> (wire, state) ---------
    def compress(self, y2: jax.Array, state: Optional[jax.Array],
                 seed: jax.Array) -> Tuple[LeafWire, Optional[jax.Array]]:
        """EF-aware leaf compression: feeds the residual ``state`` into the
        wire input and returns the updated residual.  ``state=None``
        disables error feedback (the compensated mixing form still keeps
        the self term exact)."""
        y = y2 if state is None else y2 + state
        wire = self.compress_leaf(y, seed)
        if state is None:
            return wire, None
        q = self.decompress_leaf(wire, y2.shape[-1])
        return wire, y - q


# ---------------------------------------------------------------------------
# Shared randomness: one counter-based hash, identical on every node and in
# both backends (reference jnp + Pallas kernel), parameterized only by
# (seed, leaf salt, element index).
# ---------------------------------------------------------------------------
_GOLDEN = np.uint32(0x9E3779B9)


def hash_u32(h: jax.Array) -> jax.Array:
    """32-bit avalanche (xorshift-multiply); uint32 in, uint32 out.  Plain
    jnp ops so it runs identically under jit, Pallas interpret mode, and
    Mosaic."""
    h = h.astype(jnp.uint32)
    h = (h ^ (h >> 16)) * np.uint32(0x7FEB352D)
    h = (h ^ (h >> 15)) * np.uint32(0x846CA68B)
    return h ^ (h >> 16)


def leaf_seed(seed: jax.Array, salt: int) -> jax.Array:
    """Per-leaf effective seed: fold the (traced) round seed with a static
    per-leaf salt.  Both backends iterate leaves in ``jax.tree`` order, so
    matching salts guarantee matching random bits."""
    s = jnp.asarray(seed).astype(jnp.uint32)
    return hash_u32(s + np.uint32(((salt + 1) * int(_GOLDEN)) & 0xFFFFFFFF))


def column_bits(seed: jax.Array, cols: jax.Array) -> jax.Array:
    """uint32 random bits per column index.  ``cols`` may be any shape of
    uint32 element indices (an ``arange`` on the reference path, a
    ``program_id``-offset iota inside the kernel); ``seed`` a uint32
    scalar from :func:`leaf_seed`.  Deliberately *node-independent*: every
    node rounds the same way, which is what makes a constant state an
    exact fixed point of the compressed round."""
    return hash_u32(cols.astype(jnp.uint32) ^ seed)


def uniform_columns(seed: jax.Array, cols: jax.Array) -> jax.Array:
    """U[0, 1) from the top 24 bits of :func:`column_bits` (fp32-exact).
    The shifted value is below 2^24, so the int32 hop changes nothing; it
    exists because Mosaic has no uint32 -> float32 cast."""
    top = (column_bits(seed, cols) >> 8).astype(jnp.int32)
    return top.astype(jnp.float32) * np.float32(2.0 ** -24)


# ---------------------------------------------------------------------------
# Pytree plumbing
# ---------------------------------------------------------------------------
def _rows_view(leaf: jax.Array) -> jax.Array:
    return leaf.reshape(leaf.shape[0], -1).astype(jnp.float32)


def compress_tree(comp: Compressor, x: PyTree, ef: Optional[PyTree],
                  seed: jax.Array):
    """Compress every leaf of a node-stacked pytree.

    Returns ``(wires, new_ef)``: ``wires`` is the list of per-leaf
    ``LeafWire`` in ``jax.tree`` leaf order (the order fixes each leaf's
    randomness salt), ``new_ef`` the updated error-feedback tree (or None
    when ``ef`` is None).
    """
    leaves, treedef = jax.tree.flatten(x)
    ef_leaves = jax.tree.flatten(ef)[0] if ef is not None else [None] * len(
        leaves)
    wires, new_ef = [], []
    for i, (leaf, e) in enumerate(zip(leaves, ef_leaves)):
        e2 = None if e is None else _rows_view(e)
        wire, e_new = comp.compress(_rows_view(leaf), e2, leaf_seed(seed, i))
        wires.append(wire)
        if e is not None:
            new_ef.append(e_new.reshape(e.shape).astype(e.dtype))
    ef_tree = jax.tree.unflatten(treedef, new_ef) if ef is not None else None
    return wires, ef_tree


def decompress_tree(comp: Compressor, wires, like: PyTree) -> PyTree:
    """Rebuild the (rows, D)-per-leaf estimate tree from per-leaf wires;
    leaves keep 2-D row-block shape (the mixing algebra consumes them
    flattened)."""
    leaves, treedef = jax.tree.flatten(like)
    out = [comp.decompress_leaf(w, int(np.prod(lf.shape[1:], dtype=np.int64)))
           for w, lf in zip(wires, leaves)]
    return jax.tree.unflatten(treedef, out)


def apply_tree(comp: Compressor, x: PyTree, ef: Optional[PyTree],
               seed: jax.Array):
    """``(q, new_ef)``: the decompressed wire estimate of ``x (+ ef)`` with
    leaves restored to their stacked shapes/dtypes-agnostic fp32 rows —
    the reference path's one-call compress→decompress."""
    wires, new_ef = compress_tree(comp, x, ef, seed)
    q2 = decompress_tree(comp, wires, x)
    q = jax.tree.map(lambda lf, q_: q_.reshape(lf.shape[0], *lf.shape[1:]),
                     x, q2)
    return q, new_ef


def init_ef_state(params: PyTree) -> PyTree:
    """Zero-initialized per-node error-feedback memory (fp32: the residual
    is the difference of fp32 wire inputs and must not re-quantize)."""
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def tree_wire_bytes(comp: Compressor, x: PyTree) -> int:
    """Analytic bytes-on-wire for one compressed broadcast of ``x``."""
    return sum(comp.wire_bytes(lf.shape[0],
                               int(np.prod(lf.shape[1:], dtype=np.int64)))
               for lf in jax.tree.leaves(x))
