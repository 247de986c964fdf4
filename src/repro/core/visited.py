"""Visited-set layouts: the representation seam under the BFS hot loop.

Algorithm 5's resultSet bitmap is per-query state the processor carries
through every hop; at (B, n) bool it is the processor-side scale wall for
>100K-node graphs (ROADMAP).  This module turns the raw array plumbing into
a `VisitedSet` layout seam, mirroring the expansion-backend seam of PR 3:

  - `dense`  -- (B, n) bool, one byte per node: the reference layout,
    exactly the representation the engine always used;
  - `packed` -- (B, ceil(n/32)) uint32 words, one BIT per node: 8x smaller,
    result counts via `lax.population_count`, expansion via the blocked
    packed Pallas kernel (`kernels.frontier.frontier_expand_packed`) or a
    pack-after-scatter reference path.

Layouts are SEMANTICALLY INTERCHANGEABLE: `unpack(packed_op(...)) ==
dense_op(...)` for every operation, so a layout change must not move a
single cache touch, storage read, backlog slot, or drop -- the
engine<->simulator parity oracle runs over the {layout} x {backend} grid
(`tests/test_engine_parity.py`) and `tests/test_visited_properties.py` is
the fast property gate (roundtrip, popcount, idempotence, padded-frontier
no-op).

A layout instance is PYTHON-STATIC (resolved once from
`EngineConfig.visited_layout`, never traced); the visited state itself
stays a raw `jax.Array` whose dtype/width the layout dictates, so it
passes through scan carries, vmap and shard_map unchanged.

The expansion backends (`EXPAND_BACKENDS`) live here too: a backend is an
execution strategy FOR a layout (`layout.expander(name, n)`), and the two
seams compose -- {dense, packed} x {scatter, pallas, auto}.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.kernels.frontier import (
    WORD_BITS, dense_frontier, dense_frontier_packed, frontier_expand_batched,
    frontier_expand_packed, n_words, pack_words, unpack_words,
)
from repro.kernels.ops import on_tpu

VISITED_LAYOUTS = ("dense", "packed")
EXPAND_BACKENDS = ("scatter", "pallas", "pallas-interpret", "auto", "auto-interpret")


# ---------------------------------------------------------------------------
# Expansion backends (the step-4 execution seam).
#
# Protocol: fn(rows (B, F, W) int32, deg (B, F) int32, mask) -> mask' with
# every valid neighbor marked, where mask is IN THE LAYOUT'S REPRESENTATION.
# Valid = row id >= 0, at a column below the row's remaining degree (a row
# holds min(deg, W) entries), and < n (continuation-row ids >= n are
# engine-internal and never enter the bitmap).
# ---------------------------------------------------------------------------


def _scatter_expand(rows_b: jax.Array, deg_b: jax.Array, mask: jax.Array,
                    n: int) -> jax.Array:
    """Dense reference backend: per-query scatter via XLA `.at[].max()`."""
    B, F, W = rows_b.shape
    width_ok = jnp.arange(W)[None, None, :] < deg_b[:, :, None]
    nbr_valid = (rows_b >= 0) & width_ok & (rows_b < n)
    flat_nbrs = jnp.where(nbr_valid, rows_b, 0).reshape(B, F * W)
    flat_ok = nbr_valid.reshape(B, F * W)
    bidx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, F * W))
    return mask.at[bidx, flat_nbrs].max(flat_ok)


def _pallas_expand(rows_b: jax.Array, deg_b: jax.Array, mask: jax.Array,
                   n: int, interpret: bool) -> jax.Array:
    """Dense batched compare-reduce kernel: one launch for the whole batch.

    Row ids >= n (continuation rows / out-of-range) are masked to -1 pad
    before the kernel; width masking rides the kernel's own deg clip.
    """
    rows_in = jnp.where(rows_b < n, rows_b, -1)
    return frontier_expand_batched(rows_in, deg_b, mask, interpret=interpret)


def _scatter_expand_packed(rows_b: jax.Array, deg_b: jax.Array,
                           mask: jax.Array, n: int) -> jax.Array:
    """Packed reference backend: XLA has no scatter-OR into words, so the
    hop's delta is scattered into a transient dense bitmap and packed once.
    The packed mask is what LIVES across the chain loop / hop carries; the
    dense delta exists only inside this op (XLA is free to fuse it away)."""
    B = rows_b.shape[0]
    delta = _scatter_expand(rows_b, deg_b, jnp.zeros((B, n), bool), n)
    return mask | pack_words(delta)


def _pallas_expand_packed(rows_b: jax.Array, deg_b: jax.Array,
                          mask: jax.Array, n: int, interpret: bool) -> jax.Array:
    """Packed blocked kernel: compare-reduce straight into uint32 words."""
    return frontier_expand_packed(rows_b, deg_b, mask, n, interpret=interpret)


# ---------------------------------------------------------------------------
# The layouts
# ---------------------------------------------------------------------------


class DenseVisited:
    """(B, n) bool -- the reference layout (one byte per node)."""

    name = "dense"

    def empty(self, B: int, n: int) -> jax.Array:
        return jnp.zeros((B, n), dtype=bool)

    def seed(self, queries: jax.Array, n: int) -> jax.Array:
        """Visited set holding each valid query's own node (-1 pad -> empty)."""
        B = queries.shape[0]
        valid = queries >= 0
        vis = self.empty(B, n)
        return vis.at[jnp.arange(B), jnp.maximum(queries, 0)].max(valid)

    def count(self, vis: jax.Array) -> jax.Array:
        return jnp.sum(vis, axis=1).astype(jnp.int32)

    def to_dense(self, vis: jax.Array, n: int) -> jax.Array:
        return vis

    def from_dense(self, dense: jax.Array) -> jax.Array:
        return dense

    def union(self, a: jax.Array, b: jax.Array) -> jax.Array:
        return a | b

    def minus(self, a: jax.Array, b: jax.Array) -> jax.Array:
        return a & ~b

    def overlap_any(self, a: jax.Array, b: jax.Array) -> jax.Array:
        return jnp.any(a & b, axis=1)

    def nbytes_per_query(self, n: int) -> int:
        return n  # XLA stores bool as one byte per element

    def expander(self, backend: str, n: int) -> Callable:
        return _make_expander(backend, n, _scatter_expand, _pallas_expand,
                              lambda deg, _mask: dense_frontier(deg, n))

    def init_search(self, queries: jax.Array, n: int, F: int):
        return _init_search(self, queries, n, F)


class PackedVisited:
    """(B, ceil(n/32)) uint32 -- one bit per node, 8x below dense.

    Node id -> (word id // 32, bit id % 32), little-endian within the word
    (the order `kernels.frontier.pack_words` fixes). Counts are word
    popcounts; set algebra is word-wise bitwise ops; padding bits past n
    are an invariant zero, so popcounts never over-count.
    """

    name = "packed"

    def empty(self, B: int, n: int) -> jax.Array:
        return jnp.zeros((B, n_words(n)), dtype=jnp.uint32)

    def seed(self, queries: jax.Array, n: int) -> jax.Array:
        B = queries.shape[0]
        valid = queries >= 0
        q = jnp.maximum(queries, 0)
        bit = jnp.uint32(1) << (q % WORD_BITS).astype(jnp.uint32)
        vis = self.empty(B, n)
        return vis.at[jnp.arange(B), q // WORD_BITS].set(
            jnp.where(valid, bit, jnp.uint32(0))
        )

    def count(self, vis: jax.Array) -> jax.Array:
        return jnp.sum(jax.lax.population_count(vis), axis=1).astype(jnp.int32)

    def to_dense(self, vis: jax.Array, n: int) -> jax.Array:
        return unpack_words(vis, n)

    def from_dense(self, dense: jax.Array) -> jax.Array:
        return pack_words(dense)

    def union(self, a: jax.Array, b: jax.Array) -> jax.Array:
        return a | b

    def minus(self, a: jax.Array, b: jax.Array) -> jax.Array:
        return a & ~b

    def overlap_any(self, a: jax.Array, b: jax.Array) -> jax.Array:
        return jnp.any((a & b) != 0, axis=1)

    def nbytes_per_query(self, n: int) -> int:
        return n_words(n) * 4

    def expander(self, backend: str, n: int) -> Callable:
        # popcount-refined density predicate: free on the packed words.
        # `expand_hop` feeds the expander visited | hop marks, so the
        # occupancy the predicate weighs is the query's real visited set.
        return _make_expander(backend, n, _scatter_expand_packed,
                              _pallas_expand_packed,
                              lambda deg, mask: dense_frontier_packed(deg, mask, n))

    def init_search(self, queries: jax.Array, n: int, F: int):
        return _init_search(self, queries, n, F)


def _interpret_mode(backend: str) -> bool:
    """Interpret mode only when asked for by name ("-interpret": the CPU
    tests' kernel path). "pallas"/"auto" compile the kernel for a TPU and
    refuse any other platform instead of quietly interpreting it."""
    if backend not in EXPAND_BACKENDS:
        raise ValueError(
            f"unknown expand_backend {backend!r}; one of {EXPAND_BACKENDS}")
    if backend.endswith("-interpret"):
        return True
    if backend != "scatter" and not on_tpu():
        raise RuntimeError(
            f"expand_backend {backend!r} runs its Pallas kernel natively, which "
            f"needs a TPU (default backend: {jax.default_backend()!r}); "
            f"ask for {backend}-interpret to run it in the interpreter")
    return False


def _make_expander(backend: str, n: int, scatter_fn: Callable,
                   pallas_fn: Callable, dense_pred: Callable) -> Callable:
    """The shared backend dispatch both layouts resolve through.

    A layout supplies its two execution strategies (`scatter_fn` /
    `pallas_fn`, protocol fn(rows, deg, mask, n[, interpret])) and its
    density predicate `dense_pred(deg, mask)` for the per-hop `auto` cond,
    which it gives each row's own entry count min(deg, W) (a row's `deg` is
    its node's remaining degree); the scatter/pallas/auto name resolution
    itself exists exactly once."""
    interpret = _interpret_mode(backend)
    if backend == "scatter":
        return functools.partial(scatter_fn, n=n)
    if backend.startswith("pallas"):
        return functools.partial(pallas_fn, n=n, interpret=interpret)

    def auto(rows_b, deg_b, mask):
        return jax.lax.cond(
            dense_pred(jnp.minimum(deg_b, rows_b.shape[-1]), mask),
            lambda r, d, m: pallas_fn(r, d, m, n=n, interpret=interpret),
            lambda r, d, m: scatter_fn(r, d, m, n=n),
            rows_b, deg_b, mask,
        )

    return auto


def _init_search(layout, queries: jax.Array, n: int, F: int):
    """THE shared visited/frontier constructor for a batch of BFS queries.

    Returns (visited, frontier, valid): visited holds each valid query's
    own node in the layout's representation, frontier is (B, F) int32 with
    the query in slot 0 (-1 padded). Formerly copy-pasted between
    `run_neighbor_aggregation` and the reachability BFS.
    """
    B = queries.shape[0]
    valid = queries >= 0
    visited = layout.seed(queries, n)
    frontier = jnp.full((B, F), -1, jnp.int32)
    frontier = frontier.at[:, 0].set(jnp.where(valid, queries, -1))
    return visited, frontier, valid


_LAYOUTS = {"dense": DenseVisited(), "packed": PackedVisited()}


def get_visited_layout(name: str):
    """Resolve a layout name to its strategy singleton (python-static)."""
    try:
        return _LAYOUTS[name]
    except KeyError:
        raise ValueError(
            f"unknown visited_layout {name!r}; one of {VISITED_LAYOUTS}"
        ) from None


def get_expand_backend(name: str, n: int, layout: str = "dense") -> Callable:
    """Resolve (backend, layout) to the protocol callable (python-static).

    Kept as the PR 3 entry point; `layout` defaults to the historical dense
    representation."""
    return get_visited_layout(layout).expander(name, n)


def visited_nbytes(layout: str, B: int, n: int) -> int:
    """Bytes of one (B, n)-query visited set under `layout` (the scan-carry
    cost the packed layout exists to cut; reported by bench_engine)."""
    return B * get_visited_layout(layout).nbytes_per_query(n)
