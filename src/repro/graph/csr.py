"""CSR graph structures.

Two layouts are used throughout the framework:

- ``CSRGraph``: classic (indptr, indices) compressed sparse rows. Host-side
  (numpy) canonical representation; all generators produce this.
- ``PaddedAdjacency``: fixed-width neighbor matrix ``(n, max_degree)`` with a
  per-node ``degree`` vector, padded with ``-1``.  This is the device layout:
  it is what the decoupled storage tier shards, what the processor cache
  stores rows of, and what the Pallas frontier kernel consumes.  Padding is a
  deliberate TPU adaptation: RAMCloud stored variable-length adjacency values;
  on TPU the storage row must be fixed-shape.  For power-law graphs we cap
  ``max_degree`` and spill the overflow into *continuation rows* (virtual node
  ids >= n chaining the remainder), preserving exact adjacency.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np


@dataclasses.dataclass
class CSRGraph:
    """Host-side CSR graph. Directed; see make_bidirected for the bi-directed view."""

    n: int
    indptr: np.ndarray  # (n+1,) int64
    indices: np.ndarray  # (e,) int32/int64

    @property
    def e(self) -> int:
        return int(self.indices.shape[0])

    def degree(self) -> np.ndarray:
        return np.diff(self.indptr)

    def neighbors(self, u: int) -> np.ndarray:
        return self.indices[self.indptr[u] : self.indptr[u + 1]]

    def validate(self) -> None:
        assert self.indptr.shape == (self.n + 1,)
        assert self.indptr[0] == 0 and self.indptr[-1] == self.e
        assert np.all(np.diff(self.indptr) >= 0)
        if self.e:
            assert self.indices.min() >= 0 and self.indices.max() < self.n


@dataclasses.dataclass
class PaddedAdjacency:
    """Fixed-width adjacency rows; device/storage layout.

    rows:   (n_rows, max_degree) int32, -1 padded.
    degree: (n_rows,) int32 -- REMAINING degree: the adjacency entries from
            this row to the end of its node's adjacency. A base row holds the
            node's full degree, continuation row k of a node of degree d
            holds d - k * max_degree; the row's own entries are the first
            min(degree, max_degree).
    cont:   (n_rows,) int32 -- continuation row id (>= n base rows) or -1.
            Rows whose true degree exceeds max_degree chain into continuation
            rows appended after the n base rows.
    n:      number of *real* nodes (base rows); n_rows >= n.

    A node's continuation rows are consecutive: segment k >= 1 of node u is
    row cont[u] + k - 1, so a base row's (degree, cont) name every row of its
    chain without following it.
    """

    n: int
    rows: np.ndarray
    degree: np.ndarray
    cont: np.ndarray

    @property
    def n_rows(self) -> int:
        return int(self.rows.shape[0])

    @property
    def max_degree(self) -> int:
        return int(self.rows.shape[1])

    def full_neighbors(self, u: int) -> np.ndarray:
        """Follow continuation chain; host-side oracle for tests."""
        out = []
        r = u
        while r != -1:
            d = min(int(self.degree[r]), self.max_degree)
            out.append(self.rows[r, :d])
            r = int(self.cont[r])
        if not out:
            return np.zeros((0,), np.int32)
        return np.concatenate(out)


def build_csr(n: int, src: np.ndarray, dst: np.ndarray, dedup: bool = True) -> CSRGraph:
    """Build CSR from an edge list (directed src->dst)."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    if dedup and src.size:
        key = src * n + dst
        key = np.unique(key)
        src, dst = key // n, key % n
    order = np.argsort(src, kind="stable")
    src, dst = src[order], dst[order]
    counts = np.bincount(src, minlength=n)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    return CSRGraph(n=n, indptr=indptr, indices=dst.astype(np.int32))


def make_bidirected(g: CSRGraph) -> CSRGraph:
    """Union of edges and reversed edges (paper: every edge treated bi-directed
    because both in- and out-neighbors are stored per node)."""
    src = np.repeat(np.arange(g.n, dtype=np.int64), np.diff(g.indptr))
    dst = g.indices.astype(np.int64)
    all_src = np.concatenate([src, dst])
    all_dst = np.concatenate([dst, src])
    return build_csr(g.n, all_src, all_dst, dedup=True)


def to_padded(g: CSRGraph, max_degree: Optional[int] = None) -> PaddedAdjacency:
    """Convert CSR to the padded storage layout with continuation rows.

    If max_degree is None, uses the true max degree (no continuations).
    The contract readers rely on (see `PaddedAdjacency`): a row's degree is
    the node's remaining degree, and a node's continuation rows are
    consecutive, so a node of degree d > max_degree owns the rows
    cont[u] + j for j < ceil(d / max_degree) - 1.
    """
    deg = np.diff(g.indptr).astype(np.int64)
    true_max = int(deg.max()) if g.n else 0
    if max_degree is None:
        max_degree = max(true_max, 1)
    max_degree = max(int(max_degree), 2)  # need >= 2 for continuation chaining

    # Every row holds up to max_degree entries; the chain pointer is kept
    # out-of-band in cont[], so chained rows lose no payload capacity.
    n_chain = np.where(deg <= max_degree, 0, np.ceil((deg - max_degree) / max_degree).astype(np.int64))
    total_rows = g.n + int(n_chain.sum())

    # node u's chain rows are consecutive, allocated in node order after the
    # n base rows; segment k of u (k >= 1) is row chain_start[u] + k - 1
    chain_start = g.n + np.cumsum(n_chain) - n_chain
    rows = np.full((total_rows, max_degree), -1, dtype=np.int32)
    degree = np.zeros((total_rows,), dtype=np.int32)
    cont = np.full((total_rows,), -1, dtype=np.int32)

    # every neighbor entry j of u lands in segment j // W, column j % W
    owner = np.repeat(np.arange(g.n, dtype=np.int64), deg)
    j = np.arange(g.e, dtype=np.int64) - g.indptr[owner]
    seg = j // max_degree
    row_of = np.where(seg == 0, owner, chain_start[owner] + seg - 1)
    rows[row_of, j % max_degree] = g.indices

    degree[: g.n] = deg
    has_chain = n_chain > 0
    cont[: g.n][has_chain] = chain_start[has_chain]
    # chain rows: segment k = 1..n_chain[u] of each chained node u
    c_owner = np.repeat(np.arange(g.n, dtype=np.int64), n_chain)
    c_seg = np.arange(total_rows - g.n, dtype=np.int64) - (chain_start[c_owner] - g.n) + 1
    degree[g.n :] = deg[c_owner] - c_seg * max_degree
    last = c_seg == n_chain[c_owner]
    cont[g.n :] = np.where(last, -1, np.arange(g.n + 1, total_rows + 1))
    return PaddedAdjacency(n=g.n, rows=rows, degree=degree, cont=cont)


def iter_bfs_levels(g: CSRGraph, source: int, max_hops: Optional[int] = None):
    """Host BFS oracle: yields the nodes at hop distance 0, 1, .. max_hops
    from `source` (until the ball stops growing when max_hops is None), one
    sorted int64 array per level. Lazy, so a caller stops the search by
    not asking for the next level; vectorized per level, so a level of a
    million nodes costs numpy time, not a python loop."""
    seen = np.zeros(g.n, dtype=bool)
    seen[source] = True
    level = np.array([source], dtype=np.int64)
    hop = 0
    while level.size:
        yield level
        if max_hops is not None and hop >= max_hops:
            return
        starts, lens = g.indptr[level], g.indptr[level + 1] - g.indptr[level]
        pos = np.repeat(starts - np.cumsum(lens) + lens, lens) + np.arange(lens.sum())
        level = np.unique(g.indices[pos])
        level = level[~seen[level]].astype(np.int64)
        seen[level] = True
        hop += 1


def bfs_levels(g: CSRGraph, source: int, max_hops: Optional[int] = None) -> list:
    """All of `iter_bfs_levels` as a list."""
    return list(iter_bfs_levels(g, source, max_hops))


def csr_to_edge_index(g: CSRGraph) -> Tuple[np.ndarray, np.ndarray]:
    """(src, dst) int32 arrays -- the GNN edge-index layout."""
    src = np.repeat(np.arange(g.n, dtype=np.int32), np.diff(g.indptr))
    return src, g.indices.astype(np.int32)
