"""The benchmark's graph: a power-law graph, the same for every run.

`powerlaw_edges` is the program's preferential-attachment generator
(`repro.graph.generators.powerlaw_graph`, bi-directed, deduplicated), copied
here so that the yardstick cannot move with the program: the same structure
seed gives the same graph as the program's generator.

The served graph is that structure under a node relabelling drawn from the
configuration's `label_seed`, so that node ids say nothing of a node's age
or degree, and its queries are the traffic's queries on the structure under
the same relabelling. Every run serves that one graph and those queries, in
the same rounds and the same order within each round (`run.seed_order`, from
the traffic's query seed); the run's seed picks only the rounds whose answers
are checked. A relabelling drawn from the run's seed changed the work
(storage placement, cache sets, and which frontier nodes a truncation by node
id keeps): on a TPU v5e it moved a round's time by up to 1% from seed to
seed, against 0.1% between two runs of one seed. So did an order drawn from
the run's seed, once each processor pooled the hub chains of the queries it
holds: the order decides which queries share a processor.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Graph:
    """Undirected graph as CSR arrays (each edge stored in both directions)."""

    n: int
    indptr: np.ndarray  # (n + 1,) int64
    indices: np.ndarray  # (e,) int32
    perm: Optional[np.ndarray] = None  # node u of the structure is node perm[u] here

    @property
    def e(self) -> int:
        return int(self.indices.size)

    def degree(self) -> np.ndarray:
        return np.diff(self.indptr)

    def neighbors(self, u: int) -> np.ndarray:
        return self.indices[self.indptr[u]:self.indptr[u + 1]]


def _csr(n: int, src: np.ndarray, dst: np.ndarray) -> Graph:
    key = np.unique(src.astype(np.int64) * n + dst.astype(np.int64))
    src, dst = key // n, key % n
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    return Graph(n=n, indptr=indptr, indices=dst.astype(np.int32))


def powerlaw_edges(n: int, m: int, seed: int) -> Graph:
    """Preferential attachment, m edges per new node, made bi-directed."""
    rng = np.random.default_rng(seed)
    m = max(1, min(m, n - 1))
    src = np.zeros(n * m, dtype=np.int64)
    dst = np.zeros(n * m, dtype=np.int64)
    k = 0
    for u in range(1, m + 1):  # seed clique over the first m + 1 nodes
        for v in range(u):
            src[k], dst[k] = u, v
            k += 1
    batch = max(1024, m * 64)
    pool = np.empty(2 * (k + max(0, n - m - 1) * m), dtype=np.int64)
    pool[:k], pool[k:2 * k] = src[:k], dst[:k]
    pool_size = 2 * k
    u = m + 1
    while u < n:
        ub = min(n, u + batch)
        cnt = (ub - u) * m
        targets = pool[rng.integers(0, pool_size, size=cnt)]
        news = np.repeat(np.arange(u, ub, dtype=np.int64), m)
        targets = np.where(targets >= news, targets % np.maximum(news, 1), targets)
        src[k:k + cnt] = news
        dst[k:k + cnt] = targets
        k += cnt
        pool[pool_size:pool_size + cnt] = news
        pool[pool_size + cnt:pool_size + 2 * cnt] = targets
        pool_size += 2 * cnt
        u = ub
    directed = _csr(n, src[:k], dst[:k])
    s = np.repeat(np.arange(n, dtype=np.int64), directed.degree())
    d = directed.indices.astype(np.int64)
    return _csr(n, np.concatenate([s, d]), np.concatenate([d, s]))


def relabel(g: Graph, rng: np.random.Generator) -> Graph:
    """The same graph with node u renamed perm[u], perm drawn from `rng`."""
    perm = rng.permutation(g.n)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(g.n)
    deg = g.degree()[inv]
    indptr = np.zeros(g.n + 1, dtype=np.int64)
    np.cumsum(deg, out=indptr[1:])
    pos = np.repeat(g.indptr[inv] - indptr[:-1], deg) + np.arange(g.e, dtype=np.int64)
    return Graph(n=g.n, indptr=indptr, indices=perm[g.indices[pos]].astype(np.int32),
                 perm=perm)


def structure(cfg: dict) -> Graph:
    """The configuration's graph structure, the same for every run."""
    return powerlaw_edges(cfg["nodes"], cfg["edges_per_node"], cfg["structure_seed"])

