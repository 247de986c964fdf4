"""Plain host reference for h-hop neighbour aggregation over decoupled storage.

It states what the served system guarantees, from the graph alone (it
imports nothing of the program):

- a query's answer is the number of distinct nodes within `hops` hops of
  it, the query node not counted;
- every hop expands every node of the frontier with its whole adjacency
  (continuation rows are followed to the end);
- the next frontier is the first `max_frontier` newly reached nodes by node
  id; nodes past them are counted but not expanded (the defined truncation;
  where no level overflows, the answer is the exact ball size);
- a storage row holds `row_width` neighbours, so expanding a node of degree
  d touches max(1, ceil(d / row_width)) rows.
"""

from __future__ import annotations

import numpy as np


def node_rows(deg: np.ndarray, row_width: int) -> np.ndarray:
    """Storage rows (base row plus continuation rows) of each node."""
    return np.maximum(1, -(-deg // row_width))


def serve(g, query: int, hops: int, max_frontier: int, row_width: int,
          seen: np.ndarray) -> tuple[int, int]:
    """(answer, rows touched) of one query. `seen` is an all-False (n,) bool
    scratch array, handed back all-False."""
    deg = np.diff(g.indptr)
    reached = [np.array([query], dtype=np.int64)]
    seen[query] = True
    frontier = reached[0]
    touched = 0
    for _ in range(hops):
        touched += int(node_rows(deg[frontier], row_width).sum())
        starts, lens = g.indptr[frontier], deg[frontier]
        pos = np.repeat(starts - np.cumsum(lens) + lens, lens) + np.arange(lens.sum())
        new = np.unique(g.indices[pos])
        new = new[~seen[new]]
        seen[new] = True
        reached.append(new)
        frontier = new[:max_frontier]
    answer = sum(r.size for r in reached) - 1
    for r in reached:
        seen[r] = False
    return answer, touched


def serve_all(g, queries: np.ndarray, hops: int, max_frontier: int,
              row_width: int) -> tuple[np.ndarray, np.ndarray]:
    """Answers and rows touched of each query."""
    seen = np.zeros(g.n, dtype=bool)
    out = [serve(g, int(q), hops, max_frontier, row_width, seen) for q in queries]
    answers = np.array([a for a, _ in out], dtype=np.int64)
    touched = np.array([t for _, t in out], dtype=np.int64)
    return answers, touched
