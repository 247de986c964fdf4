"""Landmark selection and multi-source BFS distances (paper Algorithm 1).

Pipeline (lines reference Algorithm 1 in the paper):
  1. take the |L| highest-degree nodes as candidate landmarks        (line 1)
  2. BFS from each to get d(u, l) for every node u                   (line 3)
  3. discard the lower-degree one of any landmark pair closer than
     `min_separation`                                                (lines 4-5)
  4. pick P far-apart *pivot* landmarks (farthest-pair + greedy
     farthest-point), one per processor                              (lines 8-11)
  5. assign remaining landmarks to the processor of their closest
     pivot                                                           (lines 12-13)
  6. d(u, p) = min over landmarks assigned to p of d(u, l)           (lines 14-15)

The BFS itself is TPU-native: distances to ALL landmarks are advanced
simultaneously with one `segment_min` relaxation per level over the edge
list (min-plus semiring Bellman-Ford restricted to unit weights == BFS),
instead of the paper's per-landmark sequential BFS. Complexity per level is
O(e * L) FLOP-equivalents, fully vectorized.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.csr import CSRGraph, csr_to_edge_index

UNREACHED = np.int32(0x3FFFFFFF)  # "infinity" that survives +1 without overflow
BFS_CHUNK = 16  # landmark candidates per bfs_distances call (bounds its memory)


@functools.partial(jax.jit, static_argnames=("n", "max_iters"))
def bfs_distances(
    src: jax.Array, dst: jax.Array, sources: jax.Array, n: int, max_iters: int = 64
) -> jax.Array:
    """Multi-source BFS levels via edge-list min-plus relaxation.

    src/dst: (e,) int32 edge list (must already be bi-directed if the paper's
    bi-directed semantics are wanted).
    sources: (L,) int32 source nodes.
    Returns dist: (n, L) int32, UNREACHED where not reachable in max_iters.
    """
    L = sources.shape[0]
    dist = jnp.full((n, L), UNREACHED, dtype=jnp.int32)
    dist = dist.at[sources, jnp.arange(L)].set(0)

    def body(state):
        dist, _changed, it = state
        msg = dist[src] + 1  # (e, L)
        relaxed = jax.ops.segment_min(msg, dst, num_segments=n)  # (n, L)
        new = jnp.minimum(dist, relaxed)
        changed = jnp.any(new != dist)
        return new, changed, it + 1

    def cond(state):
        _dist, changed, it = state
        return jnp.logical_and(changed, it < max_iters)

    dist, _, _ = jax.lax.while_loop(cond, body, (dist, jnp.array(True), jnp.array(0)))
    return dist


@dataclasses.dataclass
class LandmarkIndex:
    """Preprocessed router state for landmark routing.

    landmarks:      (L,) node ids
    dist_to_lm:     (n, L) int32 BFS distances  (O(nL) preprocessing product)
    lm_processor:   (L,) int32 processor id of each landmark
    dist_to_proc:   (n, P) int32 -- d(u, p), the O(nP) routing table the
                    router actually stores (paper: Requirement 1)
    pivots:         (P,) landmark *indices* (into landmarks) chosen as pivots
    """

    landmarks: np.ndarray
    dist_to_lm: np.ndarray
    lm_processor: np.ndarray
    dist_to_proc: np.ndarray
    pivots: np.ndarray

    @property
    def n_processors(self) -> int:
        return int(self.dist_to_proc.shape[1])


def select_landmarks(
    g: CSRGraph,
    n_landmarks: int,
    min_separation: int = 3,
    oversample: int = 3,
) -> Tuple[np.ndarray, np.ndarray]:
    """Algorithm 1 lines 1-7. Returns (landmarks, dist_to_lm (n, L))."""
    deg = g.degree()
    n_cand = min(g.n, n_landmarks * oversample)
    cand = np.argsort(-deg, kind="stable")[:n_cand].astype(np.int32)
    src, dst = csr_to_edge_index(g)
    src, dst = jnp.asarray(src), jnp.asarray(dst)
    # BFS from BFS_CHUNK candidates per call: the relaxation holds an (e, L)
    # message array, 4.3 GB at L=16 on a 2^22-node, 67M-edge graph. Sources
    # are independent, so chunking changes no distance; the last chunk is
    # padded with its first source so every call shares one compile.
    chunks = []
    for i in range(0, n_cand, BFS_CHUNK):
        part = cand[i : i + BFS_CHUNK]
        padded = np.concatenate([part, np.full(BFS_CHUNK - part.size, part[0], np.int32)])
        d = bfs_distances(src, dst, jnp.asarray(padded), g.n)
        chunks.append(np.asarray(d)[:, : part.size])
    dist = np.concatenate(chunks, axis=1)  # (n, n_cand)

    # greedy separation filter in candidate (degree-descending) order
    kept: list[int] = []
    for i in range(n_cand):
        ok = True
        for j in kept:
            if dist[cand[i], j] < min_separation:
                ok = False
                break
        if ok:
            kept.append(i)
            if len(kept) == n_landmarks:
                break
    # if separation filter starved us, relax: fill with remaining highest degree
    if len(kept) < n_landmarks:
        for i in range(n_cand):
            if i not in kept:
                kept.append(i)
                if len(kept) == n_landmarks:
                    break
    kept_arr = np.array(kept[:n_landmarks], dtype=np.int64)
    return cand[kept_arr], dist[:, kept_arr]


def assign_pivots(
    landmarks: np.ndarray, dist_to_lm: np.ndarray, n_processors: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Algorithm 1 lines 8-13: pick P pivots (farthest-pair then greedy
    farthest-point), map each landmark to the processor of its closest pivot.

    Returns (pivots (P,) indices into landmarks, lm_processor (L,)).
    """
    L = landmarks.shape[0]
    P = min(n_processors, L)
    # pairwise landmark distances: d(l_i, l_j) = dist_to_lm[landmarks[i], j]
    dmat = dist_to_lm[landmarks, :].astype(np.int64)  # (L, L)
    dmat = np.minimum(dmat, dmat.T)  # symmetrize (bi-directed BFS should already be)
    capped = np.where(dmat >= UNREACHED, -1, dmat)
    i, j = np.unravel_index(np.argmax(capped), capped.shape)
    pivots = [int(i), int(j)] if P >= 2 else [int(i)]
    while len(pivots) < P:
        dmin = np.min(dmat[:, pivots], axis=1)
        dmin[pivots] = -1
        # prefer reachable-far; unreachable (UNREACHED) counts as farthest
        nxt = int(np.argmax(dmin))
        pivots.append(nxt)
    pivots_arr = np.array(pivots, dtype=np.int32)
    lm_processor = np.argmin(dmat[:, pivots_arr], axis=1).astype(np.int32)
    lm_processor[pivots_arr] = np.arange(len(pivots_arr), dtype=np.int32)
    return pivots_arr, lm_processor


def build_landmark_index(
    g: CSRGraph,
    n_processors: int,
    n_landmarks: int = 96,
    min_separation: int = 3,
) -> LandmarkIndex:
    """Full Algorithm 1 preprocessing."""
    landmarks, dist_to_lm = select_landmarks(g, n_landmarks, min_separation)
    pivots, lm_processor = assign_pivots(landmarks, dist_to_lm, n_processors)
    P = int(lm_processor.max()) + 1 if lm_processor.size else 1
    P = max(P, min(n_processors, landmarks.shape[0]))
    # d(u, p) = min over landmarks assigned to p (lines 14-15)
    dist_to_proc = np.full((g.n, n_processors), UNREACHED, dtype=np.int32)
    for p in range(min(P, n_processors)):
        mask = lm_processor == p
        if mask.any():
            dist_to_proc[:, p] = dist_to_lm[:, mask].min(axis=1)
    return LandmarkIndex(
        landmarks=landmarks.astype(np.int32),
        dist_to_lm=dist_to_lm.astype(np.int32),
        lm_processor=lm_processor,
        dist_to_proc=dist_to_proc,
        pivots=pivots,
    )


def incremental_add_node(
    index: LandmarkIndex, g_new: CSRGraph, new_node: int
) -> LandmarkIndex:
    """Graph-update handling (paper §3.4.1): on node addition, compute the new
    node's distance to every landmark (one BFS from the node over the updated
    graph) and extend the routing table; existing entries untouched."""
    src, dst = csr_to_edge_index(g_new)
    d_new = np.asarray(
        bfs_distances(
            jnp.asarray(src), jnp.asarray(dst), jnp.asarray(np.array([new_node], np.int32)), g_new.n
        )
    )[:, 0]  # (n,) distance from new node to all
    d_lm = d_new[index.landmarks]  # (L,)
    n_old = index.dist_to_lm.shape[0]
    if new_node < n_old:
        dist_to_lm = index.dist_to_lm.copy()
        dist_to_lm[new_node] = d_lm
    else:
        pad = np.full((new_node + 1 - n_old, index.landmarks.shape[0]), UNREACHED, np.int32)
        dist_to_lm = np.concatenate([index.dist_to_lm, pad], 0)
        dist_to_lm[new_node] = d_lm
    P = index.dist_to_proc.shape[1]
    row = np.full((P,), UNREACHED, np.int32)
    for p in range(P):
        mask = index.lm_processor == p
        if mask.any():
            row[p] = d_lm[mask].min()
    if new_node < index.dist_to_proc.shape[0]:
        dist_to_proc = index.dist_to_proc.copy()
        dist_to_proc[new_node] = row
    else:
        pad = np.full((new_node + 1 - index.dist_to_proc.shape[0], P), UNREACHED, np.int32)
        dist_to_proc = np.concatenate([index.dist_to_proc, pad], 0)
        dist_to_proc[new_node] = row
    return LandmarkIndex(
        landmarks=index.landmarks,
        dist_to_lm=dist_to_lm,
        lm_processor=index.lm_processor,
        dist_to_proc=dist_to_proc,
        pivots=index.pivots,
    )
