"""The general query generator: queries in groups, each group drawn from the
r-hop ball around a centre. A traffic mix that names it is a data file,
`traffic/<name>.json`:

    {"generator": "balls", "clients": 64, "pool": 8192, "query_seed": 0,
     "group": 10, "radius": 2}
    {"generator": "balls", "clients": 256, "pool": 4000000, "query_seed": 0,
     "group": 1, "radius": 0}

The first is the paper's r-hop hotspot category, the program's
`hotspot_workload` (core/workloads.py) copied so that the yardstick cannot
move: centres uniform over the nodes, `group` consecutive query nodes sampled
from the ball of `radius` hops around each. The second, radius 0, sends every
centre once: query nodes uniform over the graph.
"""

from __future__ import annotations

import numpy as np


def hotspot_ball(g, deg: np.ndarray, center: int, r: int, limit: int) -> np.ndarray:
    """The r-hop ball around center as the program's generator grows it, in
    node-id order: BFS, where a level stops after the frontier node at which
    the ball first holds more than `limit` nodes. The frontier is taken in
    chunks just large enough to reach the limit, so a hub's adjacency is
    read only where the BFS reaches it. `deg` is the graph's degrees."""
    ball = np.array([center], dtype=np.int64)
    frontier = ball
    for _ in range(r):
        level, at = [], 0
        while at < frontier.size:
            cum = np.cumsum(deg[frontier[at:]])
            room = max(1, limit + 1 - ball.size)
            chunk = frontier[at:at + int(np.searchsorted(cum, room)) + 1]
            at += chunk.size
            lens = deg[chunk]
            pos = np.repeat(g.indptr[chunk] - np.cumsum(lens) + lens, lens) + np.arange(lens.sum())
            nbrs = g.indices[pos].astype(np.int64)
            owner = np.repeat(np.arange(chunk.size), lens)
            fresh = ~np.isin(nbrs, ball)
            nbrs, owner = nbrs[fresh], owner[fresh]
            _, first = np.unique(nbrs, return_index=True)
            first.sort()  # first sightings, in the order the BFS meets them
            nbrs, owner = nbrs[first], owner[first]
            grown = ball.size + np.cumsum(np.bincount(owner, minlength=chunk.size))
            over = np.flatnonzero(grown > limit)
            if over.size:  # the level stops after the frontier node that crossed
                nbrs, at = nbrs[owner <= over[0]], frontier.size
            level.append(nbrs)
            ball = np.union1d(ball, nbrs)
        frontier = np.concatenate(level) if level else ball[:0]
        if not frontier.size:
            break
    return ball


def make_pool(g, spec: dict, rng: np.random.Generator) -> np.ndarray:
    """`spec["pool"]` query nodes of graph `g`, in the order clients send
    them."""
    size, group, radius = int(spec["pool"]), int(spec["group"]), int(spec["radius"])
    centers = rng.integers(0, g.n, size=-(-size // group))
    if radius == 0:
        return np.repeat(centers, group)[:size]
    deg, nodes = g.degree(), []
    for c in centers:
        ball = hotspot_ball(g, deg, int(c), radius, 50 * group)
        nodes.append(rng.choice(ball, size=group, replace=ball.size < group))
    return np.concatenate(nodes)[:size]
