"""The linear-time graph builders against the straightforward algorithms
they replaced: same seed, same graph, same storage rows."""

import numpy as np
import pytest

from repro.graph.csr import build_csr, make_bidirected, to_padded
from repro.graph.generators import community_graph, powerlaw_graph


def _powerlaw_reference(n: int, m: int, seed: int):
    """Preferential attachment with the endpoint pool re-concatenated for
    every batch (quadratic; the generator must draw the same graph)."""
    rng = np.random.default_rng(seed)
    m = max(1, min(m, n - 1))
    src = np.zeros(n * m, dtype=np.int64)
    dst = np.zeros(n * m, dtype=np.int64)
    k = 0
    for u in range(1, m + 1):
        for v in range(u):
            src[k], dst[k] = u, v
            k += 1
    pool_list = [np.concatenate([src[:k], dst[:k]])]
    batch = max(1024, m * 64)
    u = m + 1
    while u < n:
        ub = min(n, u + batch)
        cnt = (ub - u) * m
        flat_pool = np.concatenate(pool_list)
        pool_list = [flat_pool]
        targets = flat_pool[rng.integers(0, flat_pool.size, size=cnt)]
        news = np.repeat(np.arange(u, ub, dtype=np.int64), m)
        targets = np.where(targets >= news, targets % np.maximum(news, 1), targets)
        src[k : k + cnt] = news
        dst[k : k + cnt] = targets
        k += cnt
        pool_list += [news, targets]
        u = ub
    return make_bidirected(build_csr(n, src[:k], dst[:k], dedup=True))


def _padded_reference(g, max_degree: int):
    """Row-by-row continuation chaining (the layout `to_padded` defines):
    each row's degree is what is left of its node's adjacency from it on."""
    deg = np.diff(g.indptr)
    n_chain = np.where(deg <= max_degree, 0, -(-(deg - max_degree) // max_degree))
    total = g.n + int(n_chain.sum())
    rows = np.full((total, max_degree), -1, np.int32)
    degree = np.zeros(total, np.int32)
    cont = np.full(total, -1, np.int32)
    next_free = g.n
    for u in range(g.n):
        nb = g.neighbors(u)
        r, off = u, 0
        while True:
            take = min(max_degree, len(nb) - off)
            rows[r, :take] = nb[off : off + take]
            degree[r] = len(nb) - off
            off += take
            if off >= len(nb):
                break
            cont[r] = next_free
            r = next_free
            next_free += 1
    return rows, degree, cont


@pytest.mark.parametrize("n,m,seed", [(4800, 6, 0), (48000, 8, 3)])
def test_powerlaw_matches_reference(n, m, seed):
    got = powerlaw_graph(n=n, m=m, seed=seed)
    want = _powerlaw_reference(n, m, seed)
    np.testing.assert_array_equal(got.indptr, want.indptr)
    np.testing.assert_array_equal(got.indices, want.indices)


@pytest.mark.parametrize("max_degree", [2, 8, 32])
def test_to_padded_matches_reference(max_degree):
    g = powerlaw_graph(n=3000, m=4, seed=1)
    adj = to_padded(g, max_degree=max_degree)
    rows, degree, cont = _padded_reference(g, max_degree)
    np.testing.assert_array_equal(adj.rows, rows)
    np.testing.assert_array_equal(adj.degree, degree)
    np.testing.assert_array_equal(adj.cont, cont)


def test_to_padded_isolated_nodes_and_exact_multiples():
    """Degree 0 rows stay empty; a degree that is an exact multiple of the
    row width fills its last chain row completely."""
    g = community_graph(n=600, community_size=60, seed=2)
    for w in (4, int(np.diff(g.indptr).max())):
        adj = to_padded(g, max_degree=w)
        rows, degree, cont = _padded_reference(g, max(w, 2))
        np.testing.assert_array_equal(adj.rows, rows)
        np.testing.assert_array_equal(adj.degree, degree)
        np.testing.assert_array_equal(adj.cont, cont)
        for u in range(0, g.n, 37):
            np.testing.assert_array_equal(adj.full_neighbors(u), g.neighbors(u))


@pytest.mark.parametrize("max_hops", [None, 0, 2])
def test_bfs_levels_matches_python_bfs(max_hops):
    """The vectorized host BFS oracle against a queue-based BFS."""
    import collections

    from repro.graph.csr import bfs_levels

    g = powerlaw_graph(n=2000, m=3, seed=4)
    limit = 10**9 if max_hops is None else max_hops
    for source in (0, 17, 1999):
        dist = {source: 0}
        q = collections.deque([source])
        while q:
            u = q.popleft()
            if dist[u] >= limit:
                continue
            for v in g.neighbors(u):
                if int(v) not in dist:
                    dist[int(v)] = dist[u] + 1
                    q.append(int(v))
        levels = bfs_levels(g, source, max_hops)
        want = [sorted(v for v, d in dist.items() if d == k)
                for k in range(max(dist.values()) + 1)]
        assert [lv.tolist() for lv in levels] == want
