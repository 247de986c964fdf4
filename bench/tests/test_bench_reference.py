"""The benchmark's own graph generator and host reference, against the program.

The reference decides `correct` on the chip, so it is checked here against
the engine on tiny graphs where frontiers overflow and hub adjacencies span
continuation rows, and against the program's BFS where nothing truncates.
"""

import numpy as np
import pytest

from bench import graph as graph_lib
from bench import reference, run
from bench.generators import balls


def _graph(n, m, structure_seed, seed):
    base = graph_lib.structure({"nodes": n, "edges_per_node": m, "structure_seed": structure_seed})
    return graph_lib.relabel(base, np.random.default_rng([seed, 0]))


def test_generator_copy_gives_the_programs_graph():
    from repro.graph.generators import powerlaw_graph

    for n, m, seed in [(300, 4, 0), (5000, 8, 7)]:
        mine = graph_lib.powerlaw_edges(n, m, seed)
        theirs = powerlaw_graph(n=n, m=m, seed=seed)
        assert np.array_equal(mine.indptr, theirs.indptr)
        assert np.array_equal(mine.indices, theirs.indices)


def test_relabel_is_an_isomorphism_and_keeps_the_shape():
    base = graph_lib.powerlaw_edges(3000, 8, 0)
    g = graph_lib.relabel(base, np.random.default_rng([123, 0]))
    perm = g.perm
    assert np.array_equal(perm, np.random.default_rng([123, 0]).permutation(base.n))
    assert np.array_equal(np.sort(g.degree()), np.sort(base.degree()))
    for u in (0, 1, 17, 2999):
        assert np.array_equal(np.sort(g.neighbors(perm[u])),
                              np.sort(perm[base.neighbors(u)]))
    other = graph_lib.relabel(base, np.random.default_rng([124, 0]))
    assert not np.array_equal(other.indices, g.indices)


@pytest.mark.parametrize("size,block", [(64 * 5, 64), (1000, 64), (40, 8)])
def test_seed_order_keeps_each_rounds_queries(size, block):
    """Each round keeps its queries under any order; the same generator seed
    gives the same order, another seed another. The harness draws it from the
    traffic's query seed alone (test_bench_order.py)."""
    pool = np.random.default_rng(1).integers(0, 10**6, size)
    a = run.seed_order(pool, block, np.random.default_rng([2**31 + 5, 0]))
    b = run.seed_order(pool, block, np.random.default_rng([2**31 + 6, 0]))
    assert np.array_equal(a, run.seed_order(pool, block, np.random.default_rng([2**31 + 5, 0])))
    assert not np.array_equal(a, b)
    for lo in range(0, size, block):
        want = np.sort(pool[lo:lo + block])
        assert np.array_equal(np.sort(a[lo:lo + block]), want)
        assert np.array_equal(np.sort(b[lo:lo + block]), want)


def test_reference_equals_bfs_ball_where_nothing_truncates():
    from repro.graph.csr import CSRGraph, bfs_levels

    g = _graph(2000, 4, 1, 5)
    csr = CSRGraph(n=g.n, indptr=g.indptr, indices=g.indices)
    queries = np.arange(0, 2000, 97)
    want = [sum(lv.size for lv in bfs_levels(csr, int(q), 2)) - 1 for q in queries]
    got, _ = reference.serve_all(g, queries, hops=2, max_frontier=g.n, row_width=8)
    assert got.tolist() == want


@pytest.mark.parametrize("layout", ["dense", "packed"])
def test_reference_equals_engine_with_truncation_and_chains(layout):
    """Frontiers of 16 on a graph whose hubs span many 8-wide rows: the
    engine's answers and rows touched are the reference's, query by query
    and round by round."""
    from repro.core.router import Router, RouterConfig
    from repro.core.storage import build_storage
    from repro.core.workloads import Workload
    from repro.graph.csr import CSRGraph, to_padded
    from repro.serve.engine import EngineRunConfig, ServingEngine

    W, F, H = 8, 16, 3
    g = _graph(3000, 6, 2, 9)
    tier = build_storage(to_padded(CSRGraph(g.n, g.indptr, g.indices), max_degree=W),
                         n_shards=4, seed=0)
    depth = -(-int(g.degree().max()) // W)
    assert depth > 8  # hubs span long continuation chains
    eng = ServingEngine(tier, Router(4, RouterConfig(scheme="hash")), EngineRunConfig(
        n_processors=4, round_size=32, capacity=8, hops=H, max_frontier=F,
        cache_sets=16, cache_ways=2, chain_depth=depth, visited_layout=layout))
    rng = np.random.default_rng(0)
    state, truncated = None, False
    for _ in range(3):
        q = rng.integers(0, g.n, 32).astype(np.int32)
        res, state = eng.run(Workload("t", q, np.zeros(32, np.int8), np.full(32, -1, np.int32),
                                      np.full(32, -1, np.int32)), state=state)
        want, touched = reference.serve_all(g, q, H, F, W)
        assert res.completed.all()
        assert res.counts.tolist() == want.tolist()
        assert res.touched == int(touched.sum())
        assert 0 < res.reads <= res.touched
        truncated |= bool(res.truncated)
    assert truncated  # the defined truncation was exercised, not only whole balls


def test_node_rows_counts_continuation_rows():
    assert reference.node_rows(np.array([0, 1, 32, 33, 64, 65]), 32).tolist() == [1, 1, 1, 2, 2, 3]


def _program_ball(g, center, r, limit):
    """The ball as the program's `hotspot_workload` grows it (a set, node by
    node), sorted."""
    ball, frontier = {center}, [center]
    for _ in range(r):
        nxt = []
        for u in frontier:
            for v in g.neighbors(u):
                if v not in ball:
                    ball.add(int(v))
                    nxt.append(int(v))
            if len(ball) > limit:
                break
        frontier = nxt
        if not frontier:
            break
    return sorted(ball)


def test_hotspot_ball_is_the_programs_ball():
    g = _graph(20000, 8, 0, 11)
    deg = g.degree()
    for c in np.random.default_rng(3).integers(0, g.n, 60):
        for r, limit in ((2, 500), (3, 50), (1, 5), (2, 10**6)):
            got = balls.hotspot_ball(g, deg, int(c), r, limit)
            assert got.tolist() == _program_ball(g, int(c), r, limit)
