"""Algorithm 5 (batched h-hop engine): aggregation vs BFS-ball oracle,
random walks stay on edges, bi-directional reachability, cache-stat
consistency, frontier truncation flagging."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import cache as cache_lib
from repro.core.query_engine import (
    EngineConfig, make_ref_multi_read, run_neighbor_aggregation,
    run_random_walk, run_reachability,
)
from repro.core.serving import hhop_ball
from repro.core.storage import build_storage
from repro.graph.csr import to_padded
from conftest import bfs_oracle


@pytest.fixture(scope="module")
def engine(tiny_graph):
    adj = to_padded(tiny_graph, max_degree=8)  # forces continuation chains
    tier = build_storage(adj, n_shards=3)
    cache = cache_lib.make_cache(n_sets=256, n_ways=4, row_width=adj.max_degree)
    # chain_depth must cover the deepest continuation chain (hub degree /
    # row width); too-small values set the truncated flag (tested below)
    cfg = EngineConfig(max_frontier=320, chain_depth=32)
    return tiny_graph, tier, cache, cfg


@pytest.mark.parametrize("h", [1, 2, 3])
def test_neighbor_aggregation_matches_bfs(engine, h):
    g, tier, cache, cfg = engine
    queries = jnp.asarray(np.array([0, 3, 50, 123, -1], np.int32))
    counts, cache, stats, _ = run_neighbor_aggregation(
        None, cache, queries, h=h, n=g.n, cfg=cfg,
        multi_read=make_ref_multi_read(tier),
    )
    counts = np.asarray(counts)
    for i, q in enumerate(np.asarray(queries)):
        if q < 0:
            assert counts[i] == 0
            continue
        _, result_size = hhop_ball(g, int(q), h)
        assert counts[i] == result_size - 1, (q, h)
    assert not bool(np.asarray(stats.truncated)[np.asarray(queries) >= 0].any())


def test_cache_improves_second_pass(engine):
    g, tier, _, cfg = engine
    cache = cache_lib.make_cache(n_sets=512, n_ways=8, row_width=tier.row_width)
    q = jnp.asarray(np.array([7, 8, 9], np.int32))
    mr = make_ref_multi_read(tier)
    _, cache, s1, _ = run_neighbor_aggregation(None, cache, q, 2, g.n, cfg, mr)
    _, cache, s2, _ = run_neighbor_aggregation(None, cache, q, 2, g.n, cfg, mr)
    assert int(s2.misses) < int(s1.misses)
    assert int(s2.touched) == int(s1.touched)  # same work, more hits


def test_stats_consistency(engine):
    g, tier, cache, cfg = engine
    q = jnp.asarray(np.array([11, 42], np.int32))
    _, cache2, stats, _ = run_neighbor_aggregation(
        None, cache, q, 2, g.n, cfg, make_ref_multi_read(tier))
    assert int(stats.misses) <= int(stats.touched)
    # engine-reported misses equal the cache's own miss counter delta
    assert int(cache2.misses) - int(cache.misses) == int(stats.misses)


def test_no_cache_mode(engine):
    g, tier, cache, _ = engine
    cfg = EngineConfig(max_frontier=320, chain_depth=32, use_cache=False)
    q = jnp.asarray(np.array([5], np.int32))
    counts, cache2, stats, _ = run_neighbor_aggregation(
        None, cache, q, 2, g.n, cfg, make_ref_multi_read(tier))
    assert int(stats.misses) == int(stats.touched)  # everything from storage
    _, result = hhop_ball(g, 5, 2)
    assert int(counts[0]) == result - 1


def test_random_walk_stays_on_edges(engine):
    g, tier, cache, cfg = engine
    B = 16
    q = jnp.asarray(np.arange(B, dtype=np.int32))
    final, _, _ = run_random_walk(
        None, cache, q, h=4, n=g.n, cfg=cfg,
        multi_read=make_ref_multi_read(tier), key=jax.random.PRNGKey(0),
        restart_prob=0.0,
    )
    final = np.asarray(final)
    # every final node is reachable within 4 hops of its start
    for i in range(B):
        oracle = bfs_oracle(g, i, max_hops=4)
        assert int(final[i]) in oracle


def test_reachability_matches_oracle(engine):
    g, tier, cache, cfg = engine
    rng = np.random.default_rng(0)
    src = rng.integers(0, g.n, 12).astype(np.int32)
    dst = rng.integers(0, g.n, 12).astype(np.int32)
    h = 3
    reach, _, _ = run_reachability(
        None, cache, jnp.asarray(src), jnp.asarray(dst), h=h, n=g.n, cfg=cfg,
        multi_read=make_ref_multi_read(tier))
    reach = np.asarray(reach)
    for i in range(12):
        oracle = bfs_oracle(g, int(src[i]), max_hops=h)
        expect = oracle.get(int(dst[i]), 10**9) <= h
        assert bool(reach[i]) == expect, (src[i], dst[i])


def test_reachability_per_direction_truncation(engine):
    """`run_reachability` surfaces which DIRECTION of the bi-directional BFS
    truncated: `truncated_fwd`/`truncated_bwd` on QueryStats, with
    `truncated` their OR. A roomy config reports neither."""
    g, tier, cache, cfg = engine
    src = jnp.asarray(np.array([0, 5], np.int32))
    dst = jnp.asarray(np.array([9, 2], np.int32))
    _, _, stats = run_reachability(
        None, cache, src, dst, h=3, n=g.n, cfg=cfg,
        multi_read=make_ref_multi_read(tier))
    assert stats.truncated_fwd is not None and stats.truncated_bwd is not None
    np.testing.assert_array_equal(
        np.asarray(stats.truncated),
        np.asarray(stats.truncated_fwd) | np.asarray(stats.truncated_bwd))
    assert not np.asarray(stats.truncated).any()

    # F too small for a hub's one-hop ball: with h=3 the FORWARD pass runs
    # 2 hops and the backward pass 1; starting both sides on hub node 0
    # must flag both directions independently.
    tight = EngineConfig(max_frontier=4, chain_depth=32)
    hub = jnp.asarray(np.array([0], np.int32))
    _, _, tstats = run_reachability(
        None, cache, hub, hub, h=3, n=g.n, cfg=tight,
        multi_read=make_ref_multi_read(tier))
    assert bool(np.asarray(tstats.truncated_fwd)[0])
    assert bool(np.asarray(tstats.truncated_bwd)[0])
    assert bool(np.asarray(tstats.truncated)[0])


def test_query_stats_truncation_detail_default_none(engine):
    """Additive contract: non-reachability query types leave the
    per-direction detail fields at their None default."""
    g, tier, cache, cfg = engine
    q = jnp.asarray(np.array([1], np.int32))
    _, _, stats, _ = run_neighbor_aggregation(
        None, cache, q, 1, g.n, cfg, make_ref_multi_read(tier))
    assert stats.truncated_fwd is None and stats.truncated_bwd is None


def test_truncation_flagged():
    """A frontier wider than max_frontier must set the truncated flag."""
    from repro.graph.generators import erdos_renyi_graph

    g = erdos_renyi_graph(200, avg_degree=12, seed=3)
    adj = to_padded(g, max_degree=32)
    tier = build_storage(adj, n_shards=2)
    cache = cache_lib.make_cache(64, 2, adj.max_degree)
    cfg = EngineConfig(max_frontier=4, chain_depth=8)  # absurdly small F
    q = jnp.asarray(np.array([0], np.int32))
    _, _, stats, _ = run_neighbor_aggregation(
        None, cache, q, 2, g.n, cfg, make_ref_multi_read(tier))
    assert bool(np.asarray(stats.truncated)[0])


def test_chain_truncation_flagged(engine, tiny_graph):
    """A chain_depth smaller than the deepest continuation chain must set
    the truncated flag (silently losing hub neighbors is not allowed)."""
    g, tier, cache, _ = engine
    cfg = EngineConfig(max_frontier=320, chain_depth=2)
    q = jnp.asarray(np.array([0], np.int32))  # node 0 is a hub in this graph
    _, _, stats, _ = run_neighbor_aggregation(
        None, cache, q, 1, g.n, cfg, make_ref_multi_read(tier))
    assert bool(np.asarray(stats.truncated)[0])


@pytest.mark.parametrize("n,F,density", [(300, 16, 0.02), (300, 16, 0.5), (1000, 64, 0.0),
                                         (64, 64, 1.0), (513, 7, 0.1)])
def test_first_set_equals_nonzero(n, F, density):
    """The frontier extraction returns exactly `jnp.nonzero(size=F,
    fill_value=-1)` per row, and each row's set count."""
    from repro.core.query_engine import _first_set

    mask = jnp.asarray(np.random.default_rng(n + F).random((5, n)) < density)
    pos, count = _first_set(mask, F)
    want = jax.vmap(lambda m: jnp.nonzero(m, size=F, fill_value=-1)[0])(mask)
    np.testing.assert_array_equal(np.asarray(pos), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(count), np.asarray(mask.sum(axis=1)))
