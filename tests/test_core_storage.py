"""Decoupled storage tier: padded adjacency, placement, multi_read
(reference and sharded), bucket_by_owner properties, feature gather."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from _hypothesis_compat import given, settings, strategies as st

from repro.core.storage import (
    StorageTier, bucket_by_owner, build_storage, multi_read_ref,
    sharded_feature_gather, sharded_multi_read, stripe_rows,
)
from repro.graph.csr import to_padded


@pytest.fixture(scope="module")
def tier(tiny_graph):
    adj = to_padded(tiny_graph, max_degree=8)
    return build_storage(adj, n_shards=4), adj


def test_multi_read_ref_returns_adjacency(tier, tiny_graph):
    t, adj = tier
    ids = jnp.asarray(np.arange(0, tiny_graph.n, 7, dtype=np.int32))
    rows, deg, cont = multi_read_ref(t, ids)
    rows, deg, cont = np.asarray(rows), np.asarray(deg), np.asarray(cont)
    for i, u in enumerate(np.asarray(ids)):
        np.testing.assert_array_equal(rows[i], adj.rows[u])
        assert deg[i] == adj.degree[u]
        assert cont[i] == adj.cont[u]


def test_multi_read_ref_invalid_ids(tier):
    t, _ = tier
    rows, deg, cont = multi_read_ref(t, jnp.asarray([-1, 0], jnp.int32))
    assert int(deg[0]) == 0 and int(cont[0]) == -1
    assert (np.asarray(rows[0]) == -1).all()


def test_continuation_chains_preserve_adjacency(tiny_graph):
    """Padded layout with a tiny max_degree must spill into continuation
    rows and reconstruct the exact neighbor set."""
    adj = to_padded(tiny_graph, max_degree=3)
    g = tiny_graph
    for u in range(0, g.n, 11):
        got = np.sort(adj.full_neighbors(u))
        expect = np.sort(g.neighbors(u))
        np.testing.assert_array_equal(got, expect)


def _chain_rows(adj, u):
    """Node u's rows by the contract: its base row, then the consecutive
    continuation rows cont[u] + j, j < ceil(deg / W) - 1."""
    W, d = adj.max_degree, int(adj.degree[u])
    n_cont = -(-d // W) - 1 if adj.cont[u] >= 0 else 0
    return [u] + [int(adj.cont[u]) + j for j in range(n_cont)]


@pytest.mark.parametrize("W", [3, 8])
def test_to_padded_remaining_degree_and_consecutive_chains(tiny_graph, W):
    """A row's degree is what is left of its node's adjacency from it on
    (a base row holds the full degree), its own entries are the first
    min(degree, W), and a node's continuation rows are consecutive ids
    from its base row's cont, each pointing at the next."""
    g = tiny_graph
    adj = to_padded(g, max_degree=W)
    full = np.diff(g.indptr)
    np.testing.assert_array_equal(adj.degree[:g.n], full)
    assert (full > W).any()
    covered = np.zeros(adj.n_rows, bool)
    for u in range(g.n):
        rows = _chain_rows(adj, u)
        assert (adj.cont[u] >= 0) == (full[u] > W)
        for k, r in enumerate(rows):
            assert adj.degree[r] == full[u] - k * W
            assert adj.cont[r] == (rows[k + 1] if k + 1 < len(rows) else -1)
            covered[r] = True
        own = [adj.rows[r, :min(int(adj.degree[r]), W)] for r in rows]
        np.testing.assert_array_equal(np.concatenate(own), g.neighbors(u))
        np.testing.assert_array_equal(adj.full_neighbors(u), g.neighbors(u))
    assert covered.all()  # every row belongs to exactly one node's chain


def test_build_storage_keeps_the_chain_contract(tiny_graph):
    """The placed tier returns each row's remaining degree, and reading a
    base row names its whole chain: the ids cont + j read in order give the
    node's adjacency."""
    W = 4
    adj = to_padded(tiny_graph, max_degree=W)
    t = build_storage(adj, n_shards=3)
    np.testing.assert_array_equal(t.shard_deg[t.owner, t.loc], adj.degree)
    np.testing.assert_array_equal(t.shard_cont[t.owner, t.loc], adj.cont)
    hubs = np.argsort(-np.diff(tiny_graph.indptr), kind="stable")[:5].astype(np.int32)
    rows, deg, cont = (np.asarray(x) for x in multi_read_ref(t, jnp.asarray(hubs)))
    for i, u in enumerate(hubs):
        assert deg[i] == tiny_graph.degree()[u] > W
        ids = cont[i] + np.arange(-(-deg[i] // W) - 1, dtype=np.int32)
        c_rows, c_deg, _ = (np.asarray(x) for x in multi_read_ref(t, jnp.asarray(ids)))
        np.testing.assert_array_equal(c_deg, deg[i] - W * np.arange(1, ids.size + 1))
        got = np.concatenate([rows[i, :W]] + [c_rows[k, :min(c_deg[k], W)]
                                              for k in range(ids.size)])
        np.testing.assert_array_equal(got, tiny_graph.neighbors(u))


def test_storage_covers_all_rows(tier):
    t, adj = tier
    # every row is placed exactly once, owner/loc consistent
    seen = np.zeros(adj.n_rows, bool)
    for r in range(adj.n_rows):
        o, l = t.owner[r], t.loc[r]
        assert 0 <= o < t.n_shards and 0 <= l < t.rows_per_shard
        np.testing.assert_array_equal(t.shard_rows[o, l], adj.rows[r])
        seen[r] = True
    assert seen.all()


@settings(max_examples=30, deadline=None)
@given(
    st.lists(st.integers(-1, 63), min_size=1, max_size=64),
    st.integers(2, 5),
    st.integers(1, 16),
)
def test_bucket_by_owner_properties(ids, n_shards, capacity):
    """Property: every kept request appears at (owner, slot); slots within a
    bucket are unique and dense-from-zero in arrival order; overflow drops
    only the excess."""
    ids_a = jnp.asarray(np.array(ids, np.int32))
    owners = jnp.asarray(np.array([i % n_shards if i >= 0 else 0 for i in ids], np.int32))
    buckets, slot = bucket_by_owner(ids_a, owners, n_shards, capacity)
    buckets, slot = np.asarray(buckets), np.asarray(slot)
    per_owner_count = {}
    for i, (raw, o) in enumerate(zip(ids, np.asarray(owners))):
        if raw < 0:
            assert slot[i] == -1
            continue
        k = per_owner_count.get(int(o), 0)
        if k < capacity:
            assert slot[i] == k, (ids, i, slot[i], k)
            assert buckets[o, k] == raw
        else:
            assert slot[i] == -1  # dropped, to be retried
        per_owner_count[int(o)] = k + 1


def _mesh11():
    from repro.launch.mesh import make_auto_mesh

    return make_auto_mesh((1, 1), ("data", "model"))


def test_sharded_multi_read_single_device(tiny_graph):
    """shard_map path on a 1x1 mesh must agree with the reference."""
    adj = to_padded(tiny_graph, max_degree=8)
    t = build_storage(adj, n_shards=1)
    mesh = _mesh11()
    ids = jnp.asarray(np.array([0, 5, -1, 17, 5], np.int32))

    def body(ids, rows, deg, cont, owner, loc):
        return sharded_multi_read(ids, rows[0], deg[0], cont[0], owner, loc,
                                  axis_name="model", n_shards=1, capacity=16)

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P("model"), P("model"), P("model"), P(), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    with mesh:
        rows, deg, cont, served = jax.jit(f)(
            ids, jnp.asarray(t.shard_rows), jnp.asarray(t.shard_deg),
            jnp.asarray(t.shard_cont), jnp.asarray(t.owner), jnp.asarray(t.loc),
        )
    r_rows, r_deg, r_cont = multi_read_ref(t, ids)
    assert bool(np.asarray(served)[np.asarray(ids) >= 0].all())
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(r_rows))
    np.testing.assert_array_equal(np.asarray(deg), np.asarray(r_deg))
    np.testing.assert_array_equal(np.asarray(cont), np.asarray(r_cont))


def test_sharded_multi_read_returns_remaining_degree(tiny_graph):
    """The all_to_all read carries the same remaining degrees: base rows of
    hubs with their full degree, continuation rows with what is left."""
    W = 4
    adj = to_padded(tiny_graph, max_degree=W)
    t = build_storage(adj, n_shards=1)
    mesh = _mesh11()
    hub = int(np.argmax(np.diff(tiny_graph.indptr)))
    ids = np.array(_chain_rows(adj, hub)[:6] + [5, -1], np.int32)

    def body(ids, rows, deg, cont, owner, loc):
        return sharded_multi_read(ids, rows[0], deg[0], cont[0], owner, loc,
                                  axis_name="model", n_shards=1, capacity=16)

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P("model"), P("model"), P("model"), P(), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    with mesh:
        rows, deg, cont, served = jax.jit(f)(
            jnp.asarray(ids), jnp.asarray(t.shard_rows), jnp.asarray(t.shard_deg),
            jnp.asarray(t.shard_cont), jnp.asarray(t.owner), jnp.asarray(t.loc),
        )
    ok = ids >= 0
    assert bool(np.asarray(served)[ok].all())
    np.testing.assert_array_equal(np.asarray(deg)[ok], adj.degree[ids[ok]])
    np.testing.assert_array_equal(np.asarray(cont)[ok], adj.cont[ids[ok]])
    np.testing.assert_array_equal(np.asarray(rows)[ok], adj.rows[ids[ok]])
    assert np.asarray(deg)[0] == tiny_graph.degree()[hub] > W


def test_sharded_feature_gather_roundtrip():
    feats = np.arange(40, dtype=np.float32).reshape(10, 4)
    striped = stripe_rows(feats, 1)
    mesh = _mesh11()
    ids = jnp.asarray(np.array([3, -1, 7, 0, 3], np.int32))

    def body(ids, local):
        return sharded_feature_gather(ids, local, axis_name="model",
                                      n_shards=1, capacity=16)

    f = jax.shard_map(body, mesh=mesh, in_specs=(P(), P("model")),
                  out_specs=(P(), P()), check_vma=False)
    with mesh:
        out, served = jax.jit(f)(ids, jnp.asarray(striped))
    out = np.asarray(out)
    for i, u in enumerate(np.asarray(ids)):
        if u >= 0:
            np.testing.assert_array_equal(out[i], feats[u])
        else:
            assert (out[i] == 0).all()


def test_stripe_rows_layout():
    x = np.arange(14, dtype=np.float32).reshape(7, 2)
    s = stripe_rows(x, 3)  # 3 shards, 3 rows each (padded)
    assert s.shape == (9, 2)
    # row r lives at shard r%3, slot r//3 -> flat index (r%3)*3 + r//3
    for r in range(7):
        np.testing.assert_array_equal(s[(r % 3) * 3 + r // 3], x[r])


def test_engine_program_size_does_not_grow_with_the_graph():
    """The storage tier and the router's O(n) table enter the jitted serving
    scan as arguments: its program text is the same size for a 20x larger
    graph. A table closed over as a constant would add >= 8 bytes of text
    per node."""
    from repro.core.embedding import EmbedConfig, GraphEmbedding
    from repro.core.router import Router, RouterConfig
    from repro.graph.generators import powerlaw_graph
    from repro.serve.engine import EngineRunConfig, ServingEngine

    sizes = []
    for n in (2000, 40000):
        g = powerlaw_graph(n=n, m=4, seed=0)
        tier = build_storage(to_padded(g, max_degree=8), n_shards=2)
        emb = GraphEmbedding(coords=np.random.default_rng(0).random((n, 4), np.float32),
                             landmarks=np.arange(4), lm_coords=np.zeros((4, 4), np.float32),
                             config=EmbedConfig(dim=4))
        router = Router(2, RouterConfig(scheme="embed"), embedding=emb)
        eng = ServingEngine(tier, router, EngineRunConfig(
            n_processors=2, round_size=8, hops=2, max_frontier=16, cache_sets=16))
        state = (router.init_state(), eng.init_caches(), eng.init_touched(), eng.init_queue())
        xs = eng._round_inputs(np.zeros(8, np.int32), 0, 0, 1)
        sizes.append(len(eng.scan.lower(eng.store, router.tables, *state, xs).as_text()))
    assert sizes[1] < sizes[0] + 2000, sizes
