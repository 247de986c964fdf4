"""The engine's own measurement of its chain loop, and its layer scopes.

Per round and processor the engine returns, per hop and per stage (0: the
frontier's base rows, 1: the packed continuation rows), `chain_iters`,
`chain_rows` ((query, row) pairs read) and `chain_unique` (distinct row ids
read). A host replay of the chain loop recounts each from the graph alone:
stage 0 is one full-width read while any processor of the round has a
frontier; a processor's packed list is its queries' chains, one per node,
in row-id order, each the node's continuation rows up to the cap; the
processors step through their lists `max_frontier` ids an iteration
together, for as many iterations as the longest list needs. Every
iteration marks its read at once, so the iterations are the expansions.

The layer scopes (`jax.named_scope`) must reach the `op_name` metadata of
the compiled round, where a profiler trace's device ops find them.
"""

import re

import numpy as np
import pytest

from repro.core.router import Router, RouterConfig
from repro.core.storage import build_storage
from repro.core.workloads import Workload
from repro.graph.csr import to_padded
from repro.graph.generators import powerlaw_graph
from repro.serve.engine import EngineRunConfig, ServingEngine

P, QPP, HOPS, W, F = 4, 4, 2, 4, 256
SCOPES = ("admission", "route", "chain", "cache_lookup", "cache_insert", "storage_read",
          "mark", "next_frontier")


@pytest.fixture(scope="module")
def graph():
    g = powerlaw_graph(n=600, m=3, seed=1)
    deg = g.degree()
    # hubs (long chains, wide frontiers) and nodes drawn uniformly, 2 rounds
    hubs = np.argsort(-deg, kind="stable")[:8]
    rest = np.random.default_rng(0).integers(0, g.n, 2 * P * QPP - hubs.size)
    queries = np.random.default_rng(1).permutation(np.r_[hubs, rest]).astype(np.int32)
    return g, queries


def _serve(g, queries, chain_depth, layout="packed", backend="scatter"):
    tier = build_storage(to_padded(g, max_degree=W), n_shards=4)
    cfg = EngineRunConfig(
        n_processors=P, round_size=P * QPP, capacity=QPP, hops=HOPS, max_frontier=F,
        cache_sets=64, cache_ways=4, chain_depth=chain_depth, expand_backend=backend,
        visited_layout=layout,
    )
    eng = ServingEngine(tier, Router(P, RouterConfig(scheme="hash"), seed=0), cfg)
    k = queries.size
    wl = Workload(name="counters", query_nodes=queries, query_types=np.zeros(k, np.int8),
                  targets=np.full(k, -1, np.int32), hotspot_id=np.full(k, -1, np.int32))
    res, _ = eng.run(wl)
    assert res.completed.all()
    return res


def _replay(g, per_proc, chain_depth):
    """Host replay of one round: (iters (hops, 2), rows (P, hops, 2),
    unique (P, hops, 2)) for processors serving `per_proc[p]` queries."""
    chain = np.maximum(1, -(-g.degree() // W))  # rows per node: base + continuations
    n_cont = np.minimum(chain - 1, chain_depth - 1)  # continuation rows read per hop
    iters = np.zeros((HOPS, 2), np.int64)
    rows = np.zeros((P, HOPS, 2), np.int64)
    unique = np.zeros((P, HOPS, 2), np.int64)
    visited = [[{int(q)} for q in qs] for qs in per_proc]
    frontier = [[np.array([q], np.int64) for q in qs] for qs in per_proc]

    for hop in range(HOPS):
        iters[hop, 0] = any(fq.size for fr in frontier for fq in fr)
        lists = []  # each processor's packed list: the node owning each position
        for p in range(P):
            nodes = np.unique(np.concatenate(frontier[p] or [np.zeros(0, np.int64)]))
            rows[p, hop] = (sum(fq.size for fq in frontier[p]),
                            sum(int(n_cont[fq].sum()) for fq in frontier[p]))
            # chains in row-id order: `to_padded` allocates them in node order
            lists.append(np.repeat(nodes, n_cont[nodes]))
            unique[p, hop] = (nodes.size, lists[p].size)
        iters[hop, 1] = max(-(-lst.size // F) for lst in lists)
        # every node's chain is read up to the cap; the next frontier is the
        # first F newly reached nodes by id
        for p in range(P):
            for j, fq in enumerate(frontier[p]):
                new = set()
                for v in fq:
                    nbrs = g.indices[g.indptr[v]:g.indptr[v + 1]]
                    new.update(nbrs[:min(chain[v], chain_depth) * W].tolist())
                new -= visited[p][j]
                visited[p][j] |= new
                frontier[p][j] = np.array(sorted(new)[:F], np.int64)
    return iters, rows, unique


def _per_proc(res, queries, r):
    """The query nodes each processor served in round r."""
    pr = res.per_round
    qid, assign = pr["offered_qid"][r], pr["assignment"][r]
    return [queries[qid[(assign == p) & (qid >= 0)]] for p in range(P)]


@pytest.fixture(scope="module")
def whole_chains(graph):
    """Chains followed to their end, under (packed, scatter)."""
    g, queries = graph
    depth = int(-(-g.degree().max() // W))
    return g, queries, depth, _serve(g, queries, depth)


@pytest.fixture(scope="module", params=["whole-chains", "capped"])
def served(request, graph, whole_chains):
    if request.param == "whole-chains":
        return whole_chains
    g, queries = graph
    return g, queries, 6, _serve(g, queries, 6)


def test_chain_rows_sum_to_touched(served):
    _, _, _, res = served
    pr = res.per_round
    shape = pr["touched"].shape + (HOPS, 2)
    assert pr["chain_rows"].shape == pr["chain_iters"].shape == pr["chain_unique"].shape == shape
    np.testing.assert_array_equal(pr["chain_rows"].sum((2, 3)), pr["touched"])
    # a row read for several queries of a processor is one distinct id
    assert (pr["chain_unique"] <= pr["chain_rows"]).all()


def test_chain_counters_match_a_host_replay(served):
    """Per hop, one base read and as many packed iterations as the longest
    processor list needs at max_frontier ids an iteration; per stage,
    iterations, rows read and distinct rows read equal the host replay."""
    g, queries, depth, res = served
    pr = res.per_round
    n_cont = np.minimum(np.maximum(1, -(-g.degree() // W)) - 1, depth - 1)
    for r in range(pr["touched"].shape[0]):
        per_proc = _per_proc(res, queries, r)
        iters, rows, unique = _replay(g, per_proc, depth)
        # the processors share one loop: every processor counts its iterations
        assert (pr["chain_iters"][r] == pr["chain_iters"][r][:1]).all()
        np.testing.assert_array_equal(pr["chain_iters"][r][0], iters)
        np.testing.assert_array_equal(pr["chain_rows"][r], rows)
        np.testing.assert_array_equal(pr["chain_unique"][r], unique)
        # hop 0's frontier is the queries themselves: each processor's list
        # is its distinct queries' continuation rows
        longest = max(int(n_cont[np.unique(qs)].sum()) for qs in per_proc)
        np.testing.assert_array_equal(iters[0], [1, -(-longest // F)])
    # the hubs' chains run the packed stage in every round
    assert (pr["chain_iters"][..., 1].sum((1, 2)) > 0).all()


@pytest.mark.parametrize("layout,backend", [("dense", "scatter"), ("dense", "pallas-interpret"),
                                            ("packed", "pallas-interpret")])
def test_counters_do_not_depend_on_layout_or_backend(whole_chains, layout, backend):
    """The chain loop's work is a property of the graph and the queries:
    the same under every (layout, backend) cell as under (packed, scatter)."""
    g, queries, depth, ref = whole_chains
    res = _serve(g, queries, depth, layout=layout, backend=backend)
    for key in ("chain_iters", "chain_rows", "chain_unique", "touched"):
        np.testing.assert_array_equal(res.per_round[key], ref.per_round[key], err_msg=key)


def _scope_paths(hlo_text: str) -> set:
    """Every component of every op_name in optimized HLO text, with JAX's
    transform wrappers unwrapped: `vmap(chain)` -> `chain`."""
    parts = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo_text):
        for part in name.split("/"):
            m = re.fullmatch(r"[\w.]+\((.*)\)", part)
            parts.add(m.group(1) if m else part)
    return parts


def test_every_layer_scope_reaches_the_compiled_round():
    """The benchmark's rehearsal shape of the one-chip cell: 4 processors
    of 16 queries, 3 hops, F=2048, 32-wide rows, packed layout, scatter
    backend, on a 4096-node graph whose hubs have continuation chains."""
    import jax.numpy as jnp

    g = powerlaw_graph(n=4096, m=18, seed=0)
    tier = build_storage(to_padded(g, max_degree=32), n_shards=4)
    cfg = EngineRunConfig(
        n_processors=4, round_size=64, capacity=16, hops=3, max_frontier=2048,
        cache_sets=2048, cache_ways=4, chain_depth=int(-(-g.degree().max() // 32)),
        visited_layout="packed",
    )
    eng = ServingEngine(tier, Router(4, RouterConfig(scheme="hash"), seed=0), cfg)
    state = (eng.router.init_state(), eng.init_caches(), eng.init_touched(), eng.init_queue())
    xs = (jnp.zeros((1, 64), jnp.int32), jnp.zeros((1, 64), jnp.int32), jnp.zeros((1,), jnp.int32))
    text = eng.scan.lower(eng.store, eng.router.tables, *state, xs).compile().as_text()
    missing = set(SCOPES) - _scope_paths(text)
    assert not missing, f"scopes missing from the compiled round: {sorted(missing)}"
