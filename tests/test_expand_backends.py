"""Backend- and layout-differential oracle for the frontier-expansion seam.

`core.query_engine.expand_hop` composes two seams: the visited-set LAYOUT
(`EngineConfig.visited_layout`: `dense` (B, n) bool vs `packed`
(B, ceil(n/32)) uint32 words) and the expansion BACKEND
(`EngineConfig.expand_backend`): `scatter` (the XLA scatter reference),
`pallas` (the blocked compare-reduce kernels -- dense and packed variants
-- exercised here through the interpreter so the exact kernel programs run
on CPU), and `auto` (per-hop density cond; popcount-refined for packed).
This suite is the fast kernel-path gate: it must fail BEFORE the slow
engine<->simulator oracle does.

Three altitudes:

  1. kernels vs reference across (B, F, W, n) shapes -- padding seams
     (F % bf != 0, n % bn != 0, word-count % bw != 0, dims smaller than
     one block), all-padded (drained) frontiers, deg == 0 rows,
     out-of-range ids; the packed kernel additionally vs pack(dense ref);
  2. the full query engine (`run_neighbor_aggregation`) run under every
     (backend, layout) cell on the same workload: counts, stats, and the
     ENTIRE cache state must be bit-identical to the (scatter, dense)
     reference -- the invariance guarantee the parity oracle then
     re-checks against the simulator;
  3. trace discipline: bucketed padding (never clamping block sizes to the
     input) keeps the jit trace count flat across frontier sizes within a
     bucket, for BOTH kernel programs -- the retrace-churn regression test.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import cache as cache_lib
from repro.core import visited as visited_lib
from repro.core.query_engine import (
    EXPAND_BACKENDS, VISITED_LAYOUTS, EngineConfig, get_expand_backend,
    get_visited_layout, make_ref_multi_read, run_neighbor_aggregation,
)
from repro.core.storage import build_storage
from repro.graph.csr import to_padded
from repro.kernels import frontier as frontier_lib
from repro.kernels import ref
from repro.kernels.frontier import (
    dense_frontier, dense_frontier_packed, frontier_expand,
    frontier_expand_batched, frontier_expand_packed, pack_words, unpack_words,
)

BF, BN = 16, 128  # small blocks so tiny shapes still cross block seams
BW = BN // 32  # packed word blocks covering the same BN-bit span


def _batch_case(B, F, W, n, seed, frac_pad=0.15):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, n, (B, F, W)).astype(np.int32)
    rows[rng.random(rows.shape) < frac_pad] = -1
    deg = rng.integers(0, W + 1, (B, F)).astype(np.int32)
    visited = rng.random((B, n)) < 0.25
    return rows, deg, visited


# every case hits a distinct seam for bf=16, bn=128; n=129/255 are the
# n-%-bn edges, F=17 the frontier pad edge, B=1 the degenerate batch
BATCH_CASES = [
    (1, 16, 4, 128, "aligned"),
    (3, 17, 4, 129, "F % bf == 1, n % bn == 1"),
    (2, 16, 5, 255, "n % bn == bn - 1"),
    (4, 7, 3, 50, "tiny: F < bf, n < bn"),
    (2, 33, 8, 513, "both ragged, n not divisible by bn"),
    (5, 16, 1, 200, "W == 1"),
]


@pytest.mark.parametrize("B,F,W,n,label", BATCH_CASES)
def test_batched_kernel_vs_ref(B, F, W, n, label):
    rows, deg, visited = _batch_case(B, F, W, n, seed=B * 7919 + n)
    out = frontier_expand_batched(
        jnp.asarray(rows), jnp.asarray(deg), jnp.asarray(visited),
        bf=BF, bn=BN, interpret=True,
    )
    expect = np.stack([
        np.asarray(ref.frontier_expand_ref(
            jnp.asarray(rows[b]), jnp.asarray(deg[b]), jnp.asarray(visited[b])))
        for b in range(B)
    ])
    np.testing.assert_array_equal(np.asarray(out), expect, err_msg=label)


@pytest.mark.parametrize("B,F,W,n,label", BATCH_CASES)
def test_packed_kernel_vs_ref(B, F, W, n, label):
    """The packed kernel == pack(dense reference) across the same padding
    seams, PLUS the word seams (n % 32 != 0 -> partial trailing word)."""
    rows, deg, visited = _batch_case(B, F, W, n, seed=B * 131 + n)
    words = pack_words(jnp.asarray(visited))
    out = frontier_expand_packed(
        jnp.asarray(rows), jnp.asarray(deg), words, n,
        bf=BF, bw=BW, interpret=True,
    )
    expect = np.stack([
        np.asarray(ref.frontier_expand_ref(
            jnp.asarray(rows[b]), jnp.asarray(deg[b]), jnp.asarray(visited[b])))
        for b in range(B)
    ])
    np.testing.assert_array_equal(
        np.asarray(unpack_words(out, n)), expect, err_msg=label)
    # padding bits past n must stay zero (popcount exactness invariant)
    nw = out.shape[1]
    tail = np.asarray(unpack_words(out, nw * 32))[:, n:]
    assert not tail.any(), label


@pytest.mark.parametrize("B,F,W,n,label", BATCH_CASES)
def test_kernels_mask_remaining_degree_as_today(B, F, W, n, label):
    """Storage rows carry their node's REMAINING degree, up to several row
    widths: both kernels mark exactly the row's own first min(deg, W)
    entries, as they mark the same rows with the degree clamped to W."""
    rows, _, visited = _batch_case(B, F, W, n, seed=B * 977 + n)
    deg = np.random.default_rng(n).integers(0, 3 * W + 1, (B, F)).astype(np.int32)
    clamped = jnp.asarray(np.minimum(deg, W))
    rows, deg, visited = jnp.asarray(rows), jnp.asarray(deg), jnp.asarray(visited)
    dense = frontier_expand_batched(rows, deg, visited, bf=BF, bn=BN, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(dense),
        np.asarray(frontier_expand_batched(rows, clamped, visited, bf=BF, bn=BN,
                                           interpret=True)), err_msg=label)
    expect = np.stack([np.asarray(ref.frontier_expand_ref(rows[b], clamped[b], visited[b]))
                       for b in range(B)])
    np.testing.assert_array_equal(np.asarray(dense), expect, err_msg=label)
    packed = frontier_expand_packed(rows, deg, pack_words(visited), n, bf=BF, bw=BW,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(unpack_words(packed, n)), expect,
                                  err_msg=label)


@pytest.mark.parametrize("layout", VISITED_LAYOUTS)
@pytest.mark.parametrize("backend", ["scatter", "pallas-interpret", "auto-interpret"])
def test_expanders_mask_remaining_degree_as_today(backend, layout):
    """Every (backend, layout) expander the engine resolves marks the same
    bits for a row's remaining degree as for that degree clamped to W."""
    B, F, W, n = 3, 17, 4, 129
    rows, _, visited = _batch_case(B, F, W, n, seed=5)
    deg = np.random.default_rng(6).integers(0, 4 * W, (B, F)).astype(np.int32)
    lay = get_visited_layout(layout)
    fn = get_expand_backend(backend, n, layout)
    mask = lay.from_dense(jnp.asarray(visited))
    got = fn(jnp.asarray(rows), jnp.asarray(deg), mask)
    want = get_expand_backend("scatter", n, "dense")(
        jnp.asarray(rows), jnp.asarray(np.minimum(deg, W)), jnp.asarray(visited))
    np.testing.assert_array_equal(np.asarray(lay.to_dense(got, n)), np.asarray(want))


def test_ops_single_query_packed_wrapper():
    """`ops.frontier_expand_packed` (the public single-query entry point):
    its pallas path and its unpack/expand/repack reference path agree with
    each other and with pack(dense reference), incl. out-of-range ids >= n
    (the continuation-row sentinel the wrapper must mask to pad)."""
    from repro.kernels import ops

    rng = np.random.default_rng(11)
    F, W, n = 12, 4, 150
    rows = rng.integers(0, n + 40, (F, W)).astype(np.int32)  # some ids >= n
    rows[rng.random(rows.shape) < 0.2] = -1
    deg = rng.integers(0, W + 1, F).astype(np.int32)
    visited = rng.random(n) < 0.25
    words = pack_words(jnp.asarray(visited))

    expect = pack_words(ref.frontier_expand_ref(
        jnp.where(jnp.asarray(rows) < n, jnp.asarray(rows), -1),
        jnp.asarray(deg), jnp.asarray(visited)))
    out_k = ops.frontier_expand_packed(
        jnp.asarray(rows), jnp.asarray(deg), words, n,
        use_pallas=True, interpret=True)
    out_r = ops.frontier_expand_packed(
        jnp.asarray(rows), jnp.asarray(deg), words, n, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(expect))
    np.testing.assert_array_equal(np.asarray(out_r), np.asarray(expect))


def test_batched_kernel_all_padded_frontier():
    """A fully drained batch (all ids -1, deg 0) marks nothing -- the shape
    the engine feeds the kernel once every query's BFS has finished."""
    B, F, W, n = 3, 16, 4, 200
    rows = np.full((B, F, W), -1, np.int32)
    deg = np.zeros((B, F), np.int32)
    visited = np.random.default_rng(0).random((B, n)) < 0.5
    out = frontier_expand_batched(
        jnp.asarray(rows), jnp.asarray(deg), jnp.asarray(visited),
        bf=BF, bn=BN, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(out), visited)
    # deg == 0 must also mask stale non-(-1) row contents
    rows2 = np.full((B, F, W), 7, np.int32)
    out2 = frontier_expand_batched(
        jnp.asarray(rows2), jnp.asarray(deg), jnp.asarray(visited),
        bf=BF, bn=BN, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(out2), visited)


def test_batched_rows_isolated_per_query():
    """Query b's neighbors must only land in row b of the bitmap."""
    B, F, W, n = 4, 16, 2, 150
    rows = np.full((B, F, W), -1, np.int32)
    deg = np.zeros((B, F), np.int32)
    for b in range(B):
        rows[b, 0, 0] = 10 * b
        deg[b, 0] = 1
    out = np.asarray(frontier_expand_batched(
        jnp.asarray(rows), jnp.asarray(deg), jnp.asarray(np.zeros((B, n), bool)),
        bf=BF, bn=BN, interpret=True,
    ))
    for b in range(B):
        assert set(np.nonzero(out[b])[0].tolist()) == {10 * b}


# ---------------------------------------------------------------------------
# the seams themselves: every (backend, layout) cell produces bit-identical
# engine behaviour
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_engine(tiny_graph):
    adj = to_padded(tiny_graph, max_degree=8)  # forces continuation chains
    tier = build_storage(adj, n_shards=3)
    return tiny_graph, tier, make_ref_multi_read(tier)


def _run_backend(g, tier, mr, backend, layout="dense"):
    cache = cache_lib.make_cache(n_sets=256, n_ways=4, row_width=tier.row_width)
    cfg = EngineConfig(max_frontier=320, chain_depth=32, expand_backend=backend,
                       visited_layout=layout)
    q = jnp.asarray(np.array([0, 3, 50, 123, -1], np.int32))
    tmap = jnp.zeros((g.n,), bool)
    counts, cache, stats, tmap = run_neighbor_aggregation(
        None, cache, q, h=2, n=g.n, cfg=cfg, multi_read=mr, touched_map=tmap)
    return (np.asarray(counts), int(stats.reads), int(stats.touched),
            int(stats.misses), np.asarray(stats.truncated),
            np.asarray(tmap), cache)


@pytest.mark.parametrize("backend,layout", [
    ("pallas-interpret", "dense"),
    ("auto-interpret", "dense"),
    ("scatter", "packed"),
    ("pallas-interpret", "packed"),
    ("auto-interpret", "packed"),
])
def test_engine_backend_invariance(small_engine, backend, layout):
    """Counts, stats, touch bitmap AND the full cache state must match the
    (scatter, dense) reference exactly -- the invariance the parity oracle
    relies on, over the full backend x layout grid."""
    g, tier, mr = small_engine
    base = _run_backend(g, tier, mr, "scatter")
    got = _run_backend(g, tier, mr, backend, layout)
    np.testing.assert_array_equal(got[0], base[0])  # counts
    assert got[1:4] == base[1:4]  # reads / touched / misses
    np.testing.assert_array_equal(got[4], base[4])  # truncated
    np.testing.assert_array_equal(got[5], base[5])  # touched_map
    for name in ("tags", "age", "data", "deg", "cont", "hits", "misses"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got[6], name)), np.asarray(getattr(base[6], name)),
            err_msg=f"cache.{name} diverged under ({backend}, {layout})")


def test_serving_engine_auto_backend_matches_scatter():
    """`auto` through the FULL jit ServingEngine: under the engine's vmap
    over processors the density cond lowers to a select (both branches
    execute), which must still be bit-invariant with the scatter reference
    across rounds, caches and stats."""
    from repro.core.router import Router, RouterConfig
    from repro.core.workloads import uniform_workload
    from repro.graph.generators import community_graph
    from repro.serve.engine import EngineRunConfig, ServingEngine

    g = community_graph(n=400, community_size=40, intra_degree=5,
                        inter_degree=1.0, seed=2)
    tier = build_storage(to_padded(g, max_degree=int(g.degree().max())),
                         n_shards=2)
    wl = uniform_workload(g, n_queries=32, seed=3)
    results = {}
    for backend, layout in (("scatter", "dense"), ("auto-interpret", "dense"),
                            ("auto-interpret", "packed")):
        cfg = EngineRunConfig(
            n_processors=2, round_size=16, capacity=16, hops=2,
            max_frontier=128, cache_sets=256, cache_ways=8, chain_depth=2,
            track_touched=True, expand_backend=backend, visited_layout=layout,
        )
        router = Router(2, RouterConfig(scheme="hash"), seed=1)
        res, _ = ServingEngine(tier, router, cfg).run(wl)
        results[(backend, layout)] = res
    base = results[("scatter", "dense")]
    for key in (("auto-interpret", "dense"), ("auto-interpret", "packed")):
        got = results[key]
        np.testing.assert_array_equal(got.counts, base.counts, err_msg=str(key))
        np.testing.assert_array_equal(got.touched_bitmap, base.touched_bitmap,
                                      err_msg=str(key))
        assert (got.reads, got.touched, got.probe_misses) == (
            base.reads, base.touched, base.probe_misses), key


def test_shard_map_auto_backend_matches_scatter():
    """`auto` through the shard_map serving step (where the density cond
    stays a REAL per-device branch): counts and global stats must match the
    scatter reference."""
    import jax
    from repro.core.storage import make_serving_storage
    from repro.graph.generators import powerlaw_graph
    from repro.launch.mesh import make_auto_mesh
    from repro.serve.graph_serving import (
        GServeConfig, make_distributed_serve_step, make_processor_caches,
    )

    g = powerlaw_graph(n=300, m=4, seed=0)
    adj = to_padded(g, max_degree=8)  # forces continuation chains
    tier = build_storage(adj, n_shards=1)
    store = make_serving_storage(tier)
    mesh = make_auto_mesh((1, 1), ("data", "model"))
    queries = jnp.asarray(np.arange(8, dtype=np.int32))[None, :]
    out = {}
    cells = (("scatter", "dense"), ("auto-interpret", "dense"),
             ("pallas-interpret", "dense"), ("scatter", "packed"),
             ("pallas-interpret", "packed"))
    for backend, layout in cells:
        cfg = GServeConfig(
            n_nodes=g.n, n_rows=adj.n_rows, row_width=adj.max_degree,
            n_storage_shards=1, queries_per_proc=8, hops=2, max_frontier=128,
            cache_sets=128, cache_ways=4, read_capacity=512, chain_depth=8,
            embed_dim=4, expand_backend=backend, visited_layout=layout,
        )
        step = jax.jit(make_distributed_serve_step(mesh, cfg))
        inputs = {
            "queries": queries, "rows": store["rows"], "deg": store["deg"],
            "cont": store["cont"], "owner": store["owner"], "loc": store["loc"],
            "coords": jnp.zeros((g.n, 4), jnp.float32),
            "ema": jnp.zeros((1, 4), jnp.float32),
            "cache": make_processor_caches(mesh, cfg),
        }
        with mesh:
            counts, _, _, stats = step(inputs)
        out[(backend, layout)] = (np.asarray(counts), np.asarray(stats))
    for cell in cells[1:]:
        np.testing.assert_array_equal(out[cell][0], out[cells[0]][0],
                                      err_msg=str(cell))
        np.testing.assert_array_equal(out[cell][1], out[cells[0]][1],
                                      err_msg=str(cell))


def test_get_expand_backend_rejects_unknown():
    with pytest.raises(ValueError, match="unknown expand_backend"):
        get_expand_backend("madeup", n=100)
    with pytest.raises(ValueError, match="unknown visited_layout"):
        get_visited_layout("madeup")
    with pytest.raises(ValueError, match="unknown visited_layout"):
        get_expand_backend("scatter", n=100, layout="madeup")
    assert set(EXPAND_BACKENDS) >= {"scatter", "pallas", "auto"}
    assert set(VISITED_LAYOUTS) == {"dense", "packed"}


def test_dense_frontier_heuristic():
    # 4 queries x 8 rows x deg 8 = 256 candidates vs 4 * n / 8 thresholds
    deg = jnp.full((4, 8), 8, jnp.int32)
    assert bool(dense_frontier(deg, n=100))  # 256 * 8 >= 400
    assert not bool(dense_frontier(deg, n=100_000))
    assert not bool(dense_frontier(jnp.zeros((4, 8), jnp.int32), n=8))


def test_dense_frontier_packed_heuristic():
    """Popcount refinement: on an empty bitmap the packed predicate equals
    the dense one; as occupancy rises the unvisited budget shrinks and the
    kernel threshold is crossed earlier."""
    B, n = 4, 1000
    deg = jnp.full((B, 8), 8, jnp.int32)  # 256 candidates, 2048 weighted
    empty = jnp.zeros((B, -(-n // 32)), jnp.uint32)
    assert bool(dense_frontier_packed(deg, empty, n=100)) == bool(
        dense_frontier(deg, n=100))
    # empty bitmap: 256 * 8 = 2048 < 4000 unvisited bits -> scatter
    assert not bool(dense_frontier_packed(deg, empty, n=n))
    # ~60% occupancy: unvisited = 1600 <= 2048 -> kernel (dense still says no)
    rng = np.random.default_rng(0)
    occ = pack_words(jnp.asarray(rng.random((B, n)) < 0.6))
    assert bool(dense_frontier_packed(deg, occ, n=n))
    assert not bool(dense_frontier(deg, n=n))


def test_density_predicates_count_a_rows_own_entries():
    """With remaining degrees beyond the row width, the `auto` backend's
    choice under both density predicates is the one it makes on the degrees
    clamped to W: a row offers at most W candidates, however long its
    node's chain. The two strategies are stubbed so the output names the
    branch taken."""
    B, F, W = 4, 8, 8
    rows = jnp.zeros((B, F, W), jnp.int32)
    remaining = jnp.full((B, F), 5 * W, jnp.int32)  # hubs: 4 more rows each
    clamped = jnp.full((B, F), W, jnp.int32)
    occ = pack_words(jnp.asarray(np.random.default_rng(0).random((B, 1000)) < 0.6))
    words = {"empty": jnp.zeros_like(occ), "occupied": occ}

    def chosen(pred, deg, mask):
        auto = visited_lib._make_expander(
            "auto-interpret", 1000, lambda r, d, m, n: jnp.int32(0),
            lambda r, d, m, n, interpret: jnp.int32(1), pred)
        return ("scatter", "pallas")[int(auto(rows, deg, mask))]

    preds = {"dense": lambda d, m: dense_frontier(d, n=1000),
             "packed": lambda d, m: dense_frontier_packed(d, m, n=1000)}
    seen = set()
    for name, pred in preds.items():
        for wname, mask in words.items():
            got = chosen(pred, remaining, mask)
            assert got == chosen(pred, clamped, mask), (name, wname)
            seen.add(got)
    assert seen == {"scatter", "pallas"}  # the cases reach both branches
    # the clamp matters: summed unclamped, the remaining degrees say "dense"
    # where the rows' own 256 candidates do not
    assert bool(dense_frontier(remaining, n=1000))
    assert not bool(dense_frontier(clamped, n=1000))


# ---------------------------------------------------------------------------
# retrace churn: padding buckets frontier sizes; block sizes never clamp
# ---------------------------------------------------------------------------


def test_frontier_trace_count_flat_within_bucket():
    """Distinct frontier sizes inside one bf bucket must share ONE compiled
    trace (the old `bf = min(bf, F)` clamp recompiled per F).
    `frontier_expand` is a B=1 view over the batched kernel, so the batched
    counter is the one that must stay flat."""
    frontier_lib.TRACE_COUNTS.clear()
    n = 300
    for F in (100, 113, 120, 128):
        rows = jnp.full((F, 4), -1, jnp.int32)
        deg = jnp.zeros((F,), jnp.int32)
        frontier_expand(rows, deg, jnp.zeros((n,), bool), bf=128, bn=256,
                        interpret=True)
    assert frontier_lib.TRACE_COUNTS["frontier_expand_batched"] == 1
    # crossing the bucket edge retraces exactly once more
    rows = jnp.full((129, 4), -1, jnp.int32)
    frontier_expand(rows, jnp.zeros((129,), jnp.int32), jnp.zeros((n,), bool),
                    bf=128, bn=256, interpret=True)
    assert frontier_lib.TRACE_COUNTS["frontier_expand_batched"] == 2


def test_batched_trace_count_flat_within_bucket():
    frontier_lib.TRACE_COUNTS.clear()
    n = 300
    for F in (30, 40, 48):
        rows = jnp.full((2, F, 4), -1, jnp.int32)
        deg = jnp.zeros((2, F), jnp.int32)
        frontier_expand_batched(rows, deg, jnp.zeros((2, n), bool), bf=48,
                                bn=256, interpret=True)
    assert frontier_lib.TRACE_COUNTS["frontier_expand_batched"] == 1


def test_packed_trace_count_flat_within_bucket():
    """The packed kernel inherits the pad-up-never-clamp discipline: any
    (F, word-count) inside one (bf, bw) bucket shares a single trace."""
    frontier_lib.TRACE_COUNTS.clear()
    for F, n in ((30, 250), (40, 255), (48, 129)):  # words 8, 8, 5 -> bw 8
        rows = jnp.full((2, F, 4), -1, jnp.int32)
        deg = jnp.zeros((2, F), jnp.int32)
        vis = jnp.zeros((2, -(-n // 32)), jnp.uint32)
        frontier_expand_packed(rows, deg, vis, n, bf=48, bw=8, interpret=True)
    assert frontier_lib.TRACE_COUNTS["frontier_expand_packed"] == 1
    # crossing the word-block bucket edge retraces exactly once more
    vis = jnp.zeros((2, 9), jnp.uint32)  # 9 words > bw=8 -> second bucket
    frontier_expand_packed(jnp.full((2, 30, 4), -1, jnp.int32),
                           jnp.zeros((2, 30), jnp.int32), vis, 9 * 32,
                           bf=48, bw=8, interpret=True)
    assert frontier_lib.TRACE_COUNTS["frontier_expand_packed"] == 2


def test_frontier_expand_matches_ref_after_padding_change():
    """Semantics unchanged by the pad-up path (F far below bf)."""
    rng = np.random.default_rng(5)
    F, W, n = 9, 4, 70
    rows = rng.integers(0, n, (F, W)).astype(np.int32)
    deg = rng.integers(0, W + 1, F).astype(np.int32)
    visited = rng.random(n) < 0.3
    out = frontier_expand(jnp.asarray(rows), jnp.asarray(deg),
                          jnp.asarray(visited), bf=128, bn=512, interpret=True)
    expect = ref.frontier_expand_ref(jnp.asarray(rows), jnp.asarray(deg),
                                     jnp.asarray(visited))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


# ---------------------------------------------------------------------------
# hub continuation chains through the served engine
# ---------------------------------------------------------------------------

HUB_W, HUB_P, HUB_H, HUB_F = 4, 2, 2, 320


@pytest.fixture(scope="module")
def hub_round(tiny_graph):
    """One round of the hub's neighbours: on 2 processors some processor
    serves several of them, so the hub's chain sits in several of its
    queries' hop-1 frontiers."""
    from repro.core.workloads import Workload

    g = tiny_graph
    hub = int(np.argmax(g.degree()))
    q = g.neighbors(hub)[:7].astype(np.int32)
    wl = Workload(name="hub", query_nodes=q, query_types=np.zeros(q.size, np.int8),
                  targets=np.full(q.size, -1, np.int32),
                  hotspot_id=np.full(q.size, -1, np.int32))
    adj = to_padded(g, max_degree=HUB_W)
    return g, adj, build_storage(adj, n_shards=3), wl


def _host_hop_ball(g, adj, q, depth):
    """Host BFS that reads each frontier node's first `depth` rows (its
    whole adjacency when the chain fits): (answer, (query, row) pairs read,
    row ids read, some chain cut)."""
    W = adj.max_degree
    seen, frontier = {q}, [q]
    touched, row_ids, cut = 0, set(), False
    for _ in range(HUB_H):
        new = set()
        for v in frontier:
            n_rows = max(1, -(-int(g.degree()[v]) // W))
            k = min(n_rows, depth)
            cut |= n_rows > depth
            touched += k
            row_ids.update([v] + [int(adj.cont[v]) + j for j in range(k - 1)])
            new.update(g.neighbors(v)[:k * W].tolist())
        frontier = sorted(new - seen)[:HUB_F]
        seen |= set(frontier)
    return len(seen) - 1, touched, row_ids, cut


@pytest.mark.parametrize("layout", VISITED_LAYOUTS)
@pytest.mark.parametrize("backend", ["scatter", "pallas-interpret", "auto-interpret"])
def test_hub_chains_match_host_bfs_and_oracle(hub_round, backend, layout):
    """A hub's chain longer than chain_depth, and the same hub shared by
    several queries of one processor: answers, rows touched, storage reads
    (a cold cache far larger than the working set: every distinct row once
    per processor) and truncation equal a host BFS reading the same rows,
    for whole chains and capped ones; with whole chains the answers are the
    BFS balls and the touch sets, loads and reads those of the simulator
    oracle, plus each hub's continuation rows."""
    from repro.core.router import Router, RouterConfig
    from repro.core.serving import ServingSimulator, SimRouter, SimRouterConfig, hhop_ball
    from repro.serve.engine import EngineRunConfig, ServingEngine

    g, adj, tier, wl = hub_round
    hub = int(np.argmax(g.degree()))
    whole = int(-(-g.degree().max() // HUB_W))
    assert whole > 3  # the hub's chain is longer than the capped depth
    for depth in (whole, 3):
        cfg = EngineRunConfig(
            n_processors=HUB_P, round_size=8, capacity=8, hops=HUB_H, max_frontier=HUB_F,
            cache_sets=1024, cache_ways=8, chain_depth=depth, track_touched=True,
            expand_backend=backend, visited_layout=layout)
        res, _ = ServingEngine(tier, Router(HUB_P, RouterConfig(scheme="hash"), seed=0),
                               cfg).run(wl)
        assert res.completed.all()
        shared = np.bincount(res.assignment, minlength=HUB_P).max()
        assert shared >= 2  # the hub is in each of these queries' hop-1 frontier
        per_round = res.per_round
        for p in range(HUB_P):
            mine = wl.query_nodes[res.assignment == p]
            host = [_host_hop_ball(g, adj, int(q), depth) for q in mine]
            for q, (answer, _, _, _) in zip(mine, host):
                assert res.counts[list(wl.query_nodes).index(q)] == answer, (q, depth)
            assert res.per_proc_touched[p] == sum(h[1] for h in host), depth
            rows = set().union(*(h[2] for h in host)) if host else set()
            assert res.per_proc_reads[p] == len(rows), depth
            assert bool(per_round["truncated"][0, p]) == any(h[3] for h in host), depth
            # rows sum to touched; distinct ids never exceed the pairs read
            assert per_round["chain_rows"][0, p].sum() == res.per_proc_touched[p]
            assert (per_round["chain_unique"][0, p] <= per_round["chain_rows"][0, p]).all()
        assert res.truncated == (depth < whole)
        # the shared hub's chain is read once for its queries in hop 1
        hop1 = (per_round["chain_unique"][0, :, 1, 1], per_round["chain_rows"][0, :, 1, 1])
        assert (hop1[0] < hop1[1]).any()
        if depth < whole:
            continue
        for i, q in enumerate(wl.query_nodes):
            assert res.counts[i] == hhop_ball(g, int(q), HUB_H)[1] - 1
        sim = ServingSimulator(g, HUB_P, SimRouter(HUB_P, SimRouterConfig(scheme="hash")),
                               cache_entries=1 << 14, h=HUB_H, steal=False)
        sres = sim.run(wl, assignments=res.assignment)
        np.testing.assert_array_equal(sres.per_proc_queries, res.per_proc_queries)
        etouch = res.touch_sets()
        n_rows = np.maximum(1, -(-g.degree() // HUB_W))
        for p in range(HUB_P):
            assert etouch[p] == sres.touched_sets[p]
            assert hub in etouch[p] or not (res.assignment == p).any()
            extra = int(sum(n_rows[v] - 1 for v in etouch[p]))
            assert res.per_proc_reads[p] == sres.per_proc_misses[p] + extra
