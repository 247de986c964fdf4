"""Batched h-hop query engine (paper Algorithm 5, TPU-native).

Algorithm 5 interleaves BFS with (a) cache probes and (b) batched storage
requests for the misses. The scalar queue/set version does not map to TPU;
this engine keeps the same semantics with fixed-shape state:

  frontier      (B, F) int32   padded -1 (F = max frontier width)
  visited       the resultSet bitmap, one row per query, in the LAYOUT
                selected by `EngineConfig.visited_layout` (see below)
  cache         CacheState     shared by the whole processor (as in paper)

Per hop (== one iteration of Algorithm 5's while loop):
  1. probe cache for all frontier rows                  (lines 6-12)
  2. multi_read the misses from storage, insert to cache (lines 17-27)
  3. follow continuation chains (bounded depth), in stages that narrow
     to the rows still live (`chain_stage_widths`)
  4. mark neighbors in `visited`; next frontier = newly visited nodes
     (the first F by node id keep shapes static; overflow beyond F is recorded
     in `truncated` -- with F sized to the h-hop ball this never triggers)

Step 4 -- the visited-bitmap update, the per-round hot loop -- sits behind
TWO composed seams (both python-static, resolved once per trace):

  REPRESENTATION (`EngineConfig.visited_layout`, `core.visited`):
  - "dense":  (B, n) bool -- the reference layout, one byte per node;
  - "packed": (B, ceil(n/32)) uint32 words, one BIT per node -- 8x less
    per-query state (the >100K-node scale path); result counts come from
    `lax.population_count`, set algebra is word-wise bitwise ops.

  EXECUTION (`EngineConfig.expand_backend`), per layout:
  - "scatter": XLA scatter reference (`.at[].max()` dense; packed scatters
    a transient dense delta and packs it into the word mask);
  - "pallas": ONE blocked compare-reduce kernel launch per hop
    (`kernels.frontier.frontier_expand_batched` for dense, grid (query,
    node-block, frontier-block); `frontier_expand_packed` for packed, grid
    (query, word-block, frontier-block) reducing straight into uint32
    words) -- scatter-free, the TPU path ("pallas-interpret" runs the
    identical kernel program via the interpreter on CPU);
  - "auto": `lax.cond` on frontier density per hop -- dense frontiers take
    the kernel, sparse ones the scatter (the packed layout refines the
    predicate with word popcounts, `dense_frontier_packed`). (Under the
    single-host engine's vmap over processors the cond's predicate is
    batched and XLA evaluates both branches then selects; inside shard_map
    the predicate is per-device and the cond stays a real branch.)

Every (layout, backend) pair must keep the engine<->simulator differential
oracle exactly green: touch sets, read volumes, and backlog evolution are
representation AND execution invariants (`tests/test_engine_parity.py`
parametrizes over both axes, `tests/test_expand_backends.py` sweeps the
backends against each other across frontier/bitmap shapes, and
`tests/test_visited_properties.py` is the layout property gate).

Three query types (paper §2.2) share the BFS core:
  - h-hop neighbor aggregation: |visited| - 1 (or label histogram)
  - h-step random walk with restart: separate light-weight walker (reads
    rows, never expands -- untouched by the backend choice)
  - h-hop reachability: bi-directional BFS, bitmap intersection
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cache as cache_lib
from repro.core.cache import CacheState
from repro.core.storage import StorageTier, multi_read_ref
# The expansion backends and visited-set layouts live in core.visited; the
# names below are re-exported here because this module is their historical
# home (PR 3 pinned the backend seam's public surface here).
from repro.core.visited import (  # noqa: F401  (re-exports)
    EXPAND_BACKENDS, VISITED_LAYOUTS, get_expand_backend, get_visited_layout,
    visited_nbytes,
)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_frontier: int = 2048  # F
    chain_depth: int = 64  # max continuation-row chasing per hop (safety cap;
    #                         the chain loop exits as soon as no row has a
    #                         continuation, so typical cost is 1-2 iterations)
    use_cache: bool = True
    # frontier-expansion backend: how step 4 (neighbors -> visited bitmap)
    # executes. One of EXPAND_BACKENDS: "scatter" (XLA scatter, the
    # reference), "pallas" (blocked compare-reduce kernel, one launch per
    # hop), "auto" (lax.cond on frontier density per hop), or the
    # "-interpret" variants that force the Pallas interpreter (CPU tests).
    # Semantics are backend-invariant; only the execution strategy changes.
    expand_backend: str = "scatter"
    # visited-set layout: how the per-query resultSet bitmap is REPRESENTED.
    # One of VISITED_LAYOUTS: "dense" ((B, n) bool, the reference) or
    # "packed" ((B, ceil(n/32)) uint32 words, 8x smaller -- the >100K-node
    # scale path). Semantics are layout-invariant (core.visited).
    visited_layout: str = "dense"
    # when the engine runs INSIDE shard_map and multi_read contains
    # collectives (all_to_all), every participant must run the same number of
    # chain iterations: the loop condition is then psum'd over these axes
    # (the single-host engine names its processor vmap axis here, so the
    # processors share one loop).
    sync_axes: Optional[Tuple[str, ...]] = None


class HopResult(NamedTuple):
    visited: jax.Array  # per-query visited set IN THE CONFIGURED LAYOUT:
    #                     (B, n) bool (dense) or (B, ceil(n/32)) uint32 (packed)
    frontier: jax.Array  # (B, F) int32
    cache: CacheState
    truncated: jax.Array  # (B,) bool -- frontier overflow happened
    reads: jax.Array  # () int32 -- unique storage rows fetched
    touched: jax.Array  # () int32 -- rows needed (hits + misses)
    probe_misses: jax.Array  # () int32 -- missed cache probes (incl. batch dups)
    chain_iters: jax.Array  # (stages,) int32 -- chain-loop iterations per stage
    chain_rows: jax.Array  # (stages,) int32 -- live row ids read per stage
    flushes: jax.Array  # () int32 -- buffered-mark flushes


def _dedup_first(ids: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Intra-batch duplicate detection for read combining.

    ids: (M,) int32. Returns (first (M,) bool -- entry is the first
    occurrence of its value; src (M,) int32 -- index of that first
    occurrence, identity for first occurrences).
    """
    M = ids.shape[0]
    if M == 0:
        return jnp.zeros((0,), bool), jnp.zeros((0,), jnp.int32)
    order = jnp.argsort(ids, stable=True)
    s = ids[order]
    is_first_s = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    head_pos_s = jax.lax.cummax(jnp.where(is_first_s, jnp.arange(M), 0))
    first_idx_s = order[head_pos_s]
    first = jnp.zeros((M,), bool).at[order].set(is_first_s)
    src = jnp.zeros((M,), jnp.int32).at[order].set(first_idx_s.astype(jnp.int32))
    return first, src


def _read_rows(
    tier_arrays,
    cache_state: CacheState,
    ids: jax.Array,
    use_cache: bool,
    multi_read: Callable,
) -> Tuple[jax.Array, jax.Array, jax.Array, CacheState, jax.Array, jax.Array, jax.Array]:
    """Cache-first row read with intra-batch read combining.

    ids: (M,) int32 (-1 padded). A row id requested more than once in the
    same batch is fetched from storage ONCE (RAMCloud's multi_read dedups
    its request set) and inserted into the cache once; later duplicates are
    served from the first fetch -- exactly the behaviour of a sequential
    engine, where the first access inserts and the rest hit. This also keeps
    duplicate keys from landing in multiple ways of one set (cache_insert
    requires deduped keys).

    Returns (rows, deg, cont, cache', n_probe_miss, n_reads, n_touch):
    n_probe_miss counts missed probes (consistent with the cache's own hit/
    miss counters); n_reads counts unique rows actually fetched from storage.
    """
    valid = ids >= 0
    n_touch = jnp.sum(valid).astype(jnp.int32)
    if not use_cache:
        # read combining is a multi_read property, not a cache one: fetch
        # unique rows only; every probe still counts as a miss (no cache).
        first, src = _dedup_first(jnp.where(valid, ids, -1))
        uniq = valid & first
        with jax.named_scope("storage_read"):
            rows, deg, cont = multi_read(jnp.where(uniq, ids, -1))
        rows, deg, cont = rows[src], deg[src], cont[src]
        n_reads = jnp.sum(uniq).astype(jnp.int32)
        return rows, deg, cont, cache_state, n_touch, n_reads, n_touch
    with jax.named_scope("cache_lookup"):
        found, c_rows, c_deg, c_cont, cache_state = cache_lib.cache_lookup(
            cache_state, ids, valid
        )
    miss = valid & ~found
    first, src = _dedup_first(jnp.where(miss, ids, -1))
    uniq = miss & first
    fetch_ids = jnp.where(uniq, ids, -1)
    with jax.named_scope("storage_read"):
        s_rows, s_deg, s_cont = multi_read(fetch_ids)
    # duplicates of a missed id read the first occurrence's fetched row
    s_rows, s_deg, s_cont = s_rows[src], s_deg[src], s_cont[src]
    with jax.named_scope("cache_insert"):
        cache_state = cache_lib.cache_insert(
            cache_state, fetch_ids, s_rows, s_deg, s_cont, valid=uniq
        )
    rows = jnp.where(found[:, None], c_rows, s_rows)
    deg = jnp.where(found, c_deg, s_deg)
    cont = jnp.where(found, c_cont, s_cont)
    n_probe_miss = jnp.sum(miss).astype(jnp.int32)
    n_reads = jnp.sum(uniq).astype(jnp.int32)
    return rows, deg, cont, cache_state, n_probe_miss, n_reads, n_touch


CHAIN_SHRINK = 8  # each continuation-chain stage is this many times narrower
CHAIN_MIN_WIDTH = 4  # rows per query of the narrowest stage


def chain_stage_widths(F: int, chain_depth: int) -> Tuple[int, ...]:
    """Rows per query that each stage of the continuation-chain loop reads.

    A hop's first read covers all F frontier slots of every query, but a
    row continues only where its node's degree exceeds the row width, so
    after a few iterations only the hubs' chains are still live -- on a
    power-law graph a handful per query, for up to ceil(max_degree / W)
    iterations. Each stage runs while some query still has more live rows
    than the next stage holds, then the live rows are compacted into it.
    At most `chain_depth` stages: a stage needs an iteration to run in."""
    widths = [F]
    while len(widths) < chain_depth:
        w = -(-widths[-1] // CHAIN_SHRINK)
        if w < CHAIN_MIN_WIDTH or w >= widths[-1]:
            break
        widths.append(w)
    return tuple(widths)


def _compact_rows(ids: jax.Array, width: int) -> jax.Array:
    """(B, w) -> (B, width): each query's live (>= 0) ids first, in their
    original order, so the cache and the read combining see the same
    sequence of requests as the wider batch (callers ensure every query
    has at most `width` live ids)."""
    order = jnp.argsort(ids < 0, axis=1, stable=True)[:, :width]
    return jnp.take_along_axis(ids, order, axis=1)


class _Marks(NamedTuple):
    """A hop's visited mask and the rows read but not yet marked in it."""

    mask: jax.Array  # visited | marks so far, in the layout's representation
    rows: jax.Array  # (B, F, W) buffered rows, -1 padded
    deg: jax.Array  # (B, F)
    fill: jax.Array  # () rows per query in the buffer
    flushes: jax.Array  # () flushes so far


class _Chain(NamedTuple):
    """Carry of the continuation-chain loop of one hop."""

    ids: jax.Array  # (B, w) row ids to read next, -1 padded
    marks: _Marks
    cache: CacheState
    reads: jax.Array
    touched: jax.Array
    probe_misses: jax.Array
    it: jax.Array  # chain iterations so far
    go: jax.Array  # some row (of the sync group) continues


def _first_set(mask: jax.Array, F: int) -> Tuple[jax.Array, jax.Array]:
    """(B, n) bool -> ((B, F) int32 positions of each row's first F set
    entries in ascending order, -1 past its last; (B,) set counts).

    The same positions as `jnp.nonzero(row, size=F, fill_value=-1)`, found
    by binary search in the row's running count: `nonzero` builds them with
    a bincount, a scatter-add of all n entries into F bins."""
    c = jnp.cumsum(mask, axis=1, dtype=jnp.int32)
    k = jnp.arange(1, F + 1, dtype=jnp.int32)
    pos = jax.vmap(lambda row: jnp.searchsorted(row, k, side="left"))(c)
    total = c[:, -1]
    return jnp.where(k[None, :] <= total[:, None], pos, -1).astype(jnp.int32), total


def expand_hop(
    tier_arrays,
    cache_state: CacheState,
    visited: jax.Array,
    frontier: jax.Array,
    cfg: EngineConfig,
    multi_read: Callable,
    n: int,
) -> HopResult:
    """One BFS hop for a batch of queries sharing one processor cache.

    `visited` is in the layout selected by `cfg.visited_layout`; the
    visited-bitmap update delegates to that layout's expansion backend
    (`cfg.expand_backend`). Both seams resolve once, python-static."""
    B, F = frontier.shape
    W = cache_state.row_width
    layout = get_visited_layout(cfg.visited_layout)
    expand_fn = layout.expander(cfg.expand_backend, n)

    def _global_any(flag: jax.Array) -> jax.Array:
        """Uniform loop decision: when multi_read contains collectives, every
        shard_map participant must agree on the trip count (and a vmapped
        caller gets one unbatched loop instead of a select per iteration)."""
        if cfg.sync_axes is not None:
            return jax.lax.psum(flag.astype(jnp.int32), cfg.sync_axes) > 0
        return flag

    def expand(rows: jax.Array, deg: jax.Array, mask: jax.Array) -> jax.Array:
        with jax.named_scope("mark"):
            return expand_fn(rows, deg, mask)

    def flush(marks: _Marks) -> _Marks:
        # mark the buffered rows' neighbors (pluggable backend). The mask
        # carries visited | this hop's marks, not a bare delta, so the
        # packed auto backend's popcount density predicate sees the TRUE
        # bitmap occupancy (already-visited bits can't yield new marks).
        return _Marks(expand(marks.rows, marks.deg, marks.mask),
                      jnp.full_like(marks.rows, -1), jnp.zeros_like(marks.deg),
                      jnp.zeros((), jnp.int32), marks.flushes + 1)

    def mark(marks: _Marks, rows: jax.Array, deg: jax.Array) -> _Marks:
        w = rows.shape[1]
        if w == F:  # a full-width read is marked at once
            return marks._replace(mask=expand(rows, deg, marks.mask))
        # a narrow read joins the buffer, which is marked when full: one
        # expansion per F rows per query instead of one per chain iteration
        # (marks are ORed in, so when they land changes no bit). `fill` is
        # the same on every processor, so the flush is one branch.
        marks = jax.lax.cond(marks.fill + w > F, flush, lambda m: m, marks)
        return marks._replace(
            rows=jax.lax.dynamic_update_slice(marks.rows, rows, (0, marks.fill, 0)),
            deg=jax.lax.dynamic_update_slice(marks.deg, deg, (0, marks.fill)),
            fill=marks.fill + w,
        )

    def chain_body(s: _Chain) -> _Chain:
        w = s.ids.shape[1]
        rows, deg, cont, cache_state, n_probe_miss, n_reads, n_touch = _read_rows(
            tier_arrays, s.cache, s.ids.reshape(-1), cfg.use_cache, multi_read
        )
        # continuation rows (hub nodes whose adjacency spans multiple rows)
        # are drained in the same hop, as in Algorithm 5's per-hop multi_read
        cont = cont.reshape(B, w)
        return _Chain(
            ids=cont,
            marks=mark(s.marks, rows.reshape(B, w, W), deg.reshape(B, w)),
            cache=cache_state,
            reads=s.reads + n_reads,
            touched=s.touched + n_touch,
            probe_misses=s.probe_misses + n_probe_miss,
            it=s.it + 1,
            go=_global_any(jnp.any(cont >= 0)),
        )

    def stage_cond(next_width):
        def cond(s: _Chain):
            run = jnp.logical_and(s.go, s.it < cfg.chain_depth)
            if next_width is None:
                return run
            live = jnp.max(jnp.sum(s.ids >= 0, axis=1))
            return jnp.logical_and(run, _global_any(live > next_width))
        return cond

    z = jnp.zeros((), jnp.int32)
    marks = _Marks(visited, jnp.full((B, F, W), -1, jnp.int32), jnp.zeros((B, F), jnp.int32),
                   z, z)
    s = _Chain(frontier, marks, cache_state, z, z, z, z, _global_any(jnp.any(frontier >= 0)))
    widths = chain_stage_widths(F, cfg.chain_depth)
    ends = []  # (iterations, rows read) so far at the end of each stage
    with jax.named_scope("chain"):
        for i, width in enumerate(widths):
            if i:
                s = s._replace(ids=_compact_rows(s.ids, width))
            nxt = widths[i + 1] if i + 1 < len(widths) else None
            s = jax.lax.while_loop(stage_cond(nxt), chain_body, s)
            ends.append((s.it, s.touched))
        marks = s.marks
        if len(widths) > 1:  # only narrow stages buffer
            marks = jax.lax.cond(marks.fill > 0, flush, lambda m: m, marks)
    chain_iters, chain_rows = (jnp.diff(jnp.stack(c), prepend=0) for c in zip(*ends))
    new_mask = marks.mask
    # this processor's chains cut off by the chain_depth cap (`s.go` may be
    # the whole sync group's)
    chain_cut = jnp.any(s.ids >= 0)

    # new_mask == visited | hop marks: the chain carry was seeded with
    # visited and every backend only ORs bits in, so it is already the
    # updated visited set -- no union pass needed in the hot loop
    # next frontier = up to F newly-visited nodes per query. Finding them
    # needs node positions, so the packed layout unpacks its DELTA here --
    # a per-hop transient XLA can fuse, not state carried across hops.
    with jax.named_scope("next_frontier"):
        newly = layout.minus(new_mask, visited)
        nxt, n_new = _first_set(layout.to_dense(newly, n), F)
    visited = new_mask
    # truncated if the frontier overflowed F, OR the continuation chain was
    # cut off by the chain_depth cap while rows still had continuations
    truncated = (n_new > F) | chain_cut
    return HopResult(visited, nxt, s.cache, truncated, s.reads, s.touched, s.probe_misses,
                     chain_iters, chain_rows, marks.flushes)


@dataclasses.dataclass
class QueryStats:
    """Per-batch execution statistics (feeds the cost model / Eq. 8 metrics).

    `misses` counts missed cache probes (consistent with the CacheState hit/
    miss counters, so duplicates within one batched probe each count);
    `reads` counts unique rows actually fetched from storage after intra-
    batch read combining -- the true storage read volume.

    `truncated_fwd`/`truncated_bwd` are only populated by `run_reachability`
    (per-direction detail of its bi-directional BFS: `truncated` is their
    OR); every other query type leaves them None.

    `chain_iters`/`chain_rows`/`flushes` are the continuation-chain loop's
    work, populated by `run_neighbor_aggregation`: iterations and live row
    ids read per hop and per stage of `chain_stage_widths` (`chain_rows`
    sums to `touched`), and flushes of the buffered narrow-stage marks.
    Each stage-0 iteration marks its full-width read at once, so the
    expansions of the visited state are stage-0 iterations plus flushes.
    """

    touched: jax.Array  # rows needed across hops (hits+misses)
    misses: jax.Array  # missed cache probes
    result_sizes: jax.Array  # (B,) |N_h(q)|
    truncated: jax.Array  # (B,) bool
    reads: jax.Array  # unique storage rows fetched
    truncated_fwd: Optional[jax.Array] = None  # (B,) bool, reachability only
    truncated_bwd: Optional[jax.Array] = None  # (B,) bool, reachability only
    chain_iters: Optional[jax.Array] = None  # (h, stages) int32
    chain_rows: Optional[jax.Array] = None  # (h, stages) int32
    flushes: Optional[jax.Array] = None  # () int32


def run_neighbor_aggregation(
    tier_arrays,
    cache_state: CacheState,
    queries: jax.Array,
    h: int,
    n: int,
    cfg: EngineConfig,
    multi_read: Callable,
    touched_map: Optional[jax.Array] = None,
):
    """h-hop Neighbor Aggregation: count nodes within h hops of each query.

    queries: (B,) int32. Returns (counts (B,), cache', stats, touched_map').
    When `touched_map` (an (n,) bool bitmap) is given, the frontier's node
    rows are accumulated into it before each hop (continuation rows >= n
    are engine-internal and not tracked) -- the cache-touch-set accounting
    the engine/simulator differential oracle compares; otherwise the fourth
    value is None.
    """
    B = queries.shape[0]
    F = cfg.max_frontier
    layout = get_visited_layout(cfg.visited_layout)
    visited, frontier, valid_q = layout.init_search(queries, n, F)

    def hop(i, carry):
        (visited, frontier, cache_state, misses, reads, touched, truncated, touched_map,
         iters, rows, flushes) = carry
        if touched_map is not None:
            ids = frontier.reshape(-1)
            ok = (ids >= 0) & (ids < n)
            touched_map = touched_map.at[jnp.where(ok, ids, 0)].max(ok)
        res = expand_hop(tier_arrays, cache_state, visited, frontier, cfg, multi_read, n)
        return (res.visited, res.frontier, res.cache, misses + res.probe_misses,
                reads + res.reads, touched + res.touched, truncated | res.truncated,
                touched_map, iters.at[i].set(res.chain_iters),
                rows.at[i].set(res.chain_rows), flushes + res.flushes)

    z = jnp.zeros((), jnp.int32)
    per_stage = jnp.zeros((h, len(chain_stage_widths(F, cfg.chain_depth))), jnp.int32)
    # one hop body in the program, looped h times (its chain stages are the
    # bulk of the program, so unrolling the hops would multiply it by h)
    (visited, _frontier, cache_state, misses, reads, touched, truncated, touched_map,
     iters, rows, flushes) = jax.lax.fori_loop(
        0, h, hop, (visited, frontier, cache_state, z, z, z, jnp.zeros((B,), bool),
                    touched_map, per_stage, per_stage, z))

    sizes = layout.count(visited)
    counts = sizes - valid_q.astype(jnp.int32)  # exclude query node
    stats = QueryStats(
        touched=touched, misses=misses, result_sizes=sizes,
        truncated=truncated, reads=reads, chain_iters=iters, chain_rows=rows,
        flushes=flushes,
    )
    return counts, cache_state, stats, touched_map


def run_random_walk(
    tier_arrays,
    cache_state: CacheState,
    queries: jax.Array,
    h: int,
    n: int,
    cfg: EngineConfig,
    multi_read: Callable,
    key: jax.Array,
    restart_prob: float = 0.15,
) -> Tuple[jax.Array, CacheState, QueryStats]:
    """h-step Random Walk with Restart. Returns final node per query."""
    B = queries.shape[0]
    cur = queries
    misses = jnp.zeros((), jnp.int32)
    reads = jnp.zeros((), jnp.int32)
    touched = jnp.zeros((), jnp.int32)
    for step in range(h):
        key, k1, k2 = jax.random.split(key, 3)
        rows, deg, cont, cache_state, n_miss, n_reads, n_touch = _read_rows(
            tier_arrays, cache_state, cur, cfg.use_cache, multi_read
        )
        misses, reads, touched = misses + n_miss, reads + n_reads, touched + n_touch
        # uniform neighbor choice over the first row (paper treats the value
        # array as the neighbor set; continuation tail neighbors are reached
        # on later steps through the chain row ids themselves)
        pick = jax.random.randint(k1, (B,), 0, jnp.maximum(deg, 1))
        nxt = rows[jnp.arange(B), pick]
        nxt = jnp.where(deg > 0, nxt, cur)  # dangling: stay
        restart = jax.random.uniform(k2, (B,)) < restart_prob
        cur = jnp.where(restart, queries, nxt)
        cur = jnp.where(queries >= 0, cur, -1)
    stats = QueryStats(
        touched=touched,
        misses=misses,
        result_sizes=jnp.ones((B,), jnp.int32) * (h + 1),
        truncated=jnp.zeros((B,), bool),
        reads=reads,
    )
    return cur, cache_state, stats


def run_reachability(
    tier_arrays,
    cache_state: CacheState,
    sources: jax.Array,
    targets: jax.Array,
    h: int,
    n: int,
    cfg: EngineConfig,
    multi_read: Callable,
) -> Tuple[jax.Array, CacheState, QueryStats]:
    """h-hop Reachability via bi-directional BFS (paper: forward from source,
    backward from target; the stored graph is bi-directed so one adjacency
    serves both directions). Returns reachable (B,) bool."""
    B = sources.shape[0]
    F = cfg.max_frontier
    layout = get_visited_layout(cfg.visited_layout)
    h_fwd = (h + 1) // 2
    h_bwd = h - h_fwd

    def bfs(starts, hops, cache_state):
        visited, frontier, _vq = layout.init_search(starts, n, F)
        m = jnp.zeros((), jnp.int32)
        r = jnp.zeros((), jnp.int32)
        t = jnp.zeros((), jnp.int32)
        tr = jnp.zeros((B,), bool)
        for _ in range(hops):
            res = expand_hop(tier_arrays, cache_state, visited, frontier, cfg, multi_read, n)
            visited, frontier, cache_state = res.visited, res.frontier, res.cache
            m, r, t, tr = (m + res.probe_misses, r + res.reads,
                           t + res.touched, tr | res.truncated)
        return visited, cache_state, m, r, t, tr

    vis_f, cache_state, m1, r1, t1, tr1 = bfs(sources, h_fwd, cache_state)
    vis_b, cache_state, m2, r2, t2, tr2 = bfs(targets, h_bwd, cache_state)
    reachable = layout.overlap_any(vis_f, vis_b)
    stats = QueryStats(
        touched=t1 + t2,
        misses=m1 + m2,
        result_sizes=layout.count(layout.union(vis_f, vis_b)),
        truncated=tr1 | tr2,
        reads=r1 + r2,
        truncated_fwd=tr1,
        truncated_bwd=tr2,
    )
    return reachable, cache_state, stats


def make_ref_multi_read(tier: StorageTier) -> Callable:
    """Bind the single-device storage reference for tests/simulator."""
    return functools.partial(multi_read_ref, tier)
