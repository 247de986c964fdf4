"""Unified jit-compiled serving engine: the full gRouting loop as one scan.

`ServingEngine` pushes a whole multi-hop query workload through a single
jit-compiled `lax.scan` over serving rounds. Each round is the paper's
entire router -> processor -> storage pipeline, end to end:

  1. carry-over admission  -- queries parked in the bounded FIFO backlog
                              ring by earlier rounds are re-offered AHEAD
                              of this round's fresh arrivals (continuous
                              batching: the round buffer refills from the
                              backlog, not just the arrival stream);
  2. `Router.route_batch`  -- sequential smart routing (Algorithms 2/4),
                              padded queries masked out;
  3. `capacity_dispatch`   -- bounded per-round processor queues; overflow
                              beyond a processor's slots is HARD query
                              stealing to the next-best (least-loaded)
                              processor (paper Requirement 2). A round is
                              NOT guaranteed to drain: under overload the
                              overflow goes back to the backlog ring, and
                              when the ring itself overflows admission
                              control drops the OLDEST waiters
                              (`core.dispatch.backlog_admit`);
  4. `processor_round`     -- vmapped over processors: each expands its
                              queries' h-hop balls via `expand_hop`, i.e.
                              set-associative `cache_lookup`/`cache_insert`
                              with batched storage `multi_read` for misses.
                              The visited bitmap inside `expand_hop` sits
                              behind two composed seams: its REPRESENTATION
                              (`EngineRunConfig.visited_layout`: "dense"
                              (B, n) bool vs "packed" (B, ceil(n/32))
                              uint32 words, 8x smaller per-query state) and
                              its update EXECUTION
                              (`EngineRunConfig.expand_backend`): "scatter"
                              (XLA reference), "pallas" (one blocked
                              compare-reduce kernel launch per hop), or
                              "auto" (`lax.cond` on frontier density).
                              Layouts and backends are semantically
                              interchangeable -- the parity oracle runs
                              over the full grid;
  5. ack                   -- router load decremented by routed counts;
                              per-round QueryStats (hit rate, storage
                              reads, backlog depth, drops, latency-in-
                              rounds) accumulate in-carry.

Because a query may complete rounds after it arrived (or never, if it is
dropped), per-query outcomes are reported through explicit masks on
`EngineResult`: `completed` (query finished; `counts[q]` is trustworthy),
`dropped` (admission control evicted it), `completion_round` / `wait_rounds`
(latency in rounds). `counts` keeps -1 for queries that never completed --
ALWAYS consult `completed` before aggregating.

`processor_round` IS the serving step: the distributed path
(`repro.serve.graph_serving`) wraps the very same function in `shard_map`
with `sharded_multi_read` over the storage axis, so the single-host engine
and the mesh path cannot drift apart (its admission driver reuses
`admission_dispatch` below). `tests/test_engine_parity.py` additionally
replays identical workloads through this engine and the event-driven
`ServingSimulator` (plain-LRU OrderedDict caches, scalar BFS, and a
numpy mirror of the same round/backlog semantics in `run_rounds`) and
asserts matching cache-touch sets, per-processor loads, storage read
volumes, per-round backlog depths, completion rounds, and drop sets --
the differential oracle for every later scaling PR.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import cache as cache_lib
from repro.core.cache import CacheState
from repro.core.dispatch import (
    BacklogState, DispatchResult, backlog_admit, backlog_offer,
    capacity_dispatch, gather_by_dispatch, make_backlog, scatter_back,
)
from repro.core.query_engine import (
    EngineConfig, QueryStats, run_neighbor_aggregation,
)
from repro.core.router import Router, RouterState
from repro.core.storage import (
    StorageArrays, StorageTier, device_storage, multi_read_arrays,
    sharded_multi_read,
)
from repro.core.workloads import Workload

PROC_AXIS = "procs"  # vmap axis of the single-host engine's processors

# ---------------------------------------------------------------------------
# The per-processor serving step (shared: ServingEngine vmap + shard_map path)
# ---------------------------------------------------------------------------


def processor_round(
    cache: CacheState,
    queries: jax.Array,
    *,
    h: int,
    n: int,
    ecfg: EngineConfig,
    multi_read: Callable,
    touched_map: Optional[jax.Array] = None,
) -> Tuple[jax.Array, CacheState, QueryStats, Optional[jax.Array]]:
    """One processor serves its dispatched query batch (h-hop aggregation).

    queries: (B,) int32, -1 padded. touched_map: optional (n,) bool bitmap
    of node rows this processor has ever read (for the differential oracle).
    Returns (counts (B,), cache', stats, touched_map').

    This is a naming shim over `run_neighbor_aggregation` -- the ONE
    implementation of the per-processor serving step, shared by the
    single-host engine (vmapped) and the shard_map device path.
    """
    return run_neighbor_aggregation(
        None, cache, queries, h=h, n=n, cfg=ecfg, multi_read=multi_read,
        touched_map=touched_map,
    )


def ema_round_update(
    ema: jax.Array, me: jax.Array, coords: jax.Array, queries: jax.Array, alpha: float
) -> jax.Array:
    """Eq. 5 applied once per round over the executed batch's mean coords.

    Returns processor `me`'s new EMA row; the caller merges it into the
    replicated (P, D) table (psum-delta on the mesh path)."""
    qc = coords[jnp.maximum(queries, 0)]
    okq = (queries >= 0)[:, None]
    mean_new = jnp.sum(jnp.where(okq, qc, 0.0), 0) / jnp.maximum(okq.sum(), 1)
    return alpha * ema[me] + (1.0 - alpha) * mean_new


def make_retrying_multi_read(
    local_rows: jax.Array,
    local_deg: jax.Array,
    local_cont: jax.Array,
    owner_lut: jax.Array,
    loc_lut: jax.Array,
    *,
    axis_name: str,
    n_shards: int,
    capacity: int,
    row_width: int,
    retries: int,
) -> Callable:
    """Bounded-retry sharded multi_read (call INSIDE shard_map).

    Requests dropped by the per-(proc, shard) capacity are re-issued; all
    participants run the same fixed round count, keeping the all_to_all
    uniform. This is the router-level retry the RAMCloud client does on RPC
    overflow."""

    def multi_read(ids: jax.Array):
        out_rows = jnp.full(ids.shape + (row_width,), -1, jnp.int32)
        out_deg = jnp.zeros(ids.shape, jnp.int32)
        out_cont = jnp.full(ids.shape, -1, jnp.int32)
        pending = ids
        # a batch no larger than the budget always fits in one round, so a
        # narrow batch (a late continuation-chain stage) ships a narrow
        # all_to_all once instead of `retries` full-capacity ones
        cap = min(capacity, ids.shape[0])
        for _ in range(retries if cap < ids.shape[0] else 1):
            r, d, c, served = sharded_multi_read(
                pending, local_rows, local_deg, local_cont, owner_lut, loc_lut,
                axis_name=axis_name, n_shards=n_shards, capacity=cap,
            )
            out_rows = jnp.where(served[:, None], r, out_rows)
            out_deg = jnp.where(served, d, out_deg)
            out_cont = jnp.where(served, c, out_cont)
            pending = jnp.where(served, -1, pending)
        return out_rows, out_deg, out_cont

    return multi_read


# ---------------------------------------------------------------------------
# Admission: backlog re-offer -> route -> bounded dispatch -> drop-oldest.
# Shared by the engine scan body and the shard_map admission driver
# (repro.serve.graph_serving.make_admission_round).
# ---------------------------------------------------------------------------


class AdmissionRound(NamedTuple):
    """Everything one admission round decides (all fixed-shape)."""

    rstate: "RouterState"  # router state after route + ack
    backlog: BacklogState  # ring after re-queue / drop-oldest
    offered_node: jax.Array  # (M,) int32: backlog-first, then fresh; -1 pad
    offered_qid: jax.Array  # (M,) int32 global query ids, -1 pad
    r_assign: jax.Array  # (M,) router's pick per offered query
    dispatch: DispatchResult  # assignment/position/counts over the offer
    placed: jax.Array  # (M,) bool: valid AND dispatched this round
    dropped: jax.Array  # (M,) bool: evicted by admission control
    depth: jax.Array  # () int32 backlog depth after the round
    n_dropped: jax.Array  # () int32 drops this round
    stolen: jax.Array  # () int32 placed on != router pick
    unplaced: jax.Array  # () int32 valid but not placed this round


def admission_dispatch(
    router: Router,
    rstate: RouterState,
    backlog: BacklogState,
    fresh_node: jax.Array,
    fresh_qid: jax.Array,
    *,
    tables: dict,
    capacity: int,
    dispatch_rounds: int,
) -> AdmissionRound:
    """One admission round over `backlog ++ fresh` (backlog offered first).

    `tables` are the router's O(n) tables as traced arguments of the
    caller's program (`Router.tables` passed in, never closed over).

    Scoring: the router's pick costs 0, every other processor 1 + its
    current load (so overflow flows to the idlest -- hard stealing). Padded
    entries get all-inf rows and stay unassigned. Valid-but-unplaced
    queries are re-queued FIFO; if the ring overflows, the oldest waiters
    are dropped. The ack decrements the ROUTER-chosen processor for every
    valid offered query -- that is where route_batch incremented load -- so
    neither stolen, re-queued, nor dropped queries leak load. (Re-queued
    queries are re-routed, and re-acked, in every later round they are
    offered: the router always scores them against current load/EMA.)
    """
    P = router.P
    with jax.named_scope("admission"):
        off_node, off_qid = backlog_offer(backlog, fresh_node, fresh_qid)
        valid = off_node >= 0
        with jax.named_scope("route"):
            rstate, r_assign = router.route_batch(rstate, off_node, tables=tables)
        onehot = jnp.arange(P)[None, :] == r_assign[:, None]
        load_term = rstate.load[None, :] / float(router.config.load_factor)
        scores = jnp.where(onehot, 0.0, 1.0 + load_term)
        scores = jnp.where(valid[:, None], scores, jnp.inf)
        d = capacity_dispatch(scores, capacity=capacity, n_rounds=dispatch_rounds)
        placed = valid & (d.assignment >= 0)
        routed = jnp.bincount(
            jnp.where(valid, r_assign, P), length=P + 1
        )[:P].astype(jnp.float32)
        rstate = dataclasses.replace(rstate, load=rstate.load - routed)
        leftover = valid & ~placed
        backlog, dropped, depth, n_dropped = backlog_admit(
            off_node, off_qid, leftover, backlog.capacity
        )
        return AdmissionRound(
            rstate=rstate,
            backlog=backlog,
            offered_node=off_node,
            offered_qid=off_qid,
            r_assign=r_assign,
            dispatch=d,
            placed=placed,
            dropped=dropped,
            depth=depth,
            n_dropped=n_dropped,
            stolen=jnp.sum(placed & (d.assignment != r_assign)).astype(jnp.int32),
            unplaced=jnp.sum(leftover).astype(jnp.int32),
        )


# ---------------------------------------------------------------------------
# The end-to-end engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EngineRunConfig:
    n_processors: int
    round_size: int = 32  # B: fresh arrivals admitted per serving round
    capacity: int = 0  # C: per-processor slots per round (0 -> round_size)
    hops: int = 2
    max_frontier: int = 256
    cache_sets: int = 512
    cache_ways: int = 4
    chain_depth: int = 8
    steal_rounds: int = 0  # dispatch passes (0 -> n_processors)
    use_cache: bool = True
    # frontier-expansion backend threaded into every processor_round (see
    # repro.core.query_engine.EXPAND_BACKENDS): "scatter" | "pallas" |
    # "auto" (+ "-interpret" variants forcing the Pallas interpreter).
    expand_backend: str = "scatter"
    # visited-set layout threaded into every processor_round (see
    # repro.core.visited.VISITED_LAYOUTS): "dense" ((B, n) bool reference)
    # | "packed" ((B, ceil(n/32)) uint32 words, 8x smaller per-query BFS
    # state -- the >100K-node scale path). Layout-invariant semantics.
    visited_layout: str = "dense"
    # K: carry-over admission queue slots. Queries `capacity_dispatch` cannot
    # place are parked here and re-offered ahead of fresh arrivals; overflow
    # beyond K drops the OLDEST waiters. 0 = no carry-over: overflow is
    # dropped immediately (the pre-backlog behaviour).
    backlog_capacity: int = 0
    # carry per-processor touch bitmaps (n bools each) for differential
    # oracles; opt-in -- it costs O(P * n) scan-carry memory
    track_touched: bool = False

    @property
    def slot_capacity(self) -> int:
        return self.capacity if self.capacity > 0 else self.round_size

    @property
    def dispatch_rounds(self) -> int:
        return self.steal_rounds if self.steal_rounds > 0 else self.n_processors


@dataclasses.dataclass
class EngineResult:
    """Host-side summary of one ServingEngine.run (all numpy).

    Under carry-over admission a query may complete rounds after it arrived,
    or never (dropped by admission control, or still backlogged when
    draining was disabled). The EXPLICIT masks are the contract:
    `completed[q]` gates every per-query field -- `counts`, `assignment`,
    `router_assignment`, `completion_round` and `wait_rounds` hold -1 where
    it is False. Never infer completion from `counts == -1` alone.
    """

    scheme: str
    n_queries: int
    counts: np.ndarray  # (Q,) per-query |N_h(q)| - 1; -1 where not completed
    completed: np.ndarray  # (Q,) bool -- query was placed and executed
    dropped: np.ndarray  # (Q,) bool -- evicted by drop-oldest admission
    completion_round: np.ndarray  # (Q,) int32 round the query executed; -1
    wait_rounds: np.ndarray  # (Q,) int32 completion - arrival round; -1
    assignment: np.ndarray  # (Q,) executed processor per query (post-steal)
    router_assignment: np.ndarray  # (Q,) router's pick in the executing round
    per_proc_queries: np.ndarray  # (P,)
    per_proc_touched: np.ndarray  # (P,)
    per_proc_reads: np.ndarray  # (P,) unique storage rows fetched
    touched: int
    reads: int
    probe_misses: int
    stolen: int
    unplaced: int  # valid queries never executed (= dropped + left in ring)
    n_dropped: int  # admission-control drops
    final_backlog: int  # ring depth at return (0 when drain=True)
    peak_backlog: int  # max per-round ring depth
    mean_wait_rounds: float  # mean latency-in-rounds over completed queries
    truncated: bool
    hit_rate: float  # (touched - reads) / touched, the sequential-equivalent rate
    load_imbalance: float  # max/mean of per_proc_queries
    wall_s: float
    throughput_qps: float  # COMPLETED queries per second (sustained rate)
    touched_bitmap: Optional[np.ndarray]  # (P, n) bool rows this proc read
    per_round: dict  # per-round arrays: touched, reads, stolen, per_proc,
    #                  backlog_depth, n_dropped, offered_qid, placed, ...

    def touch_sets(self):
        assert self.touched_bitmap is not None, "run with track_touched=True"
        return [set(np.nonzero(row)[0].tolist()) for row in self.touched_bitmap]

    def drop_set(self) -> set:
        return set(np.nonzero(self.dropped)[0].tolist())

    def row(self) -> str:
        return (
            f"{self.scheme:>10s}  qps={self.throughput_qps:9.1f}  "
            f"hit={self.hit_rate:6.3f}  reads={self.reads}  "
            f"imb={self.load_imbalance:5.2f}  stolen={self.stolen}  "
            f"dropped={self.n_dropped}  peak_bl={self.peak_backlog}"
        )


class QueueCarry(NamedTuple):
    """Admission-queue slice of the scan carry: the backlog ring plus
    cumulative backlog/latency counters accumulated inside the jit scan.
    The counters are the authoritative source for `EngineResult.n_dropped`
    and `mean_wait_rounds`; `run()` additionally re-derives both from the
    per-round offer logs and asserts agreement -- a standing self-check
    that the host-side per-query reconstruction matches what the scan
    actually did. Counters are lifetime totals (they keep growing across
    warm-state reuse); `run()` reports per-run deltas."""

    backlog: BacklogState
    completed: jax.Array  # () int32 queries executed so far
    dropped: jax.Array  # () int32 admission-control drops so far
    wait_sum: jax.Array  # () int32 sum of completed queries' wait rounds
    peak_depth: jax.Array  # () int32 max backlog depth seen


class ServingEngine:
    """Single-host end-to-end engine over decoupled storage.

    Storage is read with the single-device `multi_read_arrays` (identical
    dataflow to the sharded all_to_all path; see repro.core.storage). The
    storage tables and the router's O(n) tables are placed on the device
    once and enter the jitted scan as arguments: `scan(store, tables,
    rstate, caches, tmap, qc, xs)` closes over nothing that grows with the
    graph.

    A round need NOT fit the arrival batch (capacity * P may be smaller
    than round_size): overflow carries over through the backlog ring when
    `backlog_capacity > 0`, and is dropped otherwise.
    """

    def __init__(
        self,
        storage: StorageTier | StorageArrays,
        router: Router,
        cfg: EngineRunConfig,
    ):
        assert router.P == cfg.n_processors, (router.P, cfg.n_processors)
        if isinstance(storage, StorageTier):
            storage = device_storage(storage)
        self.store = storage
        self.router = router
        self.cfg = cfg
        self.n = storage.n
        self._ecfg = EngineConfig(
            max_frontier=cfg.max_frontier,
            chain_depth=cfg.chain_depth,
            use_cache=cfg.use_cache,
            expand_backend=cfg.expand_backend,
            visited_layout=cfg.visited_layout,
            # the vmapped processors step through each continuation chain
            # together: one loop for all, not a per-processor select over
            # the whole carry (visited state included) every iteration
            sync_axes=(PROC_AXIS,),
        )
        self.scan = jax.jit(self._run_scan)

    # -- state ---------------------------------------------------------------

    def init_caches(self) -> CacheState:
        """Stacked per-processor caches: every leaf gains a leading (P,) axis."""
        one = cache_lib.make_cache(
            self.cfg.cache_sets, self.cfg.cache_ways, self.store.row_width
        )
        P = self.cfg.n_processors
        return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (P,) + x.shape), one)

    def init_touched(self) -> Optional[jax.Array]:
        if not self.cfg.track_touched:
            return None
        return jnp.zeros((self.cfg.n_processors, self.n), dtype=bool)

    def init_queue(self) -> QueueCarry:
        z = jnp.zeros((), jnp.int32)
        return QueueCarry(
            backlog=make_backlog(self.cfg.backlog_capacity),
            completed=z, dropped=z, wait_sum=z, peak_depth=z,
        )

    # -- jit body ------------------------------------------------------------

    def _proc_round(self, store, cache, queries, touched_map):
        counts, cache, stats, touched_map = processor_round(
            cache,
            queries,
            h=self.cfg.hops,
            n=self.n,
            ecfg=self._ecfg,
            multi_read=functools.partial(multi_read_arrays, store),
            touched_map=touched_map,
        )
        scalars = (
            stats.touched,
            stats.reads,
            stats.misses,
            jnp.any(stats.truncated),
            stats.chain_iters,
            stats.chain_rows,
            stats.chain_unique,
        )
        return counts, cache, scalars, touched_map

    def _round_body(self, store, tables, carry, xs):
        cfg = self.cfg
        P, C, B = cfg.n_processors, cfg.slot_capacity, cfg.round_size
        rstate, caches, tmap, qc = carry
        fresh_node, fresh_qid, round_idx = xs

        # 1+2. carry-over admission: backlog re-offered ahead of the fresh
        #      arrivals, routed, dispatched (hard stealing), leftovers
        #      re-queued with drop-oldest admission control.
        adm = admission_dispatch(
            self.router, rstate, qc.backlog, fresh_node, fresh_qid,
            tables=tables, capacity=C, dispatch_rounds=cfg.dispatch_rounds,
        )
        rstate, d = adm.rstate, adm.dispatch
        with jax.named_scope("admission"):
            qbuf = gather_by_dispatch(adm.offered_node, d, P, C, fill_value=-1)

        # 3. every processor serves its slice (vmapped shared step; a None
        #    touch bitmap is an empty pytree and passes through vmap freely)
        counts_b, caches, scal, tmap = jax.vmap(
            functools.partial(self._proc_round, store), axis_name=PROC_AXIS,
        )(caches, qbuf, tmap)
        touched_p, reads_p, probe_p, trunc_p, iters_p, rows_p, unique_p = scal
        with jax.named_scope("admission"):
            counts = scatter_back(counts_b, d, adm.offered_node.shape[0])
        # unplaced (and padded) queries must not masquerade as |N_h(q)|-1 == 0
        counts = jnp.where(adm.placed, counts, -1)

        # 4. latency-in-rounds: arrival round is qid // B by construction
        waited = jnp.where(adm.placed, round_idx - adm.offered_qid // B, 0)
        qc = QueueCarry(
            backlog=adm.backlog,
            completed=qc.completed + jnp.sum(adm.placed).astype(jnp.int32),
            dropped=qc.dropped + adm.n_dropped,
            wait_sum=qc.wait_sum + jnp.sum(waited).astype(jnp.int32),
            peak_depth=jnp.maximum(qc.peak_depth, adm.depth),
        )

        ys = {
            "offered_qid": adm.offered_qid,
            "counts": counts,
            "assignment": jnp.where(adm.placed, d.assignment, -1),
            "router_assignment": adm.r_assign,
            "placed": adm.placed,
            "dropped": adm.dropped,
            "per_proc": d.counts,  # executed per processor (post-steal)
            "touched": touched_p,
            "reads": reads_p,
            "probe_misses": probe_p,
            "truncated": trunc_p,
            "chain_iters": iters_p,  # (P, hops, stages)
            "chain_rows": rows_p,  # (P, hops, stages)
            "chain_unique": unique_p,  # (P, hops, stages)
            "stolen": adm.stolen,
            "unplaced": adm.unplaced,
            "backlog_depth": adm.depth,
            "n_dropped": adm.n_dropped,
        }
        return (rstate, caches, tmap, qc), ys

    def _run_scan(self, store, tables, rstate, caches, tmap, qc, xs):
        body = functools.partial(self._round_body, store, tables)
        return jax.lax.scan(body, (rstate, caches, tmap, qc), xs)

    # -- host entry ----------------------------------------------------------

    def _round_inputs(self, nodes: np.ndarray, qid0: int, r0: int, n_rounds: int):
        """xs pytree for `n_rounds` scan rounds starting at round r0."""
        B = self.cfg.round_size
        qids = qid0 + np.arange(n_rounds * B, dtype=np.int32)
        return (
            jnp.asarray(nodes.reshape(n_rounds, B)),
            jnp.asarray(qids.reshape(n_rounds, B)),
            jnp.asarray(r0 + np.arange(n_rounds, dtype=np.int32)),
        )

    def run(
        self, wl: Workload, state=None, drain: bool = True
    ) -> Tuple[EngineResult, tuple]:
        """Serve a workload; returns (result, final (rstate, caches, tmap, qc)).

        Pass the returned state back in to serve a follow-up burst against
        warm caches (the paper's repeated-burst experiments). With
        `drain=True` (default) the engine appends arrival-free rounds until
        the backlog ring is empty, so every admitted query either completes
        or is dropped and the returned state's ring is empty -- required
        before reusing the state on a new workload, because backlog entries
        hold query ids relative to THIS run.
        """
        cfg = self.cfg
        P, C, K = cfg.n_processors, cfg.slot_capacity, cfg.backlog_capacity
        Q = int(wl.query_nodes.size)
        B = cfg.round_size
        R = -(-Q // B)
        padded = np.full(R * B, -1, np.int32)
        padded[:Q] = wl.query_nodes

        if state is None:
            state = (self.router.init_state(), self.init_caches(),
                     self.init_touched(), self.init_queue())
        elif len(state) == 3:  # pre-backlog state tuples still accepted
            state = (*state, self.init_queue())
        q0 = state[3]  # counter baseline: carry totals are lifetime values
        assert int(np.asarray(q0.backlog.depth())) == 0, (
            "reused state carries an undrained backlog: its query ids refer "
            "to the PREVIOUS workload; finish it with drain=True first"
        )

        # host spans, on the profiler's clock when it traces: input copies,
        # dispatch of the scan, the wait for the device, the fetch of the
        # per-round outputs, and the per-query reconstruction
        t0 = time.perf_counter()
        fixed = (self.store, self.router.tables)
        with TraceAnnotation("engine.inputs"):
            xs = self._round_inputs(padded, 0, 0, R)
        with TraceAnnotation("engine.scan"):
            carry, ys = self.scan(*fixed, *state, xs)
            ys_chunks = [ys]
            n_rounds = R
            if drain and K > 0:
                # drain in fixed-size chunks (one extra compile, reused across
                # chunks); every round with a non-empty ring places >= 1 query,
                # so <= K extra rounds suffice.
                D = max(1, -(-K // max(1, P * C)))
                empty = np.full(D * B, -1, np.int32)
                for _ in range(K + 1):
                    depth = int(np.asarray(carry[3].backlog.depth()))
                    if depth == 0:
                        break
                    with TraceAnnotation("engine.inputs"):
                        xs = self._round_inputs(empty, R * B, n_rounds, D)
                    carry, ys = self.scan(*fixed, *carry, xs)
                    ys_chunks.append(ys)
                    n_rounds += D
                assert int(np.asarray(carry[3].backlog.depth())) == 0, (
                    "backlog failed to drain"
                )
        with TraceAnnotation("engine.wait"):
            jax.block_until_ready(ys_chunks[-1]["counts"])
        wall = time.perf_counter() - t0
        with TraceAnnotation("engine.fetch"):
            ys = {
                k: np.concatenate([np.asarray(c[k]) for c in ys_chunks], axis=0)
                for k in ys_chunks[0]
            }
        with TraceAnnotation("engine.outcomes"):
            return self._outcomes(ys, carry, q0, Q, n_rounds, wall)

    def _outcomes(self, ys: dict, carry: tuple, q0: QueueCarry, Q: int, n_rounds: int,
                  wall: float) -> Tuple[EngineResult, tuple]:
        """Per-query outcomes from the per-round offer logs, checked against
        the scan's own counters (`QueueCarry`)."""
        B = self.cfg.round_size
        counts = np.full(Q, -1, np.int32)
        assign = np.full(Q, -1, np.int32)
        r_assign = np.full(Q, -1, np.int32)
        completion_round = np.full(Q, -1, np.int32)
        wait_rounds = np.full(Q, -1, np.int32)
        completed = np.zeros(Q, bool)
        dropped = np.zeros(Q, bool)
        qid_f = ys["offered_qid"].reshape(-1)
        round_f = np.repeat(np.arange(n_rounds, dtype=np.int32),
                            ys["offered_qid"].shape[1])
        placed_f = ys["placed"].reshape(-1) & (qid_f >= 0) & (qid_f < Q)
        idx = qid_f[placed_f]
        assert idx.size == np.unique(idx).size, "query executed twice"
        counts[idx] = ys["counts"].reshape(-1)[placed_f]
        assign[idx] = ys["assignment"].reshape(-1)[placed_f]
        r_assign[idx] = ys["router_assignment"].reshape(-1)[placed_f]
        completion_round[idx] = round_f[placed_f]
        wait_rounds[idx] = round_f[placed_f] - idx // B
        completed[idx] = True
        dropped_f = ys["dropped"].reshape(-1) & (qid_f >= 0) & (qid_f < Q)
        dropped[qid_f[dropped_f]] = True

        per_proc = ys["per_proc"].sum(0)
        touched_p = ys["touched"].sum(0)
        reads_p = ys["reads"].sum(0)
        touched = int(touched_p.sum())
        reads = int(reads_p.sum())
        n_completed = int(completed.sum())
        tmap = carry[2]

        # in-carry accumulators (this run's deltas) are the authoritative
        # stats; the offer-log reconstruction above must agree with them.
        qf = carry[3]
        carry_completed = int(np.asarray(qf.completed) - np.asarray(q0.completed))
        carry_dropped = int(np.asarray(qf.dropped) - np.asarray(q0.dropped))
        carry_wait = int(np.asarray(qf.wait_sum) - np.asarray(q0.wait_sum))
        assert carry_completed == n_completed, (carry_completed, n_completed)
        assert carry_dropped == int(dropped.sum()), (carry_dropped, dropped.sum())
        assert carry_wait == int(wait_rounds[completed].sum())
        peak_backlog = int(ys["backlog_depth"].max(initial=0))
        # lifetime peak can only exceed this run's peak under warm reuse
        assert int(np.asarray(qf.peak_depth)) >= peak_backlog
        result = EngineResult(
            scheme=self.router.scheme,
            n_queries=Q,
            counts=counts,
            completed=completed,
            dropped=dropped,
            completion_round=completion_round,
            wait_rounds=wait_rounds,
            assignment=assign,
            router_assignment=r_assign,
            per_proc_queries=per_proc,
            per_proc_touched=touched_p,
            per_proc_reads=reads_p,
            touched=touched,
            reads=reads,
            probe_misses=int(ys["probe_misses"].sum()),
            stolen=int(ys["stolen"].sum()),
            unplaced=Q - n_completed,
            n_dropped=carry_dropped,
            final_backlog=int(np.asarray(qf.backlog.depth())),
            peak_backlog=peak_backlog,
            mean_wait_rounds=carry_wait / n_completed if n_completed else 0.0,
            truncated=bool(ys["truncated"].any()),
            hit_rate=float((touched - reads) / touched) if touched else 0.0,
            load_imbalance=float(per_proc.max() / max(per_proc.mean(), 1e-9)),
            wall_s=wall,
            throughput_qps=n_completed / max(wall, 1e-9),
            touched_bitmap=None if tmap is None else np.asarray(tmap),
            per_round=ys,
        )
        return result, carry
