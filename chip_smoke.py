#!/usr/bin/env python3
"""Serve gRouting once on a TPU at the paper's graph shape, and check it.

    python3 chip_smoke.py [--seed N]          # one chip
    python3 chip_smoke.py --chips 4           # the four-chip shard_map path only
    python3 chip_smoke.py --rehearse --nodes 8192 [--chips 4]
                                              # CPU dress rehearsal, tiny graph

The deployment is the `grouting` configuration's serve_hot_3hop shape
(src/repro/configs/grouting.py): a power-law graph (preferential attachment,
m=8) of 2^22 nodes drawn from --seed, stored hash-partitioned over 4
storage shards in 32-wide rows with continuation rows; 4 query processors,
each with a 2048x4 set-associative row cache, 16 queries per processor per
round, 3-hop aggregation, frontiers of up to 2048 nodes. Continuation
chains are followed to the end (chain depth = ceil(max degree / 32)), so
every query whose BFS levels fit the frontier is answered exactly. On one
chip the 4 processors are vmapped, and the storage rows, the visited state
and the embed router's coordinates live in HBM.

One chip (default), all in this process:
  kernels  both Pallas frontier kernels, natively, once over a 2^22-bit
           row, bit for bit against the scatter reference.
  engine   ServingEngine.run over 2 rounds of 2-hop hotspot queries then a
           round of uniform queries, under hash and embed routing x
           {dense, packed} visited layouts, XLA scatter expansion. Per-query
           counts agree across layouts and routers; hit rate and read volume
           agree across layouts; 32 completed queries whose BFS levels fit
           the frontier match the host BFS oracle.
  pallas   the first engine round with expand_backend="pallas" under each
           layout equals that round under scatter, continuation chains
           capped at PALLAS_CHAIN_DEPTH rows per hop on both sides.
--chips 4 runs only the shard_map serve step on a (data=1, model=4) mesh --
storage in 4 shards over "model", one processor per chip, multi_read as an
all_to_all -- fed by make_admission_round under hash routing for one
hotspot and one uniform burst, and compares its per-query counts with the
BFS oracle and with ServingEngine run on one of the four chips.

Every phase prints its wall and compile seconds (host clock; compile = XLA
backend compile, a hit in the persistent cache counts its load time) on a
line of its own. The last line of a run that passed every check is one JSON
object naming the device. Without a TPU (unless --rehearse), or after any
failed check, the script exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
P = 4  # query processors, and storage shards
SHAPE = "serve_hot_3hop"
HOT_ROUNDS, UNIFORM_ROUNDS = 2, 1
# the four-chip run serves one round of each: at 4x the chip cost per
# second, two bursts exercise the all_to_all path and the comparison
FOUR_CHIP_ROUNDS = 1, 1
ORACLE_SAMPLE = 32
N_LANDMARKS = 16
EMBED_DIM = 8
KERNEL_B, KERNEL_F = 8, 512  # queries x frontier rows of the kernel phase
PALLAS_CHAIN_DEPTH = 2


class SmokeFailure(Exception):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"[check] ok: {what}", flush=True)


def log(msg: str) -> None:
    print(msg, flush=True)


class Meter:
    """Wall and compile seconds per phase, from JAX's compile events."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.hits = self.misses = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, h0, m0, t0 = self.compile_s, self.hits, self.misses, time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        log(f"[time] {name}: wall_s={wall:.3f} compile_s={self.compile_s - c0:.3f} "
            f"cache_hits={self.hits - h0} cache_misses={self.misses - m0}")


@dataclasses.dataclass
class Deployment:
    g: object
    tier: object
    n_rows: int
    chain_depth: int
    workload: object
    embedding: object = None


def build_deployment(args, meter, with_embedding: bool, rounds: tuple) -> Deployment:
    from repro.configs.grouting import ROW_WIDTH
    from repro.core.storage import build_storage
    from repro.core.workloads import Workload, hotspot_workload, uniform_workload
    from repro.graph.csr import to_padded
    from repro.graph.generators import powerlaw_graph

    with meter.phase("build_graph_and_storage"):
        g = powerlaw_graph(n=args.nodes, m=8, seed=args.seed)
        adj = to_padded(g, max_degree=ROW_WIDTH)
        tier = build_storage(adj, n_shards=P, seed=args.seed)
    max_deg = int(np.diff(g.indptr).max())
    chain_depth = -(-max_deg // ROW_WIDTH)
    log(f"[deploy] nodes={g.n} edges={g.e} storage_rows={adj.n_rows} "
        f"row_width={ROW_WIDTH} shards={P} max_degree={max_deg} "
        f"chain_depth={chain_depth} storage_bytes={tier.shard_rows.nbytes}")

    B = P * _gcfg().queries_per_proc
    hot_rounds, uniform_rounds = rounds
    hot = hotspot_workload(g, r=2, n_hotspots=-(-hot_rounds * B // 10),
                           queries_per_hotspot=10, seed=args.seed + 1)
    uni = uniform_workload(g, n_queries=uniform_rounds * B, seed=args.seed + 2)
    k = hot_rounds * B
    wl = Workload(
        name="hotspot-then-uniform",
        query_nodes=np.concatenate([hot.query_nodes[:k], uni.query_nodes]),
        query_types=np.concatenate([hot.query_types[:k], uni.query_types]),
        targets=np.concatenate([hot.targets[:k], uni.targets]),
        hotspot_id=np.concatenate([hot.hotspot_id[:k], uni.hotspot_id]),
    )
    dep = Deployment(g=g, tier=tier, n_rows=adj.n_rows, chain_depth=chain_depth,
                     workload=wl)
    if with_embedding:
        from repro.core.embedding import EmbedConfig, build_graph_embedding
        from repro.core.landmarks import build_landmark_index

        with meter.phase("preprocess_embed"):
            li = build_landmark_index(g, n_processors=P, n_landmarks=N_LANDMARKS)
            dep.embedding = build_graph_embedding(
                li.dist_to_lm, li.landmarks, EmbedConfig(dim=EMBED_DIM, seed=args.seed))
        log(f"[deploy] embedding: landmarks={N_LANDMARKS} dim={EMBED_DIM} "
            f"coords_bytes={dep.embedding.coords.nbytes}")
    return dep


def _gcfg():
    from repro.configs.grouting import model_cfg

    return model_cfg(SHAPE)


def engine_cfg(dep: Deployment, backend: str, layout: str):
    from repro.serve.engine import EngineRunConfig

    gc = _gcfg()
    return EngineRunConfig(
        n_processors=P, round_size=P * gc.queries_per_proc,
        capacity=gc.queries_per_proc, hops=gc.hops, max_frontier=gc.max_frontier,
        cache_sets=gc.cache_sets, cache_ways=gc.cache_ways,
        chain_depth=dep.chain_depth, expand_backend=backend, visited_layout=layout,
    )


def exact_ball(g, node: int) -> int | None:
    """|N_h(node)| - 1 from the host BFS, or None where a hop's frontier
    would overflow max_frontier (the engine then truncates by design). The
    search stops at the first level that overflows."""
    from repro.graph.csr import iter_bfs_levels

    gc = _gcfg()
    size = 0
    for hop, level in enumerate(iter_bfs_levels(g, node, gc.hops)):
        if 0 < hop < gc.hops and level.size > gc.max_frontier:
            return None
        size += level.size if hop else 0
    return size


def check_oracle(dep: Deployment, counts: np.ndarray, completed: np.ndarray,
                 label: str, seed: int, need: int) -> None:
    """Counts of ORACLE_SAMPLE completed queries whose BFS levels fit the
    frontier (so no hop truncates) equal the host BFS ball sizes; at least
    `need` such queries must exist."""
    qn = dep.workload.query_nodes
    order = np.random.default_rng(seed).permutation(np.flatnonzero(completed))
    checked = 0
    for q in order:
        want = exact_ball(dep.g, int(qn[q]))
        if want is None:
            continue
        check(int(counts[q]) == want,
              f"{label}: query {q} (node {qn[q]}) count {counts[q]} == BFS {want}")
        checked += 1
        if checked == ORACLE_SAMPLE:
            break
    check(checked >= need, f"{label}: {checked} queries checked against the BFS oracle")


def run_engine(dep: Deployment, meter, seed: int, need: int):
    """Engine phase; returns the (hash, layout) results for the pallas phase."""
    from repro.core.router import Router, RouterConfig
    from repro.core.storage import device_storage
    from repro.serve.engine import ServingEngine

    store = device_storage(dep.tier)
    routers = {
        "hash": Router(P, RouterConfig(scheme="hash"), seed=seed),
        "embed": Router(P, RouterConfig(scheme="embed"), embedding=dep.embedding,
                        seed=seed),
    }
    results = {}
    for scheme, router in routers.items():
        for layout in ("dense", "packed"):
            eng = ServingEngine(store, router, engine_cfg(dep, "scatter", layout))
            with meter.phase(f"engine {scheme} {layout}"):
                res, _ = eng.run(dep.workload)
            results[(scheme, layout)] = res
            log(f"[engine] {scheme} {layout}: completed={int(res.completed.sum())}/"
                f"{res.n_queries} rounds={res.per_round['counts'].shape[0]} "
                f"touched={res.touched} reads={res.reads} hit_rate={res.hit_rate:.6f} "
                f"stolen={res.stolen} truncated_rounds="
                f"{int(res.per_round['truncated'].any(axis=1).sum())}")

    for scheme in routers:
        d, p = results[(scheme, "dense")], results[(scheme, "packed")]
        check(np.array_equal(d.counts, p.counts) and np.array_equal(d.completed, p.completed),
              f"{scheme}: per-query counts identical across dense and packed")
        check((d.touched, d.reads, d.hit_rate) == (p.touched, p.reads, p.hit_rate),
              f"{scheme}: touched/reads/hit rate identical across dense and packed")
    h, e = results[("hash", "dense")], results[("embed", "dense")]
    both = h.completed & e.completed
    check(both.sum() > 0 and np.array_equal(h.counts[both], e.counts[both]),
          f"per-query counts identical across hash and embed routing ({int(both.sum())} queries)")
    check_oracle(dep, h.counts, h.completed, "engine hash dense", seed, need)
    return store, routers["hash"]


def run_kernels(dep: Deployment, meter, rehearse: bool, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.frontier import (
        frontier_expand_batched, frontier_expand_packed, pack_words,
    )
    from repro.kernels.ref import frontier_expand_ref

    n, W = dep.g.n, dep.tier.row_width
    rng = np.random.default_rng(seed)
    rows = rng.integers(-1, n, (KERNEL_B, KERNEL_F, W)).astype(np.int32)
    deg = rng.integers(0, W + 1, (KERNEL_B, KERNEL_F)).astype(np.int32)
    vis = jnp.asarray(rng.random((KERNEL_B, n)) < 0.05)
    rows, deg = jnp.asarray(rows), jnp.asarray(deg)
    want = jax.vmap(frontier_expand_ref)(rows, deg, vis)
    check(int(want.sum()) > int(vis.sum()), "kernel input marks new nodes")

    with meter.phase("kernel frontier_expand_batched"):
        got = jax.block_until_ready(
            frontier_expand_batched(rows, deg, vis, interpret=rehearse))
    check(bool(jnp.array_equal(got, want)),
          f"frontier_expand_batched (B={KERNEL_B}, F={KERNEL_F}, W={W}, n={n}) "
          "equals the scatter reference bit for bit")
    words = pack_words(vis)
    with meter.phase("kernel frontier_expand_packed"):
        got = jax.block_until_ready(
            frontier_expand_packed(rows, deg, words, n, interpret=rehearse))
    check(bool(jnp.array_equal(got, pack_words(want))),
          f"frontier_expand_packed (B={KERNEL_B}, F={KERNEL_F}, W={W}, n={n}) "
          "equals the packed scatter reference bit for bit")


def run_pallas_round(dep: Deployment, meter, store, router, backend: str) -> None:
    """The first round under the Pallas backend equals it under scatter.

    Both sides cap continuation chains at PALLAS_CHAIN_DEPTH rows per hop:
    every kernel call costs a pass over all n/BN node blocks of every
    query, and the deployment's hubs would take chain_depth calls per hop."""
    from repro.core.workloads import Workload
    from repro.serve.engine import ServingEngine

    B = P * _gcfg().queries_per_proc
    wl = dep.workload
    first = Workload(name="first-round", query_nodes=wl.query_nodes[:B],
                     query_types=wl.query_types[:B], targets=wl.targets[:B],
                     hotspot_id=wl.hotspot_id[:B])
    keys = ("counts", "assignment", "touched", "reads", "probe_misses", "truncated")
    for layout in ("dense", "packed"):
        rounds = {}
        for name in ("scatter", backend):
            cfg = dataclasses.replace(engine_cfg(dep, name, layout),
                                      chain_depth=PALLAS_CHAIN_DEPTH)
            with meter.phase(f"engine round {name} {layout} chain_depth={PALLAS_CHAIN_DEPTH}"):
                res, _ = ServingEngine(store, router, cfg).run(first)
            rounds[name] = res.per_round
        same = all(np.array_equal(rounds[backend][k], rounds["scatter"][k]) for k in keys)
        check(same, f"one round under {backend} x {layout} equals the scatter round "
                    f"({', '.join(keys)})")


def run_four_chips(dep: Deployment, meter, seed: int, need: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from repro.core.dispatch import scatter_back
    from repro.core.router import Router, RouterConfig
    from repro.core.storage import make_serving_storage
    from repro.launch.mesh import make_auto_mesh
    from repro.serve.engine import ServingEngine
    from repro.serve.graph_serving import (
        make_admission_round, make_distributed_serve_step, make_processor_caches,
    )

    devices = jax.devices()
    check(len(devices) == 4, f"four devices visible ({len(devices)})")
    mesh = make_auto_mesh((1, P), ("data", "model"), devices)
    gcfg = dataclasses.replace(
        _gcfg(), n_nodes=dep.g.n, n_rows=dep.n_rows, n_storage_shards=P,
        chain_depth=dep.chain_depth, embed_dim=1,
    )
    procs = NamedSharding(mesh, PS(("data", "model")))
    shards = NamedSharding(mesh, PS("model"))
    repl = NamedSharding(mesh, PS())
    store = make_serving_storage(dep.tier)
    inputs = {
        "rows": jax.device_put(store["rows"], shards),
        "deg": jax.device_put(store["deg"], shards),
        "cont": jax.device_put(store["cont"], shards),
        "owner": jax.device_put(store["owner"], repl),
        "loc": jax.device_put(store["loc"], repl),
        # hash routing keeps no coordinates: a 1-wide zero table feeds the
        # step's EMA update
        "coords": jax.device_put(jnp.zeros((dep.g.n, 1), jnp.float32), repl),
        "ema": jax.device_put(jnp.zeros((P, 1), jnp.float32), repl),
        "cache": jax.device_put(make_processor_caches(mesh, gcfg), procs),
    }
    del store
    step = jax.jit(make_distributed_serve_step(mesh, gcfg))
    router = Router(P, RouterConfig(scheme="hash"), seed=seed)
    admission, init_backlog = make_admission_round(router, mesh, gcfg, backlog_capacity=0)
    rstate, backlog = router.init_state(), init_backlog()

    qn = dep.workload.query_nodes
    B = P * gcfg.queries_per_proc
    counts = np.full(qn.size, -1, np.int32)
    completed = np.zeros(qn.size, bool)
    touched = reads = 0
    with meter.phase("shard_map serve bursts"):
        for b in range(qn.size // B):
            qids = np.arange(b * B, (b + 1) * B, dtype=np.int32)
            qbuf, adm = admission(rstate, backlog, jnp.asarray(qn[qids]), jnp.asarray(qids))
            rstate, backlog = adm.rstate, adm.backlog
            out_counts, ema, cache, stats = step(
                dict(inputs, queries=jax.device_put(qbuf, procs)))
            inputs["cache"], inputs["ema"] = cache, ema
            per_q = np.asarray(scatter_back(jax.device_put(out_counts, devices[0]),
                                            adm.dispatch, B))
            placed = np.asarray(adm.placed)
            off = np.asarray(adm.offered_qid)
            counts[off[placed]] = per_q[placed]
            completed[off[placed]] = True
            t, _missed, r = np.asarray(stats)
            touched, reads = touched + int(t), reads + int(r)
    log(f"[shard_map] completed={int(completed.sum())}/{qn.size} touched={touched} "
        f"reads={reads}")

    eng = ServingEngine(dep.tier, router, engine_cfg(dep, "scatter", "dense"))
    with meter.phase("engine on one chip, same queries"):
        res, _ = eng.run(dep.workload)
    log(f"[engine] completed={int(res.completed.sum())}/{res.n_queries} "
        f"touched={res.touched} reads={res.reads}  (shard_map: touched={touched} "
        f"reads={reads}; read capacity and retries differ between the paths)")
    both = completed & res.completed
    check(both.sum() > 0 and np.array_equal(counts[both], res.counts[both]),
          f"shard_map per-query counts equal the one-chip engine's ({int(both.sum())} queries)")
    check_oracle(dep, counts, completed, "shard_map", seed, need)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip shard_map path")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--nodes", type=int, default=1 << 22,
                    help="graph size (the deployment's is 2^22)")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: no TPU needed, Pallas kernels interpreted, "
                         "oracle sample as large as the graph allows (with "
                         "--chips 4, four virtual CPU devices)")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

    import jax

    dev = jax.devices()[0]
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())}")
    if dev.platform != "tpu" and not args.rehearse:
        print("chip_smoke: no TPU found; nothing was run", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache

        log(f"[cache] compilation cache: {enable_compile_cache()}")
        meter = Meter(jax)
        backend = "pallas-interpret" if args.rehearse else "pallas"
        # a tiny rehearsal graph has few queries whose 3-hop levels fit the
        # frontier; the deployment's has hundreds
        need = 1 if args.rehearse else ORACLE_SAMPLE
        with meter.phase("total"):
            if args.chips == 4:
                dep = build_deployment(args, meter, with_embedding=False, rounds=FOUR_CHIP_ROUNDS)
                run_four_chips(dep, meter, args.seed, need)
            else:
                dep = build_deployment(args, meter, with_embedding=True,
                                       rounds=(HOT_ROUNDS, UNIFORM_ROUNDS))
                run_kernels(dep, meter, args.rehearse, args.seed)
                store, router = run_engine(dep, meter, args.seed, need)
                run_pallas_round(dep, meter, store, router, backend)
        stats = dev.memory_stats() or {}
        log(f"[memory] device peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}")
    except Exception as exc:  # any failed phase fails the smoke
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
