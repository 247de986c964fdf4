#!/usr/bin/env python3
"""Closed-loop h-hop serving benchmark of the gRouting engine on TPU.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell is an entry of `workloads` in
`BENCHMARK.json`; everything else is found by name under `bench/`:

  configs/<config>.json   the deployment: graph, storage, processors, caches,
                          the served path and the guarantees it gives
  traffic/<traffic>.json  the query mix: parameters of the generator it names
  generators/<gen>.py     a query generator (`make_pool`); `balls.py` is the
                          general one
  paths/<path>.py         the entry the window drives (`Served`) and the
                          host spans it records (`SPANS`)
  metrics/<metric>.py     one reader per metric (`read(run)`)

One run, in order: build the configuration's graph and storage from it with
the program's loaders, place them on the device, put the queries of each of
the traffic's rounds in the order its query seed gives, warm every shape the
window uses, serve a closed loop for --seconds (each client sends its next
query when its last one completes), check the answers against the plain host
reference in `reference.py`, and print one JSON line last. With --trace 1
the window is traced and the per-layer metrics are reported instead of the
end-to-end ones.

Every run of a cell sends the same queries in the same rounds and in the same
order, so it does the same work: which processor and cache slot serves a
query, and so which hub chains a processor pools, follows from the order.
--seed picks only the rounds whose answers are checked (`check_rounds`). A
change is thus measured on the one order, as on the one query sequence, that
its author could also see.

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result. `--rehearse` runs a cell on the CPU at the size its
configuration's `rehearsal` entry gives, for the benchmark's own tests.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from typing import Optional  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import graph as graph_lib  # noqa: E402
from bench import reference, tracereduce  # noqa: E402

WARMUP_ROUNDS = 2


class Meter:
    """Compile seconds and persistent-cache hits and misses, from JAX's
    monitoring events (a cache hit counts its load time as compile time)."""

    def __init__(self, jax):
        self.compiles = self.hits = self.misses = 0
        self.compile_s = 0.0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


@dataclasses.dataclass
class Run:
    """What the metric readers read: the window's rounds and, when traced,
    the reduced trace."""

    setup_s: float
    window_s: float
    latencies_s: np.ndarray  # (completed,) per query, client side
    per_proc: np.ndarray  # (rounds, P) queries executed per processor
    touched: np.ndarray  # (rounds,) storage rows needed (cache hits + misses)
    reads: np.ndarray  # (rounds,) unique storage rows fetched
    completed: int
    trace: Optional[tracereduce.Summary] = None


class Bench:
    """BENCHMARK.json and the files it names, under `root`."""

    def __init__(self, root: str):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def _json(self, kind: str, name: str) -> dict:
        with open(os.path.join(self.root, "bench", kind, name + ".json")) as f:
            return json.load(f)

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def pool(self, g, traffic: dict) -> np.ndarray:
        """The traffic's queries on graph `g`, from the generator it names
        and its query seed."""
        rng = np.random.default_rng([int(traffic["query_seed"]), 1])
        return self.module("generators", traffic["generator"]).make_pool(g, traffic, rng)

    def module(self, kind: str, name: str):
        path = os.path.join(self.root, "bench", kind, name + ".py")
        spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def metrics(self, cell: str, traced: bool) -> list:
        """The cell's metrics of one kind: end-to-end untraced, per-layer
        traced. A metric without a `workloads` list belongs to every cell
        that reports the end-to-end metric it moves."""
        e2e = self.spec["end_to_end"]
        mine = [m for m in e2e if cell in m.get("workloads", [cell])]
        if not traced:
            return mine
        names = {m["name"] for m in mine}
        return [m for m in self.spec["per_layer"]
                if cell in m.get("workloads", [cell] if m["moves"] in names else [])]


def storage(cfg: dict, g):
    """The program's storage tier over the graph, and the continuation-chain
    depth that follows every hub's chain to its end."""
    from repro.core.storage import build_storage
    from repro.graph.csr import CSRGraph, to_padded

    adj = to_padded(CSRGraph(n=g.n, indptr=g.indptr, indices=g.indices),
                    max_degree=cfg["row_width"])
    tier = build_storage(adj, n_shards=cfg["storage_shards"], seed=cfg["placement_seed"])
    return tier, -(-int(g.degree().max()) // cfg["row_width"])


def seed_order(pool: np.ndarray, block: int, rng: np.random.Generator) -> np.ndarray:
    """The pool with each run of `block` consecutive queries, what one round
    of the closed loop sends together, in an order drawn from `rng`. `main`
    draws it from the traffic's query seed, never from the run's: the order
    decides which queries share a processor and so the work of a round."""
    out = pool.copy()
    full = pool.size // block * block
    out[:full] = rng.permuted(pool[:full].reshape(-1, block), axis=1).reshape(-1)
    out[full:] = rng.permutation(pool[full:])
    return out


def check_rounds(rounds: list, seed: int, budget: int) -> list:
    """Indices of the window's rounds whose answers are compared: all of
    them when they hold at most `budget` queries, else the slowest round and
    rounds drawn from the seed until the budget is spent."""
    sizes = [len(r["nodes"]) for r in rounds]
    if sum(sizes) <= budget:
        return list(range(len(rounds)))
    slowest = int(np.argmax([r["t_end"] - r["t_begin"] for r in rounds]))
    picked, total = [slowest], sizes[slowest]
    for i in np.random.default_rng([seed, 2]).permutation(len(rounds)):
        if total + sizes[i] > budget:
            break
        if i != slowest:
            picked.append(int(i))
            total += sizes[i]
    return sorted(picked)


def check(g, cfg: dict, rounds: list, seed: int, window_compiles: int) -> dict:
    """Every number compared, each as (value, bound, limit): bound "max" holds
    the value at or under its limit, "min" at or over it."""
    lost = sum(int((~r["completed"]).sum()) for r in rounds)
    load_gap = sum(abs(int(r["per_proc"].sum()) - int(r["completed"].sum())) for r in rounds)
    reads_out = sum(not 0 < r["reads"] <= r["touched"] for r in rounds)
    mismatches = touched_gaps = checked = 0
    for i in check_rounds(rounds, seed, cfg["check_queries"]):
        r = rounds[i]
        want, touched = reference.serve_all(g, r["nodes"], cfg["hops"], cfg["max_frontier"],
                                            cfg["row_width"])
        done = r["completed"]
        mismatches += int((r["counts"][done] != want[done]).sum())
        touched_gaps += int(r["touched"] != int(touched[done].sum()))
        checked += int(done.sum())
    return {
        "answers_wrong": (mismatches, "max", 0),
        "answers_checked": (checked, "min", 1),
        "queries_lost": (lost, "max", 0),
        "load_count_gap": (load_gap, "max", 0),
        "touched_rounds_wrong": (touched_gaps, "max", 0),
        "reads_rounds_out_of_range": (reads_out, "max", 0),
        "window_compiles": (window_compiles, "max", 0),
    }


def passes(value, bound: str, limit) -> bool:
    return value <= limit if bound == "max" else value >= limit


@contextlib.contextmanager
def timed(phases: dict, name: str):
    t = time.perf_counter()
    yield
    phases[name] = time.perf_counter() - t


def closed_loop(served, pool: np.ndarray, clients: int, seconds: float,
                trace_dir: Optional[str], trace_seconds: float, phases: dict):
    """Serve from `pool` for `seconds`: every client sends its next query
    when its last one completes, one round per call. With `trace_dir` the
    window is traced and lasts at most `trace_seconds` (the time the
    profiler takes to stop goes to `phases`). Returns the rounds and the
    window's start and end."""
    import jax

    B = served.round_size
    sent_at = np.zeros(clients)  # when each client's pending query was sent
    ready = list(range(clients))  # clients with a query waiting, oldest first
    rounds, nxt = [], 0
    if trace_dir is not None:
        seconds = min(seconds, trace_seconds)
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    sent_at[:] = t0
    while True:
        t_begin = time.perf_counter()
        if t_begin - t0 >= seconds:
            break
        batch, ready = ready[:B], ready[B:]
        if nxt + len(batch) > pool.size:
            raise RuntimeError(f"the traffic's pool of {pool.size} queries ran out; "
                               "make it larger")
        nodes = pool[nxt:nxt + len(batch)]
        nxt += len(batch)
        out = served.serve(nodes)
        t_end = time.perf_counter()
        out.update(nodes=nodes, t_begin=t_begin, t_end=t_end,
                   latency=t_end - sent_at[batch])
        rounds.append(out)
        # each client of the batch sends its next query now; one whose query
        # did not complete has lost it (counted by the check)
        sent_at[batch] = t_end
        ready += batch
    t1 = rounds[-1]["t_end"] if rounds else t0
    if trace_dir is not None:
        with timed(phases, "stop_trace"):
            jax.profiler.stop_trace()
    return rounds, t0, t1


def emit(result: dict, checks: dict) -> None:
    """The checks as the last lines of stderr, and the result as the last
    line of stdout with the checks as its last key."""
    for name, (value, bound, limit) in checks.items():
        op = "<=" if bound == "max" else ">="
        print(f"check {name}: {value} (limit {op} {limit})", file=sys.stderr)
    sys.stderr.flush()
    result["checks"] = {k: {"value": v, "limit": f"{'<=' if b == 'max' else '>='} {lim}"}
                        for k, (v, b, lim) in checks.items()}
    print(json.dumps(result), flush=True)


def main(argv=None, root: Optional[str] = None, chain_cap: Optional[int] = None) -> int:
    """Run one cell. `chain_cap` caps the continuation chains below their
    end: the control that `control.py` runs, which must come out incorrect."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    bench = Bench(root or ROOT)
    cell = bench.cell(args.workload)
    cfg, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    chips = int(cell["chips"])
    if args.rehearse:
        cfg.update(cfg["rehearsal"])
        os.environ["JAX_PLATFORMS"] = "cpu"
        if chips > 1:
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                       f" --xla_force_host_platform_device_count={chips}")

    import jax

    devices = jax.devices()
    if not args.rehearse and (devices[0].platform != "tpu" or len(devices) < chips):
        print(f"bench: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s); nothing was run",
              file=sys.stderr)
        return 1
    devices = devices[:chips]

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program, however quick to compile, comes from the cache after
    # the first run in a checkout
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    meter = Meter(jax)

    phases = {}
    with timed(phases, "graph"):
        base = graph_lib.structure(cfg)
        g = graph_lib.relabel(base, np.random.default_rng([int(cfg["label_seed"]), 0]))
    with timed(phases, "traffic"):
        # the traffic's queries, drawn on the structure from its query seed,
        # under the configuration's node names
        pool = g.perm[bench.pool(base, traffic)].astype(np.int32)
        del base
    with timed(phases, "storage"):
        tier, chain_depth = storage(cfg, g)
    if chain_cap is not None:
        chain_depth = min(chain_depth, chain_cap)
    path = bench.module("paths", cfg["path"])
    with timed(phases, "placement"):
        served = path.Served(cfg, tier, chain_depth, devices)
    del tier
    clients = int(traffic["clients"])
    # the traffic's rounds, each in the traffic's own order: the same in every run
    pool = seed_order(pool, min(clients, served.round_size),
                      np.random.default_rng([int(traffic["query_seed"]), 2]))
    # warm-up: the window's shapes, on rounds of padding slots only. The
    # programs are the window's (the first round starts from fresh state,
    # the second from carried state), the work nearly none, and every seed's
    # window starts from the same empty caches.
    for i in range(WARMUP_ROUNDS):
        with timed(phases, f"warmup{i}"):
            served.serve(np.full(served.round_size, -1, np.int32))
    compiles_before = meter.compiles

    with contextlib.ExitStack() as stack:
        trace_dir = stack.enter_context(tempfile.TemporaryDirectory()) if args.trace else None
        setup_s = time.perf_counter() - T_START
        rounds, t0, t1 = closed_loop(served, pool, clients, args.seconds, trace_dir,
                                     float(cfg["trace_seconds"]), phases)
        window_compiles = meter.compiles - compiles_before
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
        summary = None
        if trace_dir is not None:
            with timed(phases, "trace"):
                hlo = tracereduce.index_hlo(served.programs())
                summary = tracereduce.summarize(tracereduce.read(trace_dir, path.SPANS), hlo)

    served.close()
    del served
    gc.collect()

    with timed(phases, "check"):
        checks = check(g, cfg, rounds, args.seed, window_compiles)
    correct = all(passes(*c) for c in checks.values())
    done = np.concatenate([r["completed"] for r in rounds]) if rounds else np.zeros(0, bool)
    run = Run(
        setup_s=setup_s, window_s=t1 - t0,
        latencies_s=np.concatenate([r["latency"][r["completed"]] for r in rounds])
        if rounds else np.zeros(0),
        per_proc=np.stack([r["per_proc"] for r in rounds]) if rounds else np.zeros((0, 1)),
        touched=np.array([r["touched"] for r in rounds], np.int64),
        reads=np.array([r["reads"] for r in rounds], np.int64),
        completed=int(done.sum()), trace=summary,
    )
    metrics = {}
    for m in bench.metrics(args.workload, traced=bool(args.trace)):
        value = bench.module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": int(done.size),
              "failed": int(done.size - done.sum()) + checks["answers_wrong"][0],
              "metrics": metrics, "device": device}
    if args.trace and summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.top_ops, "idle_gaps": summary.idle_gaps}
    print(f"bench: {args.workload} seed={args.seed} rounds={len(rounds)} "
          f"phases_s={json.dumps({k: round(v, 3) for k, v in phases.items()})} "
          f"round_s={[round(r['t_end'] - r['t_begin'], 3) for r in rounds]} "
          f"trace_truncated={summary.truncated if summary else None} "
          f"window_s={run.window_s:.3f} setup_s={setup_s:.3f} chain_depth={chain_depth} "
          f"compile_s={meter.compile_s:.3f} cache_hits={meter.hits} "
          f"cache_misses={meter.misses}", file=sys.stderr)
    emit(result, checks)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception:  # a run that fails prints no result
        traceback.print_exc()
        sys.exit(1)
