"""The harness finds configurations, traffic mixes, query generators, served
paths and metrics by the names in BENCHMARK.json: a later change adds a cell,
a mix, a generator or a metric as new files and entries, and edits none."""

import json
import os

import numpy as np
import pytest

from bench import graph as graph_lib
from bench import tracereduce
from bench.generators import balls
from bench.run import Bench, Run, check_rounds

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TRACE = os.path.join(REPO, "bench/tests/data/tiny_tpu.xplane.pb")


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    root = str(tmp_path)
    spec = {
        "workloads": [{"name": "tiny.zipfish", "config": "tiny-graph", "traffic": "zipfish",
                       "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "qps", "unit": "queries/s", "better": "higher",
                        "bound": 0.1, "source": "host_clock"}],
        "per_layer": [{"name": "rounds_seen", "unit": "rounds", "better": "higher",
                       "source": "program_counter", "layer": "test", "moves": "qps"}],
    }
    _write(os.path.join(root, "BENCHMARK.json"), json.dumps(spec))
    _write(os.path.join(root, "bench/configs/tiny-graph.json"),
           json.dumps({"nodes": 500, "edges_per_node": 3, "structure_seed": 4}))
    _write(os.path.join(root, "bench/traffic/zipfish.json"),
           json.dumps({"generator": "balls", "clients": 8, "radius": 1, "group": 4,
                       "pool": 40, "query_seed": 3}))
    _write(os.path.join(root, "bench/generators/balls.py"),
           open(os.path.join(REPO, "bench/generators/balls.py")).read())
    _write(os.path.join(root, "bench/metrics/rounds_seen.py"),
           "def read(run):\n    return float(run.touched.size) or None\n")

    bench = Bench(root)
    cell = bench.cell("tiny.zipfish")
    cfg, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    g = graph_lib.structure(cfg)
    pool = bench.pool(g, traffic)
    assert pool.shape == (40,) and pool.max() < 500
    assert np.array_equal(pool, bench.pool(g, traffic))  # the query seed fixes it

    assert [m["name"] for m in bench.metrics("tiny.zipfish", traced=False)] == ["qps"]
    traced = bench.metrics("tiny.zipfish", traced=True)
    assert [m["name"] for m in traced] == ["rounds_seen"]
    run = Run(setup_s=1.0, window_s=2.0, latencies_s=np.ones(3), per_proc=np.ones((3, 4)),
              touched=np.arange(3), reads=np.arange(3), completed=12)
    assert bench.module("metrics", "rounds_seen").read(run) == 3.0
    with pytest.raises(FileNotFoundError):  # only this root's files are found
        bench.module("metrics", "qps")


def test_every_name_in_benchmark_json_has_its_file():
    bench = Bench(REPO)
    for c in bench.spec["configs"]:
        cfg = bench.config(c["name"])
        assert os.path.isfile(os.path.join(REPO, c["file"]))
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg["reduced"])
        assert os.path.isfile(os.path.join(REPO, "bench/paths", cfg["path"] + ".py"))
    for w in bench.spec["workloads"]:
        traffic = bench.traffic(w["traffic"])
        assert callable(bench.module("generators", traffic["generator"]).make_pool)
        assert bench.config(w["config"])
    for m in bench.spec["end_to_end"] + bench.spec["per_layer"]:
        assert callable(bench.module("metrics", m["name"]).read)


def test_metrics_follow_their_workloads_lists():
    bench = Bench(REPO)
    e2e = {m["name"] for m in bench.spec["end_to_end"]}
    for w in bench.spec["workloads"]:
        assert {m["name"] for m in bench.metrics(w["name"], traced=False)} == e2e
        per_layer = {m["name"] for m in bench.metrics(w["name"], traced=True)}
        assert {"load_imbalance", "reads_per_query", "cache_hit_rate", "scatter_share",
                "device_idle_share"} <= per_layer


def test_check_rounds_keeps_the_slowest_and_the_budget():
    rounds = [{"nodes": np.zeros(10), "t_begin": 0.0, "t_end": float(i == 7) + 1.0}
              for i in range(20)]
    assert check_rounds(rounds[:3], seed=1, budget=100) == [0, 1, 2]
    picked = check_rounds(rounds, seed=1, budget=45)
    assert 7 in picked and len(picked) == 4
    assert picked == check_rounds(rounds, seed=1, budget=45)


def test_new_generator_and_trace_share_need_no_edit(tmp_path):
    """A mix of a new kind is a generator module and a data file naming it; a
    new share of device time is a metric module naming its opcodes. Neither
    touches a file that is there."""
    root = str(tmp_path)
    spec = {
        "workloads": [{"name": "tiny.strided", "config": "tiny-graph", "traffic": "strided",
                       "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "qps", "unit": "queries/s", "better": "higher",
                        "bound": 0.1, "source": "host_clock"}],
        "per_layer": [{"name": "reduce_share", "unit": "%", "better": "lower",
                       "source": "device_trace", "layer": "test", "moves": "qps"},
                      {"name": "fft_share", "unit": "%", "better": "lower",
                       "source": "device_trace", "layer": "test", "moves": "qps"}],
    }
    _write(os.path.join(root, "BENCHMARK.json"), json.dumps(spec))
    _write(os.path.join(root, "bench/configs/tiny-graph.json"),
           json.dumps({"nodes": 300, "edges_per_node": 4, "structure_seed": 1}))
    _write(os.path.join(root, "bench/traffic/strided.json"),
           json.dumps({"generator": "strided", "stride": 7, "pool": 20, "query_seed": 0}))
    _write(os.path.join(root, "bench/generators/strided.py"),
           "import numpy as np\n\n\ndef make_pool(g, spec, rng):\n"
           "    start = rng.integers(0, g.n)\n"
           "    return (start + spec['stride'] * np.arange(spec['pool'])) % g.n\n")
    share = ("OPCODES = {codes!r}\n\n\ndef read(run):\n"
             "    s = run.trace.share(OPCODES)\n    return None if s is None else 100 * s\n")
    _write(os.path.join(root, "bench/metrics/reduce_share.py"), share.format(codes=("reduce",)))
    _write(os.path.join(root, "bench/metrics/fft_share.py"), share.format(codes=("fft",)))

    bench = Bench(root)
    cell = bench.cell("tiny.strided")
    g = graph_lib.structure(bench.config(cell["config"]))
    pool = bench.pool(g, bench.traffic(cell["traffic"]))
    assert np.array_equal(np.diff(pool) % g.n, np.full(19, 7))

    hlo = tracereduce.index_hlo([open(TRACE.replace(".xplane.pb", ".hlo.txt")).read()])
    summary = tracereduce.summarize(tracereduce.read(TRACE, ["round"]), hlo)
    run = Run(setup_s=1.0, window_s=1.0, latencies_s=np.ones(1), per_proc=np.ones((1, 1)),
              touched=np.ones(1), reads=np.ones(1), completed=1, trace=summary)
    values = {m["name"]: bench.module("metrics", m["name"]).read(run)
              for m in bench.metrics("tiny.strided", traced=True)}
    assert 0 < values["reduce_share"] < 100
    assert values["fft_share"] is None  # nothing to read: left out, never 0


def test_balls_generator_gives_hotspot_groups_and_uniform_nodes():
    g = graph_lib.structure({"nodes": 3000, "edges_per_node": 4, "structure_seed": 2})
    uniform = {"pool": 500, "group": 1, "radius": 0}
    got = balls.make_pool(g, uniform, np.random.default_rng([5, 1]))
    assert np.array_equal(got, np.random.default_rng([5, 1]).integers(0, g.n, 500))
    hot = {"pool": 95, "group": 10, "radius": 2}
    got = balls.make_pool(g, hot, np.random.default_rng([5, 1]))
    assert got.shape == (95,)
    centres = np.random.default_rng([5, 1]).integers(0, g.n, 10)
    deg = g.degree()
    for i, c in enumerate(centres):
        ball = balls.hotspot_ball(g, deg, int(c), 2, 500)
        assert np.isin(got[10 * i:10 * i + 10], ball).all()
