"""Compile the serving path for a described TPU v5e (no chip attached).

The TPU compiler is installed wherever libtpu is, and it compiles for a
chip that is only described: it refuses what the chip would refuse (block
shapes off the (8, 128) tiling, unsupported shape casts or reductions, a
kernel over its fast-memory budget), which the interpret-mode kernel tests
cannot see. Four compiles at the `grouting` deployment's widths:

  - each frontier kernel, natively, at B=64, F=2048, W=32, n=2^22;
  - the single-chip ServingEngine round scan, its storage and router tables
    passed as abstract arguments;
  - the shard_map serve step on a 2x2 mesh of the described devices.

The last two also bound the program text: an O(n) table closed over as a
constant would add hundreds of MB to it.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, so a test worker that does not run
this file must never touch it.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs.grouting import N_NODES, N_ROWS, ROW_WIDTH, model_cfg
from repro.kernels import frontier as fr

B, F, W, N = 64, 2048, ROW_WIDTH, N_NODES
N_PROC = 4
EMBED_DIM = 8
# a compiled serving program is a few hundred KB of text; the smallest O(n)
# table (owner, 2^22 int32) would add 16 MB as a constant
MAX_PROGRAM_CHARS = 4_000_000


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or the library is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _abstract(tree, sharding):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


def _assert_native_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_dense_kernel_compiles(one_chip):
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    compiled = fr._frontier_batched_padded.lower(
        sds((B, F, W), jnp.int32), sds((B, F), jnp.int32), sds((B, N), jnp.bool_),
        bf=fr.DEFAULT_BF, bn=fr.DEFAULT_BN, interpret=False,
    ).compile()
    _assert_native_kernel(compiled)


def test_packed_kernel_compiles(one_chip):
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    compiled = fr._frontier_packed_padded.lower(
        sds((B, F, W), jnp.int32), sds((B, F), jnp.int32),
        sds((B, fr.n_words(N)), jnp.uint32),
        bf=fr.DEFAULT_BF, bw=fr.DEFAULT_BW, interpret=False,
    ).compile()
    _assert_native_kernel(compiled)


def test_engine_scan_compiles_with_storage_as_arguments(one_chip):
    """The one-chip deployment: 4 vmapped processors, embed routing, dense
    visited state, 2 rounds per scan. Storage and router tables are
    ShapeDtypeStruct arguments, so nothing O(n) can hide in the program."""
    import numpy as np

    from repro.core.embedding import EmbedConfig, GraphEmbedding
    from repro.core.router import Router, RouterConfig
    from repro.core.storage import StorageArrays
    from repro.serve.engine import EngineRunConfig, ServingEngine

    gc = model_cfg("serve_hot_3hop")
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    rps = -(-N_ROWS // N_PROC)
    store = StorageArrays(
        rows=sds((N_PROC, rps, W), jnp.int32), deg=sds((N_PROC, rps), jnp.int32),
        cont=sds((N_PROC, rps), jnp.int32), owner=sds((N_ROWS,), jnp.int32),
        loc=sds((N_ROWS,), jnp.int32), n=N,
    )
    # the router is built over a tiny embedding; the jitted scan only sees
    # the (n, D) coordinate table it is handed
    emb = GraphEmbedding(coords=np.zeros((16, EMBED_DIM), np.float32),
                         landmarks=np.arange(4), lm_coords=np.zeros((4, EMBED_DIM), np.float32),
                         config=EmbedConfig(dim=EMBED_DIM))
    router = Router(N_PROC, RouterConfig(scheme="embed"), embedding=emb)
    tables = {"coords": sds((N, EMBED_DIM), jnp.float32)}
    cfg = EngineRunConfig(
        n_processors=N_PROC, round_size=N_PROC * gc.queries_per_proc,
        capacity=gc.queries_per_proc, hops=gc.hops, max_frontier=gc.max_frontier,
        cache_sets=gc.cache_sets, cache_ways=gc.cache_ways, chain_depth=gc.chain_depth,
    )
    eng = ServingEngine(store, router, cfg)
    state = _abstract((router.init_state(), eng.init_caches(), eng.init_touched(),
                       eng.init_queue()), one_chip)
    R = 2
    xs = (sds((R, cfg.round_size), jnp.int32), sds((R, cfg.round_size), jnp.int32),
          sds((R,), jnp.int32))
    lowered = eng.scan.lower(store, tables, *state, xs)
    assert len(lowered.as_text()) < MAX_PROGRAM_CHARS
    compiled = lowered.compile()
    assert len(compiled.as_text()) < MAX_PROGRAM_CHARS
    # the storage rows are an argument of the program, not a constant in it
    assert compiled.memory_analysis().argument_size_in_bytes >= N_PROC * rps * W * 4


def test_shard_map_serve_step_compiles_on_2x2(topo):
    from repro.launch.mesh import make_auto_mesh
    from repro.serve.graph_serving import (
        abstract_serve_inputs, make_distributed_serve_step,
    )

    mesh = make_auto_mesh((2, 2), ("data", "model"), devices=topo.devices)
    cfg = dataclasses.replace(model_cfg("serve_hot_3hop"), n_storage_shards=2,
                              embed_dim=EMBED_DIM)
    inputs = abstract_serve_inputs(mesh, cfg, -(-cfg.n_rows // cfg.n_storage_shards))
    sh = lambda spec: NamedSharding(mesh, spec)
    procs = sh(P(("data", "model")))
    shardings = {
        "queries": procs, "rows": sh(P("model")), "deg": sh(P("model")),
        "cont": sh(P("model")), "owner": sh(P()), "loc": sh(P()),
        "coords": sh(P()), "ema": sh(P()),
        "cache": {k: procs for k in inputs["cache"]},
    }
    args = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), inputs, shardings)
    lowered = jax.jit(make_distributed_serve_step(mesh, cfg)).lower(args)
    assert len(lowered.as_text()) < MAX_PROGRAM_CHARS
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(text) < MAX_PROGRAM_CHARS
    assert "all-to-all" in text  # multi_read crosses the storage axis
