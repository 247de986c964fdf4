"""Shared fixtures. NOTE: no XLA_FLAGS here -- smoke tests and benches must
see the host's real (single) device; only launch/dryrun.py forces 512."""

import numpy as np
import pytest

import jax


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running simulator / engine-parity tests "
        "(deselect with `-m 'not slow'`)",
    )


@pytest.fixture(scope="session")
def tiny_graph():
    from repro.graph.generators import powerlaw_graph

    return powerlaw_graph(n=300, m=4, seed=0)


@pytest.fixture(scope="session")
def small_graph():
    # clustered power-law graph: h-hop balls are O(community), not O(graph),
    # so topology-aware locality exists at test scale (see generators.py)
    from repro.graph.generators import community_graph

    return community_graph(n=4800, community_size=60, intra_degree=6,
                           inter_degree=1.0, seed=1)


@pytest.fixture(scope="session")
def landmark_index(small_graph):
    from repro.core.landmarks import build_landmark_index

    return build_landmark_index(small_graph, n_processors=4, n_landmarks=24,
                                min_separation=2)


@pytest.fixture(scope="session")
def graph_embedding(small_graph, landmark_index):
    from repro.core.embedding import EmbedConfig, build_graph_embedding

    return build_graph_embedding(
        landmark_index.dist_to_lm, landmark_index.landmarks,
        EmbedConfig(dim=8, lm_steps=200, node_steps=80),
    )


@pytest.fixture(scope="session")
def host_mesh():
    from repro.launch.mesh import make_auto_mesh

    n = len(jax.devices())
    return make_auto_mesh((n, 1), ("data", "model"))


def bfs_oracle(g, source: int, max_hops=None):
    """BFS level oracle as {node: hop distance} (`repro.graph.csr.bfs_levels`)."""
    from repro.graph.csr import bfs_levels

    return {int(v): d for d, level in enumerate(bfs_levels(g, source, max_hops))
            for v in level}
