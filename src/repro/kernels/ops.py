"""Backend-dispatching jit wrappers for all kernels.

`use_pallas="auto"` selects the Pallas kernel on TPU and the jnp reference
on CPU/GPU (the multi-pod dry-run therefore lowers the reference path --
FLOP-identical, see DESIGN.md §6). Tests force both paths explicitly.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.segment_reduce import segment_sum as _segsum_pallas
from repro.kernels.embedding_bag import embedding_bag as _bag_pallas
from repro.kernels.frontier import (
    frontier_expand as _frontier_pallas,
    frontier_expand_packed as _frontier_packed_pallas,
    pack_words, unpack_words,
)


def on_tpu() -> bool:
    """The platform check shared by every Pallas-vs-reference switch:
    Pallas lowers natively only on TPU. Elsewhere the wrappers below run
    their kernel interpreted or fall back to the jnp reference, while the
    engine's expansion backends (`core.visited`) refuse and must be asked
    for by their `-interpret` name."""
    return jax.default_backend() == "tpu"


_on_tpu = on_tpu


def _pick(use_pallas) -> bool:
    if use_pallas == "auto":
        return _on_tpu()
    return bool(use_pallas)


def attention(
    q, k, v,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    use_pallas="auto",
    interpret: bool = False,
    allow_chunk: bool = True,
):
    """Multi-head GQA attention. q:(B,Hq,S,D) k/v:(B,Hkv,S,D)."""
    if _pick(use_pallas) and q.shape[2] > 1 and q_offset == 0:
        return _flash(
            q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
            interpret=interpret or not _on_tpu(),
        )
    # long sequences on the jnp path: q-chunked (flash-equivalent memory);
    # keeps the dry-run's memory_analysis O(S) instead of O(S^2).
    if allow_chunk and q.shape[2] * k.shape[2] > 2048 * 2048:
        return _ref.attention_chunked_ref(
            q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
            q_offset=q_offset,
        )
    return _ref.attention_ref(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        q_offset=q_offset,
    )


def segment_sum(values, seg_ids, num_segments: int, use_pallas="auto", interpret: bool = False):
    if _pick(use_pallas):
        return _segsum_pallas(
            values, seg_ids, num_segments, interpret=interpret or not _on_tpu()
        )
    return _ref.segment_sum_ref(values, seg_ids, num_segments)


def segment_mean(values, seg_ids, num_segments: int, use_pallas="auto", interpret: bool = False):
    s = segment_sum(values, seg_ids, num_segments, use_pallas, interpret)
    ones = jnp.ones((values.shape[0], 1), values.dtype)
    cnt = segment_sum(ones, seg_ids, num_segments, use_pallas, interpret)
    return s / jnp.maximum(cnt, 1)


def segment_max(values, seg_ids, num_segments: int, **_):
    """max/min stay on the XLA path (no MXU formulation; VPU-bound anyway)."""
    return _ref.segment_max_ref(values, seg_ids, num_segments)


def segment_min(values, seg_ids, num_segments: int, **_):
    return -_ref.segment_max_ref(-values, seg_ids, num_segments)


def embedding_bag(
    table, indices, weights=None, combine: str = "sum", use_pallas="auto",
    interpret: bool = False,
):
    if _pick(use_pallas):
        return _bag_pallas(
            table, indices, weights, combine=combine,
            interpret=interpret or not _on_tpu(),
        )
    return _ref.embedding_bag_ref(table, indices, weights, combine=combine)


def frontier_expand(rows, deg, visited, use_pallas="auto", interpret: bool = False):
    if _pick(use_pallas):
        return _frontier_pallas(
            rows, deg, visited, interpret=interpret or not _on_tpu()
        )
    return _ref.frontier_expand_ref(rows, deg, visited)


def frontier_expand_packed(
    rows, deg, visited_words, n: int, use_pallas="auto", interpret: bool = False
):
    """Single-query visited update on the BIT-PACKED word layout.

    rows (F, W) int32, deg (F,), visited_words (ceil(n/32),) uint32. The
    Pallas path runs the blocked packed kernel (`kernels.frontier`); the
    reference path unpacks to the dense bool oracle, expands, and re-packs
    -- bit-identical by the pack/unpack roundtrip property
    (tests/test_visited_properties.py). The word layout also makes frontier
    DENSITY cheap: occupancy is one `lax.population_count` reduction over
    the words (see `kernels.frontier.dense_frontier_packed`, the heuristic
    the packed `auto` expansion backend branches on).
    """
    if _pick(use_pallas):
        return _frontier_packed_pallas(
            rows[None], deg[None], visited_words[None], n,
            interpret=interpret or not _on_tpu(),
        )[0]
    rows_in = jnp.where(rows < n, rows, -1)
    dense = unpack_words(visited_words, n)
    return pack_words(_ref.frontier_expand_ref(rows_in, deg, dense))
