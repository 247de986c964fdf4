"""Query-processor cache: k-way set-associative, LRU-within-set.

The paper uses an LRU cache of adjacency lists at each query processor
(§2.3). Linked-list LRU is pointer-chasing and does not vectorize; the
TPU-native equivalent implemented here is the classic hardware cache design:

  set   = hash(key) mod n_sets
  probe = compare `tags[set, :]` against key across all ways (vectorized)
  hit   -> refresh the way's age to the current clock (LRU recency)
  miss  -> evict the way with the smallest age (least recently used in set)

All state is dense arrays (a pytree), every operation is batched over a
vector of keys and fully jit-able; this preserves the paper's LRU recency
semantics (exactly LRU within each set) while mapping onto TPU vector units.

The cache stores padded adjacency rows: data[set, way, :] = neighbor ids,
deg[set, way] = remaining degree, cont[set, way] = continuation row id (see
repro.graph.csr.PaddedAdjacency).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CacheState:
    tags: jax.Array  # (n_sets, n_ways) int32, -1 = empty
    age: jax.Array  # (n_sets, n_ways) int32
    data: jax.Array  # (n_sets, n_ways, row_width) int32
    deg: jax.Array  # (n_sets, n_ways) int32
    cont: jax.Array  # (n_sets, n_ways) int32
    clock: jax.Array  # () int32
    hits: jax.Array  # () int32 cumulative
    misses: jax.Array  # () int32 cumulative

    @property
    def n_sets(self) -> int:
        return self.tags.shape[0]

    @property
    def n_ways(self) -> int:
        return self.tags.shape[1]

    @property
    def row_width(self) -> int:
        return self.data.shape[2]

    @property
    def capacity(self) -> int:
        return self.n_sets * self.n_ways


def make_cache(n_sets: int, n_ways: int, row_width: int) -> CacheState:
    return CacheState(
        tags=jnp.full((n_sets, n_ways), -1, jnp.int32),
        age=jnp.zeros((n_sets, n_ways), jnp.int32),
        data=jnp.full((n_sets, n_ways, row_width), -1, jnp.int32),
        deg=jnp.zeros((n_sets, n_ways), jnp.int32),
        cont=jnp.full((n_sets, n_ways), -1, jnp.int32),
        clock=jnp.zeros((), jnp.int32),
        hits=jnp.zeros((), jnp.int32),
        misses=jnp.zeros((), jnp.int32),
    )


def cache_bytes(state: CacheState) -> int:
    """Host-side: cache storage footprint in bytes (for Fig-11-style sweeps)."""
    per_entry = 4 * (1 + 1 + state.row_width + 1 + 1)
    return state.capacity * per_entry


def _hash_keys(keys: jax.Array, n_sets: int) -> jax.Array:
    """splitmix32-style avalanche; int32-safe."""
    x = keys.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return (x % jnp.uint32(n_sets)).astype(jnp.int32)


def cache_lookup(
    state: CacheState, keys: jax.Array, valid: jax.Array | None = None,
    probes: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, CacheState]:
    """Batched probe.

    keys: (B,) int32 node ids (may contain -1 / invalid entries).
    valid: optional (B,) bool mask; invalid keys never hit and don't count.
    probes: optional (B,) int32 -- how many probes each key stands for in
    the hit/miss counters (a pooled key read once for several queries);
    default one each.

    Returns (found (B,) bool, rows (B, W) int32, degs (B,), conts (B,),
    new_state with refreshed ages + stats).
    """
    if valid is None:
        valid = keys >= 0
    sets = _hash_keys(jnp.maximum(keys, 0), state.n_sets)  # (B,)
    set_tags = state.tags[sets]  # (B, ways)
    match = (set_tags == keys[:, None]) & valid[:, None]  # (B, ways)
    found = jnp.any(match, axis=1)
    way = jnp.argmax(match, axis=1)  # valid only where found
    rows = state.data[sets, way]  # (B, W)
    degs = jnp.where(found, state.deg[sets, way], 0)
    conts = jnp.where(found, state.cont[sets, way], -1)
    rows = jnp.where(found[:, None], rows, -1)

    # refresh age on hit (LRU recency). Duplicate keys in the batch touch the
    # same slot; last write wins which is exactly LRU for a batch processed
    # "simultaneously".
    new_age = state.age.at[
        jnp.where(found, sets, 0), jnp.where(found, way, 0)
    ].max(jnp.where(found, state.clock + 1, -1), mode="drop")
    if probes is None:
        probes = jnp.ones(keys.shape, jnp.int32)
    n_hit = jnp.sum(jnp.where(found & valid, probes, 0)).astype(jnp.int32)
    n_miss = jnp.sum(jnp.where(valid, probes, 0)).astype(jnp.int32) - n_hit
    new_state = dataclasses.replace(
        state,
        age=new_age,
        clock=state.clock + 1,
        hits=state.hits + n_hit,
        misses=state.misses + n_miss,
    )
    return found, rows, degs, conts, new_state


def cache_insert(
    state: CacheState,
    keys: jax.Array,
    rows: jax.Array,
    degs: jax.Array,
    conts: jax.Array,
    valid: jax.Array | None = None,
) -> CacheState:
    """Batched insert with LRU-within-set eviction.

    Collision policy inside one batch: if two *distinct* keys map to the same
    (set, way) victim, one insert is lost (the last scatter wins) -- a lost
    insert is benign cache behaviour (the entry is simply not cached) and is
    the price of a fully-parallel insert; sets are sized so this is rare.
    Duplicate keys should be deduped by the caller (query engine dedups
    frontiers by construction).
    """
    if valid is None:
        valid = keys >= 0
    sets = _hash_keys(jnp.maximum(keys, 0), state.n_sets)
    set_tags = state.tags[sets]  # (B, ways)
    # if the key is already present, reuse its way; else evict LRU way
    match = set_tags == keys[:, None]
    present = jnp.any(match, axis=1)
    match_way = jnp.argmax(match, axis=1)
    lru_way = jnp.argmin(state.age[sets], axis=1)
    # distinct new keys that collide on one set in the SAME batch must land
    # in distinct ways: offset each by its arrival rank within the set
    # (rank 0 takes the LRU way, rank 1 the next, ...). Without this they
    # would all pick the same argmin way and only the last insert survives.
    B = keys.shape[0]
    grp = jnp.where(valid & ~present, sets, state.n_sets)  # inserts only
    order = jnp.argsort(grp, stable=True)
    sorted_grp = grp[order]
    first = jnp.searchsorted(sorted_grp, sorted_grp, side="left")
    rank_sorted = jnp.arange(B) - first
    rank = jnp.zeros((B,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    way = jnp.where(present, match_way, (lru_way + rank) % state.n_ways)

    # invalid entries scatter to an out-of-bounds set and are dropped; they
    # must never be clamped to a real slot or they would overwrite genuine
    # inserts landing there earlier in the batch (last scatter wins).
    sets_w = jnp.where(valid, sets, state.n_sets)
    age_val = jnp.full((B,), state.clock + 1, state.age.dtype)

    return dataclasses.replace(
        state,
        tags=state.tags.at[sets_w, way].set(keys, mode="drop"),
        age=state.age.at[sets_w, way].set(age_val, mode="drop"),
        deg=state.deg.at[sets_w, way].set(degs, mode="drop"),
        cont=state.cont.at[sets_w, way].set(conts, mode="drop"),
        data=state.data.at[sets_w, way].set(rows, mode="drop"),
        clock=state.clock + 1,
    )


def hit_rate(state: CacheState) -> jax.Array:
    total = state.hits + state.misses
    return jnp.where(total > 0, state.hits / jnp.maximum(total, 1), 0.0)
