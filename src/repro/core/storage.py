"""Decoupled graph storage tier.

The paper's storage tier is RAMCloud: adjacency lists keyed by node id,
hash-partitioned (MurmurHash3) across storage servers, read with a batched
`multi_read`. The TPU-native realization (see DESIGN.md §2):

- rows live in HBM, sharded along the mesh's storage axis (default "model");
  each device along the processor axis ("data") replicates nothing -- it owns
  a slice of queries and reaches storage via collectives.
- `multi_read` = bucket-requests-by-owner + all_to_all over the storage axis
  + local padded-CSR row gather + all_to_all back. This is byte-for-byte the
  RAMCloud multi_read dataflow with ICI playing Infiniband.

Entry points:
  - StorageTier: host-side container (numpy), built once by build_storage.
  - StorageArrays / device_storage: the same tables on the device, placed
    once and passed into jitted serving code as ARGUMENTS -- never closed
    over, so a compiled program's size does not grow with the graph;
    multi_read_arrays is the single-device read over them.
  - sharded_multi_read: the shard_map body (pure function of local shards)
    usable inside any shard_map'd serving step.
  - make_serving_storage: the device tables as the dict the shard_map
    serving step takes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.csr import CSRGraph, PaddedAdjacency, to_padded
from repro.graph.partition import splitmix64


@dataclasses.dataclass
class StorageTier:
    """Host-side decoupled storage: padded adjacency + hash placement.

    Rows are *re-indexed by shard*: shard s holds rows for all row-ids r with
    owner(r) == s, densely packed in local slot order. `loc` maps global row
    id -> local slot; `owner` maps global row id -> shard.
    Continuation rows are placed like ordinary rows (their ids >= n).

    A row's `deg` is its node's remaining degree (`PaddedAdjacency`): the
    value length a key-value multi_read of a base row returns, and of a
    continuation row what is left of the adjacency. The row's own entries
    are the first min(deg, W). A node's continuation rows are consecutive
    ids from its base row's `cont`, so a reader of the base row knows every
    row id of the chain: cont + j for j < ceil(deg / W) - 1.
    """

    n_shards: int
    rows_per_shard: int
    shard_rows: np.ndarray  # (S, rows_per_shard, W) int32
    shard_deg: np.ndarray  # (S, rows_per_shard) int32
    shard_cont: np.ndarray  # (S, rows_per_shard) int32
    owner: np.ndarray  # (n_rows,) int32
    loc: np.ndarray  # (n_rows,) int32
    n: int  # real nodes
    n_rows: int  # incl. continuation rows

    @property
    def row_width(self) -> int:
        return int(self.shard_rows.shape[2])


def build_storage(adj: PaddedAdjacency, n_shards: int, seed: int = 0) -> StorageTier:
    n_rows = adj.n_rows
    h = splitmix64(np.arange(n_rows, dtype=np.uint64) + np.uint64(seed * 1315423911))
    owner = (h % np.uint64(n_shards)).astype(np.int32)
    loc = np.zeros(n_rows, dtype=np.int32)
    counts = np.zeros(n_shards, dtype=np.int64)
    order = np.argsort(owner, kind="stable")
    # local slot = rank within shard
    for s in range(n_shards):
        ids = order[owner[order] == s]
        loc[ids] = np.arange(ids.size, dtype=np.int32)
        counts[s] = ids.size
    rows_per_shard = int(counts.max()) if n_rows else 1
    shard_rows = np.full((n_shards, rows_per_shard, adj.max_degree), -1, dtype=np.int32)
    shard_deg = np.zeros((n_shards, rows_per_shard), dtype=np.int32)
    shard_cont = np.full((n_shards, rows_per_shard), -1, dtype=np.int32)
    shard_rows[owner, loc] = adj.rows
    shard_deg[owner, loc] = adj.degree
    shard_cont[owner, loc] = adj.cont
    return StorageTier(
        n_shards=n_shards,
        rows_per_shard=rows_per_shard,
        shard_rows=shard_rows,
        shard_deg=shard_deg,
        shard_cont=shard_cont,
        owner=owner,
        loc=loc,
        n=adj.n,
        n_rows=n_rows,
    )


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["rows", "deg", "cont", "owner", "loc"],
                   meta_fields=["n"])
@dataclasses.dataclass(frozen=True)
class StorageArrays:
    """The storage tier's tables as one pytree of arrays (device or abstract).

    `n` (real nodes, the visited-bitmap width) is static metadata, so a
    jitted function that takes a StorageArrays gets every O(n) table as an
    argument and `n` as part of its signature."""

    rows: jax.Array  # (S, rows_per_shard, W) int32
    deg: jax.Array  # (S, rows_per_shard) int32
    cont: jax.Array  # (S, rows_per_shard) int32
    owner: jax.Array  # (n_rows,) int32
    loc: jax.Array  # (n_rows,) int32
    n: int

    @property
    def row_width(self) -> int:
        return int(self.rows.shape[2])


def device_storage(tier: StorageTier, device=None) -> StorageArrays:
    """Place the tier's tables on `device` (default: the first) once."""
    put = functools.partial(jax.device_put, device=device)
    return StorageArrays(
        rows=put(tier.shard_rows), deg=put(tier.shard_deg),
        cont=put(tier.shard_cont), owner=put(tier.owner), loc=put(tier.loc),
        n=tier.n,
    )


def multi_read_arrays(
    store: StorageArrays, ids: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-device multi_read over the storage tables.

    ids: (B,) int32 row ids (-1 = no-op). Returns (rows (B, W), deg (B,), cont (B,)).
    """
    safe = jnp.maximum(ids, 0)
    o, l = store.owner[safe], store.loc[safe]
    rows = store.rows[o, l]
    deg = store.deg[o, l]
    cont = store.cont[o, l]
    invalid = ids < 0
    return (
        jnp.where(invalid[:, None], -1, rows),
        jnp.where(invalid, 0, deg),
        jnp.where(invalid, -1, cont),
    )


def multi_read_ref(
    tier: StorageTier, ids: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Reference multi_read straight from the host tier (tests / simulator
    path; under jit its tables become constants of the program)."""
    return multi_read_arrays(device_storage(tier), ids)


# ---------------------------------------------------------------------------
# Distributed multi_read: the shard_map body.
# ---------------------------------------------------------------------------


def bucket_by_owner(
    ids: jax.Array, owners: jax.Array, n_shards: int, capacity: int
) -> Tuple[jax.Array, jax.Array]:
    """Pack request ids into an (n_shards, capacity) matrix bucketed by owner.

    Returns (buckets (S, C) int32 padded -1,
             slot   (B,) int32 position of each request inside its bucket,
             or -1 if dropped due to capacity overflow).
    Position assignment is by stable order of appearance (argsort by owner).
    """
    B = ids.shape[0]
    valid = ids >= 0
    owners_v = jnp.where(valid, owners, n_shards)  # invalid -> overflow bucket
    # rank of each request within its owner group
    order = jnp.argsort(owners_v, stable=True)  # (B,)
    sorted_owners = owners_v[order]
    # position within group = index - first index of group
    idx = jnp.arange(B)
    first_of_group = jnp.searchsorted(sorted_owners, sorted_owners, side="left")
    pos_sorted = idx - first_of_group
    pos = jnp.zeros((B,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))
    keep = valid & (pos < capacity)
    slot = jnp.where(keep, pos, -1)
    buckets = jnp.full((n_shards, capacity), -1, jnp.int32)
    # non-kept entries scatter to an out-of-bounds row and are dropped, so
    # they can never clobber slot (0, 0)
    buckets = buckets.at[
        jnp.where(keep, owners, n_shards), jnp.where(keep, pos, 0)
    ].set(ids, mode="drop")
    # note: dropped requests (slot == -1) are re-issued by the engine next
    # round; capacity is sized to make this rare (see QueryEngineConfig).
    return buckets, slot


def sharded_multi_read(
    ids: jax.Array,
    local_rows: jax.Array,
    local_deg: jax.Array,
    local_cont: jax.Array,
    owner_lut: jax.Array,
    loc_lut: jax.Array,
    axis_name: str,
    n_shards: int,
    capacity: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """RAMCloud multi_read over ICI. Call INSIDE shard_map.

    ids:        (B,) int32 this processor's batched requests (-1 padded).
    local_*:    this device's storage shard (rows_per_shard, ...).
    owner_lut/loc_lut: (n_rows,) replicated placement tables.
    axis_name:  the storage mesh axis ("model").
    capacity:   per-(requester, shard) request budget for the all_to_all.

    Returns (rows (B, W), deg (B,), cont (B,), served (B,) bool). Requests
    that overflowed `capacity` have served=False and must be retried.
    """
    owners = owner_lut[jnp.maximum(ids, 0)]
    owners = jnp.where(ids >= 0, owners, 0)
    buckets, slot = bucket_by_owner(ids, owners, n_shards, capacity)  # (S, C)

    # ship request ids to their owning shard: after all_to_all, device j holds
    # the requests all shards' peers addressed to shard j: shape (S, C) where
    # axis 0 = requester index along the storage axis.
    req = jax.lax.all_to_all(buckets, axis_name, split_axis=0, concat_axis=0, tiled=True)

    # local gather
    safe = jnp.maximum(req, 0)
    l = loc_lut[safe]
    g_rows = local_rows[l]  # (S*C? , W) -- req is (S, C) so result (S, C, W)
    g_deg = local_deg[l]
    g_cont = local_cont[l]
    inval = req < 0
    g_rows = jnp.where(inval[..., None], -1, g_rows)
    g_deg = jnp.where(inval, 0, g_deg)
    g_cont = jnp.where(inval, -1, g_cont)

    # ship results back
    r_rows = jax.lax.all_to_all(g_rows, axis_name, split_axis=0, concat_axis=0, tiled=True)
    r_deg = jax.lax.all_to_all(g_deg, axis_name, split_axis=0, concat_axis=0, tiled=True)
    r_cont = jax.lax.all_to_all(g_cont, axis_name, split_axis=0, concat_axis=0, tiled=True)
    # r_rows: (S, C, W) -- bucket layout of OUR original requests

    served = slot >= 0
    o_sel = jnp.where(served, owners, 0)
    s_sel = jnp.where(served, slot, 0)
    rows = jnp.where(served[:, None], r_rows[o_sel, s_sel], -1)
    deg = jnp.where(served, r_deg[o_sel, s_sel], 0)
    cont = jnp.where(served, r_cont[o_sel, s_sel], -1)
    return rows, deg, cont, served


def sharded_feature_gather(
    ids: jax.Array,  # (M,) int32 global row ids (-1 padded)
    local_feat: jax.Array,  # (rows_per_shard, F) this shard's feature rows
    axis_name,  # storage axis name or tuple of names (flattened group)
    n_shards: int,
    capacity: int,
) -> Tuple[jax.Array, jax.Array]:
    """Generalized multi_read with a float payload: fetch feature rows by
    global id from their owning shards. This is byte-for-byte the RAMCloud
    multi_read dataflow (bucket-by-owner -> all_to_all -> local gather ->
    all_to_all back) carrying embeddings/activations instead of adjacency --
    the paper's decoupled-storage access pattern reused as the distributed
    GNN/recsys gather (DESIGN.md §4).

    Placement is analytic: owner(r) = r % n_shards, loc(r) = r // n_shards
    (round-robin striping; no LUT -- O(1) instead of O(n) router state).
    Returns (features (M, F), served (M,) bool).
    """
    valid = ids >= 0
    owners = jnp.where(valid, ids % n_shards, 0).astype(jnp.int32)
    buckets, slot = bucket_by_owner(ids, owners, n_shards, capacity)  # (S, C)
    req = jax.lax.all_to_all(buckets, axis_name, split_axis=0, concat_axis=0, tiled=True)
    l = jnp.where(req >= 0, req // n_shards, 0)
    g = local_feat[l]  # (S, C, F)
    g = jnp.where((req >= 0)[..., None], g, 0)
    back = jax.lax.all_to_all(g, axis_name, split_axis=0, concat_axis=0, tiled=True)
    served = slot >= 0
    o_sel = jnp.where(served, owners, 0)
    s_sel = jnp.where(served, slot, 0)
    out = jnp.where(served[:, None], back[o_sel, s_sel], 0)
    return out, served


def stripe_rows(x: np.ndarray, n_shards: int) -> np.ndarray:
    """Host-side layout for sharded_feature_gather: row r of the global array
    goes to shard r % n_shards, local slot r // n_shards. Returns
    (n_shards * rows_per_shard, F) array laid out shard-major so a
    PartitionSpec over dim 0 places each shard's rows on its device."""
    n, f = x.shape
    rows_per_shard = -(-n // n_shards)
    out = np.zeros((n_shards, rows_per_shard, f), x.dtype)
    r = np.arange(n)
    out[r % n_shards, r // n_shards] = x
    return out.reshape(n_shards * rows_per_shard, f)


def make_serving_storage(tier: StorageTier):
    """Arrays for the distributed path: per-shard rows (S, rows_per_shard,
    W) to be placed with sharding over the storage axis, plus replicated
    placement LUTs."""
    store = device_storage(tier)
    return {k: getattr(store, k) for k in ("rows", "deg", "cont", "owner", "loc")}
