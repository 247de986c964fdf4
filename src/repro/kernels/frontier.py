"""BFS frontier-expansion Pallas TPU kernels (gRouting's hot loop).

One hop of Algorithm 5: given the adjacency rows of the current frontier
and the visited bitmap, mark all neighbors visited.

TPU adaptation: vector units have no scatter, so the bitmap update is
reformulated as a *compare-reduce* over node blocks:

  step (q, b): visited[q, b*BN : (b+1)*BN] |= any_e(nbrs[q] == node_ids(b))

Total work is O(F*W*n) compares per query -- compute-rich but scatter-free,
the classic TPU trade. For sparse frontiers the engine's jnp scatter path
(`kernels.ref.frontier_expand_ref` / the `scatter` expansion backend) wins;
the kernel pays off for dense frontiers where compares are amortized
(candidate neighbors >= n / DENSE_RATIO, typical in hotspot serving with
warm caches) -- `dense_frontier` below is that selection heuristic, used by
the engine's `auto` expansion backend. Both paths are semantically
identical (tests sweep shapes; `tests/test_expand_backends.py` is the
backend-differential oracle).

Entry points (two kernel programs sharing one chunk loop):

  - `frontier_expand_batched`  -- whole admitted batch: rows (B, F, W),
    visited (B, n) bool; grid (query, node-block) so ONE kernel launch
    expands every query of a processor round. This is the variant
    `core.query_engine.expand_hop` mounts behind the `pallas` backend of
    the DENSE visited layout.
  - `frontier_expand_packed`   -- the BIT-PACKED variant: visited is
    (B, ceil(n/32)) uint32 words (8x smaller than the bool bitmap), grid
    (query, word-block). Each neighbor ORs `1 << (v % 32)` into the lane of
    word v // 32, and the chunk's rows are OR-reduced one bit plane at a
    time. This is the `pallas` backend of the PACKED visited layout
    (`core.visited.PackedVisited`).
  - `frontier_expand`          -- single query: rows (F, W), visited (n,);
    a thin B=1 view over the batched dense kernel.

Word-layout helpers (`pack_words` / `unpack_words` / `n_words`) live here
too: the packed kernel defines the word order (little-endian bits, node id
= word * 32 + bit), so the pure-jnp pack/unpack math is co-located with it
and `core.visited` consumes both.

TPU layout rules the kernels follow (Mosaic refuses the rest):

  - the query axis is squeezed out of every block (`None` block dim), and a
    visited row is viewed as (B, 1, n) so its (1, BN) block covers the full
    second-minor dim; BN and BW are multiples of 128 lanes;
  - the compare never flattens (F, W) into one axis: each neighbor column
    (BF, 1) is compared against the node ids on lanes (1, BN);
  - reductions are int32 maxima over sublanes; packed words are bitcast to
    int32 around the kernel (Mosaic has no unsigned reductions);
  - degree masking happens in XLA before the call (`_mask_rows`), so the
    kernel reads one input besides the bitmap.

The frontier axis is a loop INSIDE the kernel over BF-row chunks; a chunk
with no valid neighbor is skipped, so a drained or short frontier costs one
max per chunk. VMEM per grid step, double-buffered inputs: the frontier
block F_pad * 128 lanes * 4 B * 2 (2 MiB at F=2048; W <= 128 pads to 128
lanes), the bitmap blocks 4 * 8 sublanes * BN * 4 B (256 KiB at BN=2048;
bool travels as int32), and the (BF, BN) int32 accumulator (256 KiB at
BF=32, BN=2048). That is under the 16 MiB default scoped limit of a v5e,
so no `vmem_limit_bytes` is set.

Retrace discipline: block sizes are never clamped to the input (`min(bf,
F)` would make the static grid a function of the frontier size and retrace
per distinct F). Instead inputs are padded UP to whole blocks in a thin
host wrapper OUTSIDE the jit boundary, so every frontier size in the same
bucket of BF shares one trace (`tests/test_expand_backends.py` pins the
trace counts).
"""

from __future__ import annotations

import functools
from collections import Counter

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BF = 32  # frontier rows per chunk
DEFAULT_BN = 2048  # visited nodes per block (dense kernel)
WORD_BITS = 32  # packed layout: node id = word * 32 + bit (little-endian)
DEFAULT_BW = 256  # packed words per visited block (8192 nodes)
DENSE_RATIO = 8  # compare-reduce pays off once candidates >= n / DENSE_RATIO

# trace-regression instrumentation: each retrace of a jitted padded kernel
# re-executes its python body and bumps its counter (tests assert that
# bucketed padding keeps this flat across frontier sizes)
TRACE_COUNTS: Counter = Counter()


def dense_frontier(deg: jax.Array, n: int, ratio: int = DENSE_RATIO) -> jax.Array:
    """Density heuristic: is the compare-reduce kernel worth launching?

    deg: (..., F) int32 per-frontier-row neighbor counts (0 for -1-padded
    rows). Returns a () bool: total candidate neighbors across the batch
    >= total bitmap bits / ratio. Traced (usable as a `lax.cond` predicate
    inside the serving scan).
    """
    bits = 1
    for d in deg.shape[:-1]:
        bits *= d
    bits *= n
    return jnp.sum(deg) * ratio >= bits


# ---------------------------------------------------------------------------
# Packed-word layout math. The kernel below fixes the word order (node id =
# word * WORD_BITS + bit); these jnp helpers are the same layout in pure XLA
# and are what `core.visited.PackedVisited` packs/unpacks with.
# ---------------------------------------------------------------------------


def n_words(n: int) -> int:
    """uint32 words needed for an n-bit visited row."""
    return -(-n // WORD_BITS)


def pack_words(dense: jax.Array) -> jax.Array:
    """(..., n) bool -> (..., ceil(n/32)) uint32; bit b of word w = node
    w*32+b. Padding bits (>= n) are zero, so popcounts stay exact."""
    n = dense.shape[-1]
    nw = n_words(n)
    x = _pad_axis(dense, dense.ndim - 1, nw * WORD_BITS - n, False)
    x = x.reshape(dense.shape[:-1] + (nw, WORD_BITS)).astype(jnp.uint32)
    bits = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    return jnp.sum(x << bits, axis=-1).astype(jnp.uint32)


def unpack_words(words: jax.Array, n: int) -> jax.Array:
    """(..., ceil(n/32)) uint32 -> (..., n) bool (inverse of pack_words)."""
    bits = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    x = (words[..., None] >> bits) & jnp.uint32(1)
    x = x.reshape(words.shape[:-1] + (words.shape[-1] * WORD_BITS,))
    return x[..., :n].astype(bool)


def dense_frontier_packed(
    deg: jax.Array, visited_words: jax.Array, n: int, ratio: int = DENSE_RATIO
) -> jax.Array:
    """Popcount-refined density heuristic for the packed layout.

    Same shape as `dense_frontier`, but the candidate count is weighed
    against the UNVISITED bit budget (total bits minus the word popcounts):
    already-set bits cannot yield new marks, so as the bitmap fills the
    scatter path's useful-work fraction shrinks and the fixed-cost
    compare-reduce pass wins earlier. On the packed words the occupancy is
    one `population_count` reduction -- effectively free, which is the point
    of keeping the heuristic ON the packed representation."""
    bits = 1
    for d in deg.shape[:-1]:
        bits *= d
    bits *= n
    occupied = jnp.sum(jax.lax.population_count(visited_words)).astype(jnp.int32)
    unvisited = jnp.maximum(bits - occupied, 0)
    return jnp.sum(deg) * ratio >= unvisited


def _pad_axis(x: jax.Array, axis: int, pad: int, value) -> jax.Array:
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def frontier_expand(
    rows: jax.Array,  # (F, W) int32 adjacency rows, -1 padded
    deg: jax.Array,  # (F,) int32
    visited: jax.Array,  # (n,) bool
    bf: int = DEFAULT_BF,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
) -> jax.Array:
    """One BFS hop for a single query: the batched kernel viewed at B=1."""
    return frontier_expand_batched(
        rows[None], deg[None], visited[None], bf=bf, bn=bn, interpret=interpret
    )[0]


def _mask_rows(rows: jax.Array, deg: jax.Array) -> jax.Array:
    """Neighbor ids with every invalid entry (past the row's degree, or
    already -1) set to -1, so the kernels compare ids only. -1 never equals
    a node id, so padding cannot mark anything."""
    width = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 2)
    return jnp.where((rows >= 0) & (width < deg[..., None]), rows, -1)


def _for_active_chunks(rows_ref, bf: int, body) -> None:
    """Run body(rows (bf, W)) over the frontier chunks that hold a neighbor.

    A drained frontier (all -1) costs one max per chunk and no compares."""

    def chunk(c, carry):
        rows = rows_ref[pl.ds(pl.multiple_of(c * bf, bf), bf), :]
        pl.when(jnp.max(rows) >= 0)(lambda: body(rows))
        return carry

    jax.lax.fori_loop(0, rows_ref.shape[0] // bf, chunk, 0)


def _frontier_batched_kernel(rows_ref, vis_in_ref, vis_out_ref, *, bf: int, bn: int):
    # rows_ref (Fp, W) masked ids; vis blocks (1, bn) bool, node b*bn + lane
    ids = pl.program_id(1) * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    vis_out_ref[...] = vis_in_ref[...]

    def mark(rows):
        # compare one neighbor column at a time: (bf, 1) vs (1, bn) lanes, so
        # no reshape crosses the (sublane, lane) tiling
        acc = jnp.zeros((bf, bn), jnp.int32)
        for w in range(rows.shape[1]):
            acc = acc | (rows[:, w:w + 1] == ids).astype(jnp.int32)
        hit = jnp.max(acc, axis=0, keepdims=True) > 0
        vis_out_ref[...] = vis_out_ref[...] | hit

    _for_active_chunks(rows_ref, bf, mark)


@functools.partial(jax.jit, static_argnames=("bf", "bn", "interpret"))
def _frontier_batched_padded(rows, deg, vis, *, bf: int, bn: int, interpret: bool):
    TRACE_COUNTS["frontier_expand_batched"] += 1
    B, Fp, W = rows.shape
    npad = vis.shape[1]
    out = pl.pallas_call(
        functools.partial(_frontier_batched_kernel, bf=bf, bn=bn),
        grid=(B, npad // bn),
        in_specs=[
            # the whole frontier of query q: its block index does not change
            # along the node axis, so it is fetched once per query
            pl.BlockSpec((None, Fp, W), lambda q, b: (q, 0, 0)),
            pl.BlockSpec((None, 1, bn), lambda q, b: (q, 0, b)),
        ],
        out_specs=pl.BlockSpec((None, 1, bn), lambda q, b: (q, 0, b)),
        out_shape=jax.ShapeDtypeStruct((B, 1, npad), vis.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(_mask_rows(rows, deg), vis.reshape(B, 1, npad))
    return out.reshape(B, npad)


def frontier_expand_batched(
    rows: jax.Array,  # (B, F, W) int32 adjacency rows of every query, -1 padded
    deg: jax.Array,  # (B, F) int32
    visited: jax.Array,  # (B, n) bool
    bf: int = DEFAULT_BF,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
) -> jax.Array:
    """One BFS hop for a whole query batch in ONE kernel launch.

    grid = (query, node-block), frontier chunks looped inside; each query's
    rows are the per-hop gather from the cache/storage read results, so
    this is the expansion step `expand_hop` mounts behind the `pallas`
    backend. F and n are padded up to whole (bf, bn) blocks here, outside
    the jit boundary -- NOT clamped into the block size -- so any F in the
    same bf bucket reuses one compiled trace.
    """
    B, F, W = rows.shape
    n = visited.shape[1]
    rows = _pad_axis(rows, 1, (-F) % bf, -1)
    deg = _pad_axis(deg, 1, (-F) % bf, 0)
    vis = _pad_axis(visited, 1, (-n) % bn, False)
    out = _frontier_batched_padded(rows, deg, vis, bf=bf, bn=bn, interpret=interpret)
    return out[:, :n]


# ---------------------------------------------------------------------------
# Bit-packed blocked kernel: visited as (B, ceil(n/32)) uint32 words
# ---------------------------------------------------------------------------


def _frontier_packed_kernel(rows_ref, vis_in_ref, vis_out_ref, *, bf: int, bw: int):
    # vis blocks (1, bw) int32 words, word b*bw + lane; bit j = node word*32+j
    word = pl.program_id(1) * bw + jax.lax.broadcasted_iota(jnp.int32, (1, bw), 1)
    vis_out_ref[...] = vis_in_ref[...]

    def mark(rows):
        # each neighbor v sets bit v % 32 of word v // 32: OR its bit into the
        # lane of its word ((-1 >> 5) == -1 matches no word) ...
        acc = jnp.zeros((bf, bw), jnp.int32)
        for w in range(rows.shape[1]):
            v = rows[:, w:w + 1]
            bit = jnp.left_shift(jnp.int32(1), v & (WORD_BITS - 1))
            acc = acc | jnp.where((v >> 5) == word, bit, 0)
        # ... then OR the bf rows together, one bit plane at a time: an int32
        # max over sublanes is a reduction the TPU has, a bitwise OR is not
        words = jnp.zeros((1, bw), jnp.int32)
        for j in range(WORD_BITS):
            plane = jnp.max((acc >> j) & 1, axis=0, keepdims=True)
            words = words | (plane << j)
        vis_out_ref[...] = vis_out_ref[...] | words

    _for_active_chunks(rows_ref, bf, mark)


@functools.partial(jax.jit, static_argnames=("bf", "bw", "interpret"))
def _frontier_packed_padded(rows, deg, vis, *, bf: int, bw: int, interpret: bool):
    TRACE_COUNTS["frontier_expand_packed"] += 1
    B, Fp, W = rows.shape
    nwpad = vis.shape[1]
    # uint32 words travel as int32 bit patterns (no unsigned vector ops on TPU)
    words = jax.lax.bitcast_convert_type(vis, jnp.int32).reshape(B, 1, nwpad)
    out = pl.pallas_call(
        functools.partial(_frontier_packed_kernel, bf=bf, bw=bw),
        grid=(B, nwpad // bw),
        in_specs=[
            pl.BlockSpec((None, Fp, W), lambda q, b: (q, 0, 0)),
            pl.BlockSpec((None, 1, bw), lambda q, b: (q, 0, b)),
        ],
        out_specs=pl.BlockSpec((None, 1, bw), lambda q, b: (q, 0, b)),
        out_shape=jax.ShapeDtypeStruct((B, 1, nwpad), jnp.int32),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(_mask_rows(rows, deg), words)
    return jax.lax.bitcast_convert_type(out.reshape(B, nwpad), jnp.uint32)


def frontier_expand_packed(
    rows: jax.Array,  # (B, F, W) int32 adjacency rows of every query, -1 padded
    deg: jax.Array,  # (B, F) int32
    visited_words: jax.Array,  # (B, ceil(n/32)) uint32 packed bitmap
    n: int,  # bitmap width in BITS (<= words * 32)
    bf: int = DEFAULT_BF,
    bw: int = DEFAULT_BW,
    interpret: bool = False,
) -> jax.Array:
    """One BFS hop over the BIT-PACKED visited layout, one kernel launch.

    grid = (query, word-block), frontier chunks looped inside; each word
    block covers bw * 32 node ids and ORs packed hit words into the output.
    `n` is needed explicitly because the word array
    over-covers the id range: ids in [n, words*32) are masked to pad here
    so padding bits inside the last word stay zero and popcount-based
    result counts stay exact. Same pad-up-never-clamp bucketing as the
    dense kernel (F to whole bf blocks, words to whole bw blocks)."""
    B, F, W = rows.shape
    nw = visited_words.shape[1]
    assert nw * WORD_BITS >= n, (nw, n)
    rows = jnp.where(rows < n, rows, -1)
    rows = _pad_axis(rows, 1, (-F) % bf, -1)
    deg = _pad_axis(deg, 1, (-F) % bf, 0)
    vis = _pad_axis(visited_words, 1, (-nw) % bw, 0)
    out = _frontier_packed_padded(rows, deg, vis, bf=bf, bw=bw, interpret=interpret)
    return out[:, :nw]
