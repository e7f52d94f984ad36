"""Pallas TPU kernels: packed-key segment-min (sparse MSF path).

TPU adaptation of the paper's sparse multilinear kernel: TPUs have no
vectorized scatter, so instead of CRCW min-writes we reduce with a
compare-broadcast-min over VMEM tiles:

    out[r] = min over edges e { keys[e] : seg[e] == r }

Keys are the pack32 layout (weight << 24 | idx) from ``repro.core.semiring``
— a single uint32 min implements the full MINWEIGHT monoid in the paper's
integer-weight regime. Identity/padding = 0xFFFFFFFF.

Two layouts:

- ``segment_min_bucketed_pallas`` — edges pre-bucketed by output row block
  (host side, part of graph partitioning); one grid step per bucket.
- ``segment_min_flat_pallas``     — flat [E] edge arrays with arbitrary
  (possibly unsorted) segment ids, as produced *inside* jit by the MSF
  hook loop and the coarsening dedupe; grid = (row blocks, edge blocks),
  the row block's accumulator stays resident in VMEM and takes the min
  across the sequential edge-block dimension.

TPU tiling (shared with ``segment_min_sorted``): edge arrays are viewed
as ``[E / 128, 128]`` and read in ``(block_edges / 128, 128)`` tiles —
whole (8, 128) vregs. Each row block of ``block_rows`` segments keeps a
``(block_rows, 128)`` int32 accumulator in VMEM scratch: row r, lane l
holds the min over the edges in lane l whose segment is r, so the edge
loop is pure elementwise compare/select/min with no cross-lane work.
The final lane reduction runs once per row block, on 128 × 128
transposes, into a lane-dense ``[num_segments / 128, 128]`` output.
Inside the kernels the uint32 keys travel as order-preserving int32
(sign bit flipped): every compare and min is then a signed 32-bit op.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: Edges per (8, 128) vreg tile; edge blocks are multiples of it.
TILE_EDGES = 8 * LANES
_IMAX = np.int32(np.iinfo(np.int32).max)  # the identity 0xFFFFFFFF, sign-flipped
_SIGN = np.uint32(0x80000000)


def to_ordered_i32(keys: jax.Array) -> jax.Array:
    """uint32 keys → int32 with the same order (flip the sign bit)."""
    return jax.lax.bitcast_convert_type(keys ^ _SIGN, jnp.int32)


def from_ordered_i32(x: jax.Array) -> jax.Array:
    """Inverse of :func:`to_ordered_i32`."""
    return jax.lax.bitcast_convert_type(x, jnp.uint32) ^ _SIGN


def _accumulate(keys_ref, segs_ref, acc_ref, base):
    """Fold one edge tile into the row block's accumulator.

    ``keys_ref``/``segs_ref``: ``(sub, 128)`` int32 (ordered keys, global
    segment ids); ``acc_ref``: ``(rows, 128)`` int32 for segments
    ``[base, base + rows)``. Edges whose segment falls outside the row
    block match no row and contribute nothing.
    """
    rows = acc_ref.shape[0]
    sub = keys_ref.shape[0]
    tile = 8 if sub % 8 == 0 else sub  # a small whole block is one tile
    iota = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)

    def chunk(c, carry):
        r0 = pl.multiple_of(c * LANES, LANES)

        def edge_tile(t, acc):
            e0 = pl.multiple_of(t * tile, tile)
            keys = keys_ref[pl.ds(e0, tile), :]
            local = segs_ref[pl.ds(e0, tile), :] - (base + r0)
            for s in range(tile):
                hit = local[s:s + 1, :] == iota
                acc = jnp.minimum(acc, jnp.where(hit, keys[s:s + 1, :], _IMAX))
            return acc

        acc = jax.lax.fori_loop(
            0, sub // tile, edge_tile, acc_ref[pl.ds(r0, LANES), :]
        )
        acc_ref[pl.ds(r0, LANES), :] = acc
        return carry

    jax.lax.fori_loop(0, rows // LANES, chunk, 0)


def _finalize(acc_ref, out_ref):
    """out[c, j] = min over lanes of acc[c·128 + j, :] — a 128 × 128
    transpose turns the lane reduction into a sublane reduction whose
    result is already lane-dense."""
    for c in range(acc_ref.shape[0] // LANES):
        a = acc_ref[c * LANES:(c + 1) * LANES, :]
        out_ref[c:c + 1, :] = jnp.min(a.T, axis=0, keepdims=True)


def _init(acc_ref):
    acc_ref[...] = jnp.full(acc_ref.shape, _IMAX, jnp.int32)


def _validate_blocked(keys, rows, block_rows: int) -> None:
    """Shared shape/dtype validation — loud errors instead of silent wrong
    shapes (a mis-sized bucket used to produce garbage rows)."""
    if keys.shape != rows.shape:
        raise ValueError(
            f"keys/rows shape mismatch: {keys.shape} vs {rows.shape}"
        )
    if keys.dtype != jnp.uint32:
        raise ValueError(f"keys must be uint32 (pack32 layout), got {keys.dtype}")
    if rows.dtype != jnp.int32:
        raise ValueError(f"rows must be int32, got {rows.dtype}")
    if block_rows <= 0 or block_rows % 8:
        raise ValueError(
            f"block_rows must be a positive multiple of 8 (TPU sublane), "
            f"got {block_rows}"
        )


def _bucket_kernel(keys_ref, rows_ref, out_ref, acc_ref):
    _init(acc_ref)
    _accumulate(keys_ref, rows_ref, acc_ref, 0)
    _finalize(acc_ref, out_ref)


def segment_min_bucketed_pallas(
    keys: jax.Array,
    rows: jax.Array,
    *,
    block_rows: int = 128,
    interpret: bool = False,
):
    """keys uint32 [NB, BE]; rows int32 [NB, BE] (local row in the bucket's
    block). Returns uint32 [NB * block_rows]."""
    _validate_blocked(keys, rows, block_rows)
    if keys.ndim != 2:
        raise ValueError(f"expected [NB, BE] bucketed layout, got {keys.shape}")
    nb, be = keys.shape
    if nb == 0 or be == 0:
        raise ValueError(
            f"empty bucket layout {keys.shape}; pad each bucket to >= 128 "
            f"lanes (see kernels.ops.bucket_edges_by_row_block)"
        )
    if be % LANES:
        raise ValueError(f"bucket edge dim {be} must be a multiple of 128 lanes")
    if block_rows % LANES:
        raise ValueError(
            f"block_rows={block_rows} must be a multiple of 128 (lane-dense "
            f"output tile)"
        )
    # Whole (8, 128) tiles per bucket; padding keys are the identity.
    be_pad = -(-be // TILE_EDGES) * TILE_EDGES
    k32 = jnp.pad(to_ordered_i32(keys), ((0, 0), (0, be_pad - be)),
                  constant_values=_IMAX)
    r32 = jnp.pad(rows, ((0, 0), (0, be_pad - be)))
    sub, rsub = be_pad // LANES, block_rows // LANES
    out = pl.pallas_call(
        _bucket_kernel,
        name="segment_min_bucketed",
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((None, sub, LANES), lambda b: (b, 0, 0)),
            pl.BlockSpec((None, sub, LANES), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, rsub, LANES), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, rsub, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_rows, LANES), jnp.int32)],
        interpret=interpret,
    )(k32.reshape(nb, sub, LANES), r32.reshape(nb, sub, LANES))
    return from_ordered_i32(out.reshape(nb * block_rows))


def _flat_kernel(keys_ref, segs_ref, out_ref, acc_ref):
    rb = pl.program_id(0)
    eb = pl.program_id(1)

    @pl.when(eb == 0)
    def _():
        _init(acc_ref)

    _accumulate(keys_ref, segs_ref, acc_ref, rb * acc_ref.shape[0])

    @pl.when(eb == pl.num_programs(1) - 1)
    def _():
        _finalize(acc_ref, out_ref)


def check_flat_layout(e: int, num_segments: int, block_rows: int,
                      block_edges: int) -> tuple[int, int]:
    """Validate a flat [E] edge layout against the kernel tiling; return
    the effective ``(block_rows, block_edges)``.

    Edge blocks are whole (8, 128) tiles; a smaller edge array that is a
    multiple of 128 is one block. Likewise row blocks are multiples of
    1024 segments (an (8, 128) output tile), and fewer segments that are a
    multiple of 128 form one block.
    """
    if block_edges % TILE_EDGES:
        raise ValueError(
            f"block_edges={block_edges} must be a multiple of {TILE_EDGES} "
            f"(whole (8, 128) tiles)"
        )
    if block_rows % TILE_EDGES:
        raise ValueError(
            f"block_rows={block_rows} must be a multiple of {TILE_EDGES} "
            f"(an (8, 128) output tile)"
        )
    if e == 0:
        raise ValueError("empty edge array; pad to >= one block of edges")
    if e % (block_edges if e >= block_edges else LANES):
        raise ValueError(
            f"edge count {e} must be a multiple of block_edges={block_edges} "
            f"(pad with identity keys)"
        )
    if num_segments <= 0 or num_segments % (
        block_rows if num_segments >= block_rows else LANES
    ):
        raise ValueError(
            f"num_segments={num_segments} must be a positive multiple of "
            f"block_rows={block_rows} (pad the output)"
        )
    return min(block_rows, num_segments), min(block_edges, e)


def segment_min_flat_pallas(
    keys: jax.Array,
    segs: jax.Array,
    *,
    num_segments: int,
    block_rows: int = 1024,
    block_edges: int = 1024,
    interpret: bool = False,
):
    """Flat-layout packed segment-min: keys uint32 [E], segs int32 [E] with
    values in [0, num_segments). Returns uint32 [num_segments].

    The row block's accumulator is revisited across the (sequential)
    edge-block grid dimension and takes the ``min`` — the TPU-legal
    stand-in for a CRCW min-write. Cost is O(num_segments × E) compares
    whatever the block sizes; callers with a host-side bucketing
    opportunity should prefer ``segment_min_bucketed_pallas``.
    """
    _validate_blocked(keys, segs, block_rows)
    if keys.ndim != 1:
        raise ValueError(f"expected flat [E] layout, got {keys.shape}")
    e = keys.shape[0]
    br, be = check_flat_layout(e, num_segments, block_rows, block_edges)
    out = pl.pallas_call(
        _flat_kernel,
        name="segment_min_flat",
        grid=(num_segments // br, e // be),
        in_specs=[
            pl.BlockSpec((be // LANES, LANES), lambda rb, eb: (eb, 0)),
            pl.BlockSpec((be // LANES, LANES), lambda rb, eb: (eb, 0)),
        ],
        out_specs=pl.BlockSpec((br // LANES, LANES), lambda rb, eb: (rb, 0)),
        out_shape=jax.ShapeDtypeStruct((num_segments // LANES, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((br, LANES), jnp.int32)],
        interpret=interpret,
    )(
        to_ordered_i32(keys).reshape(e // LANES, LANES),
        segs.reshape(e // LANES, LANES),
    )
    return from_ordered_i32(out.reshape(num_segments))
