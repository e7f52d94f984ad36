"""Pallas TPU kernel: packed-key segment-min over *sorted* segment ids.

The coarsening dedupe (``repro.coarsen.filter``) produces segment ids by
a boundary-flag prefix-sum over the *sorted* pair keys, so ``segs`` is
non-decreasing and every segment occupies one contiguous edge range. The
flat kernel (``segment_min_flat_pallas``) ignores that structure and
rescans every edge block for every output row block — O(E²) compares
at ``num_segments = E``. This kernel exploits it:

- Each output row block ``rb`` covers segments
  ``[rb·block_rows, (rb+1)·block_rows)``; sortedness means those
  segments live in a contiguous *edge-block* range
  ``[first_eb[rb], last_eb[rb]]``.
- The grid is one step per (row block, edge block) *intersection pair*.
  The staircase structure bounds the pair count by
  ``num_edge_blocks + num_row_blocks`` — linear, not quadratic — and the
  per-row-block edge-block offsets are **scalar-prefetched**
  (``pltpu.PrefetchScalarGridSpec``) so the BlockSpec index maps DMA
  exactly the blocks each step touches and nothing else.
- The row block's VMEM accumulator (the tiling of
  ``segment_min_bucketed``) persists across its consecutive steps and
  accumulates with ``min``: the first touch initializes it to the
  identity, the last writes the lane-dense output tile. Steps padded
  beyond the live pair count re-reduce the final pair, which is
  idempotent under min.

Keys are the pack32 layout (``repro.core.semiring``), identity/padding
= 0xFFFFFFFF. Correctness does NOT require masking boundary blocks: an
edge whose segment falls outside the step's row block compares unequal
to every local row and contributes the identity.

Contract: ``segs`` must be non-decreasing. Violations are not detected
(the check would cost the O(E) pass this kernel exists to avoid) — the
result silently loses the out-of-order contributions. Callers with
unsorted ids want ``segment_min_flat_pallas``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.segment_min_bucketed import (
    LANES,
    _accumulate,
    _finalize,
    _init,
    _validate_blocked,
    check_flat_layout,
    from_ordered_i32,
    to_ordered_i32,
)


def build_step_maps(
    segs: jax.Array,
    *,
    num_segments: int,
    block_rows: int,
    block_edges: int,
):
    """Per-grid-step (row block, edge block) indices for the sorted kernel.

    Pure jnp (runs inside the caller's jit; the results feed the kernel as
    scalar-prefetch operands). ``segs`` is the full padded [E] sorted id
    array. Returns int32 ``(rb_map, eb_map)`` of static length
    ``num_edge_blocks + num_row_blocks``:

    - ``rb_map`` is non-decreasing and visits *every* row block at least
      once (empty row blocks get one step so their output tile is
      initialized to the identity);
    - within a row block, ``eb_map`` walks ``first_eb..last_eb``;
    - steps beyond the live pair count clamp to the last live pair
      (idempotent re-reduction).
    """
    e = segs.shape[0]
    ne = e // block_edges
    r = num_segments // block_rows
    rb = jnp.arange(r, dtype=jnp.int32)
    # Edge index range [p_lo, p_hi) of the segments in row block rb.
    p_lo = jnp.searchsorted(segs, rb * block_rows).astype(jnp.int32)
    p_hi = jnp.searchsorted(segs, (rb + 1) * block_rows).astype(jnp.int32)
    first_eb = jnp.clip(p_lo // block_edges, 0, ne - 1)
    last_eb = jnp.where(
        p_hi > p_lo, jnp.clip((p_hi - 1) // block_edges, 0, ne - 1), first_eb
    )
    last_eb = jnp.maximum(last_eb, first_eb)
    start = jnp.cumsum(last_eb - first_eb + 1) - (last_eb - first_eb + 1)
    steps = jnp.arange(ne + r, dtype=jnp.int32)
    rb_map = jnp.clip(
        jnp.searchsorted(start, steps, side="right").astype(jnp.int32) - 1,
        0,
        r - 1,
    )
    eb_map = jnp.minimum(
        first_eb[rb_map] + (steps - start[rb_map]), last_eb[rb_map]
    )
    return rb_map, eb_map.astype(jnp.int32)


def _sorted_kernel(rb_map_ref, eb_map_ref, keys_ref, segs_ref, out_ref, acc_ref):
    s = pl.program_id(0)
    last = pl.num_programs(0) - 1
    rb = rb_map_ref[s]

    @pl.when(jnp.logical_or(s == 0, rb_map_ref[jnp.maximum(s - 1, 0)] != rb))
    def _():
        _init(acc_ref)

    # Out-of-block segments match no local row.
    _accumulate(keys_ref, segs_ref, acc_ref, rb * acc_ref.shape[0])

    @pl.when(jnp.logical_or(s == last, rb_map_ref[jnp.minimum(s + 1, last)] != rb))
    def _():
        _finalize(acc_ref, out_ref)


def segment_min_sorted_pallas(
    keys: jax.Array,
    segs: jax.Array,
    *,
    num_segments: int,
    block_rows: int = 1024,
    block_edges: int = 1024,
    interpret: bool = False,
):
    """Sorted-segment packed segment-min: keys uint32 [E], segs int32 [E]
    non-decreasing with values in [0, num_segments). Returns uint32
    [num_segments] (UMAX at empty segments).

    Shape contract mirrors ``segment_min_flat_pallas`` (E a multiple of
    ``block_edges``, ``num_segments`` a multiple of ``block_rows``; callers
    pad via ``kernels.ops.segment_min_sorted``); cost is
    O((E/block_edges + num_segments/block_rows) · block_rows·block_edges)
    compares instead of the flat kernel's O(num_segments·E).
    """
    _validate_blocked(keys, segs, block_rows)
    if keys.ndim != 1:
        raise ValueError(f"expected flat [E] layout, got {keys.shape}")
    e = keys.shape[0]
    br, be = check_flat_layout(e, num_segments, block_rows, block_edges)
    ne = e // be
    rb_map, eb_map = build_step_maps(
        segs,
        num_segments=num_segments,
        block_rows=br,
        block_edges=be,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(ne + num_segments // br,),
        in_specs=[
            pl.BlockSpec((be // LANES, LANES), lambda s, rbm, ebm: (ebm[s], 0)),
            pl.BlockSpec((be // LANES, LANES), lambda s, rbm, ebm: (ebm[s], 0)),
        ],
        out_specs=pl.BlockSpec((br // LANES, LANES), lambda s, rbm, ebm: (rbm[s], 0)),
        scratch_shapes=[pltpu.VMEM((br, LANES), jnp.int32)],
    )
    out = pl.pallas_call(
        _sorted_kernel,
        name="segment_min_sorted",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_segments // LANES, LANES), jnp.int32),
        interpret=interpret,
    )(
        rb_map,
        eb_map,
        to_ordered_i32(keys).reshape(e // LANES, LANES),
        segs.reshape(e // LANES, LANES),
    )
    return from_ordered_i32(out.reshape(num_segments))
