"""Pallas TPU kernel: dense-block multilinear MSF kernel (paper §III-A).

Computes, per row i: the MINWEIGHT-monoid reduction
    (minw, mincol, minpay)_i = argmin_j { (a_ij, j) : p_i != p_j }
with payload p_j — i.e. Algorithm 1 line 9 with f(p_i, a_ij, p_j).

TPU mapping (DESIGN.md §2): grid = (rows/BI, cols/BJ) with the column
dimension innermost and *sequential*; the (BI, 1) running accumulators live in
the output VMEM blocks, which Pallas revisits for every j because their
index_map ignores j. Each grid step loads an (BI, BJ) tile of A and the
(BI, 1)/(1, BJ) slabs of p — a VPU compare/select + min-reduce over lanes, the
all-at-once form of the kernel (no materialized (a_ij, p_j) pairs, which is
exactly the paper's complaint about the pairwise SpMV formulation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

INF = np.float32(np.inf)
IMAX = np.int32(np.iinfo(np.int32).max)


def _kernel(x_ref, y_ref, a_ref, minw_ref, mincol_ref, minpay_ref, *, block_j):
    j_blk = pl.program_id(1)

    @pl.when(j_blk == 0)
    def _init():
        minw_ref[...] = jnp.full_like(minw_ref, INF)
        mincol_ref[...] = jnp.full_like(mincol_ref, IMAX)
        minpay_ref[...] = jnp.full_like(minpay_ref, IMAX)

    x = x_ref[...]  # [BI, 1] int32 (p row slab, a column)
    y = y_ref[...]  # [1, BJ] int32 (p col slab, a row)
    a = a_ref[...]  # [BI, BJ] f32
    col = j_blk * block_j + jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)

    valid = (x != y) & (a < INF)
    w = jnp.where(valid, a, INF)
    bw = jnp.min(w, axis=1, keepdims=True)
    on = (w == bw) & (bw < INF)
    bcol = jnp.min(jnp.where(on, col, IMAX), axis=1, keepdims=True)
    winner = on & (col == bcol)
    bpay = jnp.min(
        jnp.where(winner, jnp.broadcast_to(y, a.shape), IMAX),
        axis=1,
        keepdims=True,
    )

    # MINWEIGHT combine with the running accumulator (lexicographic (w, col)).
    cw, ccol, cpay = minw_ref[...], mincol_ref[...], minpay_ref[...]
    nw = jnp.minimum(cw, bw)
    c_on = (cw == nw) & (nw < INF)
    b_on = (bw == nw) & (nw < INF)
    ncol = jnp.minimum(jnp.where(c_on, ccol, IMAX), jnp.where(b_on, bcol, IMAX))
    c_win = c_on & (ccol == ncol)
    b_win = b_on & (bcol == ncol)
    npay = jnp.minimum(jnp.where(c_win, cpay, IMAX), jnp.where(b_win, bpay, IMAX))

    minw_ref[...] = nw
    mincol_ref[...] = ncol
    minpay_ref[...] = npay


def multilinear_dense_pallas(
    p_rows: jax.Array,
    p_cols: jax.Array,
    a: jax.Array,
    *,
    block_i: int = 128,
    block_j: int = 128,
    interpret: bool = False,
):
    """p_rows: int32 [n_i] (row payloads), p_cols: int32 [n_j] (column
    payloads); a: f32 [n_i, n_j] with +inf for absent edges. n_i and n_j
    must be multiples of the block sizes (``ops.multilinear_dense`` pads).

    TPU tiling: the row payloads and the three outputs are [n_i, 1]
    columns in (block_i, 1) blocks and the column payloads a [1, n_j] row
    in (1, block_j) blocks, so every block is 2-D with its short side the
    whole array dimension.
    """
    n_i, n_j = a.shape
    if n_i % block_i or n_j % block_j or block_i % 8 or block_j % 128:
        raise ValueError(
            f"a {a.shape} must tile by blocks ({block_i}, {block_j}), with "
            f"block_i a multiple of 8 and block_j a multiple of 128"
        )
    kernel = functools.partial(_kernel, block_j=block_j)
    col_block = pl.BlockSpec((block_i, 1), lambda i, j: (i, 0))
    outs = pl.pallas_call(
        kernel,
        grid=(n_i // block_i, n_j // block_j),
        in_specs=[
            col_block,
            pl.BlockSpec((1, block_j), lambda i, j: (0, j)),
            pl.BlockSpec((block_i, block_j), lambda i, j: (i, j)),
        ],
        out_specs=[col_block, col_block, col_block],
        out_shape=[
            jax.ShapeDtypeStruct((n_i, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_i, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_i, 1), jnp.int32),
        ],
        interpret=interpret,
    )(p_rows.reshape(n_i, 1), p_cols.reshape(1, n_j), a)
    return tuple(o.reshape(n_i) for o in outs)
