"""Jitted public wrappers around the Pallas kernels.

On a TPU backend the kernels compile to Mosaic. On any other backend
(the CPU test runs) they execute in ``interpret=True`` mode — the kernel
body runs as traced JAX ops, checking the TPU program's logic but not
its tiling, which only the TPU compiler checks
(``tests/test_tpu_compile.py``).
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.multilinear_dense import multilinear_dense_pallas
from repro.kernels.segment_min_bucketed import (
    LANES,
    segment_min_bucketed_pallas,
    segment_min_flat_pallas,
)
from repro.kernels.segment_min_sorted import segment_min_sorted_pallas

INF = jnp.float32(jnp.inf)
IMAX = jnp.int32(jnp.iinfo(jnp.int32).max)
UMAX = np.uint32(0xFFFFFFFF)


def _use_interpret(interpret):
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _padded(size: int, block: int) -> int:
    """``size`` rounded up to whole blocks — or, below one block, to
    whole 128-lane rows, which the kernels take as one smaller block."""
    step = block if size > block else LANES
    return max(LANES, -(-size // step) * step)


@partial(jax.jit, static_argnames=("block_i", "block_j", "interpret"))
def multilinear_dense(
    p: jax.Array,
    a: jax.Array,
    *,
    block_i: int = 128,
    block_j: int = 128,
    interpret: bool | None = None,
):
    """Min outgoing edge per vertex over a dense adjacency (see ref.py).

    Pads n up to the block size; padded rows/cols carry +inf / sentinel p
    values so they reduce to the monoid identity.
    """
    n = a.shape[0]
    bi = min(block_i, max(8, 1 << (n - 1).bit_length()))
    bj = min(block_j, max(128, 1 << (n - 1).bit_length()))
    n_i = -(-n // bi) * bi
    n_j = -(-n // bj) * bj
    a_p = jnp.full((n_i, n_j), INF, jnp.float32).at[:n, :n].set(a)
    # Padded vertices carry payload -1; they are never selected because
    # their a entries are +inf.
    p32 = p.astype(jnp.int32)
    minw, mincol, minpay = multilinear_dense_pallas(
        jnp.full((n_i,), -1, jnp.int32).at[:n].set(p32),
        jnp.full((n_j,), -1, jnp.int32).at[:n].set(p32),
        a_p,
        block_i=bi,
        block_j=bj,
        interpret=_use_interpret(interpret),
    )
    return minw[:n], mincol[:n], minpay[:n]


@partial(jax.jit, static_argnames=("block_rows", "interpret"))
def segment_min_bucketed(
    keys: jax.Array,
    rows: jax.Array,
    *,
    block_rows: int = 128,
    interpret: bool | None = None,
):
    return segment_min_bucketed_pallas(
        keys, rows, block_rows=block_rows, interpret=_use_interpret(interpret)
    )


@partial(
    jax.jit,
    static_argnames=("num_segments", "block_rows", "block_edges", "interpret"),
)
def segment_min_flat(
    keys: jax.Array,
    segs: jax.Array,
    *,
    num_segments: int,
    block_rows: int = 1024,
    block_edges: int = 1024,
    interpret: bool | None = None,
):
    """Flat packed-key segment-min over arbitrary (unsorted) segment ids.

    Pads the edge dimension to a block_edges multiple (identity keys) and
    the segment dimension to a block_rows multiple — each to a 128
    multiple when it fits one block — then slices back: the caller keeps
    natural shapes.
    """
    e = keys.shape[0]
    e_pad = _padded(e, block_edges)
    s_pad = _padded(num_segments, block_rows)
    keys_p = jnp.full((e_pad,), UMAX, jnp.uint32).at[:e].set(keys)
    segs_p = jnp.zeros((e_pad,), jnp.int32).at[:e].set(segs)
    out = segment_min_flat_pallas(
        keys_p,
        segs_p,
        num_segments=s_pad,
        block_rows=block_rows,
        block_edges=block_edges,
        interpret=_use_interpret(interpret),
    )
    return out[:num_segments]


@partial(
    jax.jit,
    static_argnames=("num_segments", "block_rows", "block_edges", "interpret"),
)
def segment_min_sorted(
    keys: jax.Array,
    segs: jax.Array,
    *,
    num_segments: int,
    block_rows: int = 1024,
    block_edges: int = 1024,
    interpret: bool | None = None,
):
    """Contiguous-range packed segment-min over **sorted** segment ids.

    Same pad-and-slice contract as :func:`segment_min_flat`, but the
    kernel scalar-prefetches per-row-block edge-block offsets so each
    grid step reads only the blocks its segments touch — O(E) compares
    for the coarsening dedupe where the flat kernel is O(E²).
    Padding entries get segment id ``num_segments_padded − 1`` (identity
    keys), preserving sortedness and covering the tail row block.
    """
    e = keys.shape[0]
    e_pad = _padded(e, block_edges)
    s_pad = _padded(num_segments, block_rows)
    keys_p = jnp.full((e_pad,), UMAX, jnp.uint32).at[:e].set(keys)
    segs_p = jnp.full((e_pad,), s_pad - 1, jnp.int32).at[:e].set(segs)
    out = segment_min_sorted_pallas(
        keys_p,
        segs_p,
        num_segments=s_pad,
        block_rows=block_rows,
        block_edges=block_edges,
        interpret=_use_interpret(interpret),
    )
    return out[:num_segments]


def dedupe_segmin_backend(backend: str | None):
    """Resolve a segmin request for a *dedupe* site — one whose segment ids
    are sorted (the boundary prefix-sum over sorted pair keys in the
    coarsening filter, single-device and distributed alike).

    Returns the packed-segmin callable to pass to the filter, or ``None``
    for the plain XLA ``segment_min``: a Pallas request ("pallas"/"sorted")
    selects the contiguous-range sorted kernel (the flat kernel's full
    rescan is O(E²) at num_segments = E and was never viable
    here); "jnp" pins XLA; None/"auto" picks the sorted kernel on TPU and
    XLA elsewhere (interpreted Pallas loses badly to XLA on CPU). The
    single home of that rule — call sites must not re-implement it.
    """
    if backend in ("pallas", "sorted"):
        return make_packed_segmin("sorted")
    if backend == "jnp":
        return None
    return (
        make_packed_segmin("sorted")
        if jax.default_backend() == "tpu"
        else None
    )


def flat_segmin_backend(backend: str | None) -> str | None:
    """Resolve a segmin backend request for a *flat* reduction site —
    one whose segment ids are unsorted (the MSF hook loops, the residual
    solve). "sorted" is dedupe-only (the contiguous-range kernel silently
    loses out-of-order contributions), so it degrades to "auto" here;
    every other request passes through. The single home of that rule —
    call sites must not re-implement it.
    """
    return "auto" if backend == "sorted" else backend


@lru_cache(maxsize=None)
def make_packed_segmin(backend: str = "auto"):
    """Resolve a packed (uint32 key, int32 seg) → uint32 [n] segment-min.

    ``backend``: "jnp" (pure-JAX ``segment_min``), "pallas" (the flat
    Pallas kernel, ``interpret=True`` selected automatically off
    ``jax.default_backend()``), "sorted" (the contiguous-range Pallas
    kernel — the caller's segment ids MUST be non-decreasing, e.g. the
    coarsening dedupe's boundary prefix-sum ranks), or "auto" (pallas on
    TPU, jnp elsewhere — interpreted Pallas is orders of magnitude slower
    than XLA on CPU, so auto never picks it there).

    Cached so repeat calls return the *same* callable — callers pass the
    result as a jit-static argument and must not miss the jit cache.
    """
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend == "jnp":
        def _jnp(keys, segs, num_segments):
            return jax.ops.segment_min(keys, segs, num_segments=num_segments)

        return _jnp
    if backend == "pallas":
        def _pallas(keys, segs, num_segments):
            return segment_min_flat(keys, segs, num_segments=num_segments)

        return _pallas
    if backend == "sorted":
        def _sorted(keys, segs, num_segments):
            return segment_min_sorted(keys, segs, num_segments=num_segments)

        return _sorted
    raise ValueError(f"unknown segment-min backend {backend!r}")


def bucket_edges_by_row_block(
    seg: np.ndarray, keys: np.ndarray, n: int, block_rows: int = 128
) -> tuple[np.ndarray, np.ndarray]:
    """Host-side bucketing for the segment-min kernel: group edges by
    ``seg // block_rows`` and pad each bucket to the max size (multiple of
    128 lanes). Returns (keys [NB, BE] uint32, rows [NB, BE] int32)."""
    nb = -(-n // block_rows)
    b = seg // block_rows
    counts = np.bincount(b, minlength=nb)
    be = max(128, int(-(-counts.max() // 128) * 128)) if len(seg) else 128
    keys_out = np.full((nb, be), UMAX, np.uint32)
    rows_out = np.zeros((nb, be), np.int32)
    order = np.argsort(b, kind="stable")
    seg_s, keys_s, b_s = seg[order], keys[order], b[order]
    starts = np.concatenate([[0], np.cumsum(counts)])
    for k in range(nb):
        lo, hi = starts[k], starts[k + 1]
        keys_out[k, : hi - lo] = keys_s[lo:hi]
        rows_out[k, : hi - lo] = seg_s[lo:hi] - k * block_rows
    return keys_out, rows_out
