"""Graph500 / GAP ``kron`` edge generator, on the device, from a seed.

The Kronecker (R-MAT) recursion of the Graph500 reference generator:
at each of ``scale`` bits an edge falls into one of the four quadrants
with probabilities A, B, C and D = 1 - A - B - C (Graph500 and GAP:
.57/.19/.19/.05). Vertex ids are then relabelled by a random bijection
of ``[0, 2^scale)``, as GAP does, so that the hubs do not sit at the
low ids; weights are integers uniform in 1..255 (GAP's weights).

The raw edges come from one jitted call. The graph is then made as
``repro.graphs.structures.from_edges`` makes it -- self-loops dropped,
each undirected pair kept once with its smallest weight, edge ids
numbered in pair-key order -- by one host sort of a packed 64-bit key.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

A, B, C = 0.57, 0.19, 0.19
WEIGHT_HI = 255


def _prng_key(seed: int):
    """A PRNG key from a seed of up to 64 bits (more than int32 holds)."""
    seed = int(seed)
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed must be in [0, 2^64), got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


@partial(jax.jit, static_argnames=("scale", "m"))
def _raw_edges(key, *, scale: int, m: int):
    k_bits, k_perm, k_w = jax.random.split(key, 3)
    ab, c_norm, a_norm = A + B, C / (1.0 - A - B), A / (A + B)

    def bit(i, uv):
        u, v = uv
        kr, kc = jax.random.split(jax.random.fold_in(k_bits, i))
        right = jax.random.uniform(kr, (m,)) >= ab
        col_p = jnp.where(right, c_norm, a_norm)
        down = jax.random.uniform(kc, (m,)) >= col_p
        u = u | (right.astype(jnp.uint32) << i.astype(jnp.uint32))
        v = v | (down.astype(jnp.uint32) << i.astype(jnp.uint32))
        return u, v

    zero = jnp.zeros((m,), jnp.uint32)
    u, v = jax.lax.fori_loop(0, scale, bit, (zero, zero))
    # Random relabelling: three rounds of (odd multiply, add, xor-shift)
    # mod 2^scale; every step is a bijection of [0, 2^scale).
    mask = jnp.uint32((1 << scale) - 1)
    mul = jax.random.bits(k_perm, (3,), jnp.uint32) | jnp.uint32(1)
    add = jax.random.bits(jax.random.fold_in(k_perm, 1), (3,), jnp.uint32)
    shift = jnp.uint32(max(1, scale // 2))

    def relabel(x):
        for r in range(3):
            x = (x * mul[r] + add[r]) & mask
            x = x ^ (x >> shift)
        return x

    w = jax.random.randint(k_w, (m,), 1, WEIGHT_HI + 1, jnp.int32)
    return relabel(u).astype(jnp.int32), relabel(v).astype(jnp.int32), w


def raw_edges(scale: int, count: int, seed: int):
    """``count`` raw Kronecker edges (u, v, w) as host int arrays, in
    generation order (duplicates and self-loops included)."""
    if not 1 <= scale <= 30:
        raise ValueError(f"scale must be in 1..30, got {scale}")
    u, v, w = _raw_edges(_prng_key(seed), scale=int(scale), m=int(count))
    return np.asarray(u), np.asarray(v), np.asarray(w)


def _pair_keys(u, v, w, scale: int):
    """Packed int64 keys ``lo << (scale+8) | hi << 8 | w`` of the edges
    that are not self-loops, and the index of each in the input."""
    lo = np.minimum(u, v).astype(np.int64)
    hi = np.maximum(u, v).astype(np.int64)
    keep = np.flatnonzero(lo != hi)
    key = (lo[keep] << (scale + 8)) | (hi[keep] << 8) | w[keep].astype(np.int64)
    return key, keep


def dedupe(u, v, w, scale: int):
    """Canonical undirected edges (lo, hi, w), one per pair with its
    smallest weight, sorted by pair key: ``from_edges``'s policy."""
    key, _ = _pair_keys(u, v, w, scale)
    key.sort()
    pair = key >> 8
    first = np.ones(len(key), bool)
    first[1:] = pair[1:] != pair[:-1]
    key = key[first]
    lo = (key >> (scale + 8)).astype(np.int32)
    hi = ((key >> 8) & ((1 << scale) - 1)).astype(np.int32)
    return lo, hi, (key & 0xFF).astype(np.float32)


def kron_graph(scale: int, edge_factor: int, seed: int):
    """The ``kron`` graph as the host ``Graph`` that ``repro.solve.plan``
    takes: 2^scale vertices, ``edge_factor * 2^scale`` generated edges
    before dedupe."""
    from repro.graphs.structures import Graph

    u, v, w = raw_edges(scale, edge_factor << scale, seed)
    lo, hi, wt = dedupe(u, v, w, scale)
    m = len(lo)
    eid = np.arange(m, dtype=np.int32)
    return Graph(
        src=np.concatenate([lo, hi]), dst=np.concatenate([hi, lo]),
        w=np.concatenate([wt, wt]), eid=np.concatenate([eid, eid]),
        valid=np.ones(2 * m, bool), n=1 << scale,
    )
