"""Shortcutting strategies (paper §IV-B, Algorithm 2).

- ``shortcut_once``      — the original AS step: p_i ← p_{p_i} for non-star i.
- ``complete_shortcut``  — iterate p ← p[p] until every tree is a star
                           (removes the starcheck; ≥ half the trees then hook
                           each iteration → log2(n) outer iterations).
- ``csp_shortcut``       — Complete Shortcutting with Prefetching: gather the
                           ``changed = {(i, p_i) : p_i ≠ p_i^prev}`` pairs
                           once (the only vertices whose parent moved are
                           star roots that hooked), compress that map to its
                           fixpoint by pointer doubling *within the map*
                           (local reads only), then apply it in one pass.
- ``optimized_shortcut`` — the paper's OS policy: CSP when |changed| fits the
                           prefetch budget, plain complete shortcut otherwise
                           (empirical threshold, paper uses 1310k ≈ 20 MB).

All functions are jit-safe (static shapes; ``lax.while_loop`` inner loops).
The distributed variants live in ``repro.core.msf_dist`` — there CSP's
all-gather-once vs per-sub-iteration remote reads is the real win.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

IMAX = jnp.int32(jnp.iinfo(jnp.int32).max)


def shortcut_once(p: jax.Array, star: jax.Array) -> jax.Array:
    """AS step (iii): p_i ← p_{p_i} for each vertex not in a star."""
    return jnp.where(star, p, p[p])


def complete_shortcut(p: jax.Array) -> jax.Array:
    """Pointer-jump until p == p[p] (every tree a star)."""

    def cond(p):
        return jnp.any(p != p[p])

    def body(p):
        return p[p]

    return jax.lax.while_loop(cond, body, p)


def count_shortcut_subiters(p: jax.Array):
    """complete_shortcut that also counts its p ← p[p] sub-iterations
    (the flat driver's ``shortcut_jumps``)."""

    def cond(state):
        p, _ = state
        return jnp.any(p != p[p])

    def body(state):
        p, k = state
        return p[p], k + 1

    return jax.lax.while_loop(cond, body, (p, jnp.int32(0)))


def _compress_changed_map(ids: jax.Array, vals: jax.Array):
    """Pointer-double the changed map to its fixpoint using only local reads.

    ids: sorted changed vertex ids (padded with IMAX), vals: their new
    parents. After compression, vals[k] is outside the map (or a fixpoint),
    so one application resolves any chain.
    """

    def lookup(x):
        j = jnp.searchsorted(ids, x)
        j = jnp.clip(j, 0, ids.shape[0] - 1)
        # x == IMAX are padding entries — never a hit (else the fixpoint
        # iteration would spin on padding looking itself up).
        hit = (ids[j] == x) & (x != IMAX)
        return jnp.where(hit, vals[j], x), hit

    def cond(vals_cur):
        nxt, hit = lookup(vals_cur)
        del nxt
        return jnp.any(hit & (ids != IMAX))

    def body(vals_cur):
        nxt, _ = lookup(vals_cur)
        return nxt

    # Chains over the changed roots halve each doubling step.
    vals = jax.lax.while_loop(cond, body, vals)
    return ids, vals


def build_changed(p: jax.Array, p_prev: jax.Array, capacity: int):
    """Fixed-capacity (ids, vals) buffer of vertices whose parent changed.

    Returns (ids sorted asc padded IMAX, vals, count, overflowed).
    XLA needs static shapes: ``capacity`` plays the role of the paper's
    20 MB gather threshold.
    """
    n = p.shape[0]
    capacity = min(capacity, n)
    changed = p != p_prev
    count = jnp.sum(changed.astype(jnp.int32))
    key = jnp.where(changed, jnp.arange(n, dtype=jnp.int32), IMAX)
    ids = jax.lax.top_k(-key, capacity)[0] * -1  # smallest `capacity` ids
    safe = jnp.clip(ids, 0, n - 1)
    vals = jnp.where(ids == IMAX, IMAX, p[safe])
    return ids, vals, count, count > capacity


def _csp_counted(p: jax.Array, p_prev: jax.Array, capacity: int):
    """:func:`csp_shortcut` and the p ← p[p] passes of its fallback."""
    ids, vals, _, overflow = build_changed(p, p_prev, capacity)
    ids, vals = _compress_changed_map(ids, vals)
    j = jnp.clip(jnp.searchsorted(ids, p), 0, ids.shape[0] - 1)
    hit = ids[j] == p
    p_csp = jnp.where(hit, vals[j], p)
    # Overflow ⇒ the buffer silently dropped entries; fall back (OS policy
    # makes this explicit, but csp alone must stay correct).
    return jax.lax.cond(
        overflow, count_shortcut_subiters, lambda q: (p_csp, jnp.int32(0)), p
    )


def csp_shortcut(p: jax.Array, p_prev: jax.Array, capacity: int) -> jax.Array:
    """Algorithm 2, single-shard semantics (the distributed version replaces
    ``build_changed`` with one all-gather)."""
    return _csp_counted(p, p_prev, capacity)[0]


def _os_counted(p: jax.Array, p_prev: jax.Array, capacity: int):
    """:func:`optimized_shortcut` and the p ← p[p] passes of its plain
    branch."""
    ids, vals, count, overflow = build_changed(p, p_prev, capacity)

    def use_csp(_):
        cids, cvals = _compress_changed_map(ids, vals)
        j = jnp.clip(jnp.searchsorted(cids, p), 0, cids.shape[0] - 1)
        hit = cids[j] == p
        return jnp.where(hit, cvals[j], p), jnp.int32(0)

    def use_plain(_):
        return count_shortcut_subiters(p)

    return jax.lax.cond(overflow, use_plain, use_csp, None)


def optimized_shortcut(
    p: jax.Array, p_prev: jax.Array, capacity: int
) -> jax.Array:
    """Paper's OS: invoke CSP only when |changed| ≤ capacity."""
    return _os_counted(p, p_prev, capacity)[0]


def make_shortcut_fn(strategy: str, capacity: int = 1 << 16):
    """``fn(p, p_prev) -> (p, jumps)`` for strategy ∈ {complete, csp, os}:
    ``jumps`` counts the p ← p[p] passes over the whole parent vector
    (complete shortcutting's sub-iterations; csp and os count only their
    complete-shortcut fallback, not the steps inside the changed map)."""
    if strategy == "complete":
        return lambda p, p_prev: count_shortcut_subiters(p)
    if strategy == "csp":
        return partial(_csp_counted, capacity=capacity)
    if strategy == "os":
        return partial(_os_counted, capacity=capacity)
    raise ValueError(f"unknown shortcut strategy {strategy!r}")
