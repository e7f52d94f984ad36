"""MINWEIGHT monoid machinery: segment/axis argmin vs numpy, pack32,
binary-combine consistency (hypothesis property tests)."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_stub import given, settings, st  # skips cleanly if absent

from repro.core.semiring import (
    EdgeMin,
    combine_edgemin,
    pack32,
    segment_argmin,
    unpack32,
)

IMAX = np.iinfo(np.int32).max


def _np_argmin(w, eid, pay, seg, n, valid):
    minw = np.full(n, np.inf, np.float32)
    mineid = np.full(n, IMAX, np.int64)
    minpay = np.full(n, IMAX, np.int64)
    for i in range(len(w)):
        if not valid[i]:
            continue
        s = seg[i]
        key = (w[i], eid[i])
        if (minw[s], mineid[s]) > key:
            minw[s], mineid[s], minpay[s] = w[i], eid[i], pay[i]
    return minw, mineid, minpay


@settings(max_examples=30, deadline=None)
@given(
    n=st.integers(1, 20),
    e=st.integers(0, 80),
    seed=st.integers(0, 2**31 - 1),
)
def test_segment_argmin_matches_numpy(n, e, seed):
    rng = np.random.default_rng(seed)
    w = rng.integers(1, 50, e).astype(np.float32)  # ties likely
    eid = rng.permutation(e).astype(np.int32)  # distinct tie-break
    pay = rng.integers(0, 1000, e).astype(np.int32)
    seg = rng.integers(0, n, e).astype(np.int32)
    valid = rng.random(e) < 0.8
    got = segment_argmin(
        jnp.array(w), jnp.array(eid), (jnp.array(pay),), jnp.array(seg), n,
        valid=jnp.array(valid),
    )
    want = _np_argmin(w, eid, pay, seg, n, valid)
    np.testing.assert_array_equal(np.asarray(got.w), want[0])
    np.testing.assert_array_equal(np.asarray(got.eid), want[1].astype(np.int32))
    np.testing.assert_array_equal(np.asarray(got.payload[0]), want[2].astype(np.int32))


@settings(max_examples=30, deadline=None)
@given(
    w=st.integers(0, 255),
    idx=st.integers(0, (1 << 24) - 1),
)
def test_pack32_roundtrip_and_order(w, idx):
    k = pack32(jnp.uint32(w), jnp.uint32(idx))
    w2, i2 = unpack32(k)
    assert int(w2) == w and int(i2) == idx
    # order: packing is monotone in (w, idx) lex order
    k2 = pack32(jnp.uint32(min(w + 1, 255)), jnp.uint32(0))
    if w < 255:
        assert int(k) < int(k2)


def test_combine_edgemin_matches_joint_reduction():
    rng = np.random.default_rng(0)
    n = 16
    mk = lambda: EdgeMin(
        w=jnp.array(np.where(rng.random(n) < 0.3, np.inf, rng.integers(1, 9, n)).astype(np.float32)),
        eid=jnp.array(rng.permutation(1000)[:n].astype(np.int32)),
        payload=(jnp.array(rng.integers(0, 99, n).astype(np.int32)),),
    )
    a, b = mk(), mk()
    c = combine_edgemin(a, b)
    # elementwise: c must equal whichever of (a, b) has the lex-smaller key
    for i in range(n):
        ka = (float(a.w[i]), int(a.eid[i]))
        kb = (float(b.w[i]), int(b.eid[i]))
        kc = (float(c.w[i]), int(c.eid[i]))
        assert kc == min(ka, kb)


def _ranked_case(case, seed):
    """A symmetric slot list (eid per undirected edge) and a parent
    vector for :func:`test_min_outgoing_ranked_matches_3pass`."""
    rng = np.random.default_rng(seed)
    n, m = 40, 160
    u, v = rng.integers(0, n, m), rng.integers(0, n, m)
    if case == "ties":
        w = rng.integers(1, 4, m).astype(np.float32)
    elif case == "signed_zero":
        w = rng.choice(np.array([-0.0, 0.0, 1.0], np.float32), m)
    else:
        w = rng.standard_normal(m).astype(np.float32)
    if case == "inf_weight":
        w[rng.random(m) < 0.3] = np.inf
    src = np.concatenate([u, v]).astype(np.int32)
    dst = np.concatenate([v, u]).astype(np.int32)
    eid = np.concatenate([rng.permutation(m)] * 2).astype(np.int32)
    w = np.concatenate([w, w])
    valid = np.ones(2 * m, bool)
    if case == "pad_slots":  # dropped pairs, then Graph.pad_to's padding
        drop = rng.random(m) < 0.2
        valid = ~np.concatenate([drop, drop])
        pad = 24
        src = np.concatenate([src, np.zeros(pad, np.int32)])
        dst = np.concatenate([dst, np.zeros(pad, np.int32)])
        w = np.concatenate([w, np.full(pad, np.inf, np.float32)])
        eid = np.concatenate([eid, np.full(pad, IMAX, np.int32)])
        valid = np.concatenate([valid, np.zeros(pad, bool)])
    if case == "nonstar":
        p = rng.integers(0, n, n).astype(np.int32)
    else:  # every tree a star
        roots = rng.choice(n, 12, replace=False)
        p = roots[rng.integers(0, 12, n)].astype(np.int32)
        p[roots] = roots
    return tuple(map(jnp.asarray, (p, src, dst, w, eid, valid))) + (n,)


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize(
    "case", ["ties", "float", "signed_zero", "inf_weight", "pad_slots", "nonstar"]
)
def test_min_outgoing_ranked_matches_3pass(case, seed):
    """The rank-keyed reduction picks the 3-pass reduction's winner in
    every segment: same weight, eid and p[dst]."""
    from repro.core.multilinear import (
        min_outgoing_coo,
        min_outgoing_ranked,
        rank_slots,
    )

    p, src, dst, w, eid, valid, n = _ranked_case(case, seed)
    want = min_outgoing_coo(p, src, dst, w, eid, valid, n, segment="root")
    got = min_outgoing_ranked(p, src, dst, w, eid, valid, n, rank_slots(w, eid, valid))
    assert int(jnp.sum(want.eid < IMAX)) > 0  # some segment has a winner
    np.testing.assert_array_equal(np.asarray(got.w), np.asarray(want.w))
    np.testing.assert_array_equal(np.asarray(got.eid), np.asarray(want.eid))
    np.testing.assert_array_equal(
        np.asarray(got.payload[0]), np.asarray(want.payload[0])
    )
