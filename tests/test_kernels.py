"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import numpy as np
import pytest
import jax.numpy as jnp

try:  # property tests degrade to skips in bare envs; plain tests still run
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core.index import INVALID_DOC
from repro.kernels import ops
from repro.kernels.posting_intersect import TILE, compute_skip_map
from repro.kernels.ref import intersect_mask_ref, merge_topk_ref, sort_ref

RNG = np.random.default_rng(42)


def sorted_list(n, valid, hi=50_000, rng=RNG):
    v = np.sort(rng.choice(hi, size=valid, replace=False)).astype(np.int32)
    return jnp.asarray(
        np.concatenate([v, np.full(n - valid, INVALID_DOC, np.int32)])
    )


@pytest.mark.parametrize(
    "na,va,nb,vb",
    [
        (1024, 1024, 1024, 1024),   # exact tiles, full
        (1024, 500, 2048, 1700),    # partial validity
        (2048, 2048, 1024, 64),     # tiny b
        (1024, 0, 1024, 512),       # empty driver
        (4096, 3000, 4096, 4000),   # multi-tile both sides
        (512, 300, 768, 400),       # sub-tile (padded up)
    ],
)
@pytest.mark.parametrize("attr_filter", [-1, 2])
def test_intersect_sweep(na, va, nb, vb, attr_filter):
    a = sorted_list(na, va)
    b = sorted_list(nb, vb)
    attrs = jnp.asarray(RNG.integers(0, 5, size=na).astype(np.int32))
    got = ops.intersect(a, attrs, b, attr_filter)
    want = intersect_mask_ref(a, attrs, b, attr_filter)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_skip_map_conservative():
    """Skipping must never drop a tile that contains a match."""
    a = sorted_list(2048, 1500)
    b = sorted_list(4096, 3000)
    start, n_b = compute_skip_map(
        jnp.pad(a, (0, 0)), jnp.pad(b, (0, 0))
    )
    a_np, b_np = np.asarray(a), np.asarray(b)
    bt = b_np.reshape(-1, TILE)
    for i in range(a_np.shape[0] // TILE):
        at = a_np[i * TILE:(i + 1) * TILE]
        at = at[at != INVALID_DOC]
        if at.size == 0:
            continue
        hits = np.isin(bt, at)  # tiles containing any match
        for t in np.flatnonzero(hits.any(axis=1)):
            assert start[i] <= t < start[i] + n_b[i], (i, t)


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(
        va=st.integers(0, 300),
        vb=st.integers(0, 300),
        overlap=st.integers(0, 100),
        attr=st.integers(-1, 3),
        seed=st.integers(0, 2**16),
    )
    def test_intersect_property(va, vb, overlap, attr, seed):
        rng = np.random.default_rng(seed)
        shared = rng.choice(10_000, size=overlap, replace=False)
        a_only = rng.choice(np.arange(10_000, 20_000), size=va, replace=False)
        b_only = rng.choice(np.arange(20_000, 30_000), size=vb, replace=False)
        a_v = np.sort(np.concatenate([shared, a_only])).astype(np.int32)
        b_v = np.sort(np.concatenate([shared, b_only])).astype(np.int32)
        a = jnp.asarray(np.concatenate(
            [a_v, np.full(1024 - a_v.size, INVALID_DOC, np.int32)]))
        b = jnp.asarray(np.concatenate(
            [b_v, np.full(1024 - b_v.size, INVALID_DOC, np.int32)]))
        attrs = jnp.asarray(rng.integers(0, 4, size=1024).astype(np.int32))
        got = np.asarray(ops.intersect(a, attrs, b, attr))
        want = np.asarray(intersect_mask_ref(a, attrs, b, attr))
        np.testing.assert_array_equal(got, want)
        if attr < 0:
            assert got.sum() == overlap
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_intersect_property():
        pass


@pytest.mark.parametrize("n", [2, 7, 100, 256, 777, 2048])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_bitonic_sort_sweep(n, dtype):
    if dtype == np.int32:
        x = RNG.integers(0, 1 << 30, size=n).astype(dtype)
    else:
        x = RNG.normal(size=n).astype(dtype)
    got = ops.sort(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(sort_ref(jnp.asarray(x))))


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(ns=st.integers(1, 12), k=st.integers(1, 40),
           seed=st.integers(0, 999))
    def test_merge_topk_property(ns, k, seed):
        rng = np.random.default_rng(seed)
        c = np.sort(
            rng.integers(0, 1 << 28, size=(ns, k)).astype(np.int32), axis=1
        )
        got = ops.topk_merge(jnp.asarray(c), k)
        want = merge_topk_ref(jnp.asarray(c), k)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_merge_topk_property():
        pass


@pytest.mark.parametrize("q,m,k", [(1, 20, 10), (8, 20, 10), (5, 64, 16),
                                   (3, 7, 7), (16, 300, 10)])
def test_merge_topk_rows_sweep(q, m, k):
    """Batched master merge: per-row best-k of concatenated candidates."""
    c = np.sort(RNG.integers(0, 1 << 28, size=(q, m)).astype(np.int32), axis=1)
    got = ops.topk_merge_rows(jnp.asarray(c), k)
    want = np.sort(c, axis=1)[:, :k]
    np.testing.assert_array_equal(np.asarray(got), want)


def test_merge_topk_rows_with_invalid_padding():
    """INVALID_DOC candidates (shards with < k hits) sort after real ids."""
    c = np.full((4, 24), INVALID_DOC, np.int32)
    c[0, :3] = [5, 9, 11]
    c[2, :1] = [7]
    got = np.asarray(ops.topk_merge_rows(jnp.asarray(c), 5))
    np.testing.assert_array_equal(got[0], [5, 9, 11, INVALID_DOC, INVALID_DOC])
    np.testing.assert_array_equal(got[1], [INVALID_DOC] * 5)
    np.testing.assert_array_equal(got[2], [7] + [INVALID_DOC] * 4)


def test_skip_fraction_increases_with_disjointness():
    """Disjoint ranges skip everything; identical ranges skip nothing."""
    a = sorted_list(4096, 4000, hi=50_000)
    b_same = sorted_list(4096, 4000, hi=50_000)
    b_far = jnp.asarray(
        np.sort(RNG.choice(np.arange(10**6, 2 * 10**6), 4000)).astype(np.int32)
    )
    b_far = jnp.concatenate(
        [b_far, jnp.full((96,), INVALID_DOC, jnp.int32)]
    )
    low = float(ops.skip_fraction(a, b_same))
    high = float(ops.skip_fraction(a, b_far))
    assert high > 0.9
    assert high > low


@pytest.mark.parametrize(
    "backend,interpret,packed,want",
    [
        ("cpu", None, False, True),
        ("cpu", None, True, True),         # the interpreter runs packed
        ("tpu", None, False, False),       # compiled on the chip
        ("tpu", True, False, ValueError),  # never interpret on the chip
        ("tpu", None, True, NotImplementedError),
    ],
)
def test_resolve_interpret(monkeypatch, backend, interpret, packed, want):
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if isinstance(want, bool):
        assert ops.resolve_interpret(interpret, packed=packed) is want
    else:
        with pytest.raises(want):
            ops.resolve_interpret(interpret, packed=packed)
