"""Per-kernel interpret-mode validation against the pure-jnp oracles,
with hypothesis sweeps over shapes/dtypes (task brief deliverable c)."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.bucket_min import bucket_min_pallas
from repro.kernels.bucket_update import (
    MAX_UPDATE_CAP,
    NUM_BUCKETS,
    bucket_update_pallas,
)
from repro.kernels.butterfly_combine import butterfly_combine_pallas
from repro.kernels.wedge_count import wedge_histogram_pallas


@settings(max_examples=12, deadline=None)
@given(
    n=st.integers(1, 2000),
    b=st.integers(1, 1500),
    density=st.floats(0.0, 1.0),
    seed=st.integers(0, 1 << 16),
)
def test_wedge_histogram_sweep(n, b, density, seed):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, b, n).astype(np.int32)
    valid = (rng.random(n) < density).astype(np.int32)
    got = wedge_histogram_pallas(jnp.asarray(keys), jnp.asarray(valid), b)
    want = ref.wedge_histogram_ref(jnp.asarray(keys), jnp.asarray(valid), b)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert int(np.asarray(got).sum()) == int(valid.sum())


@pytest.mark.parametrize("dtype", [np.int32, np.int16, np.int8])
def test_wedge_histogram_dtypes(dtype):
    rng = np.random.default_rng(1)
    keys = rng.integers(0, 100, 500).astype(dtype)
    valid = np.ones(500, np.int32)
    got = wedge_histogram_pallas(jnp.asarray(keys), jnp.asarray(valid), 100)
    want = ref.wedge_histogram_ref(jnp.asarray(keys), jnp.asarray(valid), 100)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(1, 4000),
    dmax=st.integers(1, 1000),
    seed=st.integers(0, 1 << 16),
)
def test_butterfly_combine_sweep(n, dmax, seed):
    rng = np.random.default_rng(seed)
    d = rng.integers(0, dmax, n).astype(np.int32)
    rep = (rng.random(n) < 0.5).astype(np.int32)
    valid = (rng.random(n) < 0.9).astype(np.int32)
    g1, glo, ghi, gt = butterfly_combine_pallas(
        jnp.asarray(d), jnp.asarray(rep), jnp.asarray(valid)
    )
    w1, wlo, whi, wt = ref.butterfly_combine_ref(
        jnp.asarray(d), jnp.asarray(rep), jnp.asarray(valid)
    )
    assert np.array_equal(np.asarray(g1), np.asarray(w1))
    assert np.array_equal(np.asarray(glo), np.asarray(wlo))
    assert np.array_equal(np.asarray(ghi), np.asarray(whi))
    # limb recombination vs the int64 ground truth (the real oracle —
    # the ref shares the limb multiply, so check against numpy too)
    c2_true = np.where(
        (valid > 0) & (rep > 0) & (d > 0),
        d.astype(np.int64) * (d.astype(np.int64) - 1) // 2,
        0,
    )
    got64 = (np.asarray(glo).astype(np.uint32).astype(np.int64)
             + (np.asarray(ghi).astype(np.int64) << 32))
    assert np.array_equal(got64, c2_true)
    # per-element outputs are exact; the f32 scalar reduction rounds
    # above 2^24 (documented kernel contract) — compare with rtol and
    # against the exact int64 sum of the (exact) per-element array
    np.testing.assert_allclose(float(gt), float(wt), rtol=1e-6)
    np.testing.assert_allclose(float(gt), float(c2_true.sum()), rtol=1e-6)


def test_butterfly_combine_wide_multiplicities():
    """Group multiplicities >= 2^16 — C(d, 2) overflows int32 — stay
    exact on the kernel via the two-limb output (PR 1 follow-up: no
    in-graph exact-path fallback needed any more)."""
    d = np.array([70_000, 1 << 20, (1 << 21) - 3, 3, 0, 65_535],
                 np.int32)
    rep = np.ones_like(d)
    valid = np.ones_like(d)
    _, lo, hi, _ = butterfly_combine_pallas(
        jnp.asarray(d), jnp.asarray(rep), jnp.asarray(valid)
    )
    got64 = (np.asarray(lo).astype(np.uint32).astype(np.int64)
             + (np.asarray(hi).astype(np.int64) << 32))
    want = np.where(d > 0, d.astype(np.int64) * (d.astype(np.int64) - 1) // 2, 0)
    assert np.array_equal(got64, want)
    assert int(np.asarray(hi).max()) > 0  # the high limb is exercised


@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 5000), seed=st.integers(0, 1 << 16))
def test_bucket_min_sweep(n, seed):
    rng = np.random.default_rng(seed)
    c = rng.integers(0, 1 << 30, n).astype(np.int32)
    alive = (rng.random(n) < 0.5).astype(np.int32)
    got = bucket_min_pallas(jnp.asarray(c), jnp.asarray(alive))
    want = ref.bucket_min_ref(jnp.asarray(c), jnp.asarray(alive))
    assert int(got) == int(want)


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(1, 3000),
    k=st.integers(1, 512),
    seed=st.integers(0, 1 << 16),
)
def test_bucket_update_sweep(n, k, seed):
    """Batched decrease-key kernel vs jnp oracle vs numpy ground truth:
    updated counts, masked min, and geometric bucket occupancy."""
    rng = np.random.default_rng(seed)
    c = rng.integers(0, 1 << 30, n).astype(np.int32)
    alive = (rng.random(n) < 0.6).astype(np.int32)
    idx = rng.integers(0, n + 1, k).astype(np.int32)  # n = drop sentinel
    dec = np.where(idx == n, 0, rng.integers(0, 1 << 20, k)).astype(np.int32)
    got = bucket_update_pallas(
        jnp.asarray(c), jnp.asarray(alive), jnp.asarray(idx),
        jnp.asarray(dec),
    )
    want = ref.bucket_update_ref(
        jnp.asarray(c), jnp.asarray(alive), jnp.asarray(idx),
        jnp.asarray(dec),
    )
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    new, mn, hist = (np.asarray(x) for x in got)
    exp = c.astype(np.int64).copy()
    np.subtract.at(exp, idx[idx < n], dec[idx < n].astype(np.int64))
    assert np.array_equal(new.astype(np.int64), exp)  # no int32 wrap here
    masked = np.where(alive > 0, exp, np.iinfo(np.int32).max)
    assert int(mn) == int(masked.min())
    v = np.maximum(exp, 0)
    bl = np.sum(
        v[:, None] >= (1 << np.arange(31, dtype=np.int64))[None, :], axis=1
    )
    assert np.array_equal(
        hist, np.bincount(bl, weights=alive, minlength=NUM_BUCKETS
                          ).astype(np.int64)[:NUM_BUCKETS]
    )
    assert int(hist.sum()) == int(alive.sum())


def test_bucket_update_rejects_oversized_batch():
    """Batches beyond the f32 limb exactness bound must raise; the ops
    dispatcher feeds them through the kernel in chunks instead."""
    from repro.kernels import ops

    n = 64
    k = MAX_UPDATE_CAP + 1
    c = jnp.zeros((n,), jnp.int32)
    alive = jnp.ones((n,), jnp.int32)
    idx = jnp.zeros((k,), jnp.int32)
    dec = jnp.ones((k,), jnp.int32)
    with pytest.raises(ValueError, match="MAX_UPDATE_CAP"):
        bucket_update_pallas(c, alive, idx, dec)
    # the ops dispatcher chunks the batch through the kernel
    new, mn, hist = ops.bucket_update(c, alive, idx, dec, use_pallas=True)
    assert int(np.asarray(new)[0]) == -k
    assert int(mn) == -k


def test_bucket_update_chunks_wide_counts_through_kernel():
    """The ops dispatcher runs the kernel on any count dtype (values
    below INT32_MAX) and on batches beyond MAX_UPDATE_CAP, chunk by
    chunk: bitwise-equal to the jnp reference, result in the caller's
    dtype."""
    import jax

    from repro.kernels import ops

    rng = np.random.default_rng(3)
    n, k = 700, 2 * MAX_UPDATE_CAP + 5
    with jax.enable_x64(True):
        c = jnp.asarray(rng.integers(0, 1 << 30, n), jnp.int64)
        alive = jnp.asarray(rng.random(n) < 0.7)
        idx = jnp.asarray(rng.integers(0, n + 1, k), jnp.int32)
        dec = jnp.where(idx == n, 0, jnp.asarray(rng.integers(0, 1 << 12, k),
                                                 jnp.int32))
        got = ops.bucket_update(c, alive, idx, dec, use_pallas=True)
        want = ref.bucket_update_ref(c, alive, idx, dec)
        assert got[0].dtype == jnp.int64
        for a, b in zip(got, want):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_bucket_min_all_dead():
    c = jnp.arange(10, dtype=jnp.int32)
    alive = jnp.zeros(10, jnp.int32)
    assert int(bucket_min_pallas(c, alive)) == np.iinfo(np.int32).max


def test_histogram_kernel_used_in_count_path():
    """The one-hot MXU histogram reproduces the aggregation of a real
    wedge stream (keys from the counting engine)."""
    from repro.core import BipartiteGraph, make_order, preprocess
    from repro.core.count import default_count_dtype
    from repro.core.wedges import (
        device_graph, gather_wedges, host_wedge_counts, slot_wedge_counts,
    )

    rng = np.random.default_rng(5)
    e = np.stack([rng.integers(0, 30, 200), rng.integers(0, 25, 200)], axis=1)
    g = BipartiteGraph(30, 25, e)
    rg = preprocess(g, make_order(g, "degree"))
    dg = device_graph(rg)
    w_cap = max(128, int(host_wedge_counts(rg).sum() + 127) // 128 * 128)
    w = gather_wedges(dg, slot_wedge_counts(dg), w_cap)
    # count-dtype helper: don't request int64 on a device array without
    # x64 (JAX truncates with a UserWarning); n_pad² fits int32 here
    kd = default_count_dtype()
    keys = w.x1.astype(kd) * dg.n_pad + w.x2.astype(kd)
    keys = jnp.where(w.valid, keys, 0).astype(jnp.int32)
    nb = dg.n_pad * dg.n_pad
    got = wedge_histogram_pallas(keys, w.valid.astype(jnp.int32), nb)
    want = ref.wedge_histogram_ref(keys, w.valid.astype(jnp.int32), nb)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# -- occupancy histogram as a consumed artifact (PR 5 range peeling) ----


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(1, 3000),
    hi_bits=st.integers(1, 31),
    density=st.floats(0.0, 1.0),
    seed=st.integers(0, 1 << 16),
)
def test_bucket_state_hist_oracle(n, hi_bits, density, seed):
    """Property test for the geometric-bucket occupancy now driving
    peel_mode="range": ``bucket_state_ref``'s histogram matches a
    numpy bincount-of-bit-length oracle over alive entries, its lowest
    non-empty bucket equals the masked min's bit length (the range-mode
    selection invariant), and the selected bucket's upper bound covers
    the min."""
    from repro.kernels.bucket_update import (
        bit_length, bucket_upper_bound, lowest_nonempty_bucket,
    )

    rng = np.random.default_rng(seed)
    c = rng.integers(0, 1 << hi_bits, n).astype(np.int32)
    alive = (rng.random(n) < density).astype(np.int32)
    mn, hist = ref.bucket_state_ref(jnp.asarray(c), jnp.asarray(alive))
    mn, hist = int(mn), np.asarray(hist)
    # oracle: bincount of bit_length over alive entries
    bl = np.array([int(v).bit_length() for v in np.maximum(c, 0)])
    want = np.bincount(bl, weights=alive, minlength=NUM_BUCKETS)
    assert np.array_equal(hist, want.astype(np.int64)[:NUM_BUCKETS])
    assert int(hist.sum()) == int(alive.sum())
    k = int(lowest_nonempty_bucket(jnp.asarray(hist)))
    if alive.any():
        masked_min = int(c[alive > 0].min())
        assert mn == masked_min
        assert k == masked_min.bit_length()
        assert k == int(bit_length(jnp.int32(mn)))
        # the selected range [2^(k-1), 2^k) contains the min
        up = int(bucket_upper_bound(jnp.int32(k)))
        assert masked_min < up
        assert k == 0 or (1 << (k - 1)) <= max(masked_min, 1)
    else:
        assert mn == np.iinfo(np.int32).max
        assert k == NUM_BUCKETS


def test_bucket_update_hist_matches_bucket_state():
    """The histogram carried out of a decrease-key pass equals the
    standalone bucket_state of the updated array — the invariant the
    range-mode round loop relies on when it consumes the carried
    occupancy instead of recomputing it."""
    rng = np.random.default_rng(3)
    n, k = 500, 128
    c = rng.integers(0, 1 << 20, n).astype(np.int32)
    alive = (rng.random(n) < 0.7).astype(np.int32)
    idx = rng.integers(0, n + 1, k).astype(np.int32)
    dec = np.where(idx == n, 0, rng.integers(0, 1 << 10, k)).astype(np.int32)
    new, mn, hist = ref.bucket_update_ref(
        jnp.asarray(c), jnp.asarray(alive), jnp.asarray(idx),
        jnp.asarray(dec),
    )
    mn2, hist2 = ref.bucket_state_ref(new, jnp.asarray(alive))
    assert int(mn) == int(mn2)
    assert np.array_equal(np.asarray(hist), np.asarray(hist2))
