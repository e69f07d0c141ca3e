"""Property-based tests of the bloom-filter kernel pair (bloom_build /
bloom_probe) via the ``hypothesis_compat`` shim (the real hypothesis
package when installed):

  * **No false negatives, ever** — every key fed to the build must pass the
    probe, across dtypes, duplicate-heavy inputs and m/n ratios. This is
    the property runtime-filter correctness rests on.
  * **False-positive rate tracks the model** — the empirical FPR on keys
    disjoint from the build set stays within 2x of the (1 - e^{-kn/m})^k
    prediction (upper bound always; lower bound only when enough expected
    events make it statistically meaningful).
  * **Bit-array invariance** — the filter is a pure function of the key
    *set*: permutations and duplications of the build input produce the
    byte-identical array.
  * Kernel == numpy reference on every case.
"""

import numpy as np
import pytest
from helpers.hypothesis_compat import given, settings
from helpers.hypothesis_compat import strategies as st

from repro.core.cost_model import bloom_fpr, bloom_params
from repro.kernels.ops import bloom_build, bloom_probe
from repro.kernels.ref import bloom_build_ref, bloom_probe_ref

#: Integer dtypes a key column may arrive in (kernels view them as int32).
KEY_DTYPES = (np.int32, np.uint32, np.int16, np.int8)


def _keys(rng, n, lo, hi, dtype=np.int32):
    return rng.integers(lo, hi, n).astype(dtype)


@pytest.mark.parametrize("dtype", KEY_DTYPES, ids=[d.__name__
                                                   for d in KEY_DTYPES])
def test_no_false_negatives_across_dtypes(dtype):
    rng = np.random.default_rng(0)
    hi = min(120, np.iinfo(dtype).max)
    keys = _keys(rng, 500, 0, hi, dtype)
    m, k = bloom_params(len(np.unique(keys)))
    bits = bloom_build(keys, m_bits=m, k=k)
    assert bool(np.asarray(bloom_probe(keys, bits, k=k)).all()), dtype


@settings(max_examples=5, deadline=None)
@given(n=st.integers(1, 512), bits_per_key=st.integers(4, 16),
       dup=st.integers(1, 50), seed=st.integers(0, 10_000))
def test_no_false_negatives_fuzz(n, bits_per_key, dup, seed):
    """Duplicate-heavy inputs (each key repeated ``dup`` times), m/n ratios
    from lean (4 bits/key) to roomy (16): membership never lies."""
    rng = np.random.default_rng(seed)
    base = rng.integers(-(1 << 30), 1 << 30, n).astype(np.int32)
    keys = np.repeat(base, dup)
    m, k = bloom_params(len(np.unique(base)), bits_per_key)
    bits = bloom_build(keys, m_bits=m, k=k)
    assert bool(np.asarray(bloom_probe(base, bits, k=k)).all())


@settings(max_examples=4, deadline=None)
@given(n=st.integers(64, 2048), bits_per_key=st.integers(4, 12),
       seed=st.integers(0, 10_000))
def test_fpr_within_2x_of_model(n, bits_per_key, seed):
    """Empirical FPR on 20k keys disjoint from the build domain, vs the
    (1 - e^{-kn/m})^k prediction."""
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.integers(0, 1 << 20, n).astype(np.int32))
    m, k = bloom_params(len(keys), bits_per_key)
    bits = bloom_build(keys, m_bits=m, k=k)
    probes = 20_000
    miss = rng.integers(1 << 20, 1 << 24, probes).astype(np.int32)
    emp = float(np.asarray(bloom_probe(miss, bits, k=k)).mean())
    pred = bloom_fpr(len(keys), m, k)
    # Upper bound always (with a tiny absolute floor for near-zero preds);
    # lower bound only when >= 20 events are expected, else 0 hits is fine.
    assert emp <= 2.0 * pred + 20.0 / probes, (emp, pred, m, k)
    if pred * probes >= 20:
        assert emp >= pred / 2.0 - 10.0 / probes, (emp, pred, m, k)


@settings(max_examples=5, deadline=None)
@given(n=st.integers(2, 600), seed=st.integers(0, 10_000))
def test_bit_array_invariant_to_key_order(n, seed):
    """The filter is a pure function of the key set: permuting and
    duplicating the input leaves the packed words byte-identical."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 10 * n, n).astype(np.int32)
    m, k = bloom_params(n)
    ref = np.asarray(bloom_build(keys, m_bits=m, k=k))
    perm = np.asarray(bloom_build(keys[rng.permutation(n)], m_bits=m, k=k))
    dup = np.asarray(bloom_build(np.concatenate([keys, keys[::-1]]),
                                 m_bits=m, k=k))
    assert np.array_equal(ref, perm)
    assert np.array_equal(ref, dup)


@settings(max_examples=5, deadline=None)
@given(n=st.integers(0, 300), seed=st.integers(0, 10_000))
def test_kernel_matches_numpy_reference(n, seed):
    rng = np.random.default_rng(seed)
    keys = rng.integers(-1000, 1000, n).astype(np.int32)
    valid = rng.random(n) < 0.8
    m, k = bloom_params(max(int(valid.sum()), 1))
    got = np.asarray(bloom_build(keys, valid, m_bits=m, k=k))
    want = bloom_build_ref(keys, valid, m_bits=m, k=k)
    assert np.array_equal(got, want)


def test_invalid_rows_do_not_contribute():
    """A masked-out key must not set bits: probing it gives (almost surely)
    False, and the array equals the build over the valid subset alone."""
    keys = np.arange(100, dtype=np.int32)
    valid = keys < 50
    m, k = bloom_params(50)
    bits = np.asarray(bloom_build(keys, valid, m_bits=m, k=k))
    only = np.asarray(bloom_build(keys[:50], m_bits=m, k=k))
    assert np.array_equal(bits, only)


def test_empty_build_rejects_everything():
    """The empty-build filter is all zeros and rejects every probe — the
    degenerate case the executor leans on for empty build sides."""
    bits = bloom_build(np.empty(0, np.int32), m_bits=256, k=3)
    assert int(np.asarray(bits).sum()) == 0
    probe = np.arange(1000, dtype=np.int32)
    assert not np.asarray(bloom_probe(probe, bits, k=3)).any()


def test_stacked_shape_roundtrip():
    """(p, cap) stacked key columns keep their shape through the probe."""
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 64, (4, 128)).astype(np.int32)
    m, k = bloom_params(64)
    bits = bloom_build(keys, m_bits=m, k=k)
    mask = bloom_probe(keys, bits, k=k)
    assert mask.shape == keys.shape
    assert bool(np.asarray(mask).all())


@settings(max_examples=5, deadline=None)
@given(n=st.integers(1, 600), probes=st.integers(1, 3000),
       seed=st.integers(0, 10_000))
def test_probe_matches_numpy_reference(n, probes, seed):
    """The probe kernel's mask equals the numpy bit test, hits and false
    positives alike."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(-1000, 1000, n).astype(np.int32)
    m, k = bloom_params(n)
    bits = np.asarray(bloom_build(keys, m_bits=m, k=k))
    q = rng.integers(-2000, 2000, probes).astype(np.int32)
    got = np.asarray(bloom_probe(q, bits, k=k))
    assert np.array_equal(got, bloom_probe_ref(q, bits, k=k))


@pytest.mark.parametrize("n", [1, 3000])
def test_multi_tile_filter_matches_numpy_reference(n):
    """A 131072-bit filter (the 12k-row customer dimension's) spans four
    bitmap row tiles: the build ORs each tile over every key tile and the
    probe counts a key's bits across the row tiles."""
    from repro.kernels.bloom import TR
    m, k = 131_072, 8
    assert m // 128 == 4 * TR
    rng = np.random.default_rng(n)
    keys = rng.integers(-(1 << 30), 1 << 30, n).astype(np.int32)
    valid = rng.random(n) < 0.8
    bits = np.asarray(bloom_build(keys, valid, m_bits=m, k=k))
    assert np.array_equal(bits, bloom_build_ref(keys, valid, m_bits=m, k=k))
    q = np.concatenate([keys, rng.integers(-(1 << 30), 1 << 30, 5000)
                        .astype(np.int32)])
    got = np.asarray(bloom_probe(q, bits, k=k))
    assert np.array_equal(got, bloom_probe_ref(q, bits, k=k))
    assert got[:n][valid].all()
