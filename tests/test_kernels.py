"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret
mode executes the exact TPU kernel body on CPU)."""

import numpy as np
import pytest
import jax.numpy as jnp
from helpers.hypothesis_compat import given, settings
from helpers.hypothesis_compat import strategies as st

from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# tiled_probe
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("na,nb", [(1, 1), (7, 5), (8, 128), (100, 100),
                                   (256, 512), (300, 700), (1000, 64),
                                   (2048, 2048)])
def test_probe_matches_ref_shapes(na, nb):
    rng = np.random.default_rng(na * 1000 + nb)
    a = rng.integers(0, max(nb // 2, 2), size=na).astype(np.int32)
    b = rng.permutation(max(nb, 1)).astype(np.int32)[:nb]
    got = ops.probe(jnp.asarray(a), jnp.asarray(b))
    want = ref.tiled_probe_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("ta,tb", [(8, 128), (64, 128), (256, 512)])
def test_probe_tile_sweep(ta, tb):
    rng = np.random.default_rng(ta + tb)
    a = rng.integers(-1, 50, size=333).astype(np.int32)
    b = rng.integers(0, 50, size=217).astype(np.int32)
    got = ops.probe(jnp.asarray(a), jnp.asarray(b))
    want = ref.tiled_probe_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("batch", [(3,), (11,), (2, 9)])
def test_probe_batched_rows(batch):
    """One independent probe per batch row: rows beyond a whole sublane
    block and probe/build lengths off the 128-lane tiles."""
    rng = np.random.default_rng(len(batch) * 100 + batch[-1])
    a = rng.integers(-2, 60, batch + (300,)).astype(np.int32)
    b = rng.integers(-2, 60, batch + (129,)).astype(np.int32)
    got = np.asarray(ops.probe(jnp.asarray(a), jnp.asarray(b)))
    want = [np.asarray(ref.tiled_probe_ref(jnp.asarray(x), jnp.asarray(y)))
            for x, y in zip(a.reshape(-1, 300), b.reshape(-1, 129))]
    np.testing.assert_array_equal(got, np.stack(want).reshape(got.shape))


def test_probe3_matches_two_probes():
    """The fused 3-way probe equals two separate probes, builds of unequal
    lengths included."""
    rng = np.random.default_rng(3)
    a1, a2 = (jnp.asarray(rng.integers(-1, 80, (11, 300)), jnp.int32)
              for _ in range(2))
    b = jnp.asarray(rng.integers(0, 80, (11, 100)), jnp.int32)
    c = jnp.asarray(rng.integers(0, 80, (11, 250)), jnp.int32)
    g1, g2 = ops.probe3(a1, a2, b, c)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(ops.probe(a1, b)))
    np.testing.assert_array_equal(np.asarray(g2), np.asarray(ops.probe(a2, c)))


def test_probe_first_match_semantics():
    a = jnp.asarray([5, 9, 5], jnp.int32)
    b = jnp.asarray([1, 5, 3, 5], jnp.int32)  # duplicate build keys
    got = np.asarray(ops.probe(a, b))
    np.testing.assert_array_equal(got, [1, -1, 1])


def test_probe_sentinels_never_match():
    a = jnp.asarray([-1, -1, 3], jnp.int32)
    b = jnp.asarray([-2, 3, -2], jnp.int32)
    got = np.asarray(ops.probe(a, b))
    np.testing.assert_array_equal(got, [-1, -1, 1])


def test_probe_rejects_bad_dtype():
    with pytest.raises(TypeError):
        ops.probe(jnp.zeros(4, jnp.float32), jnp.zeros(4, jnp.int32))


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(-3, 40), min_size=1, max_size=300),
       st.lists(st.integers(0, 40), min_size=1, max_size=300))
def test_probe_property(avals, bvals):
    a = jnp.asarray(avals, jnp.int32)
    b = jnp.asarray(bvals, jnp.int32)
    got = np.asarray(ops.probe(a, b))
    want = np.asarray(ref.tiled_probe_ref(a, b))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# partition_hist
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,nd", [(1, 2), (100, 4), (1024, 8), (5000, 16),
                                  (10000, 128), (3, 1)])
def test_hist_matches_ref(n, nd):
    rng = np.random.default_rng(n + nd)
    d = rng.integers(-1, nd, size=n).astype(np.int32)  # includes invalid -1
    got = ops.hist(jnp.asarray(d), nd)
    want = ref.partition_hist_ref(jnp.asarray(d), nd)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_hist_total_conservation():
    rng = np.random.default_rng(0)
    d = rng.integers(0, 7, size=999).astype(np.int32)
    got = np.asarray(ops.hist(jnp.asarray(d), 7))
    assert got.sum() == 999


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(-1, 15), min_size=1, max_size=500),
       st.integers(1, 16))
def test_hist_property(dvals, nd):
    d = jnp.asarray([min(v, nd - 1) for v in dvals], jnp.int32)
    got = np.asarray(ops.hist(d, nd))
    want = np.asarray(ref.partition_hist_ref(d, nd))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# bitonic_sort
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [2, 4, 8, 64, 256, 1024, 4096])
def test_bitonic_sorts_pow2_tiles(n):
    rng = np.random.default_rng(n)
    k = rng.integers(0, 1 << 20, size=n).astype(np.int32)
    v = np.arange(n, dtype=np.int32)
    gk, gv = ops.sort_pairs(jnp.asarray(k), jnp.asarray(v))
    gk, gv = np.asarray(gk), np.asarray(gv)
    assert (np.diff(gk) >= 0).all()
    # Permutation correctness: the carried payload must still address the
    # original key at every output slot.
    np.testing.assert_array_equal(k[gv], gk)


def test_bitonic_with_duplicates_and_negatives():
    k = np.asarray([3, -1, 3, 0, -5, 3, 7, -1], np.int32)
    v = np.arange(8, dtype=np.int32)
    gk, gv = ops.sort_pairs(jnp.asarray(k), jnp.asarray(v))
    np.testing.assert_array_equal(np.asarray(gk), np.sort(k))
    np.testing.assert_array_equal(k[np.asarray(gv)], np.asarray(gk))


def test_sort_pairs_non_pow2_fallback():
    k = np.asarray([5, 1, 4, 1, 3], np.int32)
    v = np.arange(5, dtype=np.int32)
    gk, gv = ops.sort_pairs(jnp.asarray(k), jnp.asarray(v))
    np.testing.assert_array_equal(np.asarray(gk), np.sort(k))


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 9), st.integers(0, 2 ** 31 - 2))
def test_bitonic_property(logn, seed):
    n = 1 << logn
    rng = np.random.default_rng(seed)
    k = rng.integers(-100, 100, size=n).astype(np.int32)
    v = np.arange(n, dtype=np.int32)
    gk, gv = ops.sort_pairs(jnp.asarray(k), jnp.asarray(v))
    np.testing.assert_array_equal(np.asarray(gk), np.sort(k))
    np.testing.assert_array_equal(k[np.asarray(gv)], np.asarray(gk))
