"""Distributed runtime-filter builds: every kind's per-device partial
payloads merged across the mesh must be bit-/value-identical to the
corresponding global-view build (``bloom_build`` / ``key_range`` /
``key_set``) — across device counts. The 1-device meshes run in every
tier; the 8-device cases run in the multi-device CI tier
(XLA_FLAGS=--xla_force_host_platform_device_count=8) and are skipped
where fewer devices exist.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.cost_model import bloom_params
from repro.core.psts import key_set
from repro.joins import from_numpy, partition_round_robin
from repro.joins.distributed import (dist_bloom_build, dist_key_set_build,
                                     dist_zone_map_build, make_join_mesh,
                                     place)
from repro.kernels.ops import bloom_build
from repro.kernels.ref import bloom_build_ref, key_range_ref


def _stacked(p, n=1000, seed=3, hole_frac=0.2):
    """Placed p-partition key table with a masked-out fraction of rows
    (post-filter survivors), plus sized bloom parameters."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(-(1 << 28), 1 << 28, n).astype(np.int32)
    t = from_numpy({"k": keys,
                    "payload": rng.integers(0, 99, n).astype(np.int32)})
    valid = np.asarray(t.valid) & (rng.random(n) >= hole_frac)
    t = t.with_valid(jnp.asarray(valid))
    mesh = make_join_mesh(p)
    stacked = place(partition_round_robin(t, p), mesh)
    m, k = bloom_params(len(np.unique(keys[valid])))
    return stacked, mesh, m, k


def _global_words(stacked, m, k):
    """Global-view build over the same (padded, masked) key material."""
    return np.asarray(bloom_build(np.asarray(stacked.column("k")),
                                  np.asarray(stacked.valid),
                                  m_bits=m, k=k))


def test_dist_build_bit_identical_to_global_single_device():
    stacked, mesh, m, k = _stacked(p=1)
    words = np.asarray(dist_bloom_build(stacked, "k", mesh, m_bits=m, k=k))
    assert (words == _global_words(stacked, m, k)).all()
    assert (words == bloom_build_ref(np.asarray(stacked.column("k")),
                                     np.asarray(stacked.valid),
                                     m_bits=m, k=k)).all()


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (multi-device CI tier)")
def test_dist_build_bit_identical_to_global_8_devices():
    """The OR-reduce is partition-invariant: the 8-way distributed build
    equals the global build bit for bit — and therefore also equals the
    1-device distributed build (device-count invariance {1, 8})."""
    stacked, mesh, m, k = _stacked(p=8)
    words = np.asarray(dist_bloom_build(stacked, "k", mesh, m_bits=m, k=k))
    assert (words == _global_words(stacked, m, k)).all()


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (multi-device CI tier)")
def test_dist_build_empty_partitions_are_neutral():
    """Partitions holding no live rows contribute the zero partial — the
    merged filter is unchanged by how rows land on devices."""
    stacked, mesh, m, k = _stacked(p=8, n=64, hole_frac=0.0)
    # Kill partitions 3..7 entirely.
    valid = np.asarray(stacked.valid).copy()
    valid[3:] = False
    dead = stacked.with_valid(jnp.asarray(valid))
    words = np.asarray(dist_bloom_build(dead, "k", mesh, m_bits=m, k=k))
    assert (words == _global_words(dead, m, k)).all()


# ---------------------------------------------------------------------------
# Zone-map / key-set distributed builds (the other two kinds' contracts)
# ---------------------------------------------------------------------------


def _zone_and_set_case(p, n=1000, seed=5, hole_frac=0.3, dup=True,
                       permute=False):
    """Placed p-partition key table with duplicated keys (distributed
    dedupe must collapse them) and a masked-out fraction of rows."""
    rng = np.random.default_rng(seed)
    base = rng.integers(-(1 << 20), 1 << 20, n // (3 if dup else 1))
    keys = np.resize(base, n).astype(np.int32)    # heavy duplication
    if permute:
        keys = rng.permutation(keys)
    t = from_numpy({"k": keys})
    valid = np.asarray(t.valid) & (rng.random(n) >= hole_frac)
    t = t.with_valid(jnp.asarray(valid))
    mesh = make_join_mesh(p)
    return place(partition_round_robin(t, p), mesh), mesh


def _assert_matches_global(stacked, mesh):
    col = np.asarray(stacked.column("k"))
    valid = np.asarray(stacked.valid)
    got = np.asarray(dist_zone_map_build(stacked, "k", mesh))
    assert (got == key_range_ref(col, valid)).all()
    ks, n = dist_key_set_build(stacked, "k", mesh)
    gk, gn = key_set(stacked.column("k"), stacked.valid)
    assert int(n) == int(gn)
    assert (np.asarray(ks) == np.asarray(gk)).all()


def test_dist_zone_map_and_key_set_match_global_single_device():
    stacked, mesh = _zone_and_set_case(p=1)
    _assert_matches_global(stacked, mesh)


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (multi-device CI tier)")
def test_dist_zone_map_and_key_set_match_global_8_devices():
    """min/max and sorted set-union are partition-invariant merges: the
    8-way distributed builds equal the global builds value for value —
    and therefore also the 1-device builds (device-count invariance)."""
    stacked, mesh = _zone_and_set_case(p=8)
    _assert_matches_global(stacked, mesh)


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (multi-device CI tier)")
def test_dist_builds_dup_and_order_invariant():
    """Permuting the input rows changes which device holds which keys —
    the merged payloads must not change (pure functions of the key set)."""
    a, mesh = _zone_and_set_case(p=8, seed=9, hole_frac=0.0)
    b, _ = _zone_and_set_case(p=8, seed=9, hole_frac=0.0, permute=True)
    za = np.asarray(dist_zone_map_build(a, "k", mesh))
    zb = np.asarray(dist_zone_map_build(b, "k", mesh))
    assert (za == zb).all()
    ka, na = dist_key_set_build(a, "k", mesh)
    kb, nb = dist_key_set_build(b, "k", mesh)
    assert int(na) == int(nb)
    assert (np.asarray(ka)[:int(na)] == np.asarray(kb)[:int(nb)]).all()


def test_dist_builds_empty_build_side():
    """All-invalid build -> the reject-everything payloads: the empty
    interval (lo > hi) and the empty key list (n = 0), matching the
    global-view degenerate-build contract."""
    stacked, mesh = _zone_and_set_case(p=1, n=64)
    dead = stacked.with_valid(jnp.zeros_like(stacked.valid))
    lo_hi = np.asarray(dist_zone_map_build(dead, "k", mesh))
    assert lo_hi[0] > lo_hi[1]
    ks, n = dist_key_set_build(dead, "k", mesh)
    assert int(n) == 0
    gk, gn = key_set(dead.column("k"), dead.valid)
    assert int(gn) == 0
    assert (np.asarray(ks) == np.asarray(gk)).all()
