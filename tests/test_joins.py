"""Join engine tests: all five methods vs the numpy oracle, join types,
exchange accounting, slot scatter, and the 8-device shard_map executor."""

import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
import jax.numpy as jnp
from helpers.hypothesis_compat import given, settings
from helpers.hypothesis_compat import strategies as st

from repro.core.cost_model import JoinMethod
from repro.joins import (broadcast, from_numpy, partition_round_robin,
                         run_equi_join, shuffle)
from repro.joins.local_join import hash_join, sort_join
from repro.joins.ref import ref_equi_join, rows_as_set
from repro.joins.slots import slot_scatter

EQUI = [JoinMethod.BROADCAST_HASH, JoinMethod.SHUFFLE_HASH,
        JoinMethod.SHUFFLE_SORT, JoinMethod.BROADCAST_NL,
        JoinMethod.CARTESIAN]


def make_tables(seed=0, na=400, nb=50, p=4, key_range_mult=2):
    rng = np.random.default_rng(seed)
    b = from_numpy({"k": rng.permutation(nb).astype(np.int32),
                    "payload": rng.integers(0, 99, nb).astype(np.int32)})
    a = from_numpy({"k": rng.integers(0, nb * key_range_mult, na
                                      ).astype(np.int32),
                    "v": rng.uniform(0, 1, na).astype(np.float32)})
    return a, b, partition_round_robin(a, p), partition_round_robin(b, p)


@pytest.mark.parametrize("method", EQUI)
def test_methods_match_oracle(method):
    a, b, A, B = make_tables()
    want = rows_as_set(ref_equi_join(a.to_numpy(), b.to_numpy(), "k", "k"))
    out, rep = run_equi_join(method, A, B, "k", "k")
    assert rows_as_set(out.to_numpy()) == want
    assert rep.output_rows == len(want)


@pytest.mark.parametrize("method", [JoinMethod.BROADCAST_HASH,
                                    JoinMethod.SHUFFLE_HASH,
                                    JoinMethod.SHUFFLE_SORT])
@pytest.mark.parametrize("jt", ["left_semi", "left_anti"])
def test_join_types(method, jt):
    a, b, A, B = make_tables(seed=3)
    want = rows_as_set(ref_equi_join(a.to_numpy(), b.to_numpy(), "k", "k",
                                     join_type=jt))
    out, _ = run_equi_join(method, A, B, "k", "k", join_type=jt)
    assert rows_as_set(out.to_numpy()) == want


def test_left_outer_preserves_probe_rows():
    a, b, A, B = make_tables(seed=5)
    out, _ = run_equi_join(JoinMethod.BROADCAST_HASH, A, B, "k", "k",
                           join_type="left_outer")
    assert out.count() == a.count()


@pytest.mark.parametrize("p", [1, 2, 4, 8])
def test_parallelism_sweep(p):
    a, b, _, _ = make_tables(seed=p)
    A, B = partition_round_robin(a, p), partition_round_robin(b, p)
    want = rows_as_set(ref_equi_join(a.to_numpy(), b.to_numpy(), "k", "k"))
    for method in (JoinMethod.BROADCAST_HASH, JoinMethod.SHUFFLE_HASH,
                   JoinMethod.SHUFFLE_SORT):
        out, _ = run_equi_join(method, A, B, "k", "k")
        assert rows_as_set(out.to_numpy()) == want, method


def test_kernel_backed_hash_join_matches():
    a, b, A, B = make_tables(seed=11, na=256, nb=32)
    want = rows_as_set(ref_equi_join(a.to_numpy(), b.to_numpy(), "k", "k"))
    out, _ = run_equi_join(JoinMethod.SHUFFLE_HASH, A, B, "k", "k",
                           use_kernel=True)
    assert rows_as_set(out.to_numpy()) == want


def test_skewed_keys_still_correct():
    # 80% of probe rows hit one hot key (paper §3.7: skew robustness).
    rng = np.random.default_rng(13)
    nb, na = 32, 500
    b = from_numpy({"k": np.arange(nb, dtype=np.int32),
                    "x": np.ones(nb, np.int32)})
    keys = np.where(rng.uniform(size=na) < 0.8, 7,
                    rng.integers(0, nb, na)).astype(np.int32)
    a = from_numpy({"k": keys, "v": np.ones(na, np.float32)})
    A, B = partition_round_robin(a, 4), partition_round_robin(b, 4)
    want = rows_as_set(ref_equi_join(a.to_numpy(), b.to_numpy(), "k", "k"))
    # Skewed shuffles need more slot capacity: capacity_factor covers it.
    out, rep = run_equi_join(JoinMethod.SHUFFLE_HASH, A, B, "k", "k",
                             capacity_factor=4.0)
    assert rows_as_set(out.to_numpy()) == want
    assert all(e.overflow_rows == 0 for e in rep.exchanges)


def test_exchange_workloads_match_model():
    """Measured broadcast bytes = Eq.1 exactly; shuffle ~= Eq.5."""
    a, b, A, B = make_tables(seed=2, na=2000, nb=64, p=4)
    full, rep = broadcast(B)
    assert rep.network_bytes == (4 - 1) * b.count() * b.row_bytes
    _, rep = shuffle(A, "k")
    model = (4 - 1) / 4 * a.count() * a.row_bytes
    assert rep.network_bytes == pytest.approx(model, rel=0.15)
    assert rep.overflow_rows == 0


def test_slot_scatter_properties():
    rng = np.random.default_rng(1)
    dest = jnp.asarray(rng.integers(0, 4, 100), jnp.int32)
    valid = jnp.asarray(rng.uniform(size=100) < 0.7)
    out = slot_scatter(dest, valid, 4, 50)
    idx = np.asarray(out.idx)
    placed = idx[idx >= 0]
    # Every valid row placed exactly once, in its destination's row.
    assert len(placed) == len(set(placed.tolist())) == int(valid.sum())
    d, v = np.asarray(dest), np.asarray(valid)
    for dd in range(4):
        rows = idx[dd][idx[dd] >= 0]
        assert all(d[r] == dd and v[r] for r in rows)


@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 200), nd=st.integers(1, 8), cap=st.integers(1, 64),
       seed=st.integers(0, 999))
def test_slot_scatter_conservation(n, nd, cap, seed):
    rng = np.random.default_rng(seed)
    dest = jnp.asarray(rng.integers(0, nd, n), jnp.int32)
    valid = jnp.asarray(rng.uniform(size=n) < 0.8)
    out = slot_scatter(dest, valid, nd, cap)
    placed = int((np.asarray(out.idx) >= 0).sum())
    assert placed + int(out.overflow) == int(valid.sum())


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), na=st.integers(1, 300),
       nb=st.integers(1, 100))
def test_local_joins_agree(seed, na, nb):
    """Hash join and sort join are interchangeable local methods (§5.3)."""
    rng = np.random.default_rng(seed)
    ak = jnp.asarray(rng.integers(0, nb * 2, na), jnp.int32)
    av = jnp.asarray(rng.uniform(size=na) < 0.9)
    bk = jnp.asarray(rng.permutation(nb * 2)[:nb], jnp.int32)
    bv = jnp.asarray(rng.uniform(size=nb) < 0.9)
    h = hash_join(ak, av, bk, bv)
    s = sort_join(ak, av, bk, bv)
    np.testing.assert_array_equal(np.asarray(h.found), np.asarray(s.found))
    np.testing.assert_array_equal(np.asarray(h.match_idx),
                                  np.asarray(s.match_idx))


@pytest.mark.parametrize("na", [3001, 400_000])
def test_hash_join_chunked_probe_matches_oracle(na):
    """The probe side goes through in row chunks of PROBE_TILE / slots
    candidate cells: 400k rows against 49-slot buckets take two chunks,
    the last one ragged. The matches are the sort join's."""
    import repro.joins.local_join as lj
    rng = np.random.default_rng(na)
    nb = 97                    # 8 buckets of 49 slots
    assert (na > lj.PROBE_TILE // 49) == (na == 400_000)
    ak = jnp.asarray(rng.integers(0, nb * 2, na), jnp.int32)
    av = jnp.asarray(rng.uniform(size=na) < 0.9)
    bk = jnp.asarray(rng.permutation(nb * 2)[:nb], jnp.int32)
    bv = jnp.asarray(rng.uniform(size=nb) < 0.9)
    got = hash_join(ak, av, bk, bv)
    want = sort_join(ak, av, bk, bv)
    np.testing.assert_array_equal(np.asarray(got.match_idx),
                                  np.asarray(want.match_idx))
    np.testing.assert_array_equal(np.asarray(got.found),
                                  np.asarray(want.found))


@pytest.mark.parametrize("nb", [128, 1024])
def test_sort_join_kernel_sort_matches(nb):
    """use_kernel_sort routes a power-of-two build tile through the bitonic
    kernel; the matches equal the default XLA-sort path."""
    rng = np.random.default_rng(nb)
    ak = jnp.asarray(rng.integers(0, nb * 2, 700), jnp.int32)
    av = jnp.asarray(rng.uniform(size=700) < 0.9)
    bk = jnp.asarray(rng.permutation(nb * 2)[:nb], jnp.int32)
    bv = jnp.asarray(rng.uniform(size=nb) < 0.9)
    want = sort_join(ak, av, bk, bv)
    got = sort_join(ak, av, bk, bv, use_kernel_sort=True)
    np.testing.assert_array_equal(np.asarray(got.match_idx),
                                  np.asarray(want.match_idx))
    np.testing.assert_array_equal(np.asarray(got.found),
                                  np.asarray(want.found))


@pytest.mark.slow
def test_distributed_shard_map_executor():
    """Real collectives on 8 placeholder devices (subprocess so the main
    process keeps its single-device view)."""
    helper = Path(__file__).parent / "helpers" / "run_distributed.py"
    proc = subprocess.run([sys.executable, str(helper)], capture_output=True,
                          text=True, timeout=600,
                          env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
                          cwd=str(Path(__file__).parent.parent))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "DISTRIBUTED_OK" in proc.stdout
