"""Differential-testing harness: every distributed join method — including
the skew-mitigating SALTED_SHUFFLE_HASH — against the pure-numpy oracle
(joins/ref.py) on a grid of adversarial inputs:

  * Zipf-skewed probe keys (mild and extreme),
  * all-duplicate probe keys (matching and non-matching),
  * empty probe / empty build / both empty,
  * fully disjoint key ranges (no matches),
  * single-partition (p=1) vs multi-partition (p=8) layouts,

asserting row-multiset equality in every cell. All tables share one static
capacity per side so XLA compiles one shape per (method, p) cell, not one
per case. Capacity overflow (the deliberately skewed cases exceed the
default slot budget) is absorbed by the same geometric-doubling retry the
executor uses — the harness thereby also exercises that contract at the
method level.

A second grid runs every method x case with the runtime bloom prefilter
(FilteredStrategy's data path) on the probe side, asserting equality with
the *unfiltered* oracle — including the empty-build-side case, where the
filter rejects everything and the result is empty rather than a crash.

A deterministic property layer (``hypothesis_compat`` shim — the real
hypothesis package, when installed) fuzzes sizes/skew/seed across all
methods with the same fixed shapes.
"""

import zlib

import numpy as np
import pytest
from helpers.hypothesis_compat import given, settings
from helpers.hypothesis_compat import strategies as st

from repro.core.cost_model import JoinMethod, bloom_params
from repro.joins import from_numpy, partition_round_robin, run_equi_join
from repro.joins.methods import (HypercubeLink, HypercubeSpec,
                                 hypercube_multiway_join)
from repro.joins.ref import ref_equi_join, ref_multiway_join, rows_as_set
from repro.kernels.ops import bloom_build, bloom_probe
from repro.sql.datagen import _zipf_fks

ALL_METHODS = [JoinMethod.BROADCAST_HASH, JoinMethod.SHUFFLE_HASH,
               JoinMethod.SALTED_SHUFFLE_HASH, JoinMethod.SHUFFLE_SORT,
               JoinMethod.BROADCAST_NL, JoinMethod.CARTESIAN]
HASH_FAMILY = [JoinMethod.BROADCAST_HASH, JoinMethod.SHUFFLE_HASH,
               JoinMethod.SALTED_SHUFFLE_HASH, JoinMethod.SHUFFLE_SORT]

#: Shared static capacities: every case pads to these, so each (method, p)
#: cell compiles once and the grid stays cheap on CPU.
CAP_A, CAP_B = 256, 64
NB = 48  # build keys live in [0, NB)


def _case(name, rng):
    """Adversarial (probe_keys, build_keys) pairs."""
    build = rng.permutation(NB).astype(np.int32)
    if name == "uniform":
        return rng.integers(0, NB, 200).astype(np.int32), build
    if name == "zipf_mild":
        return _zipf_fks(rng, 200, NB, 1.2), build
    if name == "zipf_extreme":
        return _zipf_fks(rng, 200, NB, 2.0), build
    if name == "all_dup_match":
        return np.full(200, int(build[0]), np.int32), build
    if name == "all_dup_nomatch":
        return np.full(200, NB + 17, np.int32), build
    if name == "no_overlap":
        return rng.integers(NB, 2 * NB, 200).astype(np.int32), build
    if name == "empty_probe":
        return np.empty(0, np.int32), build
    if name == "empty_build":
        return rng.integers(0, NB, 200).astype(np.int32), np.empty(0, np.int32)
    if name == "both_empty":
        return np.empty(0, np.int32), np.empty(0, np.int32)
    raise ValueError(name)


CASES = ("uniform", "zipf_mild", "zipf_extreme", "all_dup_match",
         "all_dup_nomatch", "no_overlap", "empty_probe", "empty_build",
         "both_empty")


def _tables(a_keys, b_keys, p):
    """(a, b, A, B): unstacked oracles + p-partitioned engine tables with
    integer payloads (exact multiset equality, no float tolerance)."""
    a = from_numpy({"k": a_keys,
                    "v": np.arange(len(a_keys), dtype=np.int32)},
                   capacity=CAP_A)
    b = from_numpy({"k": b_keys,
                    "payload": (np.arange(len(b_keys), dtype=np.int32) * 7)},
                   capacity=CAP_B)
    return a, b, partition_round_robin(a, p), partition_round_robin(b, p)


def _run_with_retry(method, A, B, join_type="inner", salt_r=3):
    """Method-level mirror of Executor._run_join_with_retry: double the slot
    capacity factor until no exchange overflows (bounded attempts)."""
    factor = 2.0
    for _ in range(6):
        out, rep = run_equi_join(method, A, B, "k", "k", join_type=join_type,
                                 capacity_factor=factor, salt_r=salt_r)
        if all(e.overflow_rows == 0 for e in rep.exchanges):
            return out, rep
        factor *= 2
    raise AssertionError(f"{method} overflow persisted after retries")


@pytest.mark.parametrize("p", [1, 8])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("method", ALL_METHODS)
def test_differential_inner(method, case, p):
    """Inner-join grid: every method must equal the oracle's row multiset."""
    # crc32, not hash(): builtin str hashing is randomized per process and
    # would silently defeat the deterministic-grid promise.
    rng = np.random.default_rng(zlib.crc32(f"{case}/{p}".encode()))
    a_keys, b_keys = _case(case, rng)
    a, b, A, B = _tables(a_keys, b_keys, p)
    want = rows_as_set(ref_equi_join(a.to_numpy(), b.to_numpy(), "k", "k"))
    out, rep = _run_with_retry(method, A, B)
    assert rows_as_set(out.to_numpy()) == want, (method, case, p)
    assert rep.output_rows == len(want)


@pytest.mark.parametrize("jt", ["inner", "left_outer", "left_semi",
                                "left_anti"])
@pytest.mark.parametrize("method", HASH_FAMILY)
def test_differential_join_types_on_skew(method, jt):
    """All join types survive Zipf skew on every hash-family method."""
    rng = np.random.default_rng(99)
    a_keys, b_keys = _case("zipf_extreme", rng)
    a, b, A, B = _tables(a_keys, b_keys, 8)
    want = rows_as_set(ref_equi_join(a.to_numpy(), b.to_numpy(), "k", "k",
                                     join_type=jt))
    out, _ = _run_with_retry(method, A, B, join_type=jt)
    assert rows_as_set(out.to_numpy()) == want, (method, jt)


@pytest.mark.parametrize("salt_r", [2, 5, 8])
def test_salted_agrees_for_any_salt_count(salt_r):
    """The salt bucket count r is a pure performance knob — results must be
    invariant to it (including r > p)."""
    rng = np.random.default_rng(7)
    a_keys, b_keys = _case("zipf_mild", rng)
    a, b, A, B = _tables(a_keys, b_keys, 4)
    want = rows_as_set(ref_equi_join(a.to_numpy(), b.to_numpy(), "k", "k"))
    out, _ = _run_with_retry(JoinMethod.SALTED_SHUFFLE_HASH, A, B,
                             salt_r=salt_r)
    assert rows_as_set(out.to_numpy()) == want


def _bloom_prefilter(A, B, bits_per_key: int = 10):
    """Mirror of Executor._apply_runtime_filter at the method level: build a
    bloom over B's valid keys, mask A's valid rows ahead of the join — the
    FilteredStrategy data path without the cost gate."""
    nb = int(np.asarray(B.valid).sum())
    m_bits, k = bloom_params(nb, bits_per_key)
    bits = bloom_build(B.column("k"), B.valid, m_bits=m_bits, k=k)
    keep = bloom_probe(A.column("k"), bits, k=k)
    return A.with_valid(A.valid & keep)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("method", ALL_METHODS)
def test_differential_inner_with_runtime_filter(method, case, p=8):
    """FilteredStrategy's data path on the full adversarial grid: a bloom
    prefilter on the probe side must leave every method's inner-join result
    equal to the *unfiltered* oracle (no false negatives means no lost
    matches; false positives are dropped by the join itself). The
    empty-build cases double as the filter-rejects-everything path: the
    result is empty, never a crash."""
    rng = np.random.default_rng(zlib.crc32(f"filtered/{case}/{p}".encode()))
    a_keys, b_keys = _case(case, rng)
    a, b, A, B = _tables(a_keys, b_keys, p)
    want = rows_as_set(ref_equi_join(a.to_numpy(), b.to_numpy(), "k", "k"))
    out, _ = _run_with_retry(method, _bloom_prefilter(A, B), B)
    assert rows_as_set(out.to_numpy()) == want, (method, case)


@pytest.mark.parametrize("jt", ["inner", "left_semi"])
@pytest.mark.parametrize("method", HASH_FAMILY)
def test_runtime_filter_join_types(method, jt):
    """The join types a probe-side filter is semantics-free for (the
    executor's _FILTERABLE_TYPES gate) stay oracle-equal under it."""
    rng = np.random.default_rng(23)
    a_keys, b_keys = _case("zipf_mild", rng)
    a, b, A, B = _tables(a_keys, b_keys, 8)
    want = rows_as_set(ref_equi_join(a.to_numpy(), b.to_numpy(), "k", "k",
                                     join_type=jt))
    out, _ = _run_with_retry(method, _bloom_prefilter(A, B), B, join_type=jt)
    assert rows_as_set(out.to_numpy()) == want, (method, jt)


def test_runtime_filter_empty_build_yields_empty_result():
    """Filter from an empty build rejects every probe row: the join runs on
    an all-invalid probe side and returns the empty result, no crash."""
    rng = np.random.default_rng(5)
    a_keys, _ = _case("uniform", rng)
    a, b, A, B = _tables(a_keys, np.empty(0, np.int32), 8)
    for method in ALL_METHODS:
        out, rep = _run_with_retry(method, _bloom_prefilter(A, B), B)
        assert out.count() == 0, method
        assert rep.output_rows == 0, method


@settings(max_examples=6, deadline=None)
@given(na=st.integers(0, 220), nb=st.integers(1, NB),
       skew_x10=st.integers(0, 22), seed=st.integers(0, 10_000))
def test_fuzz_methods_agree(na, nb, skew_x10, seed):
    """Property layer: random sizes x skew x seed, every method vs oracle.
    Shapes stay fixed (shared capacities), so examples don't recompile."""
    rng = np.random.default_rng(seed)
    build = rng.permutation(nb).astype(np.int32)
    s = skew_x10 / 10.0
    probe = (_zipf_fks(rng, na, nb, s) if s > 0
             else rng.integers(0, nb, na).astype(np.int32))
    a, b, A, B = _tables(probe, build, 4)
    want = rows_as_set(ref_equi_join(a.to_numpy(), b.to_numpy(), "k", "k"))
    for method in ALL_METHODS:
        out, _ = _run_with_retry(method, A, B)
        assert rows_as_set(out.to_numpy()) == want, method


# ---------------------------------------------------------------------------
# Hypercube multi-way join (cyclic join graphs) vs the oracle.
# ---------------------------------------------------------------------------

#: Triangle geometry: R(ra, rb, v) x S(sb -> s_c) x T(ta -> t_c), closed by
#: the check s_c == t_c over a small shared domain (so some rows survive).
NT3, NS3, NC3 = 20, 24, 4
CAP_CUBE = 192


def _cube_case(name, rng):
    """Adversarial (probe, build...) column dicts for the multi-way grid."""
    s = {"sb": np.arange(NS3, dtype=np.int32),
         "s_c": rng.integers(0, NC3, NS3).astype(np.int32)}
    t = {"ta": np.arange(NT3, dtype=np.int32),
         "t_c": rng.integers(0, NC3, NT3).astype(np.int32)}
    if name == "skewed_key":
        ra, rb = _zipf_fks(rng, 160, NT3, 1.8), _zipf_fks(rng, 160, NS3, 1.8)
    else:
        ra = rng.integers(0, NT3, 160).astype(np.int32)
        rb = rng.integers(0, NS3, 160).astype(np.int32)
    if name == "empty_relation":
        s = {"sb": np.empty(0, np.int32), "s_c": np.empty(0, np.int32)}
    r = {"ra": ra, "rb": rb, "v": np.arange(len(ra), dtype=np.int32)}
    if name == "clique":
        # Fourth relation on a third variable + a second closing check.
        r["rc"] = rng.integers(0, NC3, 160).astype(np.int32)
        u = {"uc": np.arange(NC3, dtype=np.int32),
             "u_c": rng.integers(0, NC3, NC3).astype(np.int32)}
        return r, s, t, u
    return r, s, t


def _cube_spec(name, dims):
    """The physical plan matching _cube_case: axis 0 = variable a (R, T),
    axis 1 = variable b (R, S); the clique adds axis 2 = variable c (R, U)
    and a second closing check chaining through U's payload."""
    links = (HypercubeLink(1, "rb", "sb"), HypercubeLink(2, "ra", "ta"))
    checks = (("s_c", "t_c"),)
    axis_keys = [((0, "ra"), (1, "rb")), ((1, "sb"),), ((0, "ta"),)]
    if name == "clique":
        axis_keys[0] = ((0, "ra"), (1, "rb"), (2, "rc"))
        axis_keys.append(((2, "uc"),))
        links += (HypercubeLink(3, "rc", "uc"),)
        checks += (("t_c", "u_c"),)
    return HypercubeSpec(dims=tuple(dims), axis_keys=tuple(axis_keys),
                         links=links, checks=checks)


def _run_cube_with_retry(tables, spec, use_kernel=False):
    factor = 2.0
    for _ in range(6):
        out, rep = hypercube_multiway_join(tables, spec,
                                           capacity_factor=factor,
                                           use_kernel=use_kernel)
        if all(e.overflow_rows == 0 for e in rep.exchanges):
            return out, rep
        factor *= 2
    raise AssertionError("hypercube overflow persisted after retries")


def _cube_tables(raw, p):
    return [partition_round_robin(from_numpy(c, capacity=CAP_CUBE), p)
            for c in raw]


def _cube_dims(name, p):
    if p == 1:
        return (1,) * (3 if name == "clique" else 2)
    return (2, 2, 2) if name == "clique" else (2, 4)


CUBE_CASES = ("triangle", "clique", "empty_relation", "skewed_key")


@pytest.mark.parametrize("p", [1, 8])
@pytest.mark.parametrize("case", CUBE_CASES)
def test_differential_hypercube(case, p):
    """Multi-way grid: the hypercube join must equal the sequential
    probe-then-filter oracle's row multiset on every cyclic shape,
    including an empty build relation (empty result, no crash)."""
    rng = np.random.default_rng(zlib.crc32(f"cube/{case}/{p}".encode()))
    raw = _cube_case(case, rng)
    spec = _cube_spec(case, _cube_dims(case, p))
    want = rows_as_set(ref_multiway_join(
        raw, [(lk.build, lk.probe_col, lk.build_col) for lk in spec.links],
        spec.checks))
    out, rep = _run_cube_with_retry(_cube_tables(raw, p), spec)
    assert rows_as_set(out.to_numpy()) == want, (case, p)
    assert rep.output_rows == len(want)
    if case == "empty_relation":
        assert not want


@pytest.mark.parametrize("use_kernel", [False, True])
def test_hypercube_cube_vs_flat_meshes_identical(use_kernel):
    """The cube shape is a pure performance knob: every factorization of p
    — cube, flat-by-a, flat-by-b — and the fused-kernel probe must yield
    the identical row multiset."""
    rng = np.random.default_rng(zlib.crc32(b"cube/mesh"))
    raw = _cube_case("triangle", rng)
    outs = []
    for dims in [(2, 4), (4, 2), (8, 1), (1, 8)]:
        out, _ = _run_cube_with_retry(_cube_tables(raw, 8),
                                      _cube_spec("triangle", dims),
                                      use_kernel=use_kernel)
        outs.append(rows_as_set(out.to_numpy()))
    assert outs[0] == outs[1] == outs[2] == outs[3]
    assert outs[0] == rows_as_set(ref_multiway_join(
        raw, [(1, "rb", "sb"), (2, "ra", "ta")], (("s_c", "t_c"),)))
