"""Chip smoke run: the engine's main path on a TPU, checked end to end.

    python chip_smoke.py             # QueryService -> Executor on one chip
    python chip_smoke.py --chips 4   # the shard_map join methods, 4 chips

One chip: builds the synthetic TPC-DS-shaped catalog at scale 30 (3.0M
``store_sales`` rows, about TPC-DS SF1's 2.88M), runs the concurrent-service
suite, a skewed query and a star join through ``QueryService`` with its
default strategy (runtime filters on), and checks the rows three ways:
against ``execute_solo``, against a numpy oracle, and against the same
plans executed with the Pallas local-join kernels (``use_kernel=True``).
Every Pallas kernel on that path compiles natively; a first phase checks
each kernel against its oracle.

Four chips: the ``shard_map`` join methods and distributed filter builds
of ``repro.joins.distributed`` on a 4-device mesh over the same scale-30
data, checked against the global-view methods and the numpy oracle, with
the device that holds each output shard.

Needs a TPU: with no TPU it exits non-zero and prints no result. The last
line of a passing run is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed check exits non-zero. Seconds are wall-clock per phase,
compilation included, with the part spent in XLA compilations.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SCALE, P, SEED = 30, 8, 0


COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Smoke:
    """Phase timer, kernel-call and compilation counter, check ledger."""

    def __init__(self, device):
        import jax

        self.device = device
        self.failures: list[str] = []
        #: XLA backend compilations and their seconds.
        self.compiles = [0, 0.0]

        def compiled(event: str, duration: float, **_):
            if event == COMPILE_EVENT:
                self.compiles[0] += 1
                self.compiles[1] += duration

        jax.monitoring.register_event_duration_secs_listener(compiled)

    @property
    def calls(self) -> collections.Counter:
        """Kernel calls from Python so far, from the engine's
        ``kernel.<name>`` counters (``repro.obs``)."""
        from repro import obs

        return collections.Counter(
            {name[len("kernel."):]: n for name, n in obs.snapshot().items()
             if name.startswith("kernel.")})

    @contextlib.contextmanager
    def phase(self, name: str):
        before = self.calls
        n0, s0 = self.compiles
        print(f"-- {name}", flush=True)
        t0 = time.perf_counter()
        yield
        dt = time.perf_counter() - t0
        called = dict(self.calls - before)
        peak = self.device.memory_stats()["peak_bytes_in_use"]
        print(f"   {name}: {dt} s wall, of which {self.compiles[1] - s0} s "
              f"in {self.compiles[0] - n0} XLA compilations; kernel calls "
              f"{called}; peak_bytes_in_use so far {peak}", flush=True)

    def check(self, ok: bool, what: str) -> None:
        print(f"   {'OK  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failures.append(what)


# -- kernels ------------------------------------------------------------------

def check_kernels(smoke: Smoke) -> None:
    """Each Pallas kernel of the main path against its oracle."""
    import jax.numpy as jnp

    from repro.core.cost_model import bloom_params
    from repro.kernels import ops as kops
    from repro.kernels import ref

    rng = np.random.default_rng(SEED)
    a = rng.integers(-1, 3000, (4, 5000)).astype(np.int32)
    b = np.stack([rng.permutation(3000)[:2000] for _ in range(4)]
                 ).astype(np.int32)
    got = np.asarray(kops.probe(jnp.asarray(a), jnp.asarray(b)))
    want = np.stack([np.asarray(ref.tiled_probe_ref(jnp.asarray(x),
                                                    jnp.asarray(y)))
                     for x, y in zip(a, b)])
    smoke.check(np.array_equal(got, want), "tiled_probe (4 x 5000 vs 2000)")

    c = np.stack([rng.permutation(3000)[:1500] for _ in range(4)]
                 ).astype(np.int32)
    g1, g2 = kops.probe3(jnp.asarray(a), jnp.asarray(a[::-1].copy()),
                         jnp.asarray(b), jnp.asarray(c))
    w2 = np.stack([np.asarray(ref.tiled_probe_ref(jnp.asarray(x),
                                                  jnp.asarray(y)))
                   for x, y in zip(a[::-1], c)])
    smoke.check(np.array_equal(np.asarray(g1), want)
                and np.array_equal(np.asarray(g2), w2), "tiled_probe3")

    dest = rng.integers(-1, P, 3_000_000).astype(np.int32)
    got = np.asarray(kops.hist(jnp.asarray(dest), P))
    smoke.check(np.array_equal(got, np.bincount(dest[dest >= 0],
                                                minlength=P)),
                "partition_hist (3M rows, nd=8)")

    keys = rng.integers(0, 1 << 30, (P, 3000)).astype(np.int32)
    valid = rng.random((P, 3000)) < 0.5
    m_bits, k = bloom_params(int(valid.sum()))
    words = np.asarray(kops.bloom_build(jnp.asarray(keys),
                                        jnp.asarray(valid),
                                        m_bits=m_bits, k=k))
    smoke.check(np.array_equal(words, ref.bloom_build_ref(
        keys, valid, m_bits=m_bits, k=k)),
        f"bloom_build (m_bits={m_bits}, k={k}) bit-identical to numpy")
    probes = np.concatenate([keys.reshape(-1), rng.integers(
        0, 1 << 30, 200_000).astype(np.int32)])
    mask = np.asarray(kops.bloom_probe(jnp.asarray(probes),
                                       jnp.asarray(words), k=k))
    smoke.check(np.array_equal(mask, ref.bloom_probe_ref(probes, words,
                                                         k=k)),
                "bloom_probe matches numpy")

    lo_hi = np.asarray(kops.key_range(jnp.asarray(keys), jnp.asarray(valid)))
    smoke.check(np.array_equal(lo_hi, ref.key_range_ref(keys, valid)),
                "key_range")

    sk = rng.integers(-(1 << 20), 1 << 20, 4096).astype(np.int32)
    sv = np.arange(4096, dtype=np.int32)
    gk, gv = (np.asarray(x) for x in kops.sort_pairs(jnp.asarray(sk),
                                                     jnp.asarray(sv)))
    smoke.check(np.array_equal(gk, np.sort(sk))
                and np.array_equal(sk[gv], gk), "bitonic_sort_tile (4096)")


# -- one chip -----------------------------------------------------------------

def star_plan():
    """store_sales joined with three filtered dimensions, no aggregate:
    the rows are comparable one for one with the numpy oracle."""
    from repro.sql import Filter, Join, Scan
    return Join(
        Join(Join(Scan("store_sales"),
                  Filter(Scan("customer"), "c_region", "lt", 2),
                  "ss_customer_sk", "c_customer_sk"),
             Filter(Scan("item"), "i_category", "lt", 3),
             "ss_item_sk", "i_item_sk"),
        Filter(Scan("date_dim"), "d_month", "eq", 0),
        "ss_sold_date_sk", "d_date_sk")


def star_oracle(catalog):
    """``star_plan`` on the catalog's host columns with ``ref_equi_join``."""
    from repro.joins.ref import ref_equi_join

    def host(name, col=None, keep=None):
        cols = catalog.table(name).to_numpy()
        if col is None:
            return cols
        mask = keep(cols[col])
        return {n: c[mask] for n, c in cols.items()}

    out = ref_equi_join(host("store_sales"),
                        host("customer", "c_region", lambda c: c < 2),
                        "ss_customer_sk", "c_customer_sk")
    out = ref_equi_join(out, host("item", "i_category", lambda c: c < 3),
                        "ss_item_sk", "i_item_sk")
    return ref_equi_join(out, host("date_dim", "d_month", lambda c: c == 0),
                         "ss_sold_date_sk", "d_date_sk")


def one_chip(smoke: Smoke) -> None:
    import jax

    from repro.joins.ref import rows_as_set, rows_close
    from repro.sql import (Executor, FilterCache, QueryService,
                           filtered_queries, generate, service_queries,
                           skewed_queries)
    from repro.sql.datagen import FACTS

    with smoke.phase("kernels vs oracles"):
        check_kernels(smoke)

    with smoke.phase(f"generate(scale={SCALE}, p={P}, seed={SEED})"):
        catalog = generate(scale=SCALE, p=P, seed=SEED)
        jax.block_until_ready(catalog.tables)
    print("   only the facts scale; the dimensions keep their fixed sizes "
          "(datagen.SCHEMA)")
    for name, t in catalog.tables.items():
        kind = "fact" if name in FACTS else "dimension"
        print(f"   {name}: {t.count()} rows ({kind})")

    queries = dict(service_queries())
    filtered = sorted(set(filtered_queries()) & set(queries))
    queries["q16_hot_customer"] = skewed_queries()["q16_hot_customer"]
    queries["star_oracle"] = star_plan()
    print(f"   queries: {list(queries)}; from filtered_queries(): {filtered}")

    service = QueryService(catalog)
    before = smoke.calls
    with smoke.phase("QueryService batch (default strategy)"):
        subs = {n: service.submit(q, name=n) for n, q in queries.items()}
        results = {n: r for rep in service.run()
                   for n, r in rep.results.items()}
        rows = {n: rows_as_set(r.table.to_numpy())
                for n, r in results.items()}
    for n, r in results.items():
        kinds = [f.plan.kind for f in r.filters]
        print(f"   {n}: {len(rows[n])} rows; methods "
              f"{[m.name for m in r.methods()]}; filters {kinds}")
    called = smoke.calls - before
    for kernel in ("partition_hist", "bloom_build", "bloom_probe",
                   "key_range"):
        smoke.check(called[kernel] > 0, f"the batch ran {kernel}")

    with smoke.phase("execute_solo reference"):
        for n, q in queries.items():
            solo = rows_as_set(service.execute_solo(q).table.to_numpy())
            smoke.check(rows_close(solo, rows[n]),
                        f"{n}: batched rows match execute_solo "
                        f"({len(rows[n])} rows)")

    with smoke.phase("numpy oracle"):
        want = rows_as_set(star_oracle(catalog))
        smoke.check(want == rows["star_oracle"],
                    f"star_oracle: rows equal the numpy oracle "
                    f"({len(want)} rows)")

    before = smoke.calls
    strategy = dataclasses.replace(service.strategy, cache=FilterCache())
    with smoke.phase("Executor(use_kernel=True)"):
        for n in queries:
            ex = Executor(catalog, strategy, use_kernel=True)
            got = rows_as_set(ex.execute(subs[n].optimized.plan)
                              .table.to_numpy())
            smoke.check(rows_close(got, rows[n]),
                        f"{n}: use_kernel=True rows match the default path")
    called = smoke.calls - before
    smoke.check(called["tiled_probe"] > 0, "use_kernel=True ran tiled_probe")
    print(f"   bitonic_sort_tile ran on the kernel path: "
          f"{called['bitonic_sort_tile'] > 0} "
          f"({called['bitonic_sort_tile']} calls; it runs only where "
          f"sort_pairs gets a power-of-two tile of at most 4096)")


# -- four chips ---------------------------------------------------------------

def canonical(cols: dict) -> tuple:
    """Column names and row-sorted columns: equal iff equal multisets."""
    names = sorted(cols)
    arrs = [np.asarray(cols[n]) for n in names]
    order = np.lexsort(arrs[::-1])
    return names, [a[order] for a in arrs]


def same_rows(x: tuple, y: tuple) -> bool:
    return x[0] == y[0] and all(np.array_equal(a, b)
                                for a, b in zip(x[1], y[1]))


def placement(arr) -> list:
    """(partition, device) for each addressable shard of a placed array."""
    return sorted((s.index[0].start or 0, str(s.device))
                  for s in arr.addressable_shards)


def four_chips(smoke: Smoke, n_dev: int) -> None:
    import jax

    from repro.core.cost_model import bloom_params
    from repro.core.psts import key_set
    from repro.joins import methods
    from repro.joins.distributed import (
        dist_bloom_build, dist_broadcast_hash_join, dist_hypercube_join,
        dist_key_set_build, dist_shuffle_hash_join, dist_shuffle_sort_join,
        dist_zone_map_build, make_cube_mesh, make_join_mesh, place,
        place_cube)
    from repro.joins.ref import ref_equi_join, ref_multiway_join
    from repro.kernels import ops as kops
    from repro.kernels import ref
    from repro.sql import generate

    with smoke.phase(f"generate(scale={SCALE}, p={n_dev}, seed={SEED})"):
        catalog = generate(scale=SCALE, p=n_dev, seed=SEED)
        names = ("store_sales", "customer", "item")
        tabs = {n: catalog.table(n) for n in names}
        host = {n: t.to_numpy() for n, t in tabs.items()}
        mesh = make_join_mesh(n_dev)
        placed = {n: place(t, mesh) for n, t in tabs.items()}
        jax.block_until_ready(placed)
    for n in names:
        print(f"   {n}: {tabs[n].count()} rows; partitions on "
              f"{placement(placed[n].valid)}")

    def devices_of(out) -> set:
        where = placement(out.valid)
        print(f"   output shards: {where}")
        return {d for _, d in where}

    ss = "store_sales"
    cases = (
        ("broadcast_hash", dist_broadcast_hash_join,
         methods.broadcast_hash_join, "item", "ss_item_sk", "i_item_sk"),
        ("shuffle_hash", dist_shuffle_hash_join, methods.shuffle_hash_join,
         "customer", "ss_customer_sk", "c_customer_sk"),
        ("shuffle_sort", dist_shuffle_sort_join, methods.shuffle_sort_join,
         "customer", "ss_customer_sk", "c_customer_sk"),
    )
    for name, dist_fn, glob_fn, dim, a_key, b_key in cases:
        with smoke.phase(f"{name}: {ss} x {dim} on {n_dev} devices"):
            out = dist_fn(placed[ss], placed[dim], a_key, b_key, mesh)
            got = canonical(out.to_numpy())
        smoke.check(len(devices_of(out)) == n_dev,
                    f"{name}: output spread over {n_dev} distinct devices")
        with smoke.phase(f"{name}: global view + numpy oracle"):
            glob, _ = glob_fn(tabs[ss], tabs[dim], a_key, b_key)
            want = canonical(ref_equi_join(host[ss], host[dim], a_key,
                                           b_key))
            smoke.check(same_rows(got, want),
                        f"{name}: rows equal the numpy oracle "
                        f"({len(want[1][0])} rows)")
            smoke.check(same_rows(canonical(glob.to_numpy()), want),
                        f"{name}: global-view method equals the oracle")

    dims = (2, n_dev // 2)
    spec = methods.HypercubeSpec(
        dims=dims,
        axis_keys=(((0, "ss_customer_sk"), (1, "ss_item_sk")),
                   ((0, "c_customer_sk"),), ((1, "i_item_sk"),)),
        links=(methods.HypercubeLink(1, "ss_customer_sk", "c_customer_sk"),
               methods.HypercubeLink(2, "ss_item_sk", "i_item_sk")),
        checks=())
    cube = make_cube_mesh(dims)
    order = (ss, "customer", "item")
    with smoke.phase(f"hypercube {dims}: {' x '.join(order)}"):
        cubed = tuple(place_cube(tabs[n], cube) for n in order)
        out = dist_hypercube_join(cubed, spec, cube)
        got = canonical(out.to_numpy())
    smoke.check(len(devices_of(out)) == n_dev,
                f"hypercube: output spread over {n_dev} distinct devices")
    with smoke.phase("hypercube: global view + numpy oracle"):
        glob, _ = methods.hypercube_multiway_join([tabs[n] for n in order],
                                                  spec)
        want = canonical(ref_multiway_join(
            [host[n] for n in order],
            [(1, "ss_customer_sk", "c_customer_sk"),
             (2, "ss_item_sk", "i_item_sk")]))
        smoke.check(same_rows(got, want),
                    f"hypercube: rows equal the numpy oracle "
                    f"({len(want[1][0])} rows)")
        smoke.check(same_rows(canonical(glob.to_numpy()), want),
                    "hypercube: global-view method equals the oracle")

    # Filter builds over a filtered dimension (the runtime filters' build
    # side) and over the fact's date key (a zone map's band).
    cust = tabs["customer"]
    rich = cust.with_valid(cust.valid & (cust.column("c_income") < 74000))
    rich_placed = place(rich, mesh)
    keys = np.asarray(rich.column("c_customer_sk"))
    valid = np.asarray(rich.valid)
    m_bits, k = bloom_params(int(valid.sum()))
    with smoke.phase(f"distributed filter builds on {n_dev} devices"):
        words = np.asarray(dist_bloom_build(rich_placed, "c_customer_sk",
                                            mesh, m_bits=m_bits, k=k))
        lo_hi = np.asarray(dist_zone_map_build(placed[ss],
                                               "ss_sold_date_sk", mesh))
        ks, n_ks = dist_key_set_build(rich_placed, "c_customer_sk", mesh)
        ks, n_ks = np.asarray(ks), int(n_ks)
    glob_words = np.asarray(kops.bloom_build(rich.column("c_customer_sk"),
                                             rich.valid, m_bits=m_bits, k=k))
    smoke.check(np.array_equal(words, glob_words)
                and np.array_equal(words, ref.bloom_build_ref(
                    keys, valid, m_bits=m_bits, k=k)),
                f"dist_bloom_build (m_bits={m_bits}, k={k}) equals the "
                f"global build and numpy")
    ss_dates = tabs[ss]
    smoke.check(np.array_equal(lo_hi, np.asarray(kops.key_range(
        ss_dates.column("ss_sold_date_sk"), ss_dates.valid)))
        and np.array_equal(lo_hi, ref.key_range_ref(
            host[ss]["ss_sold_date_sk"])),
        f"dist_zone_map_build {lo_hi.tolist()} equals key_range and numpy")
    g_ks, g_n = key_set(rich.column("c_customer_sk"), rich.valid)
    smoke.check(n_ks == int(g_n) == len(np.unique(keys[valid]))
                and np.array_equal(ks[:n_ks], np.asarray(g_ks)[:n_ks]),
                f"dist_key_set_build ({n_ks} keys) equals key_set")


# -- entry point --------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the QueryService path; 4: the shard_map join "
                         "methods on a 4-chip mesh")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees "
              f"{devices[0].platform!r} devices); this smoke runs only on "
              f"a TPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the engine's sources are not beside this "
              f"script ({ROOT / 'src'}): {e}", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}")
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}; jax {jax.__version__}")

    smoke = Smoke(dev)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(smoke, args.chips)
    else:
        one_chip(smoke)
    print(f"total: {time.perf_counter() - t0} s wall, of which "
          f"{smoke.compiles[1]} s in {smoke.compiles[0]} XLA compilations; "
          f"peak_bytes_in_use {dev.memory_stats()['peak_bytes_in_use']}")
    if smoke.failures:
        print(f"chip_smoke: {len(smoke.failures)} check(s) failed:",
              file=sys.stderr)
        for f in smoke.failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
