"""Mesh programs survive a plan (docs/ici_shuffle.md, "Where a mesh program
lives"): the jitted ``shard_map`` programs of parallel/distagg.py,
distjoin.py and distsort.py live in one process-wide, LRU-bounded memo
(``parallel/mesh.py: mesh_program``), so a plan built anew finds what an
earlier plan compiled.

Two halves.  Through ``TpuSession`` under the benchmark configuration
``tpch_sf1_mesh4``'s own ``conf`` (60 k lineitem rows, a four-wide mesh of
the eight forced host devices): a second Q3 and a second Q18, each planned
from a new DataFrame, find every program (``ici.program_hits``), JAX neither
traces nor lowers nor compiles anything, and the answer is the first plan's
and the reference's.  And on ``Distributed*`` objects built directly: what
the key must tell apart, what it may share, the bound, and the object with a
``prelude`` that stays out of the shared memo.
"""

import importlib.util
import json
import os
import sys
import threading

import numpy as np
import pyarrow as pa
import pytest

import jax
import jax.monitoring

from spark_rapids_tpu.columnar.batch import host_batch_to_device
from spark_rapids_tpu.columnar.dtypes import FLOAT64, INT64, Schema
from spark_rapids_tpu.exprs.aggregates import Count, Max, Sum
from spark_rapids_tpu.exprs.arithmetic import Add
from spark_rapids_tpu.exprs.base import Alias, BoundReference, Literal
from spark_rapids_tpu.parallel import mesh as pmesh
from spark_rapids_tpu.parallel.distagg import DistributedAggregate
from spark_rapids_tpu.parallel.distjoin import (
    DistributedBroadcastJoinAggregate, DistributedHashJoin,
)
from spark_rapids_tpu.parallel.distsort import DistributedSort
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.utils.kernel_cache import KernelCache

multichip = pytest.mark.multichip

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
ROWS = 60_000
SEED = 7
# mesh programs a query asks for: a join a count and a join program, an
# aggregate one (q3: two joins, one aggregate; q18: three and two)
PROGRAMS = {"q3": 5, "q18": 8}
# what JAX says when it traces, lowers or compiles (or fetches a compiled
# program from its persistent cache): benchmark/run.py's CompileClock
# listens for the last
JAX_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

_heard = []
_listening = []


def _listener(name, _secs, **_):
    if _listening and name in JAX_EVENTS:
        _heard.append(name)


jax.monitoring.register_event_duration_secs_listener(_listener)


def _load(*parts):
    path = os.path.join(BENCH, *parts)
    name = "meshprog_" + "_".join(parts).replace(".py", "").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def _lookups():
    stats = pmesh.gather_stats()
    return stats["program_lookups"], stats["program_hits"]


# ---------------------------------------------------------------------------
# Through the session: a plan built anew finds the last plan's programs
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "tpch_sf1_mesh4.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def plans(tmp_path_factory, config):
    """Each query planned and run twice, each time from a DataFrame built
    anew: both answers, the reference's, what ``ici.program_lookups`` and
    ``ici.program_hits`` grew by in each run, and what JAX said during the
    second."""
    builders = _load("queries", "tpch_joins.py")
    reference = _load("reference", "tpch_joins.py")
    paths = _load("datagen", "tpch.py").generate(
        str(tmp_path_factory.mktemp("mesh_programs")), ROWS, SEED)
    out = {}
    sess = TpuSession(dict(config["conf"]))
    try:
        tables = {n: sess.read.parquet(p) for n, p in paths.items()}
        for q in PROGRAMS:
            run = {"want": reference.QUERIES[q](paths), "got": [],
                   "grown": []}
            for nth in (0, 1):
                before = sess.engine_stats()["ici"]
                del _heard[:]
                _listening.append(nth == 1)
                try:
                    run["got"].append(builders.build(q, tables).to_arrow())
                finally:
                    del _listening[:]
                after = sess.engine_stats()["ici"]
                run["grown"].append(
                    {k: after[k] - before[k]
                     for k in ("program_lookups", "program_hits",
                               "exchanges", "fallbacks")})
            run["heard"] = list(_heard)
            out[q] = run
    finally:
        sess.stop()
    return out


@multichip
@pytest.mark.parametrize("query", sorted(PROGRAMS))
def test_a_second_plan_finds_every_program(plans, query):
    first, second = plans[query]["grown"]
    assert first["program_lookups"] == PROGRAMS[query]
    assert second == {"program_lookups": PROGRAMS[query],
                      "program_hits": PROGRAMS[query],
                      "exchanges": first["exchanges"], "fallbacks": 0}


@multichip
@pytest.mark.parametrize("query", sorted(PROGRAMS))
def test_a_second_plan_traces_lowers_and_compiles_nothing(plans, query):
    assert plans[query]["heard"] == []


@multichip
@pytest.mark.parametrize("query", sorted(PROGRAMS))
def test_a_second_plan_answers_as_the_first(plans, query):
    first, second = plans[query]["got"]
    assert first.num_rows > 0
    assert second.equals(first)


@multichip
@pytest.mark.parametrize("query", sorted(PROGRAMS))
def test_a_second_plan_answers_as_the_reference(plans, config, query):
    compare = _load("compare.py")
    g = config["guarantees"]
    r = compare.compare_tables(plans[query]["got"][1], plans[query]["want"],
                               floor=g["float_floor"])
    over = {c: gap for c, gap in r["gaps"].items()
            if gap > compare.gap_limit(g, f"{query}.{c}")}
    assert (r["exact_mismatches"], over) == (0, {})


def test_the_listener_hears_a_compile():
    """The three event names are JAX's: a new jit is heard under each, so
    a silent second plan is a plan that built nothing."""
    del _heard[:]
    _listening.append(True)
    try:
        jax.jit(lambda x: x * 3 + len(_heard))(np.arange(7))
    finally:
        del _listening[:]
    assert set(_heard) == set(JAX_EVENTS)


# ---------------------------------------------------------------------------
# The key, on Distributed* objects built directly
# ---------------------------------------------------------------------------

@pytest.fixture
def programs(monkeypatch):
    """A memo of this test's own in the process-wide one's place."""
    memo = KernelCache("mesh_programs_under_test", 24, register=False)
    monkeypatch.setattr(pmesh, "_MESH_PROGRAMS", memo)
    return memo


def _mesh(chips=(0, 1, 2, 3)):
    devices = jax.devices()
    return pmesh.data_mesh(devices=[devices[i] for i in chips])


def _batch(table: pa.Table):
    schema = Schema.from_arrow(table.schema)
    return host_batch_to_device(
        table.combine_chunks().to_batches()[0], schema), schema


def _rows(batch, names=False):
    cols = []
    for c in batch.columns:
        vals = np.asarray(c.data)[:batch.num_rows].tolist()
        valid = np.asarray(c.validity)[:batch.num_rows]
        cols.append([v if ok else None for v, ok in zip(vals, valid)])
    rows = sorted(zip(*cols), key=lambda r: tuple(
        (v is None, 0 if v is None else v) for v in r))
    return ([f.name for f in batch.schema], rows) if names else rows


def _facts(n=600, keys=23, seed=3):
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": pa.array(rng.integers(0, keys, n), pa.int64()),
        "v": pa.array(rng.integers(-50, 50, n).astype(np.float64))})


K = BoundReference(0, INT64, True, "k")
V = BoundReference(1, FLOAT64, True, "v")


def _group_by(table, key, value, how):
    got = table.to_pandas().assign(k=key).groupby("k")["v"].agg(how)
    return sorted((int(k), float(v)) for k, v in got.items())


@multichip
def test_aggregates_that_differ_in_one_function_share_nothing(programs):
    table = _facts()
    batch, _ = _batch(table)
    mesh = _mesh()
    total = DistributedAggregate([K], [Alias(Sum(V), "a")], mesh=mesh)
    most = DistributedAggregate([K], [Alias(Max(V), "a")], mesh=mesh)
    before = _lookups()
    assert _rows(total.run(batch)) == _group_by(table, table["k"], V, "sum")
    assert _rows(most.run(batch)) == _group_by(table, table["k"], V, "max")
    assert _lookups() == (before[0] + 2, before[1])
    assert len(programs) == 2


@multichip
def test_aggregates_that_differ_in_a_grouping_literal_share_nothing(
        programs):
    table = _facts()
    batch, _ = _batch(table)
    mesh = _mesh()
    frame = table.to_pandas()
    for step in (1, 2):
        grouping = Alias(Add(K, Literal(step, INT64)), "k")
        dist = DistributedAggregate([grouping], [Alias(Sum(V), "a")],
                                    mesh=mesh)
        want = sorted((int(k), float(v)) for k, v in
                      frame.assign(k=frame.k + step)
                      .groupby("k")["v"].sum().items())
        assert _rows(dist.run(batch)) == want
    assert len(programs) == 2
    assert programs.stats()["hits"] == 0


def _sides(payload=pa.int64()):
    rng = np.random.default_rng(11)
    left = pa.table({
        "a": pa.array(rng.integers(0, 40, 300), pa.int64()),
        "b": pa.array(rng.integers(0, 40, 300), pa.int64())})
    right = pa.table({
        "c": pa.array(np.arange(30, dtype=np.int64)),
        "d": pa.array(np.arange(30)[::-1].copy(), pa.int64()),
        "p": pa.array(rng.integers(0, 9, 30), payload)})
    return left, right


def _join(left, right, lkey, rkey, how, mesh):
    lb, ls = _batch(left)
    rb, rs = _batch(right)
    dist = DistributedHashJoin(
        [BoundReference(lkey, INT64, True, left.column_names[lkey])],
        [BoundReference(rkey, INT64, True, right.column_names[rkey])],
        ls, rs, join_type=how, mesh=mesh)
    return dist.run(lb, rb)


def _joined(left, right, lkey, rkey, how):
    frame = left.to_pandas().merge(
        right.to_pandas(), how=how, left_on=left.column_names[lkey],
        right_on=right.column_names[rkey])
    rows = [tuple(None if v != v else int(v) for v in r)
            for r in frame.itertuples(index=False)]
    return sorted(rows, key=lambda r: tuple(
        (v is None, 0 if v is None else v) for v in r))


# two joins that differ in one thing: (what, first, second); a join is
# (payload type, left key, right key, join type)
JOIN_PAIRS = {
    "join_type": ((pa.int64(), 0, 0, "inner"), (pa.int64(), 0, 0, "left")),
    "field_dtype": ((pa.int64(), 0, 0, "inner"),
                    (pa.int32(), 0, 0, "inner")),
    "key_side": ((pa.int64(), 0, 1, "inner"), (pa.int64(), 1, 0, "inner")),
}


@multichip
@pytest.mark.parametrize("what", sorted(JOIN_PAIRS))
def test_joins_that_differ_in_one_thing_never_share_a_join_program(
        programs, what):
    """Never the join program; the count program counts alike for an
    inner and a left join of the same keys, and those two share it."""
    mesh = _mesh()
    for payload, lkey, rkey, how in JOIN_PAIRS[what]:
        left, right = _sides(payload)
        assert _rows(_join(left, right, lkey, rkey, how, mesh)) == \
            _joined(left, right, lkey, rkey, how)
    joins = [k for k in programs._entries if k[0] == "mesh_join"]
    counts = [k for k in programs._entries if k[0] == "mesh_join_count"]
    assert len(joins) == 2
    assert len(counts) == (1 if what == "join_type" else 2)


@multichip
def test_joins_that_differ_in_names_share_and_keep_their_names(programs):
    mesh = _mesh()
    left, right = _sides()
    other_l = left.rename_columns(["x", "y"])
    other_r = right.rename_columns(["u", "w", "z"])
    first = _rows(_join(left, right, 0, 0, "inner", mesh), names=True)
    before = _lookups()
    second = _rows(_join(other_l, other_r, 0, 0, "inner", mesh), names=True)
    assert _lookups() == (before[0] + 2, before[1] + 2)
    assert len(programs) == 2
    assert first[0] == ["a", "b", "c", "d", "p"]
    assert second[0] == ["x", "y", "u", "w", "z"]
    assert first[1] == second[1] == _joined(left, right, 0, 0, "inner")


@multichip
def test_sorts_share_by_order_and_not_by_direction(programs):
    table = _facts(n=500, keys=400)
    batch, schema = _batch(table)
    mesh = _mesh()
    want = sorted(zip(table["k"].to_pylist(), table["v"].to_pylist()))
    for ascending, n_programs in ((True, 1), (True, 1), (False, 2)):
        dist = DistributedSort([(K, ascending, ascending)], schema,
                               mesh=mesh)
        out = dist.run(batch)
        keys = np.asarray(out.columns[0].data)[:out.num_rows].tolist()
        assert keys == sorted((k for k, _ in want), reverse=not ascending)
        assert len(programs) == n_programs
    assert programs.stats()["hits"] == 1


@multichip
def test_meshes_of_one_width_over_other_chips_never_share(programs):
    """The health layer's rebuild: a mesh that lost chip 3 and took chip
    4 in is another mesh, and runs no program compiled for the old one."""
    table = _facts()
    batch, _ = _batch(table)
    want = _group_by(table, table["k"], V, "sum")
    seen = []
    for chips in ((0, 1, 2, 3), (0, 1, 2, 4), (0, 1, 2, 3)):
        mesh = _mesh(chips)
        dist = DistributedAggregate([K], [Alias(Sum(V), "a")], mesh=mesh)
        n_groups, out_cols = dist.run_sharded(batch)
        seen.append({d.id for plane in out_cols[0][:2]
                     for d in plane.sharding.device_set})
        assert _rows(dist.gather(n_groups, out_cols)) == want
    assert seen == [{0, 1, 2, 3}, {0, 1, 2, 4}, {0, 1, 2, 3}]
    assert len(programs) == 2
    assert programs.stats()["hits"] == 1  # the third: the first's mesh
    assert pmesh.mesh_key(_mesh((0, 1, 2, 3))) != \
        pmesh.mesh_key(_mesh((0, 1, 2, 4)))
    assert pmesh.mesh_key(_mesh((0, 1, 2, 3))) != \
        pmesh.mesh_key(_mesh((0, 1, 3, 2)))


@multichip
def test_a_memo_bounded_at_two_evicts_the_oldest_and_rebuilds_it(
        monkeypatch):
    memo = KernelCache("mesh_programs_of_two", 2, register=False)
    monkeypatch.setattr(pmesh, "_MESH_PROGRAMS", memo)
    table = _facts()
    batch, _ = _batch(table)
    mesh = _mesh()
    want = {how: _group_by(table, table["k"], V, how)
            for how in ("sum", "max", "count")}
    functions = {"sum": Sum, "max": Max, "count": Count}

    def run(how):
        before = _lookups()
        dist = DistributedAggregate(
            [K], [Alias(functions[how](V), "a")], mesh=mesh)
        assert _rows(dist.run(batch)) == want[how]
        after = _lookups()
        return after[1] - before[1]

    assert [run(h) for h in ("sum", "max", "count")] == [0, 0, 0]
    assert len(memo) == 2 and memo.stats()["evictions"] == 1
    assert run("count") == 1      # still there
    assert run("sum") == 0        # the oldest went, and is built again
    assert memo.stats()["evictions"] == 2
    assert run("sum") == 1


@multichip
def test_a_prelude_keeps_its_steps_to_itself(programs):
    """The broadcast join's ``prelude`` has no key by value: each object
    answers from ITS build batch, whichever ran before it, and none of
    them touches the shared memo."""
    rng = np.random.default_rng(21)
    fact = pa.table({
        "k": pa.array(rng.integers(0, 30, 512), pa.int64()),
        "v": pa.array(rng.integers(0, 9, 512).astype(np.float64))})
    fb, _ = _batch(fact)
    mesh = _mesh()

    def dim(groups):
        return pa.table({
            "k": pa.array(np.arange(20, dtype=np.int64)),
            "grp": pa.array([i % groups for i in range(20)], pa.int64())})

    def build(table):
        db, _ = _batch(table)
        key = [BoundReference(0, INT64, True, "k")]
        return DistributedBroadcastJoinAggregate(
            db, key, key, [BoundReference(3, INT64, True, "grp")],
            [Alias(Sum(BoundReference(1, FLOAT64, True, "v")), "s")],
            mesh=mesh)

    def want(table):
        joined = fact.to_pandas().merge(table.to_pandas(), on="k")
        return sorted((int(g), float(s)) for g, s in
                      joined.groupby("grp")["v"].sum().items())

    three, five = dim(3), dim(5)
    by_three, by_five = build(three), build(five)
    before = _lookups()
    assert _rows(by_three.run(fb)) == want(three)
    assert _rows(by_five.run(fb)) == want(five)
    assert _rows(by_three.run(fb)) == want(three)
    # three programs asked for, the third found in its object's own memo
    assert _lookups() == (before[0] + 3, before[1] + 1)
    assert len(programs) == 0
    assert len(by_three._prelude_steps) == len(by_five._prelude_steps) == 1
    assert want(three) != want(five)


def test_workers_asking_at_once_lose_no_lookup(programs):
    """Two server workers may ask for one program at once: a racing double
    build is benign (both values equivalent), and no lookup goes uncounted:
    hits + builds = lookups, whatever the interleaving."""
    workers, rounds, keys = 4 * (os.cpu_count() or 4), 150, 8
    built = []
    wrong = []

    def ask():
        for n in range(rounds):
            key = ("stress", n % keys)
            fn = pmesh.mesh_program(
                key, lambda k=key: built.append(k) or (lambda: k))
            if fn() != key:
                wrong.append(key)

    before = _lookups()
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=ask) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads)
    after = _lookups()
    assert wrong == []
    assert after[0] - before[0] == workers * rounds
    assert after[1] - before[1] == workers * rounds - len(built)
    assert keys <= len(built) <= keys * workers
    assert len(programs) == keys


def test_the_memo_is_one_bounded_kernel_cache_for_the_layer():
    """One declaration for the three files, LRU-bounded, in the registry
    (so tests/conftest.py's pressure relief clears it), and the bound its
    reckoning gives: under about 2 GB of loaded code a chip."""
    from spark_rapids_tpu.utils import kernel_cache
    memo = kernel_cache.find("mesh_programs")
    assert memo is pmesh._MESH_PROGRAMS
    assert isinstance(memo, KernelCache)
    assert 13 <= memo.max_entries          # q3's 5 and q18's 8
    assert memo.max_entries * 87e6 < 2.2e9  # an entry: up to 87 MB a chip
    schema = Schema.from_arrow(_facts().schema)
    for cls, args in ((DistributedAggregate, ([K], [Alias(Sum(V), "a")])),
                      (DistributedHashJoin, ([K], [K], schema, schema)),
                      (DistributedSort, ([(K, True, True)], schema))):
        held = [k for k in vars(cls(*args, mesh=_mesh((0, 1))))
                if "cache" in k or "step" in k]
        assert held in ([], ["_prelude_steps"]), (cls.__name__, held)

