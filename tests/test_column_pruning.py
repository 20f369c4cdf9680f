"""Column pruning (ISSUE 36; planner.py ``prune_scan_columns``): every
parquet relation is narrowed to the columns the plan above it reads, in
file order, so its scan decodes, uploads and carries no other.

Q3 and Q5 as the benchmark builds them (``benchmark/queries/tpch_power.py``)
narrow each table to the columns clause 2.4.3 / 2.4.5 names: 26 of the 80
columns a pass of ``tpch_sf1_joins.power`` reads.  A column only a
predicate reads is kept (the pushed predicate binds against the scan), a
join key is kept on both sides, ``count(*)`` keeps the narrowest column, a
hive partition column is appended only where it is read.  Nodes the rule
does not see through (Union, Window, Expand, Generate) and the ORC and CSV
relations keep every column.  Logical plans are shared between DataFrames,
so the rule rebuilds and never mutates; the answers equal the unpruned
plan's on both engines.
"""

import importlib.util
import os
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.api import Window, col, lit
from spark_rapids_tpu.io.parquet import TpuParquetScanExec
from spark_rapids_tpu.plan import logical as lp
from spark_rapids_tpu.plan import planner
from tests.compare import assert_tables_equal, cpu_session, tpu_session

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
ROWS = 6_000
# the columns clause 2.4's text reads of each table
CLAUSE = {
    "q3": {"customer": {"c_custkey", "c_mktsegment"},
           "orders": {"o_orderkey", "o_custkey", "o_orderdate",
                      "o_shippriority"},
           "lineitem": {"l_orderkey", "l_extendedprice", "l_discount",
                        "l_shipdate"}},
    "q5": {"customer": {"c_custkey", "c_nationkey"},
           "orders": {"o_orderkey", "o_custkey", "o_orderdate"},
           "lineitem": {"l_orderkey", "l_suppkey", "l_extendedprice",
                        "l_discount"},
           "supplier": {"s_suppkey", "s_nationkey"},
           "nation": {"n_nationkey", "n_name", "n_regionkey"},
           "region": {"r_regionkey", "r_name"}},
}


def _load(*parts):
    path = os.path.join(BENCH, *parts)
    name = "pruning_" + "_".join(parts).replace(".py", "")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def tpch(tmp_path_factory):
    return _load("datagen", "tpch.py").generate(
        str(tmp_path_factory.mktemp("tpch_pruning")), ROWS, 5)


@pytest.fixture(scope="module")
def builders():
    return _load("queries", "tpch_power.py")


def _physical(df):
    return planner.plan_query(df.plan, df.session.conf).physical


def _scans(df):
    """Every parquet scan of ``df``'s physical plan."""
    stack, out = [_physical(df)], []
    while stack:
        node = stack.pop()
        if isinstance(node, TpuParquetScanExec):
            out.append(node)
        stack.extend(node.children)
    return out


def _relations(plan):
    stack, out = [plan], []
    while stack:
        node = stack.pop()
        if isinstance(node, (lp.ParquetRelation, lp.OrcRelation,
                             lp.CsvRelation)):
            out.append(node)
        stack.extend(node.children)
    return out


def _table(scan):
    return os.path.basename(scan.paths[0]).replace(".parquet", "")


@pytest.mark.parametrize("query", sorted(CLAUSE))
def test_tpch_scans_read_the_clauses_columns_in_file_order(tpch, builders,
                                                           query):
    s = tpu_session()
    tables = {n: s.read.parquet(p) for n, p in tpch.items()}
    scans = _scans(builders.build(query, tables))
    got = {_table(sc): sc.output_schema.names for sc in scans}
    assert {t: set(names) for t, names in got.items()} == CLAUSE[query]
    for sc in scans:
        full = tables[_table(sc)].plan.schema.names
        assert sc.output_schema.names == [n for n in full
                                          if n in CLAUSE[query][_table(sc)]]
        assert sc.columns_total == len(full)
        assert f"{len(sc.output_schema.names)}/{len(full)} columns" in \
            sc.describe()


def test_a_pass_of_the_join_cell_reads_26_of_80_columns(tpch, builders):
    s = tpu_session()
    tables = {n: s.read.parquet(p) for n, p in tpch.items()}
    scans = [sc for q in ("q3", "q5")
             for sc in _scans(builders.build(q, tables))]
    assert sum(len(sc._file_schema.fields) for sc in scans) == 26
    assert sum(sc.columns_total for sc in scans) == 80


@pytest.fixture()
def wide(tmp_path):
    """A table of four columns: a string, a double, an int32 and a key."""
    rng = np.random.default_rng(3)
    n = 500
    path = str(tmp_path / "wide.parquet")
    pq.write_table(pa.table({
        "s": pa.array([f"row{i % 17}" for i in range(n)]),
        "d": pa.array(rng.normal(size=n)),
        "i": pa.array((np.arange(n) % 11).astype(np.int32)),
        "k": pa.array((np.arange(n) % 7).astype(np.int64))}), path)
    other = str(tmp_path / "other.parquet")
    pq.write_table(pa.table({
        "k": pa.array(np.arange(7, dtype=np.int64)),
        "name": pa.array([f"n{i}" for i in range(7)]),
        "w": pa.array(np.arange(7, dtype=np.float64))}), other)
    return path, other


def _wide_df(s, wide, shape):
    path, other = wide
    df = s.read.parquet(path)
    if shape == "predicate_only":
        return df.filter(col("i") > lit(4)).select("s")
    if shape == "join_keys":
        return df.select("k", "d").join(
            s.read.parquet(other).select("k", "name"), "k").select(
            "d", "name")
    if shape == "join_key_only":
        return df.join(s.read.parquet(other), "k").select("s", "w")
    if shape == "count_star":
        return df.agg(F.count(lit(1)).alias("n"))
    if shape == "sorted_limit":
        return df.order_by(col("d").desc()).limit(5).select("s")
    if shape == "group":
        return df.group_by("k").agg(F.sum(col("d")).alias("t"))
    raise AssertionError(shape)


# shape -> what each parquet scan reads, in plan order (left side first)
WIDE = {"predicate_only": [["s", "i"]],
        "join_keys": [["d", "k"], ["k", "name"]],
        "join_key_only": [["s", "k"], ["k", "w"]],
        "count_star": [["i"]],
        "sorted_limit": [["s", "d"]],
        "group": [["d", "k"]]}


@pytest.mark.parametrize("shape", sorted(WIDE))
def test_a_scan_keeps_what_its_plan_reads(wide, shape):
    df = _wide_df(tpu_session(), wide, shape)
    read = sorted(sc.output_schema.names for sc in _scans(df))
    assert read == sorted(WIDE[shape])


def test_a_predicate_only_column_is_pushed_and_bound(wide):
    (scan,) = _scans(_wide_df(tpu_session(), wide, "predicate_only"))
    assert scan.pred is not None and scan.pred.children[0].col_name == "i"


@pytest.mark.parametrize("engine", ["tpu", "cpu"])
@pytest.mark.parametrize("shape", sorted(WIDE))
def test_a_pruned_plan_answers_as_the_unpruned(wide, shape, engine,
                                               monkeypatch):
    session = tpu_session if engine == "tpu" else cpu_session
    pruned = _wide_df(session(), wide, shape).to_arrow()
    monkeypatch.setattr(planner, "prune_scan_columns", lambda root: root)
    whole = _wide_df(session(), wide, shape).to_arrow()
    assert_tables_equal(pruned, whole, ignore_order=shape != "sorted_limit")


@pytest.mark.parametrize("engine", ["tpu", "cpu"])
@pytest.mark.parametrize("query", sorted(CLAUSE))
def test_tpch_answers_as_the_unpruned_plan(tpch, builders, query, engine,
                                           monkeypatch):
    session = tpu_session if engine == "tpu" else cpu_session

    def run():
        s = session()
        tables = {n: s.read.parquet(p) for n, p in tpch.items()}
        return builders.build(query, tables).to_arrow()
    pruned = run()
    monkeypatch.setattr(planner, "prune_scan_columns", lambda root: root)
    assert_tables_equal(pruned, run(), ignore_order=False)


def test_a_cpu_scan_reads_the_pruned_columns(wide):
    df = _wide_df(cpu_session(), wide, "join_keys")
    stack, scans = [_physical(df)], []
    while stack:
        node = stack.pop()
        if type(node).__name__ == "CpuParquetScanExec":
            scans.append(node)
        stack.extend(node.children)
    assert sorted(sc.output_schema.names for sc in scans) == \
        sorted(WIDE["join_keys"])
    assert sorted(sc.describe() for sc in scans) == [
        "CpuParquetScan [1 files, 2/3 columns]",
        "CpuParquetScan [1 files, 2/4 columns]"]


def test_shared_logical_plans_are_not_mutated(wide):
    s = tpu_session()
    base = s.read.parquet(wide[0])
    before = base.plan.schema.names
    a, b = base.select("d"), base.select("s", "k")
    assert [sc.output_schema.names for sc in _scans(a)] == [["d"]]
    assert [sc.output_schema.names for sc in _scans(b)] == [["s", "k"]]
    assert base.plan.schema.names == before
    assert base.plan.full_schema is base.plan.schema
    assert a.plan.children[0] is base.plan
    assert a.to_arrow().column_names == ["d"]
    assert b.to_arrow().num_rows == 500


@pytest.fixture()
def partitioned(tmp_path):
    s = tpu_session()
    t = pa.table({"g": pa.array((np.arange(300) % 3).astype(np.int64)),
                  "v": pa.array(np.arange(300, dtype=np.float64)),
                  "w": pa.array(np.arange(300, dtype=np.int64))})
    out = str(tmp_path / "part")
    s.create_dataframe(t).write.partition_by("g").mode("overwrite").parquet(
        out)
    return out


@pytest.mark.parametrize("select,read,appended", [
    (("v",), ["v"], None),
    (("v", "g"), ["v"], ["g"]),
    (("g",), ["v"], ["g"]),
])
def test_a_partition_column_is_appended_only_where_read(partitioned, select,
                                                        read, appended):
    df = tpu_session().read.parquet(partitioned).select(*select)
    (scan,) = _scans(df)
    assert scan._file_schema.names == read
    assert (scan.part_schema.names if scan.part_schema else None) == appended
    assert scan.columns_total == 2
    got = df.to_arrow()
    assert got.column_names == list(select) and got.num_rows == 300
    if "g" in select:
        assert sorted(set(got.column("g").to_pylist())) == [0, 1, 2]


def test_a_partition_predicate_keeps_its_column_and_prunes_files(
        partitioned):
    df = tpu_session().read.parquet(partitioned).filter(
        col("g") == lit(1)).select("v")
    (scan,) = _scans(df)
    assert scan.part_schema.names == ["g"]
    got = cpu_session().read.parquet(partitioned).filter(
        col("g") == lit(1)).select("v").to_arrow()
    assert_tables_equal(df.to_arrow(), got)
    assert got.num_rows == 100


def _untouched(s, wide, shape):
    path, _ = wide
    df = s.read.parquet(path)
    if shape == "union":
        return df.union(df).select("s")
    if shape == "window":
        return df.with_column("rn", F.row_number().over(
            Window.partition_by("k").order_by("d"))).select("s", "rn")
    if shape == "expand":
        return df.rollup("k").agg(F.sum(col("d")).alias("t"))
    if shape == "generate":
        return df.select("s", F.explode(F.array(1, 2)).alias("e")).select(
            "e")
    raise AssertionError(shape)


@pytest.mark.parametrize("shape", ["union", "window", "expand", "generate"])
def test_nodes_the_rule_does_not_see_through_keep_every_column(wide, shape):
    df = _untouched(tpu_session(), wide, shape)
    scans = _scans(df)
    assert scans and all(sc.output_schema.names == ["s", "d", "i", "k"]
                         for sc in scans)
    whole = _untouched(cpu_session(), wide, shape).to_arrow()
    assert_tables_equal(df.to_arrow(), whole, approx_float=True)


@pytest.mark.parametrize("fmt", ["orc", "csv"])
def test_orc_and_csv_relations_are_left_whole(tmp_path, fmt):
    s = tpu_session()
    t = pa.table({"a": pa.array(np.arange(40, dtype=np.int64)),
                  "b": pa.array(np.arange(40, dtype=np.float64)),
                  "c": pa.array([f"x{i}" for i in range(40)])})
    out = str(tmp_path / fmt)
    getattr(s.create_dataframe(t).write.mode("overwrite"), fmt)(out)
    df = getattr(s.read, fmt)(out).filter(col("a") > lit(3)).select("b")
    root = planner.prune_scan_columns(planner.push_scan_filters(df.plan))
    (rel,) = _relations(root)
    assert rel.schema.names == ["a", "b", "c"]
    assert df.to_arrow().num_rows == 36
