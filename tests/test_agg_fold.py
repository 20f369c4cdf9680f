"""The aggregate fold (docs/fusion.md, "The aggregate fold"): a filter /
project chain whose only consumer is an aggregate update runs MASKED
inside that update's program — no compaction gather.

Covers: folded vs ``spark.rapids.sql.fusion.enabled=false`` equivalence
over both update bodies and every shape the fold composes (dictionary
keys, keyless, sorted, projections, chained filters, dictionary
predicates, NULLs, empty results, an OOM split), the cases that must NOT
fold (a nondeterministic step; a filter that feeds a join, sort, limit or
exchange keeps the parent's stage program, key and bytes), the
literal-free cache key, and what ``explain()`` shows.

And the one route (``TpuHashAggregateExec._run_update``): an update
nothing was folded into is the folded update with an empty chain, from
the same builder under the same two program names; what a run answers
does not depend on how often the process has run the query; a key
column that also feeds a function stays in the code domain.
"""

from __future__ import annotations

import hashlib

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.api import col, lit
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.exec.stage import TpuStageExec, stage_kernel_cache
from spark_rapids_tpu.utils import kernel_cache
from tests.compare import assert_tables_equal, sum_plan_metric, tpu_session

FUSION = "spark.rapids.sql.fusion.enabled"
N = 5000


def _lineitem() -> pa.Table:
    rng = np.random.default_rng(28)

    def nullable(values, share, typ=None):
        mask = rng.random(N) < share
        return pa.array([None if m else v
                         for v, m in zip(values.tolist(), mask)], typ)

    return pa.table({
        "flag": nullable(rng.choice(["A", "N", "R"], N), 0.05),
        "status": pa.array(rng.choice(["F", "O"], N)),
        "mode": pa.array(rng.choice(["AIR", "MAIL", "RAIL", "SHIP"], N)),
        "okey": pa.array(rng.integers(0, 3000, N), pa.int64()),
        "line": pa.array(rng.integers(1, 8, N), pa.int32()),
        "qty": nullable(rng.integers(1, 51, N).astype(np.float64), 0.1),
        "price": pa.array(rng.uniform(900, 100000, N)),
        "disc": pa.array(rng.integers(0, 11, N) / 100.0),
        "ship": pa.array(rng.integers(8000, 10600, N), pa.int32()),
    })


@pytest.fixture(scope="module")
def lineitem(tmp_path_factory):
    """A dictionary-encoded parquet file: the scan hands the string
    columns over as codes, as TPC-H's lineitem arrives."""
    path = str(tmp_path_factory.mktemp("aggfold") / "lineitem.parquet")
    papq.write_table(_lineitem(), path, row_group_size=2048)
    return path


def _q1_shape(df):
    return (df.filter(col("ship") <= 10471)
            .group_by("flag", "status")
            .agg(F.sum(col("qty")).alias("sum_qty"),
                 F.sum(col("price") * (lit(1.0) - col("disc")))
                 .alias("sum_disc_price"),
                 F.avg(col("disc")).alias("avg_disc"),
                 F.min(col("ship")).alias("first_ship"),
                 F.max(col("ship")).alias("last_ship"),
                 F.count(col("qty")).alias("n_qty"),
                 F.count(lit(1)).alias("n")))


def _q6_shape(df):
    return (df.filter((col("ship") >= 8766) & (col("ship") < 9131)
                      & (col("disc") >= 0.05) & (col("qty") < 24.0))
            .agg(F.sum(col("price") * col("disc")).alias("revenue"),
                 F.min(col("line")).alias("lo"),
                 F.count(lit(1)).alias("n")))


# name -> (query, every update batch dense? (None: no batch reaches the
# update), rows expected or None)
CASES = {
    "dense_dictionary_keys": (_q1_shape, True, None),
    "keyless": (_q6_shape, True, 1),
    "sorted_high_cardinality_key": (
        lambda df: df.filter(col("disc") > 0.02).group_by("okey").agg(
            F.sum(col("price")).alias("p"), F.count(lit(1)).alias("n"),
            F.max(col("line")).alias("hi")), False, None),
    "probed_integer_key": (
        lambda df: df.filter(col("disc") > 0.02).group_by("line").agg(
            F.sum(col("price")).alias("p"), F.count(lit(1)).alias("n")),
        True, 7),
    "filter_project_aggregate": (
        lambda df: df.filter(col("ship") > 9000)
        .select((col("price") * 2.0).alias("p2"), col("flag"),
                (col("line") + 1).alias("l1"))
        .group_by("flag").agg(F.sum(col("p2")).alias("p"),
                              F.max(col("l1")).alias("hi")), True, 4),
    "two_filters": (
        lambda df: df.filter(col("ship") > 8500)
        .select(col("status"), col("price"), col("disc"), col("line"))
        .filter(col("disc") < 0.08)
        .group_by("status").agg(F.sum(col("price")).alias("p"),
                                F.min(col("line")).alias("lo")), True, 2),
    "two_adjacent_filters": (
        lambda df: df.filter(col("ship") > 8500).filter(col("qty") < 40.0)
        .group_by("status").agg(F.sum(col("qty")).alias("q"),
                                F.count(lit(1)).alias("n")), True, 2),
    "dictionary_predicate_coded_keys": (
        lambda df: df.filter((col("mode") == "AIR")
                             | (col("mode") == "SHIP"))
        .group_by("flag", "status", "mode")
        .agg(F.count(lit(1)).alias("n"), F.sum(col("price")).alias("p")),
        True, 16),
    "nulls_in_predicate_and_keys": (
        lambda df: df.filter(col("qty") > 25.0).group_by("flag").agg(
            F.sum(col("qty")).alias("q"), F.count(col("qty")).alias("n"),
            F.avg(col("price")).alias("ap")), True, 4),
    # a predicate the row-group statistics cannot prune: every batch
    # reaches the update and none of its rows is live
    "keeps_no_row_grouped": (
        lambda df: df.filter((col("line") + col("ship")) < 0).group_by(
            "flag", "status").agg(F.sum(col("price")).alias("p")),
        True, 0),
    "keeps_no_row_keyless": (
        lambda df: df.filter((col("line") + col("ship")) < 0).agg(
            F.sum(col("price")).alias("p"), F.count(lit(1)).alias("n"),
            F.max(col("line")).alias("hi")), True, 1),
    # the scan's pushdown prunes every row group: no batch at all, and
    # the keyless aggregate still emits its row of initial values
    "scan_pruned_to_nothing_grouped": (
        lambda df: df.filter(col("ship") > 20000).group_by(
            "flag", "status").agg(F.sum(col("price")).alias("p")),
        None, 0),
    "scan_pruned_to_nothing_keyless": (
        lambda df: df.filter(col("ship") > 20000).agg(
            F.sum(col("price")).alias("p"), F.count(lit(1)).alias("n"),
            F.max(col("line")).alias("hi")), None, 1),
}


def _run(path, query, fusion, extra=None):
    conf = {FUSION: fusion}
    conf.update(extra or {})
    s = tpu_session(conf)
    try:
        return query(s.read.parquet(path)).to_arrow(), s
    finally:
        s.stop()


def _nodes(session, cls):
    found = []

    def walk(n):
        if isinstance(n, cls):
            found.append(n)
        for c in n.children:
            walk(c)
    walk(session._last_plan_result.physical)
    return found


def _aggregates(session):
    return _nodes(session, TpuHashAggregateExec)


def _stages(session):
    return _nodes(session, TpuStageExec)


@pytest.mark.parametrize("case", list(CASES))
def test_folded_equals_unfused(lineitem, case):
    """Keys, counts, integers, min/max and row order exact; float sums
    to the tolerance of a changed order of addition (rows reduce in
    place, not compacted to the front)."""
    query, dense, rows = CASES[case]
    on, s_on = _run(lineitem, query, True)
    off, s_off = _run(lineitem, query, False)
    (agg,) = _aggregates(s_on)
    assert agg.pre_steps and not _stages(s_on), agg.describe()
    assert not _aggregates(s_off)[0].pre_steps
    assert sum_plan_metric(s_off, "maskedFilterBatches") == 0
    if dense is not None:
        batches = sum_plan_metric(s_on, "maskedFilterBatches")
        assert batches >= 1
        assert sum_plan_metric(s_on, "pallasAggBatches") == \
            (batches if dense else 0)
        assert sum_plan_metric(s_off, "pallasAggBatches") == \
            sum_plan_metric(s_on, "pallasAggBatches")
    if rows is not None:
        assert on.num_rows == rows
    assert_tables_equal(on, off, ignore_order=False, approx_float=True)


def test_folded_update_survives_an_oom_split(lineitem, monkeypatch):
    """The retry splits the UNFILTERED input batch in half: per-row sound
    for filter and project alike, so the halves' partials merge to the
    same answer.  (The first two batches are a group: it fails twice
    and is given up, then its first member fails twice alone and
    splits: tests/test_agg_group.py has the group's side.)"""
    import spark_rapids_tpu.exec.aggregate as agg_mod
    real = agg_mod._compile_folded_update
    state = {"left": 4, "calls": 0}

    def failing(*a, **kw):
        state["calls"] += 1
        if state["left"] > 0:
            state["left"] -= 1
            raise RuntimeError("RESOURCE_EXHAUSTED: injected fault")
        return real(*a, **kw)

    monkeypatch.setattr(agg_mod, "_compile_folded_update", failing)
    on, s_on = _run(lineitem, _q1_shape, True)
    # the group's attempt and spill-retry, the member's, then two halves
    assert state["calls"] >= 6
    monkeypatch.undo()
    off, _ = _run(lineitem, _q1_shape, False)
    assert sum_plan_metric(s_on, "maskedFilterBatches") >= 2
    assert_tables_equal(on, off, ignore_order=False, approx_float=True)


def test_nondeterministic_step_is_not_folded(lineitem):
    """A step that reads row position keeps compacting: masked rows keep
    their input position, so ``monotonically_increasing_id`` and
    ``rand`` would see other rows than the compacted stream's."""
    def query(df):
        return (df.filter(col("ship") > 9000)
                .select(col("status"), col("price"),
                        F.monotonically_increasing_id().alias("id"))
                .filter(col("price") > 1000.0)
                .group_by("status").agg(F.max(col("id")).alias("hi"),
                                        F.count(lit(1)).alias("n")))
    on, s_on = _run(lineitem, query, True)
    (agg,) = _aggregates(s_on)
    assert not agg.pre_steps
    (stage,) = _stages(s_on)
    assert stage.nondeterministic and stage is agg.children[0].children[0]
    assert sum_plan_metric(s_on, "maskedFilterBatches") == 0
    off, _ = _run(lineitem, query, False)
    assert_tables_equal(on, off, ignore_order=False)


_T = pa.table({"k": [i % 7 for i in range(300)],
               "v": [float(i % 13) - 6 for i in range(300)]})
_R = pa.table({"k": list(range(7)), "w": [i * 10 for i in range(7)]})
_FILTER_KEY = (
    "((('filter', 'GreaterThan(in[1:double],hlit[0:double])'),), "
    "(('long', 512, 0), ('double', 512, 0)), (), 512)")
# what each consumer's filter compiled to at the parent of the fold
# (commit 9f74d07): the stage programs' cache keys, letter for letter
OTHER_CONSUMERS = {
    "sort": (lambda s: s.create_dataframe(_T).filter(col("v") > 1.5)
             .order_by("k", "v"), [_FILTER_KEY]),
    "limit": (lambda s: s.create_dataframe(_T).filter(col("v") > 1.5)
              .limit(5), [_FILTER_KEY]),
    "join": (lambda s: s.create_dataframe(_T).filter(col("v") > 1.5)
             .join(s.create_dataframe(_R), "k"), [
        _FILTER_KEY,
        "((('project', 'alias[k](in[0:long])', 'alias[v](in[1:double])', "
        "'alias[w](in[3:long])'),), (('long', 512, 0), ('double', 512, 0), "
        "('long', 512, 0), ('long', 512, 0)), (), 512)"]),
    "exchange": (lambda s: s.create_dataframe(_T).select(
        (col("v") * 2.0).alias("x"), col("k")).filter(col("x") > 3.0)
        .repartition(4, "k"), [
        "('fusedhash', (('project', "
        "'alias[x](Multiply(in[1:double],hlit[0:double]))', 'in[0:long]'), "
        "('filter', 'GreaterThan(in[0:double],hlit[1:double])')), "
        "'in[1:long]', (('long', 512, 0), ('double', 512, 0)), (), 512, "
        "4)"]),
}


@pytest.mark.parametrize("consumer", list(OTHER_CONSUMERS))
def test_filter_under_another_consumer_keeps_its_stage(consumer):
    """Only an aggregate update takes a chain: under a join, sort, limit
    or exchange the filter compacts through the stage compiler with the
    parent's cache key, and answers what the per-op path answers."""
    from spark_rapids_tpu.exec import exchange
    query, want = OTHER_CONSUMERS[consumer]
    stage_kernel_cache().clear()
    exchange._PARTITION_CACHE.clear()
    s = tpu_session({})
    try:
        on = query(s).to_arrow()
        assert not [a for a in _aggregates(s) if a.pre_steps]
    finally:
        s.stop()
    keys = sorted(repr(k) for k in list(stage_kernel_cache()._entries) + [
        k for k in exchange._PARTITION_CACHE._entries
        if k[0] == "fusedhash"])
    assert keys == sorted(want)
    s = tpu_session({FUSION: False})
    try:
        off = query(s).to_arrow()
    finally:
        s.stop()
    assert_tables_equal(on, off, ignore_order=consumer == "exchange")


def test_compacting_mode_lowers_to_the_parents_program():
    """``emit_steps`` in its default mode is byte for byte the parent's:
    the StableHLO of a project -> filter stage hashes to what commit
    9f74d07 lowered."""
    import jax
    from spark_rapids_tpu.columnar.dtypes import FLOAT64, INT64
    from spark_rapids_tpu.exec import stage
    from spark_rapids_tpu.exprs.base import BoundReference, Literal
    from spark_rapids_tpu.exprs.predicates import GreaterThan
    steps = (("project", (BoundReference(1, FLOAT64, True, "v"),
                          BoundReference(0, INT64, True, "k"))),
             ("filter", (GreaterThan(BoundReference(0, FLOAT64, True, "v"),
                                     Literal(1.5, FLOAT64)),)))
    sig = (("long", 512, 0), ("double", 512, 0))
    text = jax.jit(stage._build_stage_fn(steps, 512)).lower(
        *stage.aval_inputs(sig, 512, ())).as_text()
    assert "stablehlo.gather" in text
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "6a525323a22cec02ac92762bea9a2061de3fb31ee992226d8527bd2bcc9711e8")


def test_fusion_off_is_the_parents_plan(lineitem):
    out, s = _run(lineitem, _q6_shape, False)
    tree = s._last_plan_result.physical.tree_string()
    (agg,) = _aggregates(s)
    assert not agg.pre_steps and "masked=" not in agg.describe()
    # aggregate <- coalesce <- filter <- scan, as before the fold
    coalesce = agg.children[0]
    assert type(coalesce).__name__ == "TpuCoalesceBatchesExec"
    assert type(coalesce.children[0]).__name__ == "TpuFilterExec"
    assert "TpuFilter [" in tree and "TpuStage" not in tree
    assert out.num_rows == 1


def test_a_new_binding_reuses_the_folded_program(lineitem):
    """Literals ride in as traced scalars: the second binding compiles
    nothing (the aggregate kernel cache counts no miss)."""
    cache = kernel_cache.find("aggregate")

    def q(lo, hi, disc):
        return lambda df: (
            df.filter((col("ship") >= lo) & (col("ship") < hi)
                      & (col("disc") >= disc))
            .group_by("flag", "status")
            .agg(F.sum(col("price") * (lit(1.0) - col("disc")))
                 .alias("p"), F.count(lit(1)).alias("n")))
    first, _ = _run(lineitem, q(8766, 9131, 0.05), True)
    warm = cache.stats()
    second, s2 = _run(lineitem, q(9131, 9496, 0.02), True)
    after = cache.stats()
    assert after["misses"] == warm["misses"], \
        "a new binding compiled a new aggregate program"
    assert after["hits"] > warm["hits"]
    assert sum_plan_metric(s2, "maskedFilterBatches") >= 1
    assert first.column("n").to_pylist() != second.column("n").to_pylist()
    want, _ = _run(lineitem, q(9131, 9496, 0.02), False)
    assert_tables_equal(second, want, ignore_order=False,
                        approx_float=True)


def test_describe_names_the_folded_predicate(lineitem):
    _out, s = _run(lineitem, _q1_shape, True)
    (agg,) = _aggregates(s)
    text = agg.describe()
    assert text.startswith("TpuHashAggregate [keys=[flag, status]")
    assert "masked=[Filter[(ship <= 10471)]]" in text
    # explain() does not hide the filter
    tree = s._last_plan_result.physical.tree_string()
    assert "(ship <= 10471)" in tree and "TpuFilter" not in tree


# -- one route: an unfolded update is a folded update with no steps ---------

# float inputs whose products and sums are exact in any order (small
# integers), so neither a contraction nor the order of addition can
# show: masked rows reduce in place, compacted rows at the front
def _exact_aggs():
    return (F.sum(col("qty") * col("line")).alias("s"),
            F.avg(col("qty")).alias("a"), F.sum(col("line")).alias("li"),
            F.min(col("ship")).alias("lo"), F.max(col("ship")).alias("hi"),
            F.count(col("qty")).alias("nq"), F.count(lit(1)).alias("n"))


ONE_ROUTE = {
    "dictionary_keys": (lambda df: df.filter(col("ship") <= 10471)
                        .group_by("flag", "status").agg(*_exact_aggs()),
                        "aggregate_masked_pallas_update"),
    "keyless": (lambda df: df.filter(col("qty") < 24.0)
                .agg(*_exact_aggs()), "aggregate_masked_pallas_update"),
    "probed_integer_key": (lambda df: df.filter(col("ship") > 9000)
                           .group_by("line").agg(*_exact_aggs()),
                           "aggregate_masked_pallas_update"),
    "sorted_key": (lambda df: df.filter(col("ship") > 9000)
                   .group_by("okey").agg(*_exact_aggs()),
                   "aggregate_masked_update"),
    "no_filter_at_all": (lambda df: df.group_by("flag", "status")
                         .agg(*_exact_aggs()),
                         "aggregate_masked_pallas_update"),
}


@pytest.mark.parametrize("case", list(ONE_ROUTE))
def test_fusion_on_and_off_launch_from_the_one_builder(lineitem, case):
    """With the chain folded in or left to a filter below, every update
    is ``_compile_folded_update``'s: the same program name, no
    ``aggregate_update`` / ``aggregate_pallas_update`` row in the
    dispatch ledger, and the same bytes out."""
    from spark_rapids_tpu.compile import service
    query, program = ONE_ROUTE[case]
    tables = []
    for fusion in (True, False):
        before = service.ledger_rows()
        out, s = _run(lineitem, query, fusion)
        after = service.ledger_rows()
        grew = {p for p, row in after.items()
                if row["family"] == "aggregate" and row["dispatches"]
                > before.get(p, {"dispatches": 0})["dispatches"]}
        assert program in grew
        assert grew <= {program, "aggregate_merge", "aggregate_evaluate",
                        "aggregate_pallas_key_range"}, grew
        assert not {"aggregate_update", "aggregate_pallas_update"} \
            & set(after)
        (agg,) = _aggregates(s)
        assert bool(agg.pre_steps) == (fusion and case
                                       != "no_filter_at_all")
        # the filter counts as masked only where it was folded in
        assert (sum_plan_metric(s, "maskedFilterBatches") > 0) == \
            bool(agg.pre_steps)
        tables.append(out)
    assert tables[0].num_rows and tables[0].equals(tables[1])


def test_answers_do_not_depend_on_how_often_the_query_ran():
    """One int64 key over buffers that are new in every run: each run
    probes the key's range, takes the dense body and answers the same
    bytes.  (A process-global miss count used to make the third run of
    a spec memo-only, and its float sums the sorted body's.)"""
    rng = np.random.default_rng(31)
    n = 6000
    t = pa.table({"k": pa.array(rng.integers(-3, 40, n), pa.int64()),
                  "v": pa.array(rng.normal(size=n)),
                  "w": pa.array(rng.uniform(0, 1e6, n))})
    s = tpu_session({})
    try:
        outs = []
        for _ in range(4):
            outs.append(s.create_dataframe(t).group_by("k").agg(
                F.sum(col("v")).alias("sv"), F.avg(col("w")).alias("aw"),
                F.count(lit(1)).alias("n")).to_arrow())
            assert sum_plan_metric(s, "pallasAggBatches") > 0
    finally:
        s.stop()
    assert outs[0].num_rows == 43
    assert all(o.equals(outs[0]) for o in outs[1:])


def test_key_that_feeds_a_function_stays_in_the_code_domain(lineitem):
    """Each expression is viewed on its own: ``mode`` as a key stays
    codes (and leaves re-wrapped on its dictionary) while ``max(mode)``
    decodes the same column inside the update's program: no late
    decode anywhere in the query."""
    s = tpu_session({"spark.rapids.sql.scan.deviceCacheEnabled": "false"})
    try:
        before = s.engine_stats()["compressed"]
        out = (s.read.parquet(lineitem).group_by("mode")
               .agg(F.max(col("mode")).alias("m"),
                    F.count(lit(1)).alias("n")).to_arrow())
        after = s.engine_stats()["compressed"]
    finally:
        s.stop()
    assert after["encodedColumns"] > before["encodedColumns"]
    assert after["lateDecodes"] == before["lateDecodes"]
    assert out.column("mode").to_pylist() == out.column("m").to_pylist() \
        == ["AIR", "MAIL", "RAIL", "SHIP"]
    assert sum(out.column("n").to_pylist()) == N
