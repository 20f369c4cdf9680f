"""The grouped update (docs/fusion.md, "The aggregate fold"): batches
whose updates share a program wait for each other and are updated in
ONE launch that returns one partial a batch — what that many launches
returned, byte for byte.

Covers: grouped against one-a-launch partials over 1, 2, 6, 8 and 9
batches of a q1- and a q6-shaped aggregate; what closes a group (another
signature, capacity or radices, eight members, the sorted body); a
hoisted slot bound by member; an injected OOM on a group; the three
batch counters; a second binding compiling nothing.
"""

from __future__ import annotations

import jax
import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq
import pytest

import spark_rapids_tpu.exec.aggregate as agg_mod
from spark_rapids_tpu import functions as F
from spark_rapids_tpu.api import col, lit
from spark_rapids_tpu.compile import service
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.utils import kernel_cache
from tests.compare import sum_plan_metric, tpu_session

ROWS = 1024  # rows a batch: one row group, one scan batch
CONF = {"spark.rapids.sql.reader.batchSizeRows": str(ROWS),
        # keep the coalesce from merging the scan's batches
        "spark.rapids.sql.batchSizeBytes": "16384"}
DENSE, SORTED = ("aggregate_masked_pallas_update",
                 "aggregate_masked_update")


def _table(batches: int, seed: int = 32, flags=("A", "N", "R")) -> pa.Table:
    n = batches * ROWS
    rng = np.random.default_rng(seed)
    qty = rng.integers(1, 51, n).astype(np.float64)
    return pa.table({
        "flag": pa.array(rng.choice(list(flags), n)),
        "status": pa.array(rng.choice(["F", "O"], n)),
        "okey": pa.array(rng.integers(0, 100_000, n), pa.int64()),
        "qty": pa.array([None if m else v for v, m in
                         zip(qty.tolist(), rng.random(n) < 0.05)]),
        "price": pa.array(rng.uniform(900, 100000, n)),
        "disc": pa.array(rng.integers(0, 11, n) / 100.0),
        "ship": pa.array(rng.integers(8000, 10600, n), pa.int32()),
    })


def _write(tmp_path, table: pa.Table, name: str = "t.parquet") -> str:
    path = str(tmp_path / name)
    papq.write_table(table, path, row_group_size=ROWS)
    return path


def _q1(df, ship=10471):
    return (df.filter(col("ship") <= ship).group_by("flag", "status")
            .agg(F.sum(col("qty")).alias("sum_qty"),
                 F.sum(col("price") * (lit(1.0) - col("disc")))
                 .alias("sum_disc_price"),
                 F.avg(col("disc")).alias("avg_disc"),
                 F.min(col("ship")).alias("first_ship"),
                 F.count(col("qty")).alias("n_qty"),
                 F.count(lit(1)).alias("n")))


def _q6(df, lo=8766, hi=9131):
    return (df.filter((col("ship") >= lo) & (col("ship") < hi)
                      & (col("disc") >= 0.05) & (col("qty") < 24.0))
            .agg(F.sum(col("price") * col("disc")).alias("revenue"),
                 F.count(lit(1)).alias("n")))


QUERIES = {"q1": _q1, "q6": _q6}


def _planes(batch) -> list:
    """Every plane of a partial as bytes, the whole capacity."""
    return [(np.asarray(jax.device_get(
        c.codes if hasattr(c, "codes") else c.data)).tobytes(),
        np.asarray(jax.device_get(c.validity)).tobytes())
        for c in batch.columns]


class _Spy:
    """Records every ``_update_group`` call: how many members it took
    and the bytes of each partial it returned."""

    def __init__(self, monkeypatch):
        self.sizes, self.partials = [], []
        real = TpuHashAggregateExec._update_group

        def spy(node, members, batches):
            out = real(node, members, batches)
            self.sizes.append(len(members))
            self.partials.extend(_planes(b) for b in out)
            return out

        monkeypatch.setattr(TpuHashAggregateExec, "_update_group", spy)


def _launches(before: dict, program: str) -> int:
    return service.ledger_rows().get(program, {"dispatches": 0})[
        "dispatches"] - before.get(program, {"dispatches": 0})["dispatches"]


def _run(build, conf=None):
    """``(table, session)`` of ``build(session)`` under small batches."""
    s = tpu_session({**CONF, **(conf or {})})
    try:
        return build(s).to_arrow(), s
    finally:
        s.stop()


def _agg(session) -> TpuHashAggregateExec:
    def find(n):
        if isinstance(n, TpuHashAggregateExec):
            return n
        for c in n.children:
            r = find(c)
            if r is not None:
                return r
    return find(session._last_plan_result.physical)


@pytest.mark.parametrize("batches", [1, 2, 6, 8, 9])
@pytest.mark.parametrize("query", list(QUERIES))
def test_grouped_partials_equal_one_a_launch(tmp_path, monkeypatch, query,
                                             batches):
    """The group's launch returns each member's partial as its own
    launch returns it, to the byte (keys, counts, sums, validity, the
    slots past the groups too), so the merge adds the same numbers in
    the same order; and the counters count batches, not launches."""
    path = _write(tmp_path, _table(batches))
    build = lambda s: QUERIES[query](s.read.parquet(path))  # noqa: E731
    spy = _Spy(monkeypatch)
    before = service.ledger_rows()
    grouped, s = _run(build)
    want_sizes = [8, 1] if batches == 9 else [batches]
    assert spy.sizes == want_sizes
    assert _launches(before, DENSE) == len(want_sizes)
    in_groups = sum(n for n in want_sizes if n > 1)
    assert sum_plan_metric(s, "groupedUpdateBatches") == in_groups
    # launches less one: 0 for a single group
    assert sum_plan_metric(s, "overlappedUpdateLaunches") == \
        len(want_sizes) - 1
    assert sum_plan_metric(s, "pallasAggBatches") == batches
    assert sum_plan_metric(s, "maskedFilterBatches") == batches
    grouped_partials, spy.sizes, spy.partials = spy.partials, [], []

    monkeypatch.setattr(agg_mod, "GROUP_MEMBERS", 1)
    alone, s1 = _run(build)
    assert spy.sizes == [1] * batches
    assert sum_plan_metric(s1, "groupedUpdateBatches") == 0
    assert sum_plan_metric(s1, "overlappedUpdateLaunches") == batches - 1
    assert sum_plan_metric(s1, "pallasAggBatches") == batches
    assert len(grouped_partials) == batches
    assert grouped_partials == spy.partials
    assert grouped.num_rows and grouped.equals(alone)


def test_sorted_body_never_groups(tmp_path, monkeypatch):
    """A key whose domain the host does not know: one batch a launch,
    where the launch is the device's work and the host's price nothing."""
    path = _write(tmp_path, _table(4))
    spy = _Spy(monkeypatch)
    before = service.ledger_rows()
    out, s = _run(lambda s: s.read.parquet(path)
                  .filter(col("disc") > 0.02).group_by("okey")
                  .agg(F.sum(col("price")).alias("p"),
                       F.count(lit(1)).alias("n")))
    assert spy.sizes == [1, 1, 1, 1]
    assert _launches(before, SORTED) == 4 and not _launches(before, DENSE)
    assert sum_plan_metric(s, "groupedUpdateBatches") == 0
    assert sum_plan_metric(s, "pallasAggBatches") == 0
    assert sum_plan_metric(s, "maskedFilterBatches") == 4
    assert sum(out.column("n").to_pylist()) > 0


def _two_files(tmp_path, first: pa.Table, second: pa.Table):
    """A scan of two files, in this order: the first's batches, then the
    second's."""
    d = tmp_path / "two"
    d.mkdir()
    papq.write_table(first, str(d / "a.parquet"), row_group_size=ROWS)
    papq.write_table(second, str(d / "b.parquet"), row_group_size=ROWS)
    return str(d)


CLOSERS = {
    # the second file's flag dictionary has four values: other radices
    # (and another aux table), the same planes otherwise
    "radices": lambda: (_table(2), _table(2, 33, ("A", "N", "R", "X")),
                        lambda df: _q1(df), [2, 2]),
    # the second file ends in a short batch: another capacity
    "capacity": lambda: (_table(2), _table(2, 33).slice(0, ROWS + 100),
                         lambda df: _q1(df), [3, 1]),
    # the second file's ship dates climb by 0 or 1 a row and arrive as
    # a compressed plane the program decodes: another signature
    "signature": lambda: (
        _table(2), _table(2, 33).set_column(
            6, "ship", pa.array(8000 + np.arange(2 * ROWS) // 2,
                                pa.int32())),
        lambda df: _q6(df, 8000, 10600), [2, 2]),  # no group pruned
}


@pytest.mark.parametrize("what", list(CLOSERS))
def test_a_batch_that_cannot_share_the_program_closes_the_group(
        tmp_path, monkeypatch, what):
    """Members share one program: a batch whose staging differs in
    anything the builder keys on starts a group of its own, and the
    answer is the one-a-launch answer."""
    first, second, query, sizes = CLOSERS[what]()
    path = _two_files(tmp_path, first, second)
    build = lambda s: query(s.read.parquet(path))  # noqa: E731
    spy = _Spy(monkeypatch)
    grouped, _ = _run(build)
    assert spy.sizes == sizes
    assert len(spy.partials) == 4
    monkeypatch.setattr(agg_mod, "GROUP_MEMBERS", 1)
    alone, _ = _run(build)
    assert grouped.num_rows and grouped.equals(alone)


def test_members_of_a_group_keep_their_own_dictionaries(tmp_path,
                                                        monkeypatch):
    """Equal radices, other values: the members share the launch, each
    partial re-wraps on its own batch's dictionary, and the merge
    unifies them as it does one a launch."""
    path = _two_files(tmp_path, _table(2),
                      _table(2, 33, ("B", "N", "Z")))
    build = lambda s: _q1(s.read.parquet(path))  # noqa: E731
    spy = _Spy(monkeypatch)
    grouped, _ = _run(build)
    assert spy.sizes == [4]
    monkeypatch.setattr(agg_mod, "GROUP_MEMBERS", 1)
    alone, _ = _run(build)
    assert sorted(set(grouped.column("flag").to_pylist())) == \
        ["A", "B", "N", "R", "Z"]
    assert grouped.equals(alone)


def _staged(session, table: pa.Table, build):
    """The aggregate node of ``build`` over ``table`` after one run, and
    its input batches staged anew: ``(node, members, batches)``."""
    from spark_rapids_tpu.exec.base import ExecContext
    build(session.create_dataframe(table)).to_arrow()
    node = _agg(session)
    batches = list(node.children[0].execute_columnar(
        ExecContext(session.conf, session.runtime)))
    return node, [node._stage(b, session.conf) for b in batches], batches


def test_a_hoisted_slot_binds_by_member():
    """Two updates whose steps differ in a literal only share the
    program; the slot that differs rides as one vector, a value a
    member, the others once; each partial is its own update's."""
    t = _table(1)
    s = tpu_session(CONF)
    try:
        node_a, (m_a,), (b_a,) = _staged(s, t, lambda df: _q6(df, 8766))
        node_b, (m_b,), (b_b,) = _staged(s, t, lambda df: _q6(df, 8900))
        assert m_a.steps_key != m_b.steps_key
        assert m_a.shares_program(m_b)
        values = agg_mod._hoisted_by_member(
            [m_a.hoisted()[1], m_b.hoisted()[1]])
        assert sorted(v.ndim for v in values) == [0, 0, 0, 0, 1]
        assert [v.tolist() for v in values if v.ndim] == [[8766, 8900]]
        want = [_planes(node_a._run_update(b_a, s.conf)),
                _planes(node_b._run_update(b_b, s.conf))]
        assert want[0] != want[1]
        got = node_a._update_group([m_a, m_b], [b_a, b_b])
        assert [_planes(p) for p in got] == want
        # equal literals: every slot goes once
        m_a2 = node_a._stage(b_a, s.conf)
        assert m_a2.steps_key == m_a.steps_key
        assert all(v.ndim == 0 for v in agg_mod._hoisted_by_member(
            [m_a.hoisted()[1], m_a2.hoisted()[1]]))
    finally:
        s.stop()


def test_injected_oom_on_a_group_falls_back_to_one_a_launch(tmp_path,
                                                            monkeypatch):
    """A group that does not fit after the spill is given up: its
    members go through the per-batch retry, rows splitting to the full
    depth, and answer the same; ``kernel.launch`` fires once an attempt
    (the group's, then each member's)."""
    from spark_rapids_tpu import faults
    path = _write(tmp_path, _table(3))
    build = lambda s: _q1(s.read.parquet(path))  # noqa: E731
    want, _ = _run(build)

    fired = []
    real_fire = faults.maybe_fail_oom
    monkeypatch.setattr(
        faults, "maybe_fail_oom",
        lambda site: (fired.append(site), real_fire(site))[1])
    real = TpuHashAggregateExec._update_group
    calls = []

    def failing(node, members, batches):
        calls.append(len(members))
        # the group fails twice (attempt, spill-retry); the first member
        # alone fails twice more, so its rows split
        if len(calls) <= 4:
            raise RuntimeError("RESOURCE_EXHAUSTED: injected fault")
        return real(node, members, batches)

    monkeypatch.setattr(TpuHashAggregateExec, "_update_group", failing)
    got, s = _run(build)
    assert calls == [3, 3, 1, 1, 1, 1, 1, 1]
    assert [f for f in fired if f == "kernel.launch"] == \
        ["kernel.launch"] * 6  # the group, a member, two halves, two more
    assert sum_plan_metric(s, "groupedUpdateBatches") == 0
    assert sum_plan_metric(s, "pallasAggBatches") == 4  # two are halves
    assert got.num_rows == want.num_rows
    for name in want.column_names:
        a, b = got.column(name).to_pylist(), want.column(name).to_pylist()
        if isinstance(b[0], float):  # halves add in another order
            np.testing.assert_allclose(a, b, rtol=1e-12)
        else:
            assert a == b


def test_conf_driven_oom_retries_the_group_whole(tmp_path):
    """The fault site as a deployment injects it: the first attempt of
    the group meets the OOM, the spill-retry launches the same group."""
    path = _write(tmp_path, _table(3))
    build = lambda s: _q6(s.read.parquet(path))  # noqa: E731
    want, _ = _run(build)
    got, s = _run(build, {"spark.rapids.faults.kernel.launch": "count:1"})
    assert sum_plan_metric(s, "groupedUpdateBatches") == 3
    assert got.equals(want)


def test_a_second_binding_compiles_nothing(tmp_path):
    """The group program's key is literal-free as the one-member
    program's is: a new binding over the same batches reuses it."""
    path = _write(tmp_path, _table(6))
    cache = kernel_cache.find("aggregate")
    first, _ = _run(lambda s: _q6(s.read.parquet(path), 8766, 9131))
    warm = cache.stats()
    before = service.ledger_rows()
    second, s = _run(lambda s: _q6(s.read.parquet(path), 9131, 9496))
    after = cache.stats()
    assert after["misses"] == warm["misses"], \
        "a new binding compiled a new aggregate program"
    assert after["hits"] > warm["hits"]
    assert _launches(before, DENSE) == 1
    assert sum_plan_metric(s, "groupedUpdateBatches") == 6
    assert first.column("n").to_pylist() != second.column("n").to_pylist()


def test_the_member_count_is_in_the_key_and_one_member_is_traced_alone():
    """One builder: the group program is the one-member program's body
    mapped over a stack, under the same name; with one member nothing is
    stacked or mapped."""
    t = _table(1)
    s = tpu_session(CONF)
    try:
        node, (m,), (b,) = _staged(s, t, lambda df: _q6(df))
        h_steps, values = m.hoisted()
        progs = [agg_mod._compile_folded_update(
            h_steps, m.sig, m.aux_sig, m.capacity, m.spec, m.radices, n)
            for n in (1, 3)]
        assert progs[0] is not progs[1]
        assert {p.name for p in progs} == {"masked_pallas_update"}
        assert progs[1] is agg_mod._compile_folded_update(
            h_steps, m.sig, m.aux_sig, m.capacity, m.spec, m.radices, 3)
        from spark_rapids_tpu.columnar.encoding import stage_planes
        flat = stage_planes(b)[0]
        hoisted = agg_mod.hoisted_args(values)
        tops = [{e.primitive.name for e in p.trace(
            (flat,) * n, (m.aux,) * n, np.full((n,), b.num_rows, np.int32),
            hoisted, np.zeros((n, 0), np.int64)).jaxpr.jaxpr.eqns}
            for p, n in zip(progs, (1, 3))]
        assert "pallas_call" in tops[0] and "scan" not in tops[0]
        assert "scan" in tops[1] and "pallas_call" not in tops[1]
    finally:
        s.stop()
