"""Which side a one-chip hash join builds on (exec/joins.py:
``TpuHashJoinExec._join``, ``_peek_smaller_left``).

An inner equi-join with no condition builds on its left child where the
left side ends holding fewer rows than the right, by the host-known row
bounds, and its keys are unique; the right batches then stream past it,
on the fk route the left keys pick, and each output batch is cut to the
count of its rows.  A smaller left side with repeated keys, every other
join, and a tie build on the right as before.  Each join here runs over sources of
prepared device batches, so the sides' batches and bounds are exact, and
its rows are held to a plain Python join of the same tables.  The ``join``
group of ``engine_stats()`` says which side was built on (``build_left``)
and which route produced the rows.
"""

import datetime as dt

import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar import encoding
from spark_rapids_tpu.columnar.batch import (
    batches_to_arrow_table, host_batch_to_device,
)
from spark_rapids_tpu.columnar.column import bucket_capacity
from spark_rapids_tpu.columnar.dtypes import (
    DATE, FLOAT64, INT32, INT64, STRING, Schema,
)
from spark_rapids_tpu.exec import joins
from spark_rapids_tpu.exec.base import ExecContext, TpuExec
from spark_rapids_tpu.exec.broadcast import (
    TpuBroadcastExchangeExec, TpuBroadcastHashJoinExec,
)
from spark_rapids_tpu.exprs import predicates as pr
from spark_rapids_tpu.exprs.base import BoundReference
from tests.compare import tpu_session


class _Source(TpuExec):
    """Yields prepared device batches and counts each one handed out."""

    def __init__(self, batches, schema):
        super().__init__()
        self.batches = batches
        self.schema = schema
        self.children = []
        self.pulled = 0

    @property
    def output_schema(self):
        return self.schema

    def execute_columnar(self, ctx):
        for b in self.batches:
            self.pulled += 1
            yield b


@pytest.fixture(scope="module")
def ctx():
    sess = tpu_session()
    try:
        yield ExecContext(sess.conf)
    finally:
        sess.stop()


def _source(table: pa.Table, sizes, encode=False) -> _Source:
    """``table`` cut into batches of ``sizes`` rows, strings dictionary-
    encoded on the device where ``encode`` says so."""
    schema = Schema.from_arrow(table.schema)
    batches, at = [], 0
    for n in sizes:
        enc = encoding.IngestEncoder(max_dict_fraction=1.0) if encode \
            else None
        batches.append(host_batch_to_device(table.slice(at, n), schema,
                                            encoder=enc))
        at += n
    assert at == table.num_rows
    return _Source(batches, schema)


def _keys(schema, names):
    return [BoundReference(schema.fields.index(f), f.dtype, True, f.name)
            for f in schema.fields if f.name in names]


def _rows(table: pa.Table):
    # by position: a join's output names its key twice
    return list(zip(*(c.to_pylist() for c in table.columns))) \
        if table.num_columns else []


def _reference(left, right, lk, rk, how, cond=None):
    """The join in plain Python: a null key matches nothing; ``cond``
    sees the left row and the right row."""
    lrows, rrows = _rows(left), _rows(right)
    li = [left.column_names.index(k) for k in lk]
    ri = [right.column_names.index(k) for k in rk]

    def match(a, b):
        ka, kb = [a[i] for i in li], [b[i] for i in ri]
        if how != "cross" and (None in ka or None in kb or ka != kb):
            return False
        return cond is None or cond(a, b)

    out, hit_r = [], set()
    for a in lrows:
        found = [j for j, b in enumerate(rrows) if match(a, b)]
        hit_r.update(found)
        if how == "semi":
            out += [a] if found else []
        elif how == "anti":
            out += [] if found else [a]
        else:
            out += [a + rrows[j] for j in found]
            if not found and how in ("left", "full"):
                out.append(a + (None,) * len(right.column_names))
    if how in ("right", "full"):
        out += [(None,) * len(left.column_names) + b
                for j, b in enumerate(rrows) if j not in hit_r]
    return out


def _run(ctx, join):
    """The join's output batches, its rows, and what it added to the
    ``join`` group."""
    before = joins.join_stats()
    out = list(join.execute_columnar(ctx))
    grown = {k: v - before[k] for k, v in joins.join_stats().items()}
    if out:
        table = batches_to_arrow_table(out, join.output_schema)
    else:
        table = pa.table({f.name: [] for f in join.output_schema.fields})
    return out, _rows(table), grown


def _same(got, want):
    assert sorted(got, key=repr) == sorted(want, key=repr)


def _days(n):
    return [dt.date(1994, 1, 1) + dt.timedelta(days=int(d)) for d in n]


# key column(s) of each case, and the route the small side's keys pick
# as the build side, whichever child it is ("generic": they repeat, so a
# small left side is not built on)
_SMALL = 20      # small side's rows, in batches of 12 and 8
_LARGE = 120     # large side's rows, in batches of 40
_CASES = {
    # one int key in a narrow range: the direct-address table
    "int": (lambda i: {"k": pa.array(i, pa.int32())}, ["k"], "fk_dense"),
    # one long key over a range wider than the table's 2^24 slots
    "long": (lambda i: {"k": pa.array([x * (1 << 30) for x in i],
                                      pa.int64())}, ["k"], "fk"),
    "date": (lambda i: {"k": pa.array(_days(i), pa.date32())}, ["k"],
             "fk_dense"),
    "string": (lambda i: {"k": pa.array([f"key-{x:04d}" for x in i])},
               ["k"], "fk"),
    # both sides' keys dictionary-encoded: they join as INT32 codes, so
    # the direct-address table applies
    "encoded_string": (lambda i: {"k": pa.array([f"key-{x:04d}"
                                                 for x in i])},
                       ["k"], "fk_dense"),
    "two_keys": (lambda i: {"k": pa.array([x % 7 for x in i], pa.int64()),
                            "k2": pa.array([f"s{x // 7}" for x in i])},
                 ["k", "k2"], "fk"),
    # a null key matches nothing, and the small side's keys repeat
    "nulls": (lambda i: {"k": pa.array([None if x % 5 == 0 else x // 4
                                        for x in i], pa.int64())},
              ["k"], "generic"),
}


def _tables(case):
    cols, names, _ = _CASES[case]
    # small: 20 distinct key values (repeats only where the case makes
    # them); large: 120 rows over a range twice as wide, so a third of
    # them and some small-side rows find no partner
    small = pa.table(dict(cols(list(range(0, 2 * _SMALL, 2))),
                          a=pa.array(range(_SMALL), pa.int64())))
    ids = [(7 * j) % (2 * _LARGE // 3) for j in range(_LARGE)]
    large = pa.table(dict(cols(ids),
                          b=pa.array(range(_LARGE), pa.int64()),
                          w=pa.array([j * 0.5 for j in range(_LARGE)],
                                     pa.float64())))
    return small, large, names


@pytest.mark.parametrize("small_side", ["left", "right"])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_the_smaller_side_is_the_build_side(ctx, case, small_side):
    """The same rows as the reference, built on the smaller side, on the
    route its keys pick, unless it is the left side and its keys repeat.
    Built on the left, the larger right side streams past it, and each
    output batch is cut to its own rows: an fk program's count is
    pulled, as the generic route's is."""
    small, large, names = _tables(case)
    encode = case == "encoded_string"
    ss = _source(small, [12, 8], encode)
    ls_ = _source(large, [40, 40, 40], encode)
    left, right = (small, large) if small_side == "left" \
        else (large, small)
    lsrc, rsrc = (ss, ls_) if small_side == "left" else (ls_, ss)
    join = joins.TpuHashJoinExec(lsrc, rsrc, _keys(lsrc.schema, names),
                                 _keys(rsrc.schema, names), "inner")
    out, got, grown = _run(ctx, join)
    _same(got, _reference(left, right, names, names, "inner"))
    assert got, "the case must join some rows"
    route = _CASES[case][2]
    build_left = small_side == "left" and route != "generic"
    # a small left side with repeated keys streams past the large right
    # side, whose keys repeat too in this case
    built_small = small_side == "right" or build_left
    assert (grown["build_left"], grown[route], grown["joins"]) == \
        (int(build_left), 1, 1)
    assert (grown["build_rows"], grown["stream_rows"]) == \
        ((_SMALL, _LARGE) if built_small else (_LARGE, _SMALL))
    assert ss.pulled == 2 and ls_.pulled == 3
    if build_left:
        # sized by the count pulled: every row handed on is a match
        assert all(b.rows_known for b in out)
        assert all(b.capacity == bucket_capacity(max(1, b.num_rows))
                   for b in out)
        assert grown["out_rows"] == len(got)
    elif small_side == "right" and route != "generic":
        # built on the right, an fk program hands on its stream batch's
        # capacity, as before
        assert [b.capacity for b in out] == [64, 64, 64]


def _band(nl):
    # right.b between left.a and left.a60 (ordinals: left a=1, a60=2;
    # right b=nl+1): a band over one integer build column
    return pr.And(
        pr.GreaterThanOrEqual(BoundReference(nl + 1, INT64, True, "b"),
                              BoundReference(1, INT64, True, "a")),
        pr.LessThanOrEqual(BoundReference(nl + 1, INT64, True, "b"),
                           BoundReference(2, INT64, True, "a60")))


_SCOPE = ("left", "right", "full", "semi", "anti", "cross", "band",
          "conditioned", "broadcast", "equal_bounds", "empty_left")


@pytest.mark.parametrize("kind", _SCOPE)
def test_every_other_join_builds_right(ctx, kind):
    """Outer, semi, anti and cross joins, joins with a condition, the
    broadcast join and a tie keep the right side as the build side, and
    their rows are the reference's."""
    left, right, names = _tables("int")
    left = left.append_column("a60", pa.array(
        [x + 60 for x in left["a"].to_pylist()], pa.int64()))
    sizes = [12, 8]
    if kind == "equal_bounds":
        right = right.slice(0, _SMALL)
    if kind == "empty_left":
        left, sizes = left.slice(0, 0), []
    ls = _source(left, sizes)
    rs = _source(right, [40, 40, 40] if right.num_rows == _LARGE
                 else [right.num_rows])
    lk, rk = _keys(ls.schema, names), _keys(rs.schema, names)
    how, cond, ref_cond = "inner", None, None
    if kind in ("left", "right", "full", "semi", "anti"):
        how = kind
    elif kind == "cross":
        how, lk, rk = "cross", [], []
    elif kind == "band":
        cond = _band(len(left.column_names))
        ref_cond = lambda a, b: a[1] <= b[1] <= a[2]  # noqa: E731
    elif kind == "conditioned":
        # a float comparison: no band to narrow the probe with
        cond = pr.LessThan(BoundReference(1, INT64, True, "a"),
                           BoundReference(len(left.column_names) + 2,
                                          FLOAT64, True, "w"))
        ref_cond = lambda a, b: a[1] < b[2]  # noqa: E731
    if kind == "broadcast":
        join = TpuBroadcastHashJoinExec(ls, TpuBroadcastExchangeExec(rs),
                                        lk, rk, "inner")
    else:
        join = joins.TpuHashJoinExec(ls, rs, lk, rk, how, cond)
    _, got, grown = _run(ctx, join)
    _same(got, _reference(left, right, names, names, how, ref_cond))
    assert grown["build_left"] == 0 and grown["joins"] == 1
    assert grown["build_rows"] == right.num_rows
    assert grown["stream_rows"] == left.num_rows
    assert grown["broadcast"] == int(kind == "broadcast")
    assert ls.pulled == len(sizes)


@pytest.mark.parametrize("left_sizes", [(30, 30, 30, 30), (50, 70),
                                        (40, 10, 0, 30, 40)])
def test_a_larger_left_side_streams_every_batch_once(ctx, left_sizes):
    """The peek stops once the left side's bounds reach the right's (50
    rows): the batches it pulled go first in the stream, and the rest
    follow, each joined exactly once."""
    n = sum(left_sizes)
    left = pa.table({"k": pa.array([i % 45 for i in range(n)], pa.int64()),
                     "a": pa.array(range(n), pa.int64())})
    right = pa.table({"k": pa.array(range(50), pa.int64()),
                      "b": pa.array(range(100, 150), pa.int64())})
    ls, rs = _source(left, left_sizes), _source(right, [50])
    join = joins.TpuHashJoinExec(ls, rs, _keys(ls.schema, ["k"]),
                                 _keys(rs.schema, ["k"]), "inner")
    out, got, grown = _run(ctx, join)
    want = _reference(left, right, ["k"], ["k"], "inner")
    assert len(got) == len(want) == n
    _same(got, want)
    assert grown["build_left"] == 0 and grown["fk_dense"] == 1
    assert ls.pulled == len(left_sizes) and rs.pulled == 1
    assert grown["stream_rows"] == n and len(out) == len(left_sizes)


@pytest.mark.parametrize("left_rows", [20, 200])
def test_the_output_keeps_its_column_order_and_encodings(ctx, left_rows):
    """Built on either side, the output is [left..., right...] by name
    and type, and a dictionary column stays encoded at its position and
    decodes to its own values."""
    left = pa.table({
        "k": pa.array(range(left_rows), pa.int64()),
        "name": pa.array([f"n{i % 3}" for i in range(left_rows)]),
        "d": pa.array(_days(range(left_rows)), pa.date32())})
    right = pa.table({
        "k": pa.array([i % 30 for i in range(90)], pa.int64()),
        "tag": pa.array([f"t{i % 4}" for i in range(90)]),
        "v": pa.array(range(90), pa.int32())})
    ls, rs = _source(left, [left_rows], True), _source(right, [90], True)
    join = joins.TpuHashJoinExec(ls, rs, _keys(ls.schema, ["k"]),
                                 _keys(rs.schema, ["k"]), "inner")
    out, got, grown = _run(ctx, join)
    assert grown["build_left"] == int(left_rows < 90)
    _same(got, _reference(left, right, ["k"], ["k"], "inner"))
    assert [f.name for f in join.output_schema.fields] == \
        ["k", "name", "d", "k", "tag", "v"]
    assert [f.dtype for f in join.output_schema.fields] == \
        [INT64, STRING, DATE, INT64, STRING, INT32]
    for b in out:
        assert b.schema == join.output_schema
        assert [isinstance(c, encoding.EncodedColumn)
                for c in b.columns] == \
            [False, True, False, False, True, False]
        assert [c.dtype for c in b.columns] == \
            [f.dtype for f in join.output_schema.fields]


@pytest.mark.parametrize("left_sizes", [(12, 8), (20,)])
def test_a_smaller_left_side_with_repeated_keys_streams(ctx, left_sizes):
    """A smaller left side whose keys repeat is not built on: the
    uniqueness probe finds a key twice, and the right side, whose keys
    are unique, is built as before, on ``fk_dense``; the left batches
    pulled to size the left side stream past it, each joined once."""
    left = pa.table({"k": pa.array([i // 2 for i in range(20)], pa.int64()),
                     "a": pa.array(range(20), pa.int64())})
    right = pa.table({"k": pa.array(range(0, 100, 2), pa.int64()),
                      "b": pa.array(range(50), pa.int64())})
    ls, rs = _source(left, left_sizes), _source(right, [30, 20])
    join = joins.TpuHashJoinExec(ls, rs, _keys(ls.schema, ["k"]),
                                 _keys(rs.schema, ["k"]), "inner")
    out, got, grown = _run(ctx, join)
    want = _reference(left, right, ["k"], ["k"], "inner")
    assert len(got) == len(want) == 10
    _same(got, want)
    assert (grown["build_left"], grown["fk_dense"], grown["joins"]) == \
        (0, 1, 1)
    assert (grown["build_rows"], grown["stream_rows"]) == (50, 20)
    assert ls.pulled == len(left_sizes) and rs.pulled == 2
    # built on the right, an fk program hands on its stream batch's
    # capacity, one output batch a left batch
    assert [b.capacity for b in out] == \
        [bucket_capacity(n) for n in left_sizes]


def test_a_held_side_is_let_go_batch_by_batch():
    """The batches a join held, the right side it built on the left or
    the left batches its peek pulled, leave its hands as they stream:
    none stays referenced once handed on."""
    held = ["b0", "b1", "b2"]
    stream = joins._handing_on(held, iter(["b3"]))
    assert next(stream) == "b0" and held == ["b1", "b2"]
    assert list(stream) == ["b1", "b2", "b3"] and held == []
