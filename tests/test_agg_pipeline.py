"""The aggregate's update launches, one deep (docs/fusion.md, "Retry"): the
aggregate launches update k + 1 before it waits on update k, and each
confirm appends its partials in launch order.

Covers: the order of launches and waits over three groups (never more
than one launch unconfirmed); a deferred OOM at the wait of group k while
k + 1 is in flight, retried whole, given up to one launch a member, and
split, with the partials in their order; sorted-body updates between dense
groups; an input that fails while a launch is pending leaves no handle
open; the ``overlappedUpdateLaunches`` counter.
"""

from __future__ import annotations

import numpy as np
import pyarrow.parquet as papq
import pytest

import spark_rapids_tpu.exec.aggregate as agg_mod
from spark_rapids_tpu.columnar import transfer
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.exec.base import ExecContext
from spark_rapids_tpu.utils import retry
from tests.compare import sum_plan_metric
from tests.test_agg_group import (
    CONF, ROWS, _agg, _planes, _q1, _q6, _run, _table, _write,
)

OVERLAPPED = "overlappedUpdateLaunches"


class _Recorder:
    """Records, in order, every update launch (``("L", i)``) and every
    retry-scope wait on a launch's partials (``("W", i)``), and the
    partials the merge's concat took; a wait on a launch listed in
    ``fail`` raises a device OOM, once, as a deferred launch failure
    surfaces at ``block_until_ready``."""

    def __init__(self, monkeypatch, fail=()):
        self.events, self.outputs, self.ids = [], [], []
        self.concat = None
        self.fail = set(fail)
        real_update = TpuHashAggregateExec._update_group
        real_wait = transfer.blocking_wait
        real_concat = agg_mod.concat_batches

        def update(node, members, batches):
            out = real_update(node, members, batches)
            arrays = []
            retry._collect_arrays(out, arrays)
            self.events.append(("L", len(self.outputs)))
            self.outputs.append(out)
            self.ids.append({id(a) for a in arrays})
            return out

        def wait(arrays, what):
            if what == "retry" and arrays:
                owner = [i for i, ids in enumerate(self.ids)
                         if id(arrays[0]) in ids]
                if owner:
                    self.events.append(("W", owner[0]))
                    if owner[0] in self.fail:
                        self.fail.discard(owner[0])
                        raise RuntimeError(
                            "RESOURCE_EXHAUSTED: deferred launch failure")
            return real_wait(arrays, what)

        def concat(batches):
            self.concat = [_planes(b) for b in batches]
            return real_concat(batches)

        monkeypatch.setattr(TpuHashAggregateExec, "_update_group", update)
        monkeypatch.setattr(transfer, "blocking_wait", wait)
        monkeypatch.setattr(agg_mod, "concat_batches", concat)

    def most_unconfirmed(self) -> int:
        """The most launches out at once with no wait yet on them, as each
        launch goes out (a launch's retry re-runs count as its own)."""
        waited, most = set(), 0
        for kind, i in self.events:
            if kind == "W":
                waited.add(i)
            else:
                most = max(most, sum(j not in waited for j in range(i)))
        return most


def _one_run(tmp_path, monkeypatch, batches, members, query=_q1, fail=()):
    path = _write(tmp_path, _table(batches))
    monkeypatch.setattr(agg_mod, "GROUP_MEMBERS", members)
    rec = _Recorder(monkeypatch, fail)
    out, s = _run(lambda s: query(s.read.parquet(path)))
    monkeypatch.undo()
    return out, s, rec


@pytest.mark.parametrize("query", [_q1, _q6], ids=["q1", "q6"])
def test_the_wait_on_a_launch_follows_the_next_launch(tmp_path, monkeypatch,
                                                      query):
    """Three groups (2, 2 and 1 batches): group k is waited on after group
    k + 1 is launched, the last after the input ends, and at most one
    launch is unconfirmed when another goes out."""
    out, s, rec = _one_run(tmp_path, monkeypatch, 5, 2, query)
    assert rec.events == [("L", 0), ("L", 1), ("W", 0), ("L", 2),
                          ("W", 1), ("W", 2)]
    assert rec.most_unconfirmed() == 1
    assert sum_plan_metric(s, OVERLAPPED) == 2
    assert sum_plan_metric(s, "groupedUpdateBatches") == 4
    assert len(rec.concat) == 5
    assert rec.concat == [_planes(p) for out_ in rec.outputs for p in out_]
    assert out.num_rows


# launch indices whose first wait fails; the events that follow; the
# partials the merge takes, by the no-fault run's position ("h" a half)
DEFERRED = {
    # group 1 fails at its wait while group 2 is in flight; the spill's
    # re-run of group 1 (launch 3) fits
    "spill_retry": ({1}, [("L", 0), ("L", 1), ("W", 0), ("L", 2), ("W", 1),
                          ("L", 3), ("W", 3), ("W", 2)],
                    [0, 1, 2, 3, 4, 5]),
    # the re-run fails too: the group is given up and its two members go
    # one a launch (4, 5), their partials in the group's place
    "one_a_launch": ({1, 3}, [("L", 0), ("L", 1), ("W", 0), ("L", 2),
                              ("W", 1), ("L", 3), ("W", 3), ("L", 4),
                              ("W", 4), ("L", 5), ("W", 5), ("W", 2)],
                     [0, 1, 2, 3, 4, 5]),
    # the first member alone fails twice as well: its rows split in two
    # halves (6, 7) before the second member (8)
    "split": ({1, 3, 4, 5}, [("L", 0), ("L", 1), ("W", 0), ("L", 2),
                             ("W", 1), ("L", 3), ("W", 3), ("L", 4),
                             ("W", 4), ("L", 5), ("W", 5), ("L", 6),
                             ("W", 6), ("L", 7), ("W", 7), ("L", 8),
                             ("W", 8), ("W", 2)],
              [0, 1, "h", "h", 3, 4, 5]),
}


@pytest.mark.parametrize("case", list(DEFERRED))
def test_a_deferred_oom_at_a_confirm_is_retried_in_its_place(
        tmp_path, monkeypatch, case):
    """Six batches in groups of two; group 1's wait raises a device OOM
    while group 2 is in flight.  The confirm runs the ladder inside the
    scope, and the merge takes the no-fault run's partials in their order:
    the answer is the no-fault answer to the bit, except where rows split
    (the halves add in another order)."""
    fail, events, order = DEFERRED[case]
    want, _, clean = _one_run(tmp_path, monkeypatch, 6, 2)
    got, s, rec = _one_run(tmp_path, monkeypatch, 6, 2, fail=fail)
    assert not rec.fail, "every planned fault fired"
    assert rec.events == events
    assert [rec.concat[i] for i, o in enumerate(order) if o != "h"] == \
        [clean.concat[o] for o in order if o != "h"]
    assert sum_plan_metric(s, OVERLAPPED) == 2
    if case != "split":
        assert got.equals(want)
        return
    assert got.num_rows == want.num_rows
    for name in want.column_names:
        a, b = got.column(name).to_pylist(), want.column(name).to_pylist()
        if isinstance(b[0], float):
            np.testing.assert_allclose(a, b, rtol=1e-12)
        else:
            assert a == b


def test_sorted_body_updates_between_dense_groups_keep_their_order(
        tmp_path, monkeypatch):
    """Three files: two batches whose keys the dense body takes, two whose
    flag dictionary is too wide for it (the sorted body, one launch a
    batch), two dense again.  Every launch is waited on one launch behind,
    and the merge takes the partials in launch order."""
    wide = [f"F{i:03d}" for i in range(600)]
    d = tmp_path / "mixed"
    d.mkdir()
    for name, t in (("a", _table(2)), ("b", _table(2, 33, wide)),
                    ("c", _table(2, 34))):
        papq.write_table(t, str(d / f"{name}.parquet"), row_group_size=ROWS)
    rec = _Recorder(monkeypatch)
    got, s = _run(lambda s: _q1(s.read.parquet(str(d))))
    assert [len(o) for o in rec.outputs] == [2, 1, 1, 2]
    assert rec.events == [("L", 0), ("L", 1), ("W", 0), ("L", 2), ("W", 1),
                          ("L", 3), ("W", 2), ("W", 3)]
    assert sum_plan_metric(s, OVERLAPPED) == 3
    assert sum_plan_metric(s, "pallasAggBatches") == 4
    assert rec.concat == [_planes(p) for o in rec.outputs for p in o]
    monkeypatch.undo()
    monkeypatch.setattr(agg_mod, "GROUP_MEMBERS", 1)
    alone, _ = _run(lambda s: _q1(s.read.parquet(str(d))))
    assert got.num_rows > 400
    assert got.equals(alone)


class _FailingInput:
    """A child that hands on ``batches`` and raises before the
    ``fail_at``-th."""

    children = ()

    def __init__(self, batches, fail_at):
        self.batches, self.fail_at = batches, fail_at

    def execute_columnar(self, ctx):
        for i, b in enumerate(self.batches):
            if i == self.fail_at:
                raise RuntimeError("input failed")
            yield b


def test_an_input_failure_with_a_launch_pending_leaves_no_handle_open(
        tmp_path, monkeypatch):
    """Groups of two: with the input failing at its sixth batch, group 0
    is confirmed into the partials, group 1 is pending and the fifth batch
    waits in the open group; all of it is released, none leaks."""
    monkeypatch.setattr(agg_mod, "GROUP_MEMBERS", 2)
    path = _write(tmp_path, _table(6))
    from tests.compare import tpu_session
    s = tpu_session(CONF)
    try:
        _q1(s.read.parquet(path)).to_arrow()
        node = _agg(s)
        ctx = ExecContext(s.conf, s.runtime)
        batches = list(node.children[0].execute_columnar(ctx))
        assert len(batches) == 6
        cat = s.runtime.catalog
        leaks, open_before = cat.leak_count, cat.audit_leaks()
        node.children = [_FailingInput(batches, 5)]
        before = node.metrics[OVERLAPPED].value
        with pytest.raises(RuntimeError, match="input failed"):
            list(node.execute_columnar(ctx))
        assert node.metrics[OVERLAPPED].value - before == 1
        assert cat.audit_leaks() == open_before
        assert cat.leak_count == leaks
    finally:
        s.stop()


def test_the_retry_scope_in_two_phases_is_with_retry():
    """``launch`` holds a device OOM its call raised for ``confirm``, which
    recovers from it like ``with_retry``; any other error raises at the
    launch, and nothing is waited on before the confirm."""
    calls = []

    class Ctx:
        class runtime:
            class catalog:
                spilled = 0

                @classmethod
                def spill_all(cls):
                    cls.spilled += 1

    def fn(b):
        calls.append(b)
        if len(calls) == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: at the launch")
        return b * 2

    launched = retry.launch(fn, 21, Ctx)
    assert calls == [21] and Ctx.runtime.catalog.spilled == 0
    assert launched.confirm() == [42]
    assert calls == [21, 21] and Ctx.runtime.catalog.spilled == 1

    def bad(b):
        raise ValueError("not an OOM")
    with pytest.raises(ValueError):
        retry.launch(bad, 1, Ctx)
