"""Physical plan node protocol.

Reference: GpuExec.scala:43-60 (``doExecuteColumnar``), GpuMetricNames
(GpuExec.scala:25-41).  Two engine families exist, mirroring the
reference's GPU-vs-CPU split: ``TpuExec`` nodes stream device
``ColumnarBatch``es; ``CpuExec`` nodes stream host ``pyarrow.RecordBatch``es
(the fallback engine, reference = operators left un-replaced on the Spark
CPU).  Transition nodes convert between them (GpuTransitionOverrides
analog lives in plan/transitions.py).
"""

from __future__ import annotations

from typing import Iterator, List, Optional, TYPE_CHECKING

import pyarrow as pa

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.dtypes import Schema
from spark_rapids_tpu.utils.metrics import (
    MetricSet, METRIC_NUM_OUTPUT_ROWS, METRIC_NUM_OUTPUT_BATCHES,
    METRIC_TOTAL_TIME,
)

if TYPE_CHECKING:
    from spark_rapids_tpu.conf import TpuConf
    from spark_rapids_tpu.runtime import TpuRuntime


class ExecContext:
    """Per-query execution context: conf + runtime singletons (the analog
    of the Spark TaskContext + plugin environment)."""

    __slots__ = ("conf", "runtime")

    def __init__(self, conf: "TpuConf", runtime: Optional["TpuRuntime"] = None):
        self.conf = conf
        if runtime is None:
            from spark_rapids_tpu.runtime import TpuRuntime
            runtime = TpuRuntime.get_or_create(conf)
        self.runtime = runtime
        # NOTE: the supervising QueryContext is deliberately NOT stored
        # here — operators read the LIVE scope via lifecycle.current()/
        # check_cancel(), so a context captured at construction can
        # never go stale
        # process-global span switch (the reference's NVTX ranges are
        # likewise process-global); every execution entry point builds an
        # ExecContext, so this covers collect/write/handoff paths
        from spark_rapids_tpu.utils import tracing
        tracing.set_enabled(conf.trace_enabled)
        # literal hoisting rides the fusion gate (docs/fusion.md): the
        # switch is process-global like the span switch, set at every
        # execution entry point
        from spark_rapids_tpu.exprs import base as _exprs_base
        _exprs_base.set_literal_hoisting(
            conf.fusion_enabled and conf.fusion_literal_hoisting)
        # compressed-domain execution switches (docs/compressed.md):
        # same process-global convention as the two switches above
        from spark_rapids_tpu.columnar import encoding as _encoding
        _encoding.set_conf(conf)
        # placement-calibration switch (plan/cost.py): with
        # placement.mode != tpu the CPU engine's operators count
        # rows/wall for throughput calibration; the default records
        # nothing and metrics stay byte-identical (docs/placement.md)
        from spark_rapids_tpu.plan import cost as _cost
        _cost.set_mode(conf.placement_mode)


class PhysicalPlan:
    """Base for both engines; a tree of physical operators."""

    children: List["PhysicalPlan"] = []

    def __init__(self):
        self.metrics = MetricSet(owner=self.node_name)

    @property
    def output_schema(self) -> Schema:
        raise NotImplementedError(type(self).__name__)

    @property
    def node_name(self) -> str:
        return type(self).__name__

    def describe(self) -> str:
        return self.node_name

    # engine discriminator -------------------------------------------------
    @property
    def is_device(self) -> bool:
        raise NotImplementedError

    def tree_string(self, indent: int = 0) -> str:
        pad = "  " * indent
        lines = [f"{pad}{self.describe()}"]
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)


class TpuExec(PhysicalPlan):
    """Device-columnar operator (reference GpuExec GpuExec.scala:43)."""

    @property
    def is_device(self) -> bool:
        return True

    def child_coalesce_goals(self, conf: "TpuConf") -> list:
        """Per-child batching requirement; the planner inserts a
        TpuCoalesceBatchesExec where a child's ``output_batching`` does not
        already satisfy it (reference childrenCoalesceGoal GpuExec +
        GpuCoalesceBatches insertion, GpuTransitionOverrides.scala:36)."""
        return [None] * len(self.children)

    @property
    def output_batching(self):
        """Batching guarantee of this exec's output stream (reference
        outputBatching GpuExec.scala), or None if unknown."""
        return None

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        """Yield device batches (the doExecuteColumnar analog)."""
        raise NotImplementedError(type(self).__name__)

    def _count_output(self, it: Iterator[ColumnarBatch]
                      ) -> Iterator[ColumnarBatch]:
        rows = self.metrics[METRIC_NUM_OUTPUT_ROWS]
        batches = self.metrics[METRIC_NUM_OUTPUT_BATCHES]
        # every operator's output stream passes through here, so this
        # is THE cooperative pull boundary: a cancelled or past-deadline
        # query raises typed within one batch of work (lifecycle.py);
        # a one-global-read no-op when no query is supervised
        from spark_rapids_tpu.lifecycle import check_cancel
        from spark_rapids_tpu.utils import tracing
        if tracing.is_enabled():
            # a program launched while this node's next() runs is this
            # node's (compile/service.py charges the top of the stack):
            # deviceTime / deviceDispatches, under the switch only
            it = tracing.running(self, it)
        for b in it:
            check_cancel()
            rows.add(b.rows_raw)  # no sync for device-resident counts
            batches.add(1)
            yield b


class CpuExec(PhysicalPlan):
    """Host (pyarrow) operator — the not-on-TPU fallback engine."""

    @property
    def is_device(self) -> bool:
        return False

    def execute_host(self, ctx: ExecContext) -> Iterator[pa.RecordBatch]:
        raise NotImplementedError(type(self).__name__)

    def _count_output(self, it: Iterator[pa.RecordBatch]
                      ) -> Iterator[pa.RecordBatch]:
        """Calibration hook (plan/cost.py): rows + wall time per CPU
        operator, so the placement cost model can learn CPU-engine
        throughputs from executed queries.  Records ONLY while cost
        calibration is active (``spark.rapids.sql.placement.mode`` !=
        ``tpu``); the default mode returns the stream untouched — zero
        overhead, per-operator metrics byte-identical to the
        pre-placement engine."""
        from spark_rapids_tpu.plan import cost as _cost
        if not _cost.calibration_active():
            return it
        import time
        rows = self.metrics[METRIC_NUM_OUTPUT_ROWS]
        batches = self.metrics[METRIC_NUM_OUTPUT_BATCHES]
        total = self.metrics[METRIC_TOTAL_TIME]

        def gen():
            inner = iter(it)
            while True:
                t0 = time.perf_counter_ns()
                try:
                    rb = next(inner)
                except StopIteration:
                    total.add(time.perf_counter_ns() - t0)
                    return
                total.add(time.perf_counter_ns() - t0)
                rows.add(rb.num_rows)
                batches.add(1)
                yield rb
        return gen()
