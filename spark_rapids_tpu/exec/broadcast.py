"""Broadcast exchange + broadcast hash join.

Reference: GpuBroadcastExchangeExec.scala:47-341 (build side collected,
serialized once, and replicated to every executor) and
GpuBroadcastHashJoinExec.scala:83 (streams the big side against the
broadcast table without any shuffle).

TPU design: on a device mesh the broadcast table is replicated to every
chip while the stream side stays sharded, so the join needs no collective
at all (the scaling-book "weight-replicated" layout applied to a build
table).  Single-process, the exchange materializes its child ONCE into a
single coalesced device batch and caches it for the exec's lifetime; the
join exec is the shared hash-join core with the cached batch as the build
side, streaming stream-side batches through the probe without ever
concatenating them.  The planner picks the build side by estimated size
(spark.rapids.sql.autoBroadcastJoinThreshold) and swaps sides behind a
column-reordering projection when the LEFT side is the small one.
"""

from __future__ import annotations

from typing import Iterator, List, Optional

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.dtypes import Schema
from spark_rapids_tpu.exec.base import ExecContext, TpuExec
from spark_rapids_tpu.exec.coalesce import concat_batches
from spark_rapids_tpu.exec.joins import TpuHashJoinExec, _empty_batch
from spark_rapids_tpu.exprs.base import Expression


class TpuBroadcastExchangeExec(TpuExec):
    """Materializes the child once into a single device batch and caches
    it; consumers see a one-batch stream (reference
    GpuBroadcastExchangeExec.scala:47, relation built once per query)."""

    def __init__(self, child):
        super().__init__()
        self.children = [child]
        self._handle = None      # SpillableBatch in the catalog
        self._serialized = None  # Arrow IPC bytes (rebuild path)
        self._reg = None         # lifecycle registration of close()

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def describe(self) -> str:
        return "TpuBroadcastExchange"

    @property
    def output_batching(self):
        from spark_rapids_tpu.exec.coalesce import SINGLE_BATCH
        return SINGLE_BATCH

    def materialize(self, ctx: ExecContext) -> ColumnarBatch:
        """Build (once) the broadcast batch, registered with the spill
        catalog so it participates in the device budget and can demote
        under memory pressure (reference GpuBroadcastExchangeExec builds
        a spillable SerializeConcatHostBuffersDeserializeBatch,
        GpuBroadcastExchangeExec.scala:47-129)."""
        from spark_rapids_tpu.memory.spill import SpillableBatch
        if self._handle is None:
            with self.metrics.timed("broadcastTime"):
                batches = list(self.children[0].execute_columnar(ctx))
                if batches:
                    built = concat_batches(batches)
                else:
                    built = _empty_batch(self.output_schema)
            self.metrics["dataSize"].add(built.size_bytes())
            from spark_rapids_tpu.memory.spill import PRIORITY_RETAIN
            self._handle = SpillableBatch(built, ctx.runtime.catalog,
                                          priority=PRIORITY_RETAIN)
            self._handle.suppress_leak_warning = True
            # the build table outlives the probe loop by design (a
            # multi-consumer plan reuses it), so nothing downstream
            # closes it: register with the query's lifecycle so the
            # handle is reclaimed at query end instead of pinning
            # catalog budget until this exec object is GC'd
            from spark_rapids_tpu import lifecycle
            self._reg = lifecycle.register_resource(
                self.close, kind="broadcast", name="broadcast-build",
                nbytes=lambda: (self._handle.size
                                if self._handle is not None else 0))
            if self._reg.rejected:
                # query teardown raced the build: close() already ran
                # on arrival (handle released from the catalog), so the
                # batch in hand is untracked — surface the typed abort
                # instead of handing it to the probe loop
                self._reg = None
                from spark_rapids_tpu.errors import QueryCancelledError
                raise QueryCancelledError(
                    "broadcast build raced query teardown")
            return built
        return self._handle.get(device=ctx.runtime.device)

    def serialized(self, ctx: ExecContext) -> bytes:
        """Arrow-IPC serialization of the built table — the rebuild
        payload a multi-process executor would receive instead of the
        in-process device buffers (reference: the broadcast relation is
        shipped serialized and rebuilt per executor,
        GpuBroadcastExchangeExec.scala:220-341)."""
        if self._serialized is None:
            from spark_rapids_tpu.columnar.batch import (
                device_batch_to_host,
            )
            from spark_rapids_tpu.shuffle.serializer import (
                serialize_batch,
            )
            rb = device_batch_to_host(self.materialize(ctx),
                                      self.output_schema)
            self._serialized = serialize_batch(rb)
        return self._serialized

    def close(self) -> None:
        if self._reg is not None:
            self._reg.release()
            self._reg = None
        if self._handle is not None:
            self._handle.close()
            self._handle = None

    def execute_columnar(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        def gen():
            yield self.materialize(ctx)
        return self._count_output(gen())


class TpuBroadcastHashJoinExec(TpuHashJoinExec):
    """Hash join whose build side is a broadcast exchange (reference
    GpuBroadcastHashJoinExec.scala:83).  Identical probe core; the build
    batch comes from the exchange's cache, so re-executions (or a future
    multi-consumer plan) build the hash table input only once."""

    # the plan chose the build side: the broadcast one
    _sides_by_size = False

    def __init__(self, left, broadcast: TpuBroadcastExchangeExec,
                 left_keys: List[Expression],
                 right_keys: List[Expression], join_type: str = "inner",
                 condition: Optional[Expression] = None):
        assert isinstance(broadcast, TpuBroadcastExchangeExec), \
            "build side of a broadcast join must be a broadcast exchange"
        super().__init__(left, broadcast, left_keys, right_keys,
                         join_type, condition)

    def describe(self) -> str:
        ks = ", ".join(f"{l.name}={r.name}"
                       for l, r in zip(self.left_keys, self.right_keys))
        return f"TpuBroadcastHashJoin [{self.join_type}, {ks}]"
