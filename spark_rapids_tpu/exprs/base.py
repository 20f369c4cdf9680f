"""Expression base classes, binding, and jit compilation.

Reference: GpuExpressions.scala:74-98 (``columnarEval``), GpuBoundAttribute.scala:24,65
(``GpuBindReferences.bindReferences`` rewriting attribute references to
ordinals), literals.scala:33,120 (``GpuScalar``/``GpuLiteral``),
namedExpressions.scala:28,96 (``GpuAlias``/``GpuAttributeReference``).

TPU-first design: a bound expression tree ``emit``s jax.numpy operations on
``ColVal`` (data, validity, chars) triples inside a traced function.  The
whole output projection of an operator compiles to ONE jitted function per
(expressions, input signature) pair, cached process-wide, so XLA fuses the
entire expression DAG into a single kernel launch.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from spark_rapids_tpu.compile.service import engine_jit
from spark_rapids_tpu.columnar.dtypes import (
    DataType, Schema, BOOLEAN, INT8, INT16, INT32, INT64, FLOAT32, FLOAT64,
    DATE, TIMESTAMP, STRING, common_type, device_dtype,
)
from spark_rapids_tpu.columnar.column import DeviceColumn, bucket_capacity
from spark_rapids_tpu.columnar.batch import ColumnarBatch


class ColVal(NamedTuple):
    """A traced column value inside a jitted expression evaluation.

    ``data`` is the value vector (for STRING it is the int32 lengths),
    ``validity`` the null mask (False = null), ``chars`` the padded byte
    matrix for STRING columns, else None.
    """
    data: jnp.ndarray
    validity: jnp.ndarray
    chars: Optional[jnp.ndarray]


class EvalContext:
    """Carries the traced batch into ``Expression.emit``."""

    __slots__ = ("cols", "num_rows", "capacity", "partition_id", "hoisted",
                 "aux")

    def __init__(self, cols: Sequence[ColVal], num_rows, capacity: int,
                 partition_id=0, hoisted: Sequence = (),
                 aux: Sequence = ()):
        self.cols = list(cols)
        self.num_rows = num_rows      # traced int32 scalar
        self.capacity = capacity      # static python int
        # traced int64 scalar: the task/batch ordinal feeding
        # nondeterministic expressions (rand, monotonically_increasing_id,
        # spark_partition_id — reference GpuRandomExpressions.scala,
        # GpuMonotonicallyIncreasingID.scala, GpuSparkPartitionID.scala)
        self.partition_id = partition_id
        # traced scalar args for hoisted literal constants (slot-indexed
        # by HoistedLiteral; empty when literal hoisting is off)
        self.hoisted = tuple(hoisted)
        # dictionary-domain gather tables for the compressed code view
        # (columnar/encoding.py DictGather) — a SEPARATE ordinal space
        # from ``cols`` so filter compaction never sweeps them
        self.aux = tuple(ColVal(*t) if not isinstance(t, ColVal) else t
                         for t in aux)


class Expression:
    """Immutable expression tree node (reference GpuExpression,
    GpuExpressions.scala:74)."""

    children: Tuple["Expression", ...] = ()

    @property
    def dtype(self) -> DataType:
        raise NotImplementedError(type(self).__name__)

    @property
    def nullable(self) -> bool:
        return any(c.nullable for c in self.children)

    @property
    def name(self) -> str:
        return str(self)

    def key(self) -> str:
        """Stable cache key for compiled-kernel memoization."""
        args = ",".join(c.key() for c in self.children)
        return f"{type(self).__name__}({args})"

    def emit(self, ctx: EvalContext) -> ColVal:
        raise NotImplementedError(type(self).__name__)

    # resolution ------------------------------------------------------------

    @property
    def resolved(self) -> bool:
        return all(c.resolved for c in self.children)

    def with_children(self, children: Sequence["Expression"]) -> "Expression":
        """Generic rebuild; subclasses with extra state must override."""
        new = object.__new__(type(self))
        new.__dict__.update(self.__dict__)
        new.children = tuple(children)
        return new

    def __repr__(self):
        return self.key()


class UnresolvedAttribute(Expression):
    """A by-name column reference prior to binding (the Catalyst analog that
    ``GpuBindReferences`` resolves to ordinals, GpuBoundAttribute.scala:24)."""

    def __init__(self, col_name: str):
        self.col_name = col_name
        self.children = ()

    @property
    def resolved(self) -> bool:
        return False

    @property
    def name(self) -> str:
        return self.col_name

    def key(self) -> str:
        return f"attr[{self.col_name}]"

    def emit(self, ctx):
        raise RuntimeError(f"unresolved attribute {self.col_name!r}; "
                           "bind_expression() first")


class BoundReference(Expression):
    """Input column by ordinal (reference GpuBoundReference,
    GpuBoundAttribute.scala:65)."""

    def __init__(self, ordinal: int, dtype: DataType, nullable: bool = True,
                 col_name: str = ""):
        self.ordinal = ordinal
        self._dtype = dtype
        self._nullable = nullable
        self.col_name = col_name
        self.children = ()

    @property
    def dtype(self) -> DataType:
        return self._dtype

    @property
    def nullable(self) -> bool:
        return self._nullable

    @property
    def name(self) -> str:
        return self.col_name or f"c{self.ordinal}"

    def key(self) -> str:
        return f"in[{self.ordinal}:{self._dtype.name}]"

    def emit(self, ctx: EvalContext) -> ColVal:
        return ctx.cols[self.ordinal]


class Literal(Expression):
    """A scalar constant broadcast at trace time (reference GpuLiteral
    literals.scala:120; scalars enter kernels as XLA constants, fused for
    free instead of cuDF Scalar device objects)."""

    def __init__(self, value, dtype: Optional[DataType] = None):
        import datetime as _dt
        if isinstance(value, _dt.datetime):
            # UTC micros (timestamps are UTC-only, dtypes.py); integer
            # arithmetic — float seconds round-trips lose the last micro
            if value.tzinfo is None:
                value = value.replace(tzinfo=_dt.timezone.utc)
            epoch = _dt.datetime(1970, 1, 1, tzinfo=_dt.timezone.utc)
            value = (value - epoch) // _dt.timedelta(microseconds=1)
            dtype = dtype or TIMESTAMP
        elif isinstance(value, _dt.date):
            value = (value - _dt.date(1970, 1, 1)).days
            dtype = dtype or DATE
        self.value = value
        self._dtype = dtype if dtype is not None else _infer_literal_type(value)
        self.children = ()

    @property
    def dtype(self) -> DataType:
        return self._dtype

    @property
    def nullable(self) -> bool:
        return self.value is None

    @property
    def name(self) -> str:
        return repr(self.value)

    def key(self) -> str:
        return f"lit[{self.value!r}:{self._dtype.name}]"

    def emit(self, ctx: EvalContext) -> ColVal:
        cap = ctx.capacity
        if self.value is None:
            if self._dtype == STRING:
                return ColVal(jnp.zeros(cap, jnp.int32),
                              jnp.zeros(cap, jnp.bool_),
                              jnp.zeros((cap, 8), jnp.uint8))
            return ColVal(jnp.zeros(cap, device_dtype(self._dtype)),
                          jnp.zeros(cap, jnp.bool_), None)
        valid = jnp.ones(cap, jnp.bool_)
        if self._dtype == STRING:
            b = self.value.encode("utf-8")
            width = bucket_capacity(max(1, len(b)))
            row = np.zeros(width, np.uint8)
            row[:len(b)] = np.frombuffer(b, np.uint8)
            chars = jnp.broadcast_to(jnp.asarray(row), (cap, width))
            return ColVal(jnp.full(cap, len(b), jnp.int32), valid, chars)
        data = jnp.full(cap, self.value, dtype=device_dtype(self._dtype))
        return ColVal(data, valid, None)


class ParamLiteral(Literal):
    """A prepared-statement parameter binding (sql.py ``?`` markers;
    docs/serving.md).  Behaves exactly like the Literal it carries —
    the value stays in ``key()`` so a kernel that BAKES the constant
    (hoisting off, string/null values, non-hoist-safe parents) can
    never be wrongly shared across bindings — while the slot index lets
    the plan fingerprint and the prepared-statement re-binding rewrite
    identify it structurally.  Kernel sharing across bindings comes
    from literal hoisting, which replaces this node (it IS a Literal)
    with a value-free HoistedLiteral slot before the cache key forms."""

    def __init__(self, slot: int, value, dtype=None):
        super().__init__(value, dtype)
        self.slot = int(slot)

    def key(self) -> str:
        return f"param[{self.slot}]{super().key()}"


class HoistedLiteral(Expression):
    """A literal whose VALUE enters the kernel as a traced scalar argument
    instead of an XLA constant (the ``Future:`` note that used to sit on
    the projection cache).  The cache key carries only the slot index and
    dtype, so two queries differing solely in their constants share one
    compiled kernel; the concrete values ride in per call through
    ``EvalContext.hoisted``."""

    def __init__(self, slot: int, dtype: DataType):
        self.slot = int(slot)
        self._dtype = dtype
        self.children = ()

    @property
    def dtype(self) -> DataType:
        return self._dtype

    @property
    def nullable(self) -> bool:
        return False  # null literals are never hoisted

    @property
    def name(self) -> str:
        return f"$lit{self.slot}"

    def key(self) -> str:
        return f"hlit[{self.slot}:{self._dtype.name}]"

    def emit(self, ctx: EvalContext) -> ColVal:
        v = ctx.hoisted[self.slot]
        data = jnp.broadcast_to(v, (ctx.capacity,))
        return ColVal(data, jnp.ones(ctx.capacity, jnp.bool_), None)


# Literal hoisting is only sound where the parent expression treats its
# literal children opaquely (pure ``child.emit(ctx)``).  String ops
# capture pattern bytes at trace/construction time, generators and
# window defaults read ``.value`` directly — literals under those stay
# inline.  The gate is by defining module: every class in these modules
# emits literal children opaquely (verified; new introspecting
# expression classes must live outside this set or opt out).
_HOIST_SAFE_MODULES = frozenset({
    "arithmetic", "predicates", "math", "bitwise", "cast",
    "conditional", "datetime", "nullexprs",
})

_HOIST_ENABLED = False


def set_literal_hoisting(on: bool) -> None:
    """Flip the process-global hoisting switch (set from ExecContext with
    the session's ``spark.rapids.sql.fusion.*`` conf, like tracing)."""
    global _HOIST_ENABLED
    _HOIST_ENABLED = bool(on)


def literal_hoisting_enabled() -> bool:
    return _HOIST_ENABLED


def _parent_allows_hoist(parent: Optional[Expression]) -> bool:
    if parent is None or isinstance(parent, Alias):
        return True
    mod = type(parent).__module__.rsplit(".", 1)[-1]
    return mod in _HOIST_SAFE_MODULES


def hoist_literals(exprs: Sequence[Expression]):
    """Rewrite hoistable Literal nodes to HoistedLiteral placeholders.

    Returns ``(new_exprs, values)`` where ``values`` is a tuple of
    ``(python value, DataType)`` in slot order.  With hoisting disabled
    (or nothing hoistable) the input expressions come back unchanged
    with an empty values tuple.  Null and STRING literals stay inline:
    nulls change validity shape, and string constants bake into padded
    char matrices whose width is part of the kernel shape."""
    if not _HOIST_ENABLED:
        return tuple(exprs), ()
    values: list = []

    def walk(e: Expression, parent: Optional[Expression]) -> Expression:
        if isinstance(e, Literal) and e.value is not None \
                and e._dtype != STRING and _parent_allows_hoist(parent):
            slot = len(values)
            values.append((e.value, e._dtype))
            return HoistedLiteral(slot, e._dtype)
        if not e.children:
            return e
        new_children = [walk(c, e) for c in e.children]
        if all(a is b for a, b in zip(new_children, e.children)):
            return e
        return e.with_children(new_children)

    out = tuple(walk(e, None) for e in exprs)
    return out, tuple(values)


def hoisted_args(values) -> tuple:
    """Concrete traced-scalar call args for hoisted literal slots: HOST
    scalars, which the launch itself carries to the device — a
    ``jnp.asarray`` per slot is an eager device op per slot per batch
    (0.4 ms each on the chip: PERF.md, PR 28)."""
    return tuple(np.asarray(v, device_dtype(dt)) for v, dt in values)


def _infer_literal_type(value) -> DataType:
    if value is None:
        raise ValueError("untyped null literal; pass dtype explicitly")
    if isinstance(value, bool):
        return BOOLEAN
    if isinstance(value, (int, np.integer)):
        return INT32 if -(2 ** 31) <= int(value) < 2 ** 31 else INT64
    if isinstance(value, (float, np.floating)):
        return FLOAT64
    if isinstance(value, str):
        return STRING
    raise TypeError(f"cannot infer literal type for {value!r}")


class Alias(Expression):
    """Named output column (reference GpuAlias namedExpressions.scala:28)."""

    def __init__(self, child: Expression, out_name: str):
        self.children = (child,)
        self.out_name = out_name

    @property
    def child(self) -> Expression:
        return self.children[0]

    @property
    def dtype(self) -> DataType:
        return self.child.dtype

    @property
    def nullable(self) -> bool:
        return self.child.nullable

    @property
    def name(self) -> str:
        return self.out_name

    def key(self) -> str:
        return f"alias[{self.out_name}]({self.child.key()})"

    def emit(self, ctx: EvalContext) -> ColVal:
        return self.child.emit(ctx)

    def with_children(self, children):
        return Alias(children[0], self.out_name)


# ---------------------------------------------------------------------------
# Binding / resolution
# ---------------------------------------------------------------------------

def bind_expression(expr: Expression, schema: Schema) -> Expression:
    """Resolve attributes to BoundReference and apply type coercion
    (reference GpuBindReferences.bindReferences GpuBoundAttribute.scala:24)."""
    if isinstance(expr, UnresolvedAttribute):
        i = schema.field_index(expr.col_name)
        f = schema[i]
        return BoundReference(i, f.dtype, f.nullable, f.name)
    if not expr.children:
        return expr
    bound_children = [bind_expression(c, schema) for c in expr.children]
    rebuilt = expr.with_children(bound_children)
    coerce = getattr(rebuilt, "coerce", None)
    if coerce is not None:
        rebuilt = coerce()
    return rebuilt


def bind_expressions(exprs: Sequence[Expression],
                     schema: Schema) -> List[Expression]:
    return [bind_expression(e, schema) for e in exprs]


def numeric_common_children(left: Expression,
                            right: Expression) -> Optional[DataType]:
    return common_type(left.dtype, right.dtype)


# ---------------------------------------------------------------------------
# Compilation: expression list -> one jitted function per input signature
# ---------------------------------------------------------------------------

def _batch_signature(batch: ColumnarBatch) -> tuple:
    sig = []
    for c in batch.columns:
        width = c.string_width if c.chars is not None else 0
        sig.append((c.dtype.name, c.capacity, width))
    return tuple(sig)


def _flatten_batch(batch: ColumnarBatch):
    return tuple((c.data, c.validity, c.chars) for c in batch.columns)


from spark_rapids_tpu.utils.kernel_cache import KernelCache

# LRU-bounded + counter-instrumented: expression keys may still embed
# literal values (string/null constants, or hoisting disabled), so the
# bound stays; with hoisting ON the keys carry HoistedLiteral slots and
# distinct-constant queries share one entry.
_PROJECTION_CACHE = KernelCache("projection", 512)


def compile_projection(exprs: Sequence[Expression], input_sig: tuple,
                       capacity: int):
    """Build (and cache) a jitted fn evaluating ``exprs`` over a batch of
    the given signature, plus the hoisted-literal call values.  Returns
    ``(fn, values)`` where fn's signature is ``(flat_cols, num_rows,
    partition_id, hoisted) -> tuple[(data, validity, chars|None), ...]``
    and ``hoisted`` must be ``hoisted_args(values)``."""
    exprs, values = hoist_literals(tuple(exprs))
    key = (tuple(e.key() for e in exprs), input_sig, capacity)
    fn = _PROJECTION_CACHE.get(key)
    if fn is not None:
        return fn, values

    def run(flat_cols, num_rows, partition_id, hoisted):
        cols = [ColVal(*t) for t in flat_cols]
        ctx = EvalContext(cols, num_rows, capacity, partition_id,
                          hoisted=hoisted)
        outs = tuple(e.emit(ctx) for e in exprs)
        # Enforce the column invariant (column.py docstring): padding rows
        # beyond num_rows are never valid.  Expressions like Literal/IsNull
        # emit full-capacity validity; mask once here instead of in every
        # expression class.
        live = jnp.arange(capacity) < num_rows
        return tuple(ColVal(o.data, o.validity & live, o.chars)
                     for o in outs)

    fn = engine_jit(run, family="stage", name="projection")
    _PROJECTION_CACHE[key] = fn
    return fn, values


def evaluate_projection(exprs: Sequence[Expression],
                        batch: ColumnarBatch,
                        partition_id: int = 0) -> List[DeviceColumn]:
    """The columnarEval entry point: evaluate bound expressions against a
    device batch, returning new device columns (reference
    GpuExpressions.scala:74-98).  ``partition_id``: the batch ordinal,
    feeding nondeterministic expressions."""
    fn, values = compile_projection(exprs, _batch_signature(batch),
                                    batch.capacity)
    outs = fn(_flatten_batch(batch), batch.rows_traced,
              jnp.int64(partition_id), hoisted_args(values))
    cols = []
    for e, out in zip(exprs, outs):
        cols.append(DeviceColumn(e.dtype, out.data, out.validity,
                                 batch.rows_raw, chars=out.chars))
    return cols


def evaluate_single(expr: Expression, batch: ColumnarBatch) -> DeviceColumn:
    return evaluate_projection([expr], batch)[0]


# ---------------------------------------------------------------------------
# Shared emit helpers
# ---------------------------------------------------------------------------

def both_valid(a: ColVal, b: ColVal) -> jnp.ndarray:
    return a.validity & b.validity


def fixed(data, validity) -> ColVal:
    return ColVal(data, validity, None)


def align_chars(a_chars: jnp.ndarray, b_chars: jnp.ndarray):
    """Pad the narrower of two char matrices so both share max width."""
    wa, wb = a_chars.shape[1], b_chars.shape[1]
    w = max(wa, wb)
    if wa < w:
        a_chars = jnp.pad(a_chars, ((0, 0), (0, w - wa)))
    if wb < w:
        b_chars = jnp.pad(b_chars, ((0, 0), (0, w - wb)))
    return a_chars, b_chars
