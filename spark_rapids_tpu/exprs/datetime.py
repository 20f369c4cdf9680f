"""Date/time expressions.

Reference: datetimeExpressions.scala (464 LoC: year/month/day/hour/minute/
second, dateadd/datesub/datediff, unix_timestamp family; UTC-only
enforcement GpuOverrides.scala:713-715).

DATE is days-since-epoch int32; TIMESTAMP is microseconds-since-epoch int64
UTC.  Civil-date decomposition uses Howard Hinnant's branch-free integer
algorithm, which vectorizes perfectly on the VPU (no table lookups)."""

from __future__ import annotations

import jax.numpy as jnp

from spark_rapids_tpu.columnar.dtypes import (
    DataType, INT32, INT64, DATE, TIMESTAMP,
)
from spark_rapids_tpu.exprs.base import (
    ColVal, EvalContext, Expression, both_valid, fixed,
)

MICROS_PER_SECOND = 1_000_000
MICROS_PER_DAY = 86_400 * MICROS_PER_SECOND


def days_to_civil(days):
    """days-since-epoch -> (year, month, day), vectorized (Hinnant's
    civil_from_days).  int32 throughout: DATE is int32 days and every
    intermediate stays far inside int32 for any year within +-5.8
    million (Spark's DATE range is years 0001-9999).  The TPU emulates
    int64, and the dozen constant divisions below expand to ~16k HLO
    instructions in int64 (25 s of XLA:TPU compile for this function
    alone at 2^20 rows, minutes inside a fused aggregate) against 200
    in int32."""
    z = days.astype(jnp.int32) + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + 3 - 12 * (mp // 10)
    y = y + (m <= 2)
    return y.astype(jnp.int32), m.astype(jnp.int32), d.astype(jnp.int32)


def civil_to_days(y, m, d):
    """(year, month, day) -> days-since-epoch (Hinnant's days_from_civil);
    int32 throughout, like ``days_to_civil``."""
    y = y.astype(jnp.int32) - (m <= 2)
    era = jnp.floor_divide(y, 400)
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9).astype(jnp.int32)
    doy = (153 * mp + 2) // 5 + d.astype(jnp.int32) - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return (era * 146097 + doe - 719468).astype(jnp.int32)


def timestamp_to_days(us):
    return jnp.floor_divide(us, MICROS_PER_DAY).astype(jnp.int32)


def timestamp_time_of_day(us):
    """-> (hour, minute, second, micros) in UTC."""
    tod = us - timestamp_to_days(us).astype(jnp.int64) * MICROS_PER_DAY
    secs = tod // MICROS_PER_SECOND
    micro = tod - secs * MICROS_PER_SECOND
    h = secs // 3600
    mi = (secs % 3600) // 60
    s = secs % 60
    return (h.astype(jnp.int32), mi.astype(jnp.int32),
            s.astype(jnp.int32), micro.astype(jnp.int64))


class _DatePart(Expression):
    """Extract a civil component from DATE or TIMESTAMP."""
    fname = "?"

    def __init__(self, child: Expression):
        self.children = (child,)

    @property
    def dtype(self) -> DataType:
        return INT32

    @property
    def name(self) -> str:
        return f"{self.fname}({self.children[0].name})"

    def _days(self, c: ColVal) -> jnp.ndarray:
        if self.children[0].dtype == TIMESTAMP:
            return timestamp_to_days(c.data)
        return c.data

    def emit(self, ctx: EvalContext) -> ColVal:
        c = self.children[0].emit(ctx)
        return fixed(self.part(self._days(c)), c.validity)

    def part(self, days):
        raise NotImplementedError


class Year(_DatePart):
    fname = "year"

    def part(self, days):
        return days_to_civil(days)[0]


class Month(_DatePart):
    fname = "month"

    def part(self, days):
        return days_to_civil(days)[1]


class DayOfMonth(_DatePart):
    fname = "dayofmonth"

    def part(self, days):
        return days_to_civil(days)[2]


class DayOfWeek(_DatePart):
    """1 = Sunday ... 7 = Saturday (Spark semantics)."""
    fname = "dayofweek"

    def part(self, days):
        # 1970-01-01 was a Thursday (day-of-week 5 in Spark's scheme)
        return (jnp.mod(days.astype(jnp.int64) + 4, 7) + 1).astype(jnp.int32)


class WeekDay(_DatePart):
    """0 = Monday ... 6 = Sunday."""
    fname = "weekday"

    def part(self, days):
        return jnp.mod(days.astype(jnp.int64) + 3, 7).astype(jnp.int32)


class DayOfYear(_DatePart):
    fname = "dayofyear"

    def part(self, days):
        y, _, _ = days_to_civil(days)
        jan1 = civil_to_days(y, jnp.full_like(y, 1), jnp.full_like(y, 1))
        return (days - jan1 + 1).astype(jnp.int32)


class Quarter(_DatePart):
    fname = "quarter"

    def part(self, days):
        m = days_to_civil(days)[1]
        return ((m - 1) // 3 + 1).astype(jnp.int32)


class LastDay(_DatePart):
    """Last day of the month, as DATE."""
    fname = "last_day"

    @property
    def dtype(self) -> DataType:
        return DATE

    def part(self, days):
        y, m, _ = days_to_civil(days)
        ny = jnp.where(m == 12, y + 1, y)
        nm = jnp.where(m == 12, 1, m + 1)
        first_next = civil_to_days(ny, nm, jnp.full_like(nm, 1))
        return (first_next - 1).astype(jnp.int32)


class _TimePart(Expression):
    fname = "?"

    def __init__(self, child: Expression):
        self.children = (child,)

    @property
    def dtype(self) -> DataType:
        return INT32

    @property
    def name(self) -> str:
        return f"{self.fname}({self.children[0].name})"

    def emit(self, ctx):
        c = self.children[0].emit(ctx)
        h, mi, s, _ = timestamp_time_of_day(c.data)
        return fixed(self.pick(h, mi, s), c.validity)


class Hour(_TimePart):
    fname = "hour"

    def pick(self, h, mi, s):
        return h


class Minute(_TimePart):
    fname = "minute"

    def pick(self, h, mi, s):
        return mi


class Second(_TimePart):
    fname = "second"

    def pick(self, h, mi, s):
        return s


class DateAdd(Expression):
    """date_add(date, days) (reference GpuDateAdd)."""

    def __init__(self, start: Expression, days: Expression):
        self.children = (start, days)

    @property
    def dtype(self) -> DataType:
        return DATE

    @property
    def name(self) -> str:
        return f"date_add({self.children[0].name}, {self.children[1].name})"

    def emit(self, ctx):
        a = self.children[0].emit(ctx)
        b = self.children[1].emit(ctx)
        out = (a.data.astype(jnp.int64)
               + b.data.astype(jnp.int64)).astype(jnp.int32)
        return fixed(out, both_valid(a, b))


class DateSub(Expression):
    def __init__(self, start: Expression, days: Expression):
        self.children = (start, days)

    @property
    def dtype(self) -> DataType:
        return DATE

    @property
    def name(self) -> str:
        return f"date_sub({self.children[0].name}, {self.children[1].name})"

    def emit(self, ctx):
        a = self.children[0].emit(ctx)
        b = self.children[1].emit(ctx)
        out = (a.data.astype(jnp.int64)
               - b.data.astype(jnp.int64)).astype(jnp.int32)
        return fixed(out, both_valid(a, b))


class DateDiff(Expression):
    """datediff(end, start) -> int days."""

    def __init__(self, end: Expression, start: Expression):
        self.children = (end, start)

    @property
    def dtype(self) -> DataType:
        return INT32

    @property
    def name(self) -> str:
        return f"datediff({self.children[0].name}, {self.children[1].name})"

    def emit(self, ctx):
        a = self.children[0].emit(ctx)
        b = self.children[1].emit(ctx)
        return fixed(a.data - b.data, both_valid(a, b))


class UnixTimestampFromDateTime(Expression):
    """to_unix_timestamp / unix_timestamp on DATE/TIMESTAMP input ->
    seconds since epoch as LONG (string-input parsing is the gated path)."""

    def __init__(self, child: Expression):
        self.children = (child,)

    @property
    def dtype(self) -> DataType:
        return INT64

    @property
    def name(self) -> str:
        return f"unix_timestamp({self.children[0].name})"

    def emit(self, ctx):
        c = self.children[0].emit(ctx)
        if self.children[0].dtype == DATE:
            secs = c.data.astype(jnp.int64) * 86_400
        else:
            secs = jnp.floor_divide(c.data, MICROS_PER_SECOND)
        return fixed(secs, c.validity)


class TimeSub(Expression):
    """timestamp - interval(us) (reference GpuTimeSub; the interval is a
    literal microsecond count)."""

    def __init__(self, start: Expression, interval_us: int):
        self.children = (start,)
        self.interval_us = int(interval_us)

    @property
    def dtype(self) -> DataType:
        return TIMESTAMP

    @property
    def name(self) -> str:
        return f"({self.children[0].name} - INTERVAL {self.interval_us}us)"

    def key(self) -> str:
        return f"TimeSub[{self.interval_us}]({self.children[0].key()})"

    def with_children(self, children):
        return TimeSub(children[0], self.interval_us)

    def emit(self, ctx):
        c = self.children[0].emit(ctx)
        return fixed(c.data - jnp.int64(self.interval_us), c.validity)


class TimeAdd(TimeSub):
    @property
    def name(self) -> str:
        return f"({self.children[0].name} + INTERVAL {self.interval_us}us)"

    def key(self) -> str:
        return f"TimeAdd[{self.interval_us}]({self.children[0].key()})"

    def with_children(self, children):
        return TimeAdd(children[0], self.interval_us)

    def emit(self, ctx):
        c = self.children[0].emit(ctx)
        return fixed(c.data + jnp.int64(self.interval_us), c.validity)
