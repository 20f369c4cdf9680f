"""Public column functions (the pyspark.sql.functions analog)."""

from __future__ import annotations

from spark_rapids_tpu.api import Column, col, lit, when, coalesce, _to_expr
from spark_rapids_tpu.exprs import aggregates as ag
from spark_rapids_tpu.exprs import math as mt
from spark_rapids_tpu.exprs import datetime as dte
from spark_rapids_tpu.exprs import nullexprs as ne
from spark_rapids_tpu.exprs import predicates as pr
from spark_rapids_tpu.exprs.base import Alias, Literal


def _c(v):
    """pyspark convention: bare strings name columns (use lit() for string
    literals)."""
    from spark_rapids_tpu.exprs.base import UnresolvedAttribute
    if isinstance(v, str):
        return UnresolvedAttribute(v)
    return _to_expr(v)


def _named(expr, name):
    return Column(Alias(expr, name))


# aggregates
def count(c) -> Column:
    # NB: Column overloads ==, so `c == "*"` would be a truthy Column for
    # every Column argument — the string check must be explicit
    e = Literal(1) if isinstance(c, str) and c == "*" else _c(c)
    return Column(ag.Count(e))


def sum(c) -> Column:  # noqa: A001 - mirrors pyspark naming
    return Column(ag.Sum(_c(c)))


def min(c) -> Column:  # noqa: A001
    return Column(ag.Min(_c(c)))


def max(c) -> Column:  # noqa: A001
    return Column(ag.Max(_c(c)))


def avg(c) -> Column:
    return Column(ag.Average(_c(c)))


mean = avg


def first(c, ignore_nulls: bool = True) -> Column:
    return Column(ag.First(_c(c), ignore_nulls))


def last(c, ignore_nulls: bool = True) -> Column:
    return Column(ag.Last(_c(c), ignore_nulls))


# math
def pmod(a, n) -> Column:
    from spark_rapids_tpu.exprs.arithmetic import Pmod
    return Column(Pmod(_c(a), _c(n)))


def sqrt(c) -> Column:
    return Column(mt.Sqrt(_c(c)))


def exp(c) -> Column:
    return Column(mt.Exp(_c(c)))


def log(c) -> Column:
    return Column(mt.Log(_c(c)))


def pow(c, p) -> Column:  # noqa: A001
    return Column(mt.Pow(_c(c), _c(p)))


def floor(c) -> Column:
    return Column(mt.Floor(_c(c)))


def ceil(c) -> Column:
    return Column(mt.Ceil(_c(c)))


def abs(c) -> Column:  # noqa: A001
    from spark_rapids_tpu.exprs.arithmetic import Abs
    return Column(Abs(_c(c)))


# null handling
def isnull(c) -> Column:
    return Column(pr.IsNull(_c(c)))


def isnan(c) -> Column:
    return Column(pr.IsNaN(_c(c)))


def nanvl(a, b) -> Column:
    return Column(ne.NaNvl(_c(a), _c(b)))


# datetime
def year(c) -> Column:
    return Column(dte.Year(_c(c)))


def month(c) -> Column:
    return Column(dte.Month(_c(c)))


def dayofmonth(c) -> Column:
    return Column(dte.DayOfMonth(_c(c)))


def dayofweek(c) -> Column:
    return Column(dte.DayOfWeek(_c(c)))


def dayofyear(c) -> Column:
    return Column(dte.DayOfYear(_c(c)))


def quarter(c) -> Column:
    return Column(dte.Quarter(_c(c)))


def hour(c) -> Column:
    return Column(dte.Hour(_c(c)))


def minute(c) -> Column:
    return Column(dte.Minute(_c(c)))


def second(c) -> Column:
    return Column(dte.Second(_c(c)))


def date_add(c, days) -> Column:
    return Column(dte.DateAdd(_c(c), _c(days)))


def date_sub(c, days) -> Column:
    return Column(dte.DateSub(_c(c), _c(days)))


def datediff(end, start) -> Column:
    return Column(dte.DateDiff(_c(end), _c(start)))


def last_day(c) -> Column:
    return Column(dte.LastDay(_c(c)))


def unix_timestamp(c) -> Column:
    return Column(dte.UnixTimestampFromDateTime(_c(c)))


# strings (reference stringFunctions.scala; patterns are literals like the
# reference's rules require)
def upper(c) -> Column:
    from spark_rapids_tpu.exprs import strings as st
    return Column(st.Upper(_c(c)))


def lower(c) -> Column:
    from spark_rapids_tpu.exprs import strings as st
    return Column(st.Lower(_c(c)))


def length(c) -> Column:
    from spark_rapids_tpu.exprs import strings as st
    return Column(st.StringLength(_c(c)))


def substring(c, pos, length=None) -> Column:
    """pos/len may be ints (device path) or Columns (CPU fallback)."""
    from spark_rapids_tpu.exprs import strings as st
    ln = None if length is None else _to_expr(length)
    return Column(st.Substring(_c(c), _to_expr(pos), ln))


def concat(*cols) -> Column:
    from spark_rapids_tpu.exprs import strings as st
    return Column(st.Concat(*[_c(x) for x in cols]))


def trim(c, trim_str: str = None) -> Column:
    from spark_rapids_tpu.exprs import strings as st
    ts = None if trim_str is None else Literal(trim_str)
    return Column(st.StringTrim(_c(c), ts))


def ltrim(c, trim_str: str = None) -> Column:
    from spark_rapids_tpu.exprs import strings as st
    ts = None if trim_str is None else Literal(trim_str)
    return Column(st.StringTrimLeft(_c(c), ts))


def rtrim(c, trim_str: str = None) -> Column:
    from spark_rapids_tpu.exprs import strings as st
    ts = None if trim_str is None else Literal(trim_str)
    return Column(st.StringTrimRight(_c(c), ts))


# -- window functions (reference GpuWindowExpression rules) ------------------

def row_number() -> Column:
    from spark_rapids_tpu.exprs.windows import RowNumber
    return Column(RowNumber())


def rank() -> Column:
    from spark_rapids_tpu.exprs.windows import Rank
    return Column(Rank())


def dense_rank() -> Column:
    from spark_rapids_tpu.exprs.windows import DenseRank
    return Column(DenseRank())


def lag(c, offset: int = 1, default=None) -> Column:
    from spark_rapids_tpu.exprs.windows import Lag
    d = None if default is None else Literal(default)
    return Column(Lag(_c(c), offset, d))


def lead(c, offset: int = 1, default=None) -> Column:
    from spark_rapids_tpu.exprs.windows import Lead
    d = None if default is None else Literal(default)
    return Column(Lead(_c(c), offset, d))


def grouping_id() -> Column:
    """Bitmask of masked grouping keys under rollup/cube (reference
    Spark grouping_id; lowered from the expand's grouping-id column)."""
    from spark_rapids_tpu.api import GROUPING_ID_COL
    from spark_rapids_tpu.exprs.base import UnresolvedAttribute
    return Column(UnresolvedAttribute(GROUPING_ID_COL))


# generators (reference GpuGenerateExec.scala:33-190: literal arrays only)
def array(*vals, elem_dtype=None) -> Column:
    """A literal array, usable only inside explode()/posexplode().
    ``elem_dtype`` (DataType or Spark type name) is required when the
    element type cannot be inferred — empty or all-null arrays, as used
    with explode_outer."""
    from spark_rapids_tpu.exprs.generators import ArrayLiteral
    if isinstance(elem_dtype, str):
        from spark_rapids_tpu.columnar.dtypes import from_name
        elem_dtype = from_name(elem_dtype)
    items = [v.expr if isinstance(v, Column) else v for v in vals]
    return Column(ArrayLiteral(items, elem_dtype))


def explode(c) -> Column:
    from spark_rapids_tpu.exprs.generators import Explode
    return Column(Explode(_c(c)))


def explode_outer(c) -> Column:
    from spark_rapids_tpu.exprs.generators import Explode
    return Column(Explode(_c(c), outer=True))


def posexplode(c) -> Column:
    from spark_rapids_tpu.exprs.generators import Explode
    return Column(Explode(_c(c), with_pos=True))


def posexplode_outer(c) -> Column:
    from spark_rapids_tpu.exprs.generators import Explode
    return Column(Explode(_c(c), with_pos=True, outer=True))


# nondeterministic (reference GpuRandomExpressions.scala,
# GpuMonotonicallyIncreasingID.scala, GpuSparkPartitionID.scala)
def rand(seed=None) -> Column:
    """Uniform [0,1) per row.  Incompat: threefry sequence, not Spark's
    XORShift (enable spark.rapids.sql.incompatibleOps.enabled)."""
    import random as _random
    from spark_rapids_tpu.exprs.nondeterministic import Rand
    if seed is None:
        seed = _random.randint(0, 2**31 - 1)
    return Column(Rand(seed))


def monotonically_increasing_id() -> Column:
    from spark_rapids_tpu.exprs.nondeterministic import (
        MonotonicallyIncreasingID,
    )
    return Column(MonotonicallyIncreasingID())


def spark_partition_id() -> Column:
    from spark_rapids_tpu.exprs.nondeterministic import SparkPartitionID
    return Column(SparkPartitionID())


def initcap(c) -> Column:
    from spark_rapids_tpu.exprs import strings as st
    return Column(st.InitCap(_c(c)))


def locate(substr: str, c, pos: int = 1) -> Column:
    from spark_rapids_tpu.exprs import strings as st
    return Column(st.StringLocate(Literal(substr), _c(c), Literal(pos)))


def instr(c, substr: str) -> Column:
    from spark_rapids_tpu.exprs import strings as st
    return Column(st.StringLocate(Literal(substr), _c(c), Literal(1)))


def replace(c, search, rep) -> Column:
    from spark_rapids_tpu.exprs import strings as st
    sr = search if isinstance(search, Column) else lit(search)
    rp = rep if isinstance(rep, Column) else lit(rep)
    return Column(st.StringReplace(_c(c), _to_expr(sr), _to_expr(rp)))


def substring_index(c, delim: str, count: int) -> Column:
    from spark_rapids_tpu.exprs import strings as st
    return Column(st.SubstringIndex(_c(c), Literal(delim),
                                    Literal(count)))


def concat_ws(sep: str, *cols) -> Column:
    from spark_rapids_tpu.exprs import strings as st
    s = sep if isinstance(sep, Column) else lit(sep)
    return Column(st.ConcatWs(_to_expr(s), *[_c(x) for x in cols]))


def regexp_replace(c, pattern, rep) -> Column:
    from spark_rapids_tpu.exprs import strings as st
    p = pattern if isinstance(pattern, Column) else lit(pattern)
    r = rep if isinstance(rep, Column) else lit(rep)
    return Column(st.RegExpReplace(_c(c), _to_expr(p), _to_expr(r)))


def contains(c, substr) -> Column:
    """Substring predicate.  Literal needles of ``PALLAS_PATTERN_MIN``
    bytes or more route to the Pallas kernel (constant program size and
    no HBM temp in pattern length); shorter ones keep the XLA unrolled
    compare, which fuses into the stage."""
    from spark_rapids_tpu.exprs import strings as st
    from spark_rapids_tpu.exprs import pallas_strings as ps
    p = substr if isinstance(substr, Column) else lit(substr)
    pe = _to_expr(p)
    is_static, pb = st._static_pattern(pe)
    if is_static and pb is not None and len(pb) >= ps.PALLAS_PATTERN_MIN:
        return Column(ps.PallasContains(_c(c), pe))
    return Column(st.Contains(_c(c), pe))


def rlike(c, pattern) -> Column:
    """RLIKE/regexp: the regex-lite subset runs on device (code-set
    membership over a dictionary); anything else falls back to CPU."""
    from spark_rapids_tpu.exprs import strings as st
    p = pattern if isinstance(pattern, Column) else lit(pattern)
    return Column(st.RLike(_c(c), _to_expr(p)))


def split_part(c, delim: str, part: int) -> Column:
    """split(str, delim)[part] as one device kernel (Spark
    split_part: 1-based, negative from the end, '' out of range)."""
    from spark_rapids_tpu.exprs import strings as st
    return Column(st.SplitPart(_c(c), Literal(delim), Literal(part)))
