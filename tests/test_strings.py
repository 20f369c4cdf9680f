"""CPU-vs-TPU compare tests for the string expression family (reference
test methodology: StringOperatorsSuite.scala + StringFallbackSuite via
SparkQueryCompareTestSuite.scala)."""

import pyarrow as pa
import pytest

from spark_rapids_tpu.api import col, lit
from spark_rapids_tpu import functions as F

from compare import assert_tpu_and_cpu_equal
from fuzzer import gen_table

INCOMPAT = {"spark.rapids.sql.incompatibleOps.enabled": True}


def _fuzz(seed=11, n=300):
    return gen_table(seed, [("s", pa.string()), ("t", pa.string())], n,
                     null_prob=0.15)


# explicit UTF-8 edge cases: multi-byte chars, embedded NUL, empties
UTF8 = pa.table({"s": pa.array([
    "", "a", "abc", "héllo", "héllo wörld", "中文字符", "naïve",
    "a\x00b", "\x00", "mix中a文b", "  padded  ", "🎉emoji🎉", None,
    "tab\tsep", "ZZ top", "%literal%", "under_score",
])})


def test_upper_lower_compare():
    t = _fuzz(1)
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(
            F.upper(col("s")).alias("u"), F.lower(col("s")).alias("l")),
        conf=INCOMPAT)


def test_length_utf8():
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(UTF8).select(
            F.length(col("s")).alias("n")))


@pytest.mark.parametrize("pos,ln", [
    (1, 2), (2, None), (0, 3), (-2, 2), (-5, 2), (3, 0), (2, -1),
    (100, 5), (-100, 3),
])
def test_substring_compare(pos, ln):
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(UTF8).select(
            F.substring(col("s"), pos, ln).alias("sub")))


def test_substr_method_fuzzed():
    t = _fuzz(2)
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(
            col("s").substr(2, 4).alias("a"),
            col("s").substr(-3, 2).alias("b")))


def test_concat_compare():
    t = _fuzz(3)
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(
            F.concat(col("s"), col("t")).alias("st"),
            F.concat(col("s"), lit("-"), col("t")).alias("dashed")))


def test_concat_utf8():
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(UTF8).select(
            F.concat(col("s"), lit("→"), col("s")).alias("dup")))


def test_starts_ends_contains_fuzzed():
    t = _fuzz(4)
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(
            col("s").startswith("a").alias("sw"),
            col("s").endswith("9").alias("ew"),
            col("s").contains("bc").alias("ct"),
            col("s").startswith("").alias("sw0"),
            col("s").contains("").alias("ct0")))


def test_pattern_predicates_utf8():
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(UTF8).select(
            col("s").startswith("hé").alias("sw"),
            col("s").endswith("符").alias("ew"),
            col("s").contains("中").alias("ct"),
            col("s").contains("\x00").alias("nul")))


@pytest.mark.parametrize("pat", [
    "a%", "%9", "%bc%", "a_c", "_", "%", "", "abc", "a%c_",
    r"\%literal\%", r"under\_score", "%_%",
])
def test_like_compare(pat):
    t = _fuzz(5)
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(
            col("s").like(pat).alias("m")))


def test_like_utf8_char_exact():
    # '_' must match one CODEPOINT, not one byte — multi-byte chars count 1
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(UTF8).select(
            col("s").like("h_llo").alias("a"),
            col("s").like("中_字_").alias("b"),
            col("s").like("%ö%").alias("c"),
            col("s").like("__").alias("two_chars")))


def test_trim_family_compare():
    t = pa.table({"s": pa.array([
        "  both  ", "left only   ", "   right", "no pad", "", "   ",
        " x ", "..dots..", None, "  mixed . ", "\x00 keep\x00",
    ])})
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(
            F.trim(col("s")).alias("t"),
            F.ltrim(col("s")).alias("lt"),
            F.rtrim(col("s")).alias("rt"),
            F.trim(col("s"), ". ").alias("tc")))


def test_string_filter_pipeline():
    """String predicates driving a filter + projection, planner end-to-end."""
    t = _fuzz(6, 500)
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t)
        .filter(col("s").contains("a") | col("t").like("%X%"))
        .select(F.concat(col("s"), col("t")).alias("c"),
                F.length(col("s")).alias("n")))


def test_upper_falls_back_without_incompat():
    """Upper/Lower are incompat-gated: without the conf the plan must fall
    back to CPU (not crash)."""
    from compare import tpu_session
    t = _fuzz(7, 50)
    sess = tpu_session({"spark.rapids.sql.test.enabled": False})
    df = sess.create_dataframe(t).select(F.upper(col("s")).alias("u"))
    ex = df.explain()
    assert "Upper" in ex and "disabled" in ex
    df.to_arrow()  # executes via CPU fallback


def test_dynamic_pattern_falls_back_to_cpu():
    """contains(column) can't run on device (pattern not literal) — the
    planner must fall back cleanly and still produce Spark answers."""
    from compare import tpu_session
    t = pa.table({"s": pa.array(["abcd", "xyz", "aa", None, "zz"]),
                  "t": pa.array(["bc", "q", "aa", "x", None])})
    sess = tpu_session({"spark.rapids.sql.test.enabled": False})
    df = sess.create_dataframe(t).select(
        col("s").contains(col("t")).alias("c"),
        F.substring(col("s"), 2, 2).alias("sub"))
    assert "pattern must be a literal" in df.explain()
    assert df.to_arrow().column("c").to_pylist() == [
        True, False, True, None, None]


def test_like_invalid_escape_raises():
    with pytest.raises(ValueError, match="escape"):
        col("s").like(r"a\bc")
    with pytest.raises(ValueError, match="escape"):
        col("s").like("trailing\\")


# ---------------------------------------------------------------------------
# Round-3 breadth: initcap / locate / replace / substring_index /
# concat_ws / regexp_replace
# ---------------------------------------------------------------------------

def test_initcap_compare():
    t = pa.table({"s": pa.array([
        "hello world", "HELLO  WORLD", "a b c", "", " lead", "trail ",
        "mIxEd CaSe", None, "one", "x y"])})
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(
            F.initcap(col("s")).alias("i")), conf=INCOMPAT)


@pytest.mark.parametrize("sub,start", [
    ("l", 1), ("l", 4), ("", 1), ("", 3), ("zz", 1), ("hél", 1),
    ("o", 0), ("o", -2), ("中", 1),
])
def test_locate_compare(sub, start):
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(UTF8).select(
            F.locate(sub, col("s"), start).alias("p")))


@pytest.mark.parametrize("search,rep", [
    ("a", "XY"), ("ab", ""), ("", "Q"), ("l", "l"), ("é", "e"),
    ("中", "ZZZ"), ("\x00", "N"), ("aa", "b"),
])
def test_replace_compare(search, rep):
    t = pa.table({"s": pa.array([
        "", "a", "aaa", "aaaa", "abab", "ababab", "héllo", "中文中",
        "a\x00b\x00", None, "no match here", "aabbaabb"])})
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(
            F.replace(col("s"), search, rep).alias("r")))


@pytest.mark.parametrize("delim,count", [
    (".", 1), (".", 2), (".", -1), (".", -2), (".", 0), (".", 10),
    (".", -10), ("ab", 1), ("aa", 1), ("aa", -1), ("", 2),
])
def test_substring_index_compare(delim, count):
    t = pa.table({"s": pa.array([
        "a.b.c.d", "www.apache.org", "no-dots", "", ".lead", "trail.",
        "..", "...", "aaaa", "abab", None, "one.two"])})
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(
            F.substring_index(col("s"), delim, count).alias("x")))


def test_concat_ws_skips_nulls():
    t = pa.table({
        "a": pa.array(["x", None, "p", None, ""]),
        "b": pa.array(["y", "q", None, None, "z"]),
    })
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(
            F.concat_ws(",", col("a"), col("b"), lit("k")).alias("j"),
            F.concat_ws("", col("a"), col("b")).alias("e"),
            F.concat_ws("--", col("a")).alias("one")))


def test_concat_ws_fuzzed():
    t = _fuzz(21)
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(
            F.concat_ws("|", col("s"), col("t"), col("s")).alias("j")))


def test_regexp_replace_plain_pattern_on_device():
    t = pa.table({"s": pa.array(["aXbXc", "", "XX", None, "noX"])})

    def q(s):
        return s.create_dataframe(t).select(
            F.regexp_replace(col("s"), "X", "-").alias("r"))
    assert_tpu_and_cpu_equal(q)
    from tests.compare import tpu_session
    s = tpu_session()
    assert "cannot run on TPU" not in q(s).explain()


def test_regexp_replace_real_regex_falls_back():
    from tests.compare import tpu_session
    t = pa.table({"s": pa.array(["a1b22c333", "no digits", ""])})
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    df = s.create_dataframe(t).select(
        F.regexp_replace(col("s"), r"\d+", "#").alias("r"))
    assert "cannot run on TPU" in df.explain()
    assert df.to_arrow().column("r").to_pylist() == ["a#b#c#",
                                                     "no digits", ""]


def test_locate_replace_fuzzed():
    t = _fuzz(31)
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(
            F.locate("a", col("s")).alias("p"),
            F.replace(col("s"), "a", "!!").alias("r"),
            F.substring_index(col("s"), "a", 1).alias("x")))


def test_substring_index_overlapping_occurrences():
    """UTF8String.subStringIndex advances by one byte per match, so
    occurrences overlap: substring_index('aaa','aa',2) = 'a'."""
    t = pa.table({"s": pa.array(["aaa", "aaaa", "aa"])})
    for enabled in ("true", "false"):
        from tests.compare import tpu_session
        s = tpu_session({"spark.rapids.sql.enabled": enabled,
                         "spark.rapids.sql.test.enabled": "false"})
        out = s.create_dataframe(t).select(
            F.substring_index(col("s"), "aa", 2).alias("l"),
            F.substring_index(col("s"), "aa", -2).alias("r")).to_arrow()
        # 'aaaa': finds at 0 then (overlap) 1 -> prefix 'a'; from the
        # right: 2 then 1 -> suffix 'a'
        assert out.column("l").to_pylist() == ["a", "a", "aa"], enabled
        assert out.column("r").to_pylist() == ["a", "a", "aa"], enabled


def test_regexp_replace_java_group_refs_cpu():
    """$0 is the whole match; $12 with one group = group 1 + literal 2
    (Java longest-valid-prefix parsing)."""
    from tests.compare import tpu_session
    t = pa.table({"s": pa.array(["a123b", "xy"])})
    s = tpu_session({"spark.rapids.sql.enabled": "false",
                     "spark.rapids.sql.test.enabled": "false"})
    out = s.create_dataframe(t).select(
        F.regexp_replace(col("s"), r"(\d+)", "[$0]").alias("whole"),
        F.regexp_replace(col("s"), r"(\d+)", "<$12>").alias("prefix"))
    got = out.to_arrow()
    assert got.column("whole").to_pylist() == ["a[123]b", "xy"]
    assert got.column("prefix").to_pylist() == ["a<1232>b", "xy"]


def test_nondeterministic_rejected_on_cpu_engine_too():
    from tests.compare import tpu_session
    import pyarrow as _pa
    s = tpu_session({"spark.rapids.sql.enabled": "false",
                     "spark.rapids.sql.test.enabled": "false"})
    df = s.create_dataframe(_pa.table({"k": _pa.array([1, 2])}))
    with pytest.raises(ValueError):
        df.order_by(F.rand(1)).to_arrow()


def test_regexp_replace_backslash_rep_falls_back_and_java_errors():
    from tests.compare import tpu_session
    t = pa.table({"s": pa.array(["abc"])})
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    df = s.create_dataframe(t).select(
        F.regexp_replace(col("s"), "abc", r"x\y").alias("r"))
    assert "cannot run on TPU" in df.explain()
    assert df.to_arrow().column("r").to_pylist() == ["xy"]
    # out-of-range group reference raises like Java
    bad = s.create_dataframe(t).select(
        F.regexp_replace(col("s"), "(a)", "$2").alias("r"))
    with pytest.raises(Exception):
        bad.to_arrow()


# ---------------------------------------------------------------------------
# gen_string_table fuzz: every device string kernel vs the CPU oracle
# ---------------------------------------------------------------------------

from fuzzer import gen_string_table  # noqa: E402


@pytest.mark.parametrize("seed", [3, 17])
def test_fuzz_contains_short_and_long_needles(seed):
    """Short needles keep the unrolled XLA compare; needles of
    PALLAS_PATTERN_MIN (24) bytes or more route to the Pallas contains
    kernel.  Both must match the oracle
    over the needle-planted fuzz column."""
    t = gen_string_table(seed, 600)
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(
            F.contains(col("s"), "qu").alias("a"),
            F.contains(col("s"), "%").alias("b"),
            F.contains(col("s"), "").alias("c"),
            F.contains(col("s"),
                       "the needle is surely long enough!").alias("d"),
            # 20 bytes: long, but still under the Pallas threshold
            F.contains(col("s"), "the needle is surely").alias("e")))


def test_fuzz_contains_pallas_kernel_selected():
    from spark_rapids_tpu.exprs import pallas_strings as ps
    needle = "the needle is surely long enough!"
    assert len(needle) >= ps.PALLAS_PATTERN_MIN
    t = gen_string_table(5, 200)
    s_tpu = __import__("tests.compare", fromlist=["tpu_session"])
    expr = F.contains(col("s"), needle)
    assert type(expr.expr).__name__ == "PallasContains"
    short = F.contains(col("s"), "qu")
    assert type(short.expr).__name__ == "Contains"
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(expr.alias("hit")))


@pytest.mark.parametrize("pattern", [
    "ick",            # unanchored literal (implicit .* both sides)
    "^qu",            # start anchor
    "9$",             # end anchor
    "^the .*enough!$", # anchors + wildcard run
    "q.ick",          # any1
    "z.+9",           # one-or-more
    r"\.",            # escaped metachar as literal
])
def test_fuzz_rlike_device_subset(pattern):
    t = gen_string_table(13, 600)
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(
            F.rlike(col("s"), pattern).alias("m")))


def test_rlike_real_regex_falls_back_to_cpu():
    t = gen_string_table(19, 200)
    from tests.compare import tpu_session
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    df = s.create_dataframe(t).select(
        F.rlike(col("s"), "[0-9]+|qu").alias("m"))
    assert "cannot run on TPU" in df.explain()
    import re
    pat = re.compile("[0-9]+|qu")
    got = df.to_arrow().column("m").to_pylist()
    want = [None if v is None else bool(pat.search(v))
            for v in t.column("s").to_pylist()]
    assert got == want


@pytest.mark.parametrize("delim,part", [
    (",", 1), (",", 2), (",", -1), (",", 5), ("|", 1), ("|", -2),
    ("::", 1), ("::", 2), ("::", -1),
])
def test_fuzz_split_part(delim, part):
    colname = {",": "c0", "|": "c1", "::": "c2"}[delim]
    t = gen_string_table(29, 600)
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(
            F.split_part(col(colname), delim, part).alias("p")))


def test_fuzz_split_part_wrong_delimiter():
    """Splitting on a delimiter the column does not use: part 1 is the
    whole string, part 2 is '' (Spark out-of-range semantics)."""
    t = gen_string_table(31, 300)
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t).select(
            F.split_part(col("c0"), "::", 1).alias("a"),
            F.split_part(col("c0"), "::", 2).alias("b")))


def test_fuzz_string_kernels_compose_in_one_stage():
    """The full device family composes in one projection (fusable into
    TpuStageExec) and an aggregate over a string predicate matches."""
    t = gen_string_table(37, 600)
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(t)
        .with_column("m", F.contains(col("s"), "ick"))
        .with_column("p", F.split_part(col("c0"), ",", 1))
        .with_column("u", F.substring(col("s"), 2, 5))
        .filter(F.rlike(col("s"), "^[^z]").expr.children[0].name
                is not None and col("s").is_not_null())
        .group_by("m").agg(F.sum(col("v")).alias("sv"),
                           F.count(col("p")).alias("np"))
        .sort("m"))


def test_rlike_dict_column_code_set_membership():
    """Over a dictionary-encoded column a regex-lite predicate runs
    ONCE per dictionary value — code-set membership — and matches the
    oracle."""
    import pyarrow.parquet as pq
    from spark_rapids_tpu.columnar import encoding
    t = gen_string_table(41, 800)
    import tempfile, os
    d = tempfile.mkdtemp()
    p = os.path.join(d, "t.parquet")
    pq.write_table(t, p)
    conf = {"spark.rapids.sql.compressed.enabled": "true",
            "spark.rapids.sql.scan.deviceCacheEnabled": "false"}
    before = encoding.compressed_stats()
    assert_tpu_and_cpu_equal(
        lambda s: s.read.parquet(p).select(
            F.rlike(col("d"), "^val_000.").alias("m"),
            col("v")),
        conf=conf)
    after = encoding.compressed_stats()
    assert after["encoded_columns"] > before["encoded_columns"], \
        "the dict column must ingest encoded for code-set membership"
