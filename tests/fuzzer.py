"""Seeded random data generation for compare tests.

Reference: FuzzerUtils.scala:33-300 (random schema/batch generation with
EnhancedRandom) and integration_tests data_gen.py (typed generators with
edge-case special values).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np
import pyarrow as pa


_INT_SPECIALS = {
    pa.int8(): [0, 1, -1, 127, -128],
    pa.int16(): [0, 1, -1, 32767, -32768],
    pa.int32(): [0, 1, -1, 2 ** 31 - 1, -2 ** 31],
    pa.int64(): [0, 1, -1, 2 ** 63 - 1, -2 ** 63],
}

_FLOAT_SPECIALS = [0.0, -0.0, 1.0, -1.0, float("nan"), float("inf"),
                   float("-inf"), 1e-300, 1e300]


def gen_column(rng: np.random.Generator, dtype: pa.DataType, n: int,
               null_prob: float = 0.1,
               special_prob: float = 0.15) -> pa.Array:
    """One random column with nulls and edge-case special values."""
    nulls = rng.random(n) < null_prob
    if pa.types.is_integer(dtype):
        lo, hi = (-100, 100)
        vals = rng.integers(lo, hi, n).astype(object)
        specials = _INT_SPECIALS[dtype]
        for i in np.nonzero(rng.random(n) < special_prob)[0]:
            vals[i] = specials[rng.integers(0, len(specials))]
    elif pa.types.is_floating(dtype):
        vals = (rng.standard_normal(n) * 100).astype(object)
        for i in np.nonzero(rng.random(n) < special_prob)[0]:
            vals[i] = _FLOAT_SPECIALS[rng.integers(0, len(_FLOAT_SPECIALS))]
    elif pa.types.is_boolean(dtype):
        vals = (rng.random(n) < 0.5).astype(object)
    elif pa.types.is_string(dtype):
        alphabet = list("abcXYZ019 _%")
        vals = np.empty(n, dtype=object)
        for i in range(n):
            ln = int(rng.integers(0, 12))
            vals[i] = "".join(rng.choice(alphabet, ln))
    elif pa.types.is_date32(dtype):
        vals = rng.integers(-30000, 30000, n).astype(object)
        return pa.array(
            [None if m else int(v) for v, m in zip(vals, nulls)],
            pa.int32()).cast(pa.date32())
    elif pa.types.is_timestamp(dtype):
        vals = rng.integers(-2 ** 40, 2 ** 40, n).astype(object)
        return pa.array(
            [None if m else int(v) for v, m in zip(vals, nulls)],
            pa.int64()).cast(pa.timestamp("us", tz="UTC"))
    else:
        raise TypeError(f"no generator for {dtype}")
    return pa.array([None if m else v for v, m in zip(vals, nulls)], dtype)


def gen_table(seed: int, spec: Sequence[tuple], n: int,
              null_prob: float = 0.1) -> pa.Table:
    """spec: [(name, pa.DataType)] -> table of n rows."""
    rng = np.random.default_rng(seed)
    return pa.table({name: gen_column(rng, dt, n, null_prob)
                     for name, dt in spec})


def gen_skewed_keys(rng: np.random.Generator, n: int, n_keys: int = 32,
                    zipf_a: float = 1.2) -> np.ndarray:
    """Zipf-distributed key ranks over a bounded domain: key r (0-based
    rank) drawn with probability proportional to 1/(r+1)^a, so rank 0
    dominates — the hot-key shape that serializes one hash partition
    while the rest idle.  Deterministic for a given generator state."""
    ranks = np.arange(1, n_keys + 1, dtype=np.float64)
    pmf = ranks ** -float(zipf_a)
    pmf /= pmf.sum()
    return rng.choice(n_keys, size=n, p=pmf).astype(np.int64)


def gen_skewed_table(seed: int, n: int, n_keys: int = 32,
                     zipf_a: float = 1.2) -> pa.Table:
    """Seeded skewed-join fixture: a zipf-skewed int64 key column ``k``
    plus float64/int32 payloads (reference: the AQE skew suites'
    RepeatSeqGen-with-hot-key data).  Same seed -> same table,
    byte-for-byte, so skew regression baselines replay exactly."""
    rng = np.random.default_rng(seed)
    keys = gen_skewed_keys(rng, n, n_keys, zipf_a)
    return pa.table({
        "k": pa.array(keys, pa.int64()),
        "v": pa.array(rng.standard_normal(n), pa.float64()),
        "w": pa.array(rng.integers(-1000, 1000, n, dtype=np.int32),
                      pa.int32()),
    })


def gen_dict_column(rng: np.random.Generator, n: int,
                    cardinality: int = 8, null_prob: float = 0.1,
                    run_length: int = 1) -> pa.Array:
    """Dictionary-shaped string column for the compressed-domain tests
    (docs/compressed.md): ``cardinality`` distinct values drawn over
    ``n`` rows.  ``run_length > 1`` repeats each draw that many times —
    the long-run RLE shape parquet dictionary+RLE pages compress best
    (and the shape the encoded ingest must win on).  Low cardinality =
    dictionary-heavy; cardinality near ``n`` = the `plain` passthrough
    edge where the encoder must decline."""
    values = [f"val_{i:04d}_{'x' * int(rng.integers(0, 12))}"
              for i in range(cardinality)]
    if run_length > 1:
        n_runs = -(-n // run_length)
        draws = rng.integers(0, cardinality, n_runs)
        idx = np.repeat(draws, run_length)[:n]
    else:
        idx = rng.integers(0, cardinality, n)
    nulls = rng.random(n) < null_prob
    return pa.array([None if m else values[i]
                     for i, m in zip(idx, nulls)], pa.string())


def gen_dict_table(seed: int, n: int, cardinality: int = 8,
                   null_prob: float = 0.1,
                   run_length: int = 1) -> pa.Table:
    """Seeded dictionary-heavy fixture: a dict-shaped string key ``k``
    (optionally long-run RLE), a second independent dict column ``g``,
    and int/float payloads — the fuzz shape the compressed-domain
    kernels (code filters, group-by over codes, encoded egress) are
    compared against the CPU oracle on."""
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": gen_dict_column(rng, n, cardinality, null_prob,
                             run_length),
        "g": gen_dict_column(rng, n, max(2, cardinality // 2),
                             null_prob),
        "v": pa.array(rng.integers(-1000, 1000, n), pa.int64()),
        "f": pa.array(rng.standard_normal(n), pa.float64()),
    })


def gen_join_tables(seed: int, n_left: int, n_right: int,
                    key_type=None) -> tuple:
    """Two tables sharing a key column with repeated values (reference
    RepeatSeqGen for join keys)."""
    key_type = key_type or pa.int64()
    rng = np.random.default_rng(seed)
    key_pool = list(range(20))
    lk = [None if rng.random() < 0.05 else
          int(rng.choice(key_pool)) for _ in range(n_left)]
    rk = [None if rng.random() < 0.05 else
          int(rng.choice(key_pool)) for _ in range(n_right)]
    left = pa.table({
        "k": pa.array(lk, key_type),
        "lv": gen_column(rng, pa.float64(), n_left),
    })
    right = pa.table({
        "k": pa.array(rk, key_type),
        "rv": gen_column(rng, pa.int32(), n_right),
    })
    return left, right


_NEEDLES = ["qu", "ick", "%", "_", "",
            "the needle is surely long enough!",  # >= PALLAS_PATTERN_MIN
            "zz9"]
_DELIMS = [",", "|", "::"]


def gen_string_column(rng: np.random.Generator, n: int,
                      null_prob: float = 0.08,
                      needle_prob: float = 0.35) -> pa.Array:
    """Free-form strings exercising the device string kernels: random
    alphabet runs with planted needles (short and >=24-byte, so both
    the unrolled-XLA and the Pallas contains paths fire), empty
    strings, and LIKE metacharacters as literal content."""
    alphabet = list("abcdefgh XYZ019._%")
    vals = np.empty(n, dtype=object)
    for i in range(n):
        ln = int(rng.integers(0, 16))
        s = "".join(rng.choice(alphabet, ln))
        if rng.random() < needle_prob:
            needle = _NEEDLES[int(rng.integers(0, len(_NEEDLES)))]
            cut = int(rng.integers(0, len(s) + 1))
            s = s[:cut] + needle + s[cut:]
        vals[i] = s
    nulls = rng.random(n) < null_prob
    return pa.array([None if m else v for v, m in zip(vals, nulls)],
                    pa.string())


def gen_delimited_column(rng: np.random.Generator, n: int,
                         delim: str = ",",
                         null_prob: float = 0.08) -> pa.Array:
    """Delimiter-joined field lists for split_part: 0..5 fields per
    row (0 fields = empty string, the out-of-range edge), fields may
    be empty, and some rows carry the delimiter of ANOTHER generator
    as literal content."""
    fields = ["", "a", "bb", "x9", "%f", "long_field_value"]
    vals = np.empty(n, dtype=object)
    for i in range(n):
        k = int(rng.integers(0, 6))
        vals[i] = delim.join(
            fields[int(rng.integers(0, len(fields)))] for _ in range(k))
    nulls = rng.random(n) < null_prob
    return pa.array([None if m else v for v, m in zip(vals, nulls)],
                    pa.string())


def gen_string_table(seed: int, n: int,
                     null_prob: float = 0.08) -> pa.Table:
    """Seeded string-kernel fixture (docs/compressed.md string
    coverage): free-form needle-planted ``s``, a dict-shaped low-
    cardinality ``d`` (so regex-lite predicates can run as dictionary
    code-set membership), one delimited column per delimiter class,
    and an int payload for aggregates over string predicates."""
    rng = np.random.default_rng(seed)
    cols = {
        "s": gen_string_column(rng, n, null_prob),
        "d": gen_dict_column(rng, n, cardinality=9,
                             null_prob=null_prob),
    }
    for j, delim in enumerate(_DELIMS):
        cols[f"c{j}"] = gen_delimited_column(rng, n, delim, null_prob)
    cols["v"] = pa.array(rng.integers(-1000, 1000, n), pa.int64())
    return pa.table(cols)
