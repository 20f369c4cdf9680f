"""The TPC-H tables from a seed, written as parquet.

Follows the TPC-H specification v3: the eight tables with every column of
clause 1.4, the cardinalities of clause 4.2.5 (at SF1: supplier 10,000,
part 200,000, partsupp 800,000, customer 150,000, orders 1,500,000, one to
seven lineitems an order), and the column rules of clause 4.2.3: sparse
order keys, customers that never order, ``ps_suppkey``/``l_suppkey`` from
the part key, ``p_retailprice`` and ``l_extendedprice`` from the key and the
quantity, ``l_returnflag`` and ``l_linestatus`` from the dates,
``o_orderstatus`` and ``o_totalprice`` from the order's lines.

What is not dbgen's: the random stream (numpy's, from ``--seed``), and the
value text.  Comments and addresses are drawn from ``_POOL`` pseudo-texts a
column, built from the spec's word lists at the spec's lengths, not from
dbgen's grammar; ``s_comment`` carries no "Customer Complaints" marks.  The
number of lines an order has is a shuffle of 1..7 repeated, not a draw, so
that every seed makes the same number of lineitem rows (``table_rows``).
Money and quantities are DOUBLE, as the configuration states under
``assumed``.  Imports nothing of the engine.
"""

from __future__ import annotations

import datetime as dt
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

TABLES = ("region", "nation", "customer", "supplier", "part", "partsupp",
          "orders", "lineitem")

# clause 1.4: every column, in the spec's order, with its declared type
SCHEMA = {
    "region": (("r_regionkey", "identifier"), ("r_name", "char(25)"),
               ("r_comment", "varchar(152)")),
    "nation": (("n_nationkey", "identifier"), ("n_name", "char(25)"),
               ("n_regionkey", "identifier"), ("n_comment", "varchar(152)")),
    "customer": (("c_custkey", "identifier"), ("c_name", "varchar(25)"),
                 ("c_address", "varchar(40)"), ("c_nationkey", "identifier"),
                 ("c_phone", "char(15)"), ("c_acctbal", "decimal"),
                 ("c_mktsegment", "char(10)"), ("c_comment", "varchar(117)")),
    "supplier": (("s_suppkey", "identifier"), ("s_name", "char(25)"),
                 ("s_address", "varchar(40)"), ("s_nationkey", "identifier"),
                 ("s_phone", "char(15)"), ("s_acctbal", "decimal"),
                 ("s_comment", "varchar(101)")),
    "part": (("p_partkey", "identifier"), ("p_name", "varchar(55)"),
             ("p_mfgr", "char(25)"), ("p_brand", "char(10)"),
             ("p_type", "varchar(25)"), ("p_size", "integer"),
             ("p_container", "char(10)"), ("p_retailprice", "decimal"),
             ("p_comment", "varchar(23)")),
    "partsupp": (("ps_partkey", "identifier"), ("ps_suppkey", "identifier"),
                 ("ps_availqty", "integer"), ("ps_supplycost", "decimal"),
                 ("ps_comment", "varchar(199)")),
    "orders": (("o_orderkey", "identifier"), ("o_custkey", "identifier"),
               ("o_orderstatus", "char(1)"), ("o_totalprice", "decimal"),
               ("o_orderdate", "date"), ("o_orderpriority", "char(15)"),
               ("o_clerk", "char(15)"), ("o_shippriority", "integer"),
               ("o_comment", "varchar(79)")),
    "lineitem": (("l_orderkey", "identifier"), ("l_partkey", "identifier"),
                 ("l_suppkey", "identifier"), ("l_linenumber", "integer"),
                 ("l_quantity", "decimal"), ("l_extendedprice", "decimal"),
                 ("l_discount", "decimal"), ("l_tax", "decimal"),
                 ("l_returnflag", "char(1)"), ("l_linestatus", "char(1)"),
                 ("l_shipdate", "date"), ("l_commitdate", "date"),
                 ("l_receiptdate", "date"), ("l_shipinstruct", "char(25)"),
                 ("l_shipmode", "char(10)"), ("l_comment", "varchar(44)")),
}

# clause 4.2.3: text-string and v-string lengths, [min, max]
TEXT_LENGTHS = {
    "r_comment": (31, 115), "n_comment": (31, 114), "c_address": (10, 40),
    "c_comment": (29, 116), "s_address": (10, 40), "s_comment": (25, 100),
    "p_comment": (5, 22), "ps_comment": (49, 198), "o_comment": (19, 78),
    "l_comment": (10, 43),
}

START_DATE = dt.date(1992, 1, 1)
CURRENT_DATE = dt.date(1995, 6, 17)
END_DATE = dt.date(1998, 12, 31)
LINES_PER_ORDER = 7  # one to seven lineitems an order

_SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
_PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
_INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                 "TAKE BACK RETURN"]
_SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
_TYPES = [f"{a} {b} {c}"
          for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY",
                    "PROMO")
          for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
          for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
_CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
               for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                         "DRUM")]
_REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
_NATIONS = [("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
            ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
            ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
            ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
            ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
            ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
            ("UNITED KINGDOM", 3), ("UNITED STATES", 1)]
_COLORS = (
    "almond antique aquamarine azure beige bisque black blanched blue blush "
    "brown burlywood burnished chartreuse chiffon chocolate coral cornflower "
    "cornsilk cream cyan dark deep dim dodger drab firebrick floral forest "
    "frosted gainsboro ghost goldenrod green grey honeydew hot indian ivory "
    "khaki lace lavender lawn lemon light lime linen magenta maroon medium "
    "metallic midnight mint misty moccasin navajo navy olive orange orchid "
    "pale papaya peach peru pink plum powder puff purple red rose rosy royal "
    "saddle salmon sandy seashell sienna sky slate smoke snow spring steel "
    "tan thistle tomato turquoise violet wheat white yellow").split()
# clause 4.2.2.13: the words of the text grammar
_WORDS = (
    "foxes ideas theodolites pinto beans instructions dependencies excuses "
    "platelets asymptotes courts dolphins multipliers sauternes warthogs "
    "frets dinos attainments somas Tiresias' patterns forges braids "
    "hockey players frays warhorses dugouts notornis epitaphs pearls "
    "tithes waters orbits gifts sheaves depths sentiments decoys realms "
    "pains grouches escapades sleep wake are cajole haggle nag use boost "
    "affix detect integrate maintain nod was lose sublate solve thrash "
    "promise engage hinder print x-ray breach eat grow impress mold poach "
    "serve run dazzle snooze doze unwind kindle play hang believe doubt "
    "furious sly careful blithe quick fluffy slow quiet ruthless thin "
    "close dogged daring brave stealthy permanent enticing idle busy "
    "regular final ironic even bold silent sometimes always never "
    "furiously slyly carefully blithely quickly fluffily slowly quietly "
    "ruthlessly thinly closely doggedly daringly bravely stealthily "
    "permanently enticingly idly busily regularly finally ironically "
    "evenly boldly silently about above according to across after "
    "against along alongside of among around at atop before behind "
    "beneath beside besides between beyond by despite during except for "
    "from in place of inside instead of into near of on outside over "
    "past since through throughout to toward under until up upon "
    "without with within packages requests accounts deposits").split()
_POOL = 1 << 14  # distinct pseudo-texts a column


def table_rows(lineitem_rows: int) -> Dict[str, int]:
    """Rows of every table at a nominal ``lineitem_rows`` (6,000,000 is
    SF1): clause 4.2.5's ratios, and the lineitem rows that ``_line_counts``
    makes of them, the same for every seed."""
    n_orders = max(1, lineitem_rows // 4)
    n_part = max(1, lineitem_rows // 30)
    whole, rest = divmod(n_orders, LINES_PER_ORDER)
    lines = (whole * LINES_PER_ORDER * (LINES_PER_ORDER + 1) // 2
             + rest * (rest + 1) // 2)
    return {"region": 5, "nation": 25,
            "customer": max(1, n_orders // 10),
            "supplier": max(4, lineitem_rows // 600),
            "part": n_part, "partsupp": n_part * 4,
            "orders": n_orders, "lineitem": lines}


def _line_counts(rng, n_orders: int) -> np.ndarray:
    """Lineitems of each order: 1..7 repeated, in an order from the seed."""
    counts = np.arange(n_orders, dtype=np.int64) % LINES_PER_ORDER + 1
    rng.shuffle(counts)
    return counts


def _days(d: dt.date) -> int:
    return (d - dt.date(1970, 1, 1)).days


def _take(values, idx) -> pa.Array:
    """``[values[i] for i in idx]`` as a plain string column."""
    return pa.DictionaryArray.from_arrays(
        pa.array(np.asarray(idx, dtype=np.int32)),
        pa.array(values, pa.string())).cast(pa.string())


def _text(rng, column: str, n: int) -> pa.Array:
    """``n`` pseudo-texts of the column's lengths (uniform in [min, max]),
    drawn from a pool of ``_POOL`` made of the grammar's words."""
    lo, hi = TEXT_LENGTHS[column]
    words = np.array(_WORDS)
    run = " ".join(words[rng.integers(0, len(words), 4 * _POOL + hi)])
    starts = rng.integers(0, len(run) - hi, min(n, _POOL))
    lengths = rng.integers(lo, hi + 1, len(starts))
    pool = [run[s:s + k] for s, k in zip(starts.tolist(), lengths.tolist())]
    return _take(pool, rng.integers(0, len(pool), n))


def _numbered(prefix: str, keys: np.ndarray) -> pa.Array:
    return pa.array(np.char.add(prefix, np.char.zfill(keys.astype(str), 9)))


def _phone(rng, nationkey: np.ndarray) -> pa.Array:
    parts = [(nationkey + 10).astype(str)] + [
        rng.integers(lo, hi + 1, len(nationkey)).astype(str)
        for lo, hi in ((100, 999), (100, 999), (1000, 9999))]
    out = parts[0]
    for p in parts[1:]:
        out = np.char.add(np.char.add(out, "-"), p)
    return pa.array(out)


def _money(rng, lo_cents: int, hi_cents: int, n: int) -> pa.Array:
    return pa.array(rng.integers(lo_cents, hi_cents + 1, n) / 100.0)


def _supplier_of(partkey: np.ndarray, i: np.ndarray, s: int) -> np.ndarray:
    """Clause 4.2.3: the i-th supplier of a part."""
    return (partkey + i * (s // 4 + (partkey - 1) // s)) % s + 1


def retail_cents(partkey: np.ndarray) -> np.ndarray:
    """Clause 4.2.3: ``p_retailprice`` in cents."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def _dates(days: np.ndarray) -> pa.Array:
    return pa.array(days.astype(np.int32), pa.int32()).cast(pa.date32())


def generate(out_dir: str, lineitem_rows: int, seed: int) -> Dict[str, str]:
    """Write the eight tables under ``out_dir``; returns name -> path."""
    rng = np.random.default_rng(seed)
    os.makedirs(out_dir, exist_ok=True)
    rows = table_rows(lineitem_rows)
    n_orders, n_cust = rows["orders"], rows["customer"]
    n_supp, n_part = rows["supplier"], rows["part"]
    i64 = np.int64

    region = pa.table({
        "r_regionkey": pa.array(np.arange(5, dtype=i64)),
        "r_name": pa.array(_REGIONS),
        "r_comment": _text(rng, "r_comment", 5)})
    nation = pa.table({
        "n_nationkey": pa.array(np.arange(25, dtype=i64)),
        "n_name": pa.array([n for n, _ in _NATIONS]),
        "n_regionkey": pa.array(np.array([r for _, r in _NATIONS], i64)),
        "n_comment": _text(rng, "n_comment", 25)})

    custkey = np.arange(1, n_cust + 1, dtype=i64)
    c_nation = rng.integers(0, 25, n_cust).astype(i64)
    customer = pa.table({
        "c_custkey": pa.array(custkey),
        "c_name": _numbered("Customer#", custkey),
        "c_address": _text(rng, "c_address", n_cust),
        "c_nationkey": pa.array(c_nation),
        "c_phone": _phone(rng, c_nation),
        "c_acctbal": _money(rng, -99999, 999999, n_cust),
        "c_mktsegment": _take(_SEGMENTS, rng.integers(0, 5, n_cust)),
        "c_comment": _text(rng, "c_comment", n_cust)})

    suppkey = np.arange(1, n_supp + 1, dtype=i64)
    s_nation = rng.integers(0, 25, n_supp).astype(i64)
    supplier = pa.table({
        "s_suppkey": pa.array(suppkey),
        "s_name": _numbered("Supplier#", suppkey),
        "s_address": _text(rng, "s_address", n_supp),
        "s_nationkey": pa.array(s_nation),
        "s_phone": _phone(rng, s_nation),
        "s_acctbal": _money(rng, -99999, 999999, n_supp),
        "s_comment": _text(rng, "s_comment", n_supp)})

    partkey = np.arange(1, n_part + 1, dtype=i64)
    colors = np.array(_COLORS)
    five = np.argpartition(rng.random((n_part, len(colors)), np.float32),
                           5, axis=1)[:, :5]  # five different words each
    p_name = colors[five[:, 0]]
    for k in range(1, 5):
        p_name = np.char.add(np.char.add(p_name, " "), colors[five[:, k]])
    mfgr = rng.integers(1, 6, n_part)
    brand = np.char.add(np.char.add("Brand#", mfgr.astype(str)),
                        rng.integers(1, 6, n_part).astype(str))
    part = pa.table({
        "p_partkey": pa.array(partkey),
        "p_name": pa.array(p_name),
        "p_mfgr": _take([f"Manufacturer#{k}" for k in range(1, 6)],
                        mfgr - 1),
        "p_brand": pa.array(brand),
        "p_type": _take(_TYPES, rng.integers(0, len(_TYPES), n_part)),
        "p_size": pa.array(rng.integers(1, 51, n_part).astype(i64)),
        "p_container": _take(_CONTAINERS,
                             rng.integers(0, len(_CONTAINERS), n_part)),
        "p_retailprice": pa.array(retail_cents(partkey) / 100.0),
        "p_comment": _text(rng, "p_comment", n_part)})

    ps_part = np.repeat(partkey, 4)
    n_ps = len(ps_part)
    partsupp = pa.table({
        "ps_partkey": pa.array(ps_part),
        "ps_suppkey": pa.array(_supplier_of(
            ps_part, np.tile(np.arange(4, dtype=i64), n_part), n_supp)),
        "ps_availqty": pa.array(rng.integers(1, 10_000, n_ps).astype(i64)),
        "ps_supplycost": _money(rng, 100, 100000, n_ps),
        "ps_comment": _text(rng, "ps_comment", n_ps)})

    # lineitem first: an order's status and total come from its lines
    index = np.arange(n_orders, dtype=i64)
    orderkey = index // 8 * 32 + index % 8 + 1  # 8 of every 32 keys used
    odate = rng.integers(_days(START_DATE), _days(END_DATE) - 151 + 1,
                         n_orders)
    counts = _line_counts(rng, n_orders)
    n = int(counts.sum())
    assert n == rows["lineitem"]
    of = np.repeat(index, counts)  # the order of every line
    first = np.cumsum(counts) - counts
    l_part = rng.integers(1, n_part + 1, n).astype(i64)
    qty = rng.integers(1, 51, n)
    ext_cents = qty * retail_cents(partkey)[l_part - 1]
    disc, tax = rng.integers(0, 11, n), rng.integers(0, 9, n)
    ship = odate[of] + rng.integers(1, 122, n)
    commit = odate[of] + rng.integers(30, 91, n)
    receipt = ship + rng.integers(1, 31, n)
    today = _days(CURRENT_DATE)
    returned = receipt <= today
    flag = np.where(returned, rng.integers(0, 2, n), 2)  # R or A, else N
    open_ = ship > today
    lineitem = pa.table({
        "l_orderkey": pa.array(orderkey[of]),
        "l_partkey": pa.array(l_part),
        "l_suppkey": pa.array(_supplier_of(
            l_part, rng.integers(0, 4, n).astype(i64), n_supp)),
        "l_linenumber": pa.array(np.arange(n, dtype=i64) - first[of] + 1),
        "l_quantity": pa.array(qty.astype(np.float64)),
        "l_extendedprice": pa.array(ext_cents / 100.0),
        "l_discount": pa.array(disc / 100.0),
        "l_tax": pa.array(tax / 100.0),
        "l_returnflag": _take(["R", "A", "N"], flag),
        "l_linestatus": _take(["F", "O"], open_),
        "l_shipdate": _dates(ship),
        "l_commitdate": _dates(commit),
        "l_receiptdate": _dates(receipt),
        "l_shipinstruct": _take(_INSTRUCTIONS,
                                rng.integers(0, len(_INSTRUCTIONS), n)),
        "l_shipmode": _take(_SHIPMODES, rng.integers(0, len(_SHIPMODES), n)),
        "l_comment": _text(rng, "l_comment", n)})

    open_lines = np.add.reduceat(open_.astype(i64), first)
    status = np.where(open_lines == 0, 0, np.where(open_lines == counts,
                                                   1, 2))
    total = np.add.reduceat(
        ext_cents * (100 + tax) * (100 - disc) / 1e6, first)
    cust = rng.integers(0, n_cust - n_cust // 3, n_orders)
    n_clerk = max(1, lineitem_rows // 6000)  # SF x 1,000 clerks
    orders = pa.table({
        "o_orderkey": pa.array(orderkey),
        # a third of the customers (keys divisible by three) never order
        "o_custkey": pa.array((cust // 2 * 3 + cust % 2 + 1).astype(i64)),
        "o_orderstatus": _take(["F", "O", "P"], status),
        "o_totalprice": pa.array(np.round(total, 2)),
        "o_orderdate": _dates(odate),
        "o_orderpriority": _take(_PRIORITIES, rng.integers(0, 5, n_orders)),
        "o_clerk": _take(
            _numbered("Clerk#", np.arange(1, n_clerk + 1)).to_pylist(),
            rng.integers(0, n_clerk, n_orders)),
        "o_shippriority": pa.array(np.zeros(n_orders, dtype=i64)),
        "o_comment": _text(rng, "o_comment", n_orders)})

    tables = dict(zip(TABLES, (region, nation, customer, supplier, part,
                               partsupp, orders, lineitem)))
    paths = {name: os.path.join(out_dir, f"{name}.parquet")
             for name in TABLES}
    for name, table in tables.items():
        assert table.column_names == [c for c, _ in SCHEMA[name]], name
    with ThreadPoolExecutor(4) as pool:  # the largest first
        list(pool.map(lambda name: pq.write_table(
            tables[name], paths[name], row_group_size=1 << 16),
            reversed(TABLES)))
    return paths
