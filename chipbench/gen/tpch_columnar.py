"""TPC-H's own schema and its 22 query templates (TPC-H Standard
Specification, rev. 3.0.1) at a stated scale factor, stored column by
column: the deployment of an analytics column store.

Items (clause 1.4's 8 tables and 61 columns, in the spec's order):

* each column of a month-partitioned table (LINEITEM by L_SHIPDATE, ORDERS
  by O_ORDERDATE) is one item per calendar month its partitioning date
  takes (clause 4.2.3: O_ORDERDATE uniform over 1992-01-01 .. 1998-08-02,
  L_SHIPDATE = O_ORDERDATE + 1..121 days: 84 and 80 months);
* every other column is cut into key-range chunks: the smallest power of
  two that keeps each chunk at or under ``chunk_max_share`` of a partition.

An item's size is its rows (clause 4.2.5's cardinalities x SF, split over
the months by the dates' distributions) times its declared bytes.  The
capacity makes the data fill ``target_min_partitions`` at ``fill``.

Queries: one instance of a template (uniform over Q1-Q22, clause 2.4)
reads every chunk of each column it references, and only the months that
its literal range on the partitioning date allows; no other predicate
prunes.  Only the date parameters change a template's access set, so only
they are drawn.  Every draw comes from the stream generators of
``chipbench.gen``; this module imports nothing of the program."""

from __future__ import annotations

import datetime

import numpy as np

from . import stream_rng

# clause 1.4: table -> [(column, declared type)], the spec's order.  A type
# is "identifier", "integer", "decimal", "date", ("char", n) or "varchar"
# (whose bytes are the configuration's generated mean for that column)
SCHEMA = {
    "PART": [("P_PARTKEY", "identifier"), ("P_NAME", "varchar"),
             ("P_MFGR", ("char", 25)), ("P_BRAND", ("char", 10)),
             ("P_TYPE", "varchar"), ("P_SIZE", "integer"),
             ("P_CONTAINER", ("char", 10)), ("P_RETAILPRICE", "decimal"),
             ("P_COMMENT", "varchar")],
    "SUPPLIER": [("S_SUPPKEY", "identifier"), ("S_NAME", ("char", 25)),
                 ("S_ADDRESS", "varchar"), ("S_NATIONKEY", "identifier"),
                 ("S_PHONE", ("char", 15)), ("S_ACCTBAL", "decimal"),
                 ("S_COMMENT", "varchar")],
    "PARTSUPP": [("PS_PARTKEY", "identifier"), ("PS_SUPPKEY", "identifier"),
                 ("PS_AVAILQTY", "integer"), ("PS_SUPPLYCOST", "decimal"),
                 ("PS_COMMENT", "varchar")],
    "CUSTOMER": [("C_CUSTKEY", "identifier"), ("C_NAME", "varchar"),
                 ("C_ADDRESS", "varchar"), ("C_NATIONKEY", "identifier"),
                 ("C_PHONE", ("char", 15)), ("C_ACCTBAL", "decimal"),
                 ("C_MKTSEGMENT", ("char", 10)), ("C_COMMENT", "varchar")],
    "ORDERS": [("O_ORDERKEY", "identifier"), ("O_CUSTKEY", "identifier"),
               ("O_ORDERSTATUS", ("char", 1)), ("O_TOTALPRICE", "decimal"),
               ("O_ORDERDATE", "date"), ("O_ORDERPRIORITY", ("char", 15)),
               ("O_CLERK", ("char", 15)), ("O_SHIPPRIORITY", "integer"),
               ("O_COMMENT", "varchar")],
    "LINEITEM": [("L_ORDERKEY", "identifier"), ("L_PARTKEY", "identifier"),
                 ("L_SUPPKEY", "identifier"), ("L_LINENUMBER", "integer"),
                 ("L_QUANTITY", "decimal"), ("L_EXTENDEDPRICE", "decimal"),
                 ("L_DISCOUNT", "decimal"), ("L_TAX", "decimal"),
                 ("L_RETURNFLAG", ("char", 1)), ("L_LINESTATUS", ("char", 1)),
                 ("L_SHIPDATE", "date"), ("L_COMMITDATE", "date"),
                 ("L_RECEIPTDATE", "date"), ("L_SHIPINSTRUCT", ("char", 25)),
                 ("L_SHIPMODE", ("char", 10)), ("L_COMMENT", "varchar")],
    "NATION": [("N_NATIONKEY", "identifier"), ("N_NAME", ("char", 25)),
               ("N_REGIONKEY", "identifier"), ("N_COMMENT", "varchar")],
    "REGION": [("R_REGIONKEY", "identifier"), ("R_NAME", ("char", 25)),
               ("R_COMMENT", "varchar")],
}

_PREFIX = {"PS": "PARTSUPP", "P": "PART", "S": "SUPPLIER", "C": "CUSTOMER",
           "O": "ORDERS", "L": "LINEITEM", "N": "NATION", "R": "REGION"}


def table_of(column: str) -> str:
    return _PREFIX[column.split("_")[0]]


def _cols(text: str) -> tuple:
    return tuple(c.strip().upper() for c in text.split(","))


def _d(text: str) -> int:
    return datetime.date.fromisoformat(text).toordinal()


def _month_start(m: int) -> int:
    """Day ordinal of the first day of month ``m`` counted from 1992-01."""
    return datetime.date(1992 + m // 12, 1 + m % 12, 1).toordinal()


# clause 2.4: each template's columns, and its literal ranges on the
# partitioning dates.  ``dates(k)`` gives, for the k-th of ``choices``
# values of the template's date parameter, {table: (lo, hi)}: the
# half-open day range [lo, hi) the partitioning date of that table may take
# (None: unbounded).  A template with no such range reads every month.
def _months_from(first: int, span: int):
    """Parameter k = the first day of month ``first + k`` (1992-01 = 0);
    the range runs ``span`` months from it."""
    return lambda k: (_month_start(first + k), _month_start(first + k + span))


def _q1(k):   # l_shipdate <= 1998-12-01 - DELTA days, DELTA in 60..120
    return {"LINEITEM": (None, _d("1998-12-01") - (60 + k) + 1)}


def _q3(k):   # o_orderdate < DATE and l_shipdate > DATE, DATE in 1995-03
    day = _d("1995-03-01") + k
    return {"ORDERS": (None, day), "LINEITEM": (day + 1, None)}


def _years(table):   # DATE = January 1 of 1993..1997, a range of one year
    return lambda k: {table: (_month_start(12 * (1 + k)),
                              _month_start(12 * (2 + k)))}


def _in(table, f):
    return lambda k: {table: f(k)}


_FIXED_95_96 = (_d("1995-01-01"), _d("1997-01-01"))

TEMPLATES = {
    "Q1": (_cols("l_returnflag, l_linestatus, l_quantity, l_extendedprice, "
                 "l_discount, l_tax, l_shipdate"), 61, _q1),
    "Q2": (_cols("p_partkey, p_mfgr, p_size, p_type, s_acctbal, s_name, "
                 "s_address, s_phone, s_comment, s_suppkey, s_nationkey, "
                 "ps_partkey, ps_suppkey, ps_supplycost, n_name, n_nationkey, "
                 "n_regionkey, r_regionkey, r_name"), 1, None),
    "Q3": (_cols("c_mktsegment, c_custkey, o_custkey, o_orderkey, "
                 "o_orderdate, o_shippriority, l_orderkey, l_extendedprice, "
                 "l_discount, l_shipdate"), 31, _q3),
    "Q4": (_cols("o_orderpriority, o_orderdate, o_orderkey, l_orderkey, "
                 "l_commitdate, l_receiptdate"), 58,
           _in("ORDERS", _months_from(12, 3))),
    "Q5": (_cols("c_custkey, c_nationkey, o_custkey, o_orderkey, "
                 "o_orderdate, l_orderkey, l_suppkey, l_extendedprice, "
                 "l_discount, s_suppkey, s_nationkey, n_name, n_nationkey, "
                 "n_regionkey, r_regionkey, r_name"), 5, _years("ORDERS")),
    "Q6": (_cols("l_extendedprice, l_discount, l_shipdate, l_quantity"), 5,
           _years("LINEITEM")),
    "Q7": (_cols("s_suppkey, s_nationkey, l_shipdate, l_extendedprice, "
                 "l_discount, l_suppkey, l_orderkey, o_orderkey, o_custkey, "
                 "c_custkey, c_nationkey, n_name, n_nationkey"), 1,
           lambda k: {"LINEITEM": _FIXED_95_96}),
    "Q8": (_cols("p_partkey, p_type, s_suppkey, s_nationkey, l_partkey, "
                 "l_suppkey, l_orderkey, l_extendedprice, l_discount, "
                 "o_orderkey, o_custkey, o_orderdate, c_custkey, "
                 "c_nationkey, n_nationkey, n_regionkey, n_name, "
                 "r_regionkey, r_name"), 1,
           lambda k: {"ORDERS": _FIXED_95_96}),
    "Q9": (_cols("p_partkey, p_name, s_suppkey, s_nationkey, l_suppkey, "
                 "l_partkey, l_orderkey, l_extendedprice, l_discount, "
                 "l_quantity, ps_suppkey, ps_partkey, ps_supplycost, "
                 "o_orderkey, o_orderdate, n_nationkey, n_name"), 1, None),
    "Q10": (_cols("c_custkey, c_name, c_acctbal, c_phone, c_address, "
                  "c_comment, c_nationkey, o_custkey, o_orderkey, "
                  "o_orderdate, l_orderkey, l_returnflag, l_extendedprice, "
                  "l_discount, n_nationkey, n_name"), 24,
            _in("ORDERS", _months_from(13, 3))),
    "Q11": (_cols("ps_partkey, ps_supplycost, ps_availqty, ps_suppkey, "
                  "s_suppkey, s_nationkey, n_nationkey, n_name"), 1, None),
    # Q12's literal range is on l_receiptdate, which partitions nothing
    "Q12": (_cols("o_orderkey, o_orderpriority, l_orderkey, l_shipmode, "
                  "l_commitdate, l_receiptdate, l_shipdate"), 1, None),
    "Q13": (_cols("c_custkey, o_custkey, o_comment, o_orderkey"), 1, None),
    "Q14": (_cols("l_extendedprice, l_discount, l_partkey, l_shipdate, "
                  "p_partkey, p_type"), 60,
            _in("LINEITEM", _months_from(12, 1))),
    "Q15": (_cols("l_suppkey, l_extendedprice, l_discount, l_shipdate, "
                  "s_suppkey, s_name, s_address, s_phone"), 58,
            _in("LINEITEM", _months_from(12, 3))),
    "Q16": (_cols("ps_partkey, ps_suppkey, p_partkey, p_brand, p_type, "
                  "p_size, s_suppkey, s_comment"), 1, None),
    "Q17": (_cols("l_extendedprice, l_partkey, l_quantity, p_partkey, "
                  "p_brand, p_container"), 1, None),
    "Q18": (_cols("c_name, c_custkey, o_orderkey, o_orderdate, "
                  "o_totalprice, o_custkey, l_orderkey, l_quantity"), 1, None),
    "Q19": (_cols("l_extendedprice, l_discount, l_partkey, l_quantity, "
                  "l_shipmode, l_shipinstruct, p_partkey, p_brand, "
                  "p_container, p_size"), 1, None),
    "Q20": (_cols("s_name, s_address, s_suppkey, s_nationkey, n_nationkey, "
                  "n_name, ps_suppkey, ps_partkey, ps_availqty, p_partkey, "
                  "p_name, l_quantity, l_partkey, l_suppkey, l_shipdate"), 5,
            _years("LINEITEM")),
    "Q21": (_cols("s_name, s_suppkey, s_nationkey, l_suppkey, l_orderkey, "
                  "l_receiptdate, l_commitdate, o_orderkey, o_orderstatus, "
                  "n_nationkey, n_name"), 1, None),
    "Q22": (_cols("c_phone, c_acctbal, c_custkey, o_custkey"), 1, None),
}


def column_bytes(cfg: dict, column: str, kind) -> float:
    """Bytes of one value of ``column`` as declared (clause 1.4)."""
    if kind == "varchar":
        return float(cfg["varchar_mean_bytes"][column])
    if isinstance(kind, tuple):
        return float(kind[1])
    return float(cfg["type_bytes"][kind])


def month_rows(cfg: dict, table: str) -> np.ndarray:
    """Rows of a month-partitioned table per month of its partitioning
    date (clause 4.2.3): orders uniform over the order dates, line items
    shipped 1..121 days after their order, uniformly."""
    d0 = _d(cfg["orderdate_first"])
    nd = _d(cfg["orderdate_last"]) - d0 + 1
    per_day = np.full(nd, 1.0 / nd)
    if table == "LINEITEM":
        lo, hi = cfg["ship_lag_days"]
        lag = np.zeros(hi + 1)
        lag[lo:] = 1.0 / (hi - lo + 1)
        per_day = np.convolve(per_day, lag)   # day 0 = d0
    assert _month_start(0) <= d0 < _month_start(1), "months count from 1992-01"
    last = d0 + len(per_day) - 1
    months = []
    m = 0
    while _month_start(m) <= last:
        a = max(_month_start(m), d0) - d0
        b = min(_month_start(m + 1), last + 1) - d0
        months.append(per_day[a:b].sum())
        m += 1
    return np.asarray(months) * table_rows(cfg, table)


def table_rows(cfg: dict, table: str) -> float:
    if table in cfg["fixed_rows"]:
        return float(cfg["fixed_rows"][table])
    return float(cfg["rows_per_sf"][table]) * float(cfg["scale_factor"])


def capacity(cfg: dict) -> float:
    """Partition capacity in GB: the data fills ``target_min_partitions``
    partitions at ``fill``."""
    total = sum(table_rows(cfg, t) * column_bytes(cfg, c, k)
                for t, cols in SCHEMA.items() for c, k in cols) / 1e9
    return total / (float(cfg["fill"]) * float(cfg["target_min_partitions"]))


def layout(cfg: dict) -> dict:
    """The items: ``sizes`` (GB, by item id) and ``first``, column -> (first
    item id, number of items); a month-partitioned column's items are its
    months in order, any other column's its key-range chunks."""
    chunk_cap = capacity(cfg) * float(cfg["chunk_max_share"])
    sizes: list[float] = []
    first: dict[str, tuple[int, int]] = {}
    for table, cols in SCHEMA.items():
        monthly = (month_rows(cfg, table) if table in cfg["month_partitioned"]
                   else None)
        for col, kind in cols:
            b = column_bytes(cfg, col, kind) / 1e9
            if monthly is not None:
                parts = list(monthly * b)
            else:
                whole = table_rows(cfg, table) * b
                k = 1
                while whole / k > chunk_cap:
                    k *= 2
                parts = [whole / k] * k
            first[col] = (len(sizes), len(parts))
            sizes.extend(parts)
    return {"sizes": np.asarray(sizes, dtype=np.float64), "first": first}


def node_weights(cfg: dict, seed: int) -> np.ndarray:
    """Item sizes in GB; the data is the deployment's and does not change
    with the run's seed (the traces do)."""
    del seed
    return layout(cfg)["sizes"]


def _access_set(cfg: dict, lay: dict, columns, ranges) -> np.ndarray:
    """Sorted item ids one instance reads: every chunk of each column, and
    the months of a month-partitioned column inside its table's range."""
    parts = []
    for col in columns:
        start, count = lay["first"][col]
        lo, hi = 0, count            # months [lo, hi) of 1992-01 = 0
        a, b = ranges.get(table_of(col), (None, None))
        if table_of(col) in cfg["month_partitioned"]:
            while a is not None and lo < hi and _month_start(lo + 1) <= a:
                lo += 1
            while b is not None and hi > lo and _month_start(hi - 1) >= b:
                hi -= 1
        parts.append(np.arange(start + lo, start + hi, dtype=np.int64))
    q = np.sort(np.concatenate(parts))
    q.setflags(write=False)
    return q


def access_sets(cfg: dict) -> dict:
    """Every access set a template instance can read: (template, k) -> the
    sorted item ids, for each of the template's ``choices`` parameters."""
    lay = layout(cfg)
    out = {}
    for name, (columns, choices, dates) in TEMPLATES.items():
        for k in range(choices):
            out[name, k] = _access_set(cfg, lay, columns,
                                       dates(k) if dates else {})
    return out


def query_lists(cfg: dict, seed: int, stream: int, count: int):
    """``count`` template instances of stream ``stream`` as sorted item
    arrays (shared, read-only: one array per access set)."""
    sets = access_sets(cfg)
    names = list(TEMPLATES)
    rng = stream_rng(seed, stream)
    t = rng.integers(0, len(names), size=int(count))
    u = rng.random(int(count))
    return [sets[names[i], int(x * TEMPLATES[names[i]][1])]
            for i, x in zip(t.tolist(), u.tolist())]
