"""TPC-H-shaped tables for Q1 and Q5, made from a seed, vectorised.

A copy of `bodo_tpu/workloads/tpch.py:gen_tpch` cut to the columns that
the cells' queries and references read (the original builds names,
phones, clerks and comments in per-row Python loops). Independent of the
program. Not dbgen: uniform foreign keys, 1-7 lines an order, dense keys
from 0. Tables keep dbgen's physical order (each sorted by its key,
lineitem by l_orderkey).

What decides a program's work is drawn from the configuration's
`structure_seed`; `--seed` draws what decides the answer's values:

  structure (same for every seed):  every key and foreign key, each
      order's date and number of lines, each line's ship date, each
      customer's and supplier's nation
  seed:  the measures (l_quantity, l_extendedprice, l_discount, l_tax)
      and the flags (l_returnflag, l_linestatus)

So every table has the same row count for every seed (lineitem's is fixed
by construction: lines per order are 1 + (a permutation of 0..n-1) mod 7),
every filter and join of Q1 and Q5 gives the same number of rows, and the
joins see the same keys. The keys matter: with the same cardinalities but
keys renamed by the seed, one Q5 took 11.7 to 12.8 s by seed and repeated
to 0.03% on the same seed (my chip run, PR 26; PERF.md, section 6), so a
renaming would put the seed's draw into every comparison of two runs.
"""

import numpy as np
import pandas as pd

NATIONS = ["ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
           "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ",
           "JAPAN", "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU",
           "CHINA", "ROMANIA", "SAUDI ARABIA", "VIETNAM", "RUSSIA",
           "UNITED KINGDOM", "UNITED STATES"]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATION_REGION = [0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2,
                 3, 4, 2, 3, 3, 1]


def _ns(days):
    return pd.Series(days.astype("datetime64[ns]"))


def _labelled(codes, labels):
    """A str column of labels[codes]. Where pandas keeps strings in arrow,
    the column is built from arrow's dictionary (2.6 s against 5.8 s for
    60M rows, my CPU run, PR 30); the values are the same either way."""
    dtype = pd.Series(labels[:1]).dtype
    if getattr(dtype, "storage", None) != "pyarrow":
        return pd.Series(np.asarray(labels)[codes])
    import pyarrow as pa
    column = pa.DictionaryArray.from_arrays(
        pa.array(codes.astype(np.int8)),
        pa.array(labels, pa.large_string())).cast(pa.large_string())
    return pd.Series(pd.arrays.ArrowStringArray(pa.chunked_array([column]),
                                                dtype=dtype))


def generate(params, seed, data_dir=None):
    """Return {"frames": {table: DataFrame}} for the six tables Q1 and Q5
    read. Nothing is written to disk."""
    n_orders = int(params["orders"])
    n_cust = max(10, n_orders // 10)
    n_supp = max(5, n_orders // 100)

    rs = np.random.default_rng(int(params["structure_seed"]))
    c_nation = rs.integers(0, len(NATIONS), n_cust)
    s_nation = rs.integers(0, len(NATIONS), n_supp)
    o_cust = rs.integers(0, n_cust, n_orders)
    o_day = rs.integers(0, 2405, n_orders)
    n_lines = 1 + rs.permutation(n_orders) % 7
    n_li = int(n_lines.sum())
    l_supp = rs.integers(0, n_supp, n_li)
    l_delay = rs.integers(1, 122, n_li)

    region = pd.DataFrame({
        "r_regionkey": np.arange(len(REGIONS), dtype=np.int64),
        "r_name": REGIONS})
    nation = pd.DataFrame({
        "n_nationkey": np.arange(len(NATIONS), dtype=np.int64),
        "n_name": NATIONS,
        "n_regionkey": np.asarray(NATION_REGION, dtype=np.int64)})
    customer = pd.DataFrame({"c_custkey": np.arange(n_cust, dtype=np.int64),
                             "c_nationkey": c_nation})
    supplier = pd.DataFrame({"s_suppkey": np.arange(n_supp, dtype=np.int64),
                             "s_nationkey": s_nation})
    epoch = np.datetime64("1992-01-01")
    orders = pd.DataFrame({
        "o_orderkey": np.arange(n_orders, dtype=np.int64),
        "o_custkey": o_cust,
        "o_orderdate": _ns(epoch + o_day.astype("timedelta64[D]"))},
        copy=False)

    r = np.random.default_rng(int(seed))
    l_order = np.repeat(np.arange(n_orders, dtype=np.int64), n_lines)
    ship_day = np.repeat(o_day, n_lines)
    ship_day += l_delay
    # columns are handed to the frame as they are (copy=False): at 60M
    # rows the copy into one block a dtype cost 11 s of a 48 s generate,
    # and `choice(labels, n)` draws `integers(0, len(labels), n)` and
    # indexes, so the flags below are the values the seed always drew
    lineitem = pd.DataFrame({
        "l_orderkey": l_order,
        "l_suppkey": l_supp,
        "l_quantity": r.integers(1, 51, n_li).astype(np.float64),
        "l_extendedprice": np.round(r.uniform(900, 100000, n_li), 2),
        "l_discount": np.round(r.uniform(0, 0.10, n_li), 2),
        "l_tax": np.round(r.uniform(0, 0.08, n_li), 2),
        "l_returnflag": _labelled(r.integers(0, 3, n_li), ["R", "A", "N"]),
        "l_linestatus": _labelled(r.integers(0, 2, n_li), ["O", "F"]),
        "l_shipdate": _ns(epoch + ship_day.astype("timedelta64[D]"))},
        copy=False)

    frames = {"region": region, "nation": nation, "supplier": supplier,
              "customer": customer, "orders": orders, "lineitem": lineitem}
    return {"frames": frames,
            "rows": {t: len(df) for t, df in frames.items()}}
