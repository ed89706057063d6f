"""The three tables TPC-H Q18 (large volume customer) reads, cut to the
eight columns it reads, with `c_name` and `o_totalprice` made as the
specification's dbgen makes them (clause 4.2.3), from a seed,
vectorised. Pandas and numpy only; imports nothing of the program.

`gen/tpch.py` makes customer, orders and lineitem; every column this
generator shares with its frames is that generator's, value for value,
at the same `orders`, `structure_seed` and `--seed`, but for
`l_quantity` (below). What is added:

  customer  c_name = "Customer#%09d" % c_custkey (keys dense from 0)
  orders    o_totalprice = round(sum over the order's lines of
            l_extendedprice * (1 + l_tax) * (1 - l_discount), 2), from
            `gen/tpch.py`'s own measures, so it moves with `--seed`

**Seeds and shapes.** Which orders pass `HAVING sum(l_quantity) > 300`
decides every row count behind the subquery, capacities round to 128
rows, and a row count is a program's shape. `gen/tpch.py` draws
`l_quantity` from `--seed`, which would give every seed another number
of large orders. So here the *multiset* of per-order quantity vectors
(uniform on 1..50 a line, 1-7 lines an order: dbgen's distribution, and
`gen/tpch.py`'s) is drawn from `structure_seed`, and `--seed` permutes
those vectors among the orders that have the same number of lines
(seven vectorised reshapes). Every seed then keeps the same count of
large orders (only an order of seven lines can pass 300) and the same
row count at every operator, the same sizes in another order, while
which orders are large, their customers, dates, prices and the order of
the answer all move with the seed. `l_quantity` is thereby the one
column shared with `gen/tpch.py` that is not that generator's value for
value.
"""

import numpy as np
import pandas as pd

from harness import spec

MAX_LINES = 7


def quantities(l_orderkey, n_orders, rs, r):
    """l_quantity for lineitem rows sorted by l_orderkey: the vectors
    drawn from `rs` (structure), dealt by `r` (the seed) among the
    orders of equal length."""
    n_lines = np.bincount(l_orderkey, minlength=n_orders)
    starts = np.cumsum(n_lines) - n_lines
    drawn = rs.integers(1, 51, len(l_orderkey)).astype(np.float64)
    qty = np.empty_like(drawn)
    for k in range(1, MAX_LINES + 1):
        at = starts[n_lines == k][:, None] + np.arange(k)
        qty[at] = drawn[at][r.permutation(len(at))]
    return qty


def generate(params, seed, data_dir=None):
    """Return {"frames": {table: DataFrame}, "rows": {table: rows}} for
    the three tables Q18 reads. Nothing is written to disk."""
    base = spec.load_module("gen", "tpch").generate(params, seed)["frames"]
    li, orders = base["lineitem"], base["orders"]
    n_orders = len(orders)
    l_order = li["l_orderkey"].to_numpy()

    # streams of their own: gen/tpch.py draws from the bare seeds
    rs = np.random.default_rng([int(params["structure_seed"]), 18])
    r = np.random.default_rng([int(seed), 18])

    charged = (li["l_extendedprice"].to_numpy()
               * (1.0 + li["l_tax"].to_numpy())
               * (1.0 - li["l_discount"].to_numpy()))
    custkey = base["customer"]["c_custkey"].to_numpy()
    frames = {
        "customer": pd.DataFrame({
            "c_custkey": custkey,
            "c_name": np.char.add("Customer#",
                                  np.char.zfill(custkey.astype(str), 9))}),
        "orders": pd.DataFrame({
            "o_orderkey": orders["o_orderkey"],
            "o_custkey": orders["o_custkey"],
            "o_orderdate": orders["o_orderdate"],
            "o_totalprice": np.round(np.bincount(
                l_order, weights=charged, minlength=n_orders), 2)},
            copy=False),
        "lineitem": pd.DataFrame({
            "l_orderkey": l_order,
            "l_quantity": quantities(l_order, n_orders, rs, r)},
            copy=False)}
    return {"frames": frames,
            "rows": {t: len(df) for t, df in frames.items()}}
