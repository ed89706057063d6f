"""Plain reference for TPC-H Q18 (large volume customer, QUANTITY =
300) in pandas, on the generated frames. Imports nothing of the
program. `precision="float32"` is the control: `l_quantity` and
`o_totalprice` held in float32 (the sums are whole numbers under 351 and
survive; `o_totalprice`, six digits and cents, does not)."""

import numpy as np

QUANTITY = 300
KEYS = ["c_name", "c_custkey", "o_orderkey", "o_orderdate", "o_totalprice"]


def answer(inputs, precision="float64"):
    t = inputs["frames"]
    ft = np.float32 if precision == "float32" else np.float64
    lineitem = t["lineitem"].assign(
        l_quantity=t["lineitem"]["l_quantity"].to_numpy(ft))
    orders = t["orders"].assign(
        o_totalprice=t["orders"]["o_totalprice"].to_numpy(ft))
    per_order = lineitem.groupby("l_orderkey")["l_quantity"].sum()
    large = per_order.index[per_order > QUANTITY]
    j = orders[orders["o_orderkey"].isin(large)]
    j = j.merge(t["customer"], left_on="o_custkey", right_on="c_custkey")
    j = j.merge(lineitem, left_on="o_orderkey", right_on="l_orderkey")
    out = j.groupby(KEYS, as_index=False).agg(sum_qty=("l_quantity", "sum"))
    return out.sort_values(["o_totalprice", "o_orderdate"],
                           ascending=[False, True]) \
        .head(100).reset_index(drop=True)
