"""Plain reference for TPC-H Q9 (product type profit measure, COLOR =
green) in pandas merges, on the generated frames. Imports nothing of the
program. `precision="float32"` is the control: measures held,
multiplied, subtracted and summed in float32."""

import numpy as np
import pandas as pd


def answer(inputs, precision="float64"):
    t = inputs["frames"]
    ft = np.float32 if precision == "float32" else np.float64
    part = t["part"]
    part = part[part["p_name"].str.contains("green", regex=False)]
    j = t["lineitem"].merge(part[["p_partkey"]], left_on="l_partkey",
                            right_on="p_partkey")
    j = j.merge(t["partsupp"], left_on=["l_partkey", "l_suppkey"],
                right_on=["ps_partkey", "ps_suppkey"])
    j = j.merge(t["supplier"], left_on="l_suppkey", right_on="s_suppkey")
    j = j.merge(t["orders"], left_on="l_orderkey", right_on="o_orderkey")
    j = j.merge(t["nation"], left_on="s_nationkey", right_on="n_nationkey")
    price = j["l_extendedprice"].to_numpy(ft)
    disc = j["l_discount"].to_numpy(ft)
    cost = j["ps_supplycost"].to_numpy(ft)
    qty = j["l_quantity"].to_numpy(ft)
    f = pd.DataFrame({"nation": j["n_name"].to_numpy(),
                      "o_year": j["o_orderdate"].dt.year.to_numpy(np.int64),
                      "amount": price * (ft(1) - disc) - cost * qty})
    out = f.groupby(["nation", "o_year"], as_index=False).agg(
        sum_profit=("amount", "sum"))
    return out.sort_values(["nation", "o_year"], ascending=[True, False]) \
        .reset_index(drop=True)
