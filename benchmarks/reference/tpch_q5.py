"""Plain reference for TPC-H Q5 in pandas merges, on the generated
frames. Imports nothing of the program. `precision="float32"` is the
control: measures held, multiplied and summed in float32."""

import numpy as np
import pandas as pd


def answer(inputs, precision="float64"):
    t = inputs["frames"]
    ft = np.float32 if precision == "float32" else np.float64
    region = t["region"][t["region"]["r_name"] == "ASIA"]
    nation = t["nation"].merge(region, left_on="n_regionkey",
                               right_on="r_regionkey")
    o = t["orders"]
    o = o[(o["o_orderdate"] >= pd.Timestamp("1994-01-01"))
          & (o["o_orderdate"] < pd.Timestamp("1995-01-01"))]
    j = o.merge(t["customer"], left_on="o_custkey", right_on="c_custkey")
    j = j.merge(t["lineitem"][["l_orderkey", "l_suppkey",
                               "l_extendedprice", "l_discount"]],
                left_on="o_orderkey", right_on="l_orderkey")
    j = j.merge(t["supplier"], left_on=["l_suppkey", "c_nationkey"],
                right_on=["s_suppkey", "s_nationkey"])
    j = j.merge(nation, left_on="s_nationkey", right_on="n_nationkey")
    price = j["l_extendedprice"].to_numpy(ft)
    disc = j["l_discount"].to_numpy(ft)
    f = pd.DataFrame({"n_name": j["n_name"].to_numpy(),
                      "revenue": price * (ft(1) - disc)})
    out = f.groupby("n_name", as_index=False).agg(revenue=("revenue", "sum"))
    return out.sort_values("revenue", ascending=False) \
        .reset_index(drop=True)
