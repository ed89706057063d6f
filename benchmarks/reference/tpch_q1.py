"""Plain reference for TPC-H Q1 in pandas, on the generated frames.
Imports nothing of the program. `precision="float32"` is the control:
measures held, multiplied and summed in float32."""

import numpy as np
import pandas as pd


def answer(inputs, precision="float64"):
    li = inputs["frames"]["lineitem"]
    li = li[li["l_shipdate"] <= pd.Timestamp("1998-09-02")]
    ft = np.float32 if precision == "float32" else np.float64
    qty = li["l_quantity"].to_numpy(ft)
    price = li["l_extendedprice"].to_numpy(ft)
    disc = li["l_discount"].to_numpy(ft)
    tax = li["l_tax"].to_numpy(ft)
    one = ft(1)
    disc_price = price * (one - disc)
    f = pd.DataFrame({
        "l_returnflag": li["l_returnflag"].to_numpy(),
        "l_linestatus": li["l_linestatus"].to_numpy(),
        "qty": qty, "price": price, "disc": disc,
        "disc_price": disc_price, "charge": disc_price * (one + tax)})
    out = f.groupby(["l_returnflag", "l_linestatus"], as_index=False).agg(
        sum_qty=("qty", "sum"), sum_base_price=("price", "sum"),
        sum_disc_price=("disc_price", "sum"), sum_charge=("charge", "sum"),
        avg_qty=("qty", "mean"), avg_price=("price", "mean"),
        avg_disc=("disc", "mean"), count_order=("qty", "size"))
    return out.sort_values(["l_returnflag", "l_linestatus"]) \
        .reset_index(drop=True)
