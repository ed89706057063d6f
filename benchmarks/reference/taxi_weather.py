"""Plain reference for `taxi_weather`: the pandas program of the source
(`bodo_tpu/workloads/taxi.py:pandas_pipeline`, copied), on the same
files. pandas and numpy only; imports nothing of the program.

`precision="float32"` is the control: the same program with the float64
measure held and averaged in float32, the nearest precision below the
one the configuration states.
"""

import numpy as np
import pandas as pd

KEYS = ["PULocationID", "DOLocationID", "month", "weekday",
        "date_with_precipitation", "time_bucket"]
# hour of day -> bucket name; the source's `bucket` function as a table
BUCKET_OF_HOUR = np.array(
    ["other"] * 8 + ["morning"] * 3 + ["midday"] * 5 + ["afternoon"] * 3
    + ["evening"] * 3 + ["other"] * 2)


def answer(inputs, precision="float64"):
    weather = pd.read_csv(inputs["files"]["weather"], parse_dates=["DATE"])
    weather = weather.rename(columns={"DATE": "date",
                                      "PRCP": "precipitation"})
    trips = pd.read_parquet(inputs["files"]["trips"])
    if precision == "float32":
        trips["trip_miles"] = trips["trip_miles"].astype(np.float32)
    weather["date"] = weather["date"].dt.date
    trips["date"] = trips["pickup_datetime"].dt.date
    trips["month"] = trips["pickup_datetime"].dt.month
    trips["hour"] = trips["pickup_datetime"].dt.hour
    trips["weekday"] = trips["pickup_datetime"].dt.dayofweek.isin(
        [0, 1, 2, 3, 4])
    m = trips.merge(weather, on="date", how="inner")
    m["date_with_precipitation"] = m["precipitation"] > 0.1
    m["time_bucket"] = BUCKET_OF_HOUR[m["hour"].to_numpy()]
    out = m.groupby(KEYS, as_index=False).agg(
        trip_count=("hvfhs_license_num", "count"),
        avg_miles=("trip_miles", "mean"))
    return out.sort_values(KEYS).reset_index(drop=True)
