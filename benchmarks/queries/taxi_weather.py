"""get_monthly_travels_weather through `bodo_tpu.pandas_api`.

The reference benchmark's dataframe program (benchmarks/nyc_taxi,
`get_monthly_travels_weather`), as `bodo_tpu/workloads/taxi.py:
frontend_pipeline` writes it: read the parquet trips and the weather CSV,
date fields, inner join on date, six-key groupby with count and mean,
result to pandas, sorted by the keys. The read is inside every query.

`plan` builds the lazy frame (the harness times it as the front end);
`collect` executes it and ends with the answer in host memory.
"""

import numpy as np

KEYS = ["PULocationID", "DOLocationID", "month", "weekday",
        "date_with_precipitation", "time_bucket"]
BUCKET_NAMES = np.array(["morning", "midday", "afternoon", "evening",
                         "other"])


def plan(bd, inputs):
    weather = bd.read_csv(inputs["files"]["weather"], parse_dates=["DATE"])
    weather = weather.rename(columns={"DATE": "date",
                                      "PRCP": "precipitation"})
    trips = bd.read_parquet(inputs["files"]["trips"])

    weather["date"] = weather["date"].dt.date
    trips["date"] = trips["pickup_datetime"].dt.date
    trips["month"] = trips["pickup_datetime"].dt.month
    trips["hour"] = trips["pickup_datetime"].dt.hour
    trips["weekday"] = trips["pickup_datetime"].dt.dayofweek.isin(
        [0, 1, 2, 3, 4])

    m = trips.merge(weather, on="date", how="inner")
    m["date_with_precipitation"] = m["precipitation"] > 0.1
    m["time_bucket"] = m["hour"].map({8: 0, 9: 0, 10: 0,
                                      11: 1, 12: 1, 13: 1, 14: 1, 15: 1,
                                      16: 2, 17: 2, 18: 2,
                                      19: 3, 20: 3, 21: 3}).fillna(4.0) \
        .astype("int32")
    return m.groupby(KEYS, as_index=False).agg(
        trip_count=("hvfhs_license_num", "count"),
        avg_miles=("trip_miles", "mean"))


def collect(lazy):
    res = lazy.to_pandas()
    res["time_bucket"] = BUCKET_NAMES[res["time_bucket"]]
    # sorted after the codes became names, as the pandas program sorts
    return res.sort_values(KEYS).reset_index(drop=True)
