"""Shared NYC-taxi-shaped workload: data generator, pandas oracle, and the
bodo_tpu pipeline. Used by the e2e test, chip_smoke.py and __graft_entry__.py.

Mirrors the reference benchmark get_monthly_travels_weather
(reference: benchmarks/nyc_taxi/bodo/nyc_taxi_precipitation.py): csv+parquet
read, datetime field extraction, inner merge on date, derived bool/bucket
columns, 6-key groupby with count+mean, multi-key sort.
"""

import numpy as np
import pandas as pd

TIME_BUCKETS = ["morning", "midday", "afternoon", "evening", "other"]


def gen_taxi_data(n_rows: int, out_parquet: str, out_csv: str, seed: int = 0):
    r = np.random.default_rng(seed)
    start = np.datetime64("2024-01-01T00:00:00")
    pickup = start + r.integers(0, 180 * 24 * 3600, n_rows).astype(
        "timedelta64[s]")
    df = pd.DataFrame({
        "hvfhs_license_num": r.choice(["HV0002", "HV0003", "HV0004",
                                       "HV0005"], n_rows),
        "PULocationID": r.integers(1, 180, n_rows).astype(np.int64),
        "DOLocationID": r.integers(1, 180, n_rows).astype(np.int64),
        "trip_miles": (r.gamma(2.0, 2.5, n_rows)).astype(np.float64),
        "pickup_datetime": pd.Series(pickup.astype("datetime64[ns]")),
    })
    df.to_parquet(out_parquet)
    dates = pd.date_range("2024-01-01", "2024-06-30", freq="D")
    weather = pd.DataFrame({
        "DATE": dates.strftime("%Y-%m-%d"),
        "PRCP": np.round(np.random.default_rng(seed + 1)
                         .gamma(0.5, 0.3, len(dates)), 2),
    })
    weather.to_csv(out_csv, index=False)
    return df, weather


def pandas_pipeline(trips_path: str, weather_path: str) -> pd.DataFrame:
    """The pandas oracle (the reference benchmark body, pandas flavor)."""
    weather = pd.read_csv(weather_path, parse_dates=["DATE"])
    weather = weather.rename(columns={"DATE": "date", "PRCP": "precipitation"})
    trips = pd.read_parquet(trips_path)
    weather["date"] = weather["date"].dt.date
    trips["date"] = trips["pickup_datetime"].dt.date
    trips["month"] = trips["pickup_datetime"].dt.month
    trips["hour"] = trips["pickup_datetime"].dt.hour
    trips["weekday"] = trips["pickup_datetime"].dt.dayofweek.isin(
        [0, 1, 2, 3, 4])
    m = trips.merge(weather, on="date", how="inner")
    m["date_with_precipitation"] = m["precipitation"] > 0.1

    def bucket(t):
        if t in (8, 9, 10):
            return "morning"
        if t in (11, 12, 13, 14, 15):
            return "midday"
        if t in (16, 17, 18):
            return "afternoon"
        if t in (19, 20, 21):
            return "evening"
        return "other"

    m["time_bucket"] = m.hour.map(bucket)
    keys = ["PULocationID", "DOLocationID", "month", "weekday",
            "date_with_precipitation", "time_bucket"]
    out = m.groupby(keys, as_index=False).agg(
        trip_count=("hvfhs_license_num", "count"),
        avg_miles=("trip_miles", "mean"))
    return out.sort_values(keys).reset_index(drop=True)


def frontend_pipeline(trips_path: str, weather_path: str) -> pd.DataFrame:
    """The same workload through the lazy pandas frontend — written to
    mirror the reference benchmark's dataframe-library flavor nearly
    line-for-line (reference: benchmarks/nyc_taxi/bodo/
    nyc_taxi_precipitation.py get_monthly_travels_weather)."""
    import bodo_tpu.pandas_api as bd

    weather = bd.read_csv(weather_path, parse_dates=["DATE"])
    weather = weather.rename(columns={"DATE": "date", "PRCP": "precipitation"})
    trips = bd.read_parquet(trips_path)

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
    keys = ["PULocationID", "DOLocationID", "month", "weekday",
            "date_with_precipitation", "time_bucket"]
    out = m.groupby(keys, as_index=False).agg(
        trip_count=("hvfhs_license_num", "count"),
        avg_miles=("trip_miles", "mean"))
    res = out.to_pandas()
    bucket_names = np.array(["morning", "midday", "afternoon", "evening",
                             "other"])
    res["time_bucket"] = bucket_names[res["time_bucket"]]
    # sort after mapping so bucket order matches the pandas oracle
    # (alphabetical names, not integer codes)
    return res.sort_values(keys).reset_index(drop=True)


def bodo_tpu_pipeline(trips_path: str, weather_path: str, shard: bool = True):
    """Same workload on the bodo_tpu relational layer. Returns a Table."""
    import bodo_tpu.relational as R
    from bodo_tpu.io import read_csv, read_parquet
    from bodo_tpu.plan.expr import ColRef as c, DtField, IsIn, Lit, Where

    weather = read_csv(weather_path, parse_dates=["DATE"])
    trips = read_parquet(trips_path)
    if shard:
        trips = trips.shard()

    weather = R.assign_columns(weather, {
        "date": DtField("date", c("DATE")),
        "precipitation": c("PRCP"),
    }).select(["date", "precipitation"])

    trips = R.assign_columns(trips, {
        "date": DtField("date", c("pickup_datetime")),
        "month": DtField("month", c("pickup_datetime")),
        "hour": DtField("hour", c("pickup_datetime")),
        "weekday": IsIn(DtField("dayofweek", c("pickup_datetime")),
                        (0, 1, 2, 3, 4)),
    })

    m = R.join_tables(trips, weather, ["date"], ["date"], "inner")
    m = R.assign_columns(m, {
        "date_with_precipitation": c("precipitation") > 0.1,
    })
    code = R.category_code
    h = c("hour")
    bucket_codes = Where(
        IsIn(h, (8, 9, 10)), Lit(code(TIME_BUCKETS, "morning")),
        Where(IsIn(h, (11, 12, 13, 14, 15)), Lit(code(TIME_BUCKETS, "midday")),
              Where(IsIn(h, (16, 17, 18)), Lit(code(TIME_BUCKETS, "afternoon")),
                    Where(IsIn(h, (19, 20, 21)),
                          Lit(code(TIME_BUCKETS, "evening")),
                          Lit(code(TIME_BUCKETS, "other"))))))
    m = R.assign_categorical(m, "time_bucket", bucket_codes, TIME_BUCKETS)

    keys = ["PULocationID", "DOLocationID", "month", "weekday",
            "date_with_precipitation", "time_bucket"]
    out = R.groupby_agg(m, keys, [
        ("hvfhs_license_num", "count", "trip_count"),
        ("trip_miles", "mean", "avg_miles"),
    ])
    out = R.sort_table(out, keys)
    return out
