"""FHVHV-shaped trips + daily weather, made from a seed.

A copy of `bodo_tpu/workloads/taxi.py:gen_taxi_data` (same columns, same
distributions: uniform zones 1-179, gamma(2, 2.5) miles, pickups uniform
over 180 days, 181 weather days), independent of the program. One change:
what decides a program's shapes is drawn from the configuration's
`structure_seed`, and `--seed` draws what decides the answer's values.

  structure (same for every seed):  pickup second of each row, the zone
      *slots* of each row, the weather file
  seed:  which zone id each slot stands for (a permutation of 1..179),
      trip_miles, the licence of each row

So every seed gives the same row count, the same join output and the same
number of groups (the grouping is the same up to a renaming of zones),
while zone ids, miles, licences and the answer differ. Engine programs are
keyed on capacities rounded to 128 rows (`table/table.py`), so a group
count that moved with the seed would recompile part of the query in every
run; see PERF.md, section 4.
"""

import os

import numpy as np
import pandas as pd

LICENCES = ["HV0002", "HV0003", "HV0004", "HV0005"]
N_ZONES = 179
DAYS = 180


def generate(params, seed, data_dir):
    """Write trips.parquet and weather.csv under data_dir; return the
    inputs that the query and its reference are both given."""
    rows = int(params["rows"])
    rs = np.random.default_rng(int(params["structure_seed"]))
    pickup_s = rs.integers(0, DAYS * 24 * 3600, rows)
    pu_slot = rs.integers(0, N_ZONES, rows)
    do_slot = rs.integers(0, N_ZONES, rows)
    prcp = np.round(rs.gamma(0.5, 0.3, DAYS + 1), 2)

    r = np.random.default_rng(int(seed))
    zone_of_slot = (r.permutation(N_ZONES) + 1).astype(np.int64)
    pickup = (np.datetime64("2024-01-01T00:00:00")
              + pickup_s.astype("timedelta64[s]"))
    trips = pd.DataFrame({
        "hvfhs_license_num": r.choice(LICENCES, rows),
        "PULocationID": zone_of_slot[pu_slot],
        "DOLocationID": zone_of_slot[do_slot],
        "trip_miles": r.gamma(2.0, 2.5, rows).astype(np.float64),
        "pickup_datetime": pd.Series(pickup.astype("datetime64[ns]")),
    })
    dates = pd.date_range("2024-01-01", periods=DAYS + 1, freq="D")
    weather = pd.DataFrame({"DATE": dates.strftime("%Y-%m-%d"),
                            "PRCP": prcp})
    os.makedirs(data_dir, exist_ok=True)
    trips_path = os.path.join(data_dir, "trips.parquet")
    weather_path = os.path.join(data_dir, "weather.csv")
    trips.to_parquet(trips_path)
    weather.to_csv(weather_path, index=False)
    return {"files": {"trips": trips_path, "weather": weather_path},
            "rows": {"trips": rows, "weather": len(weather)}}
