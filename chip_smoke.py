#!/usr/bin/env python3
"""Chip smoke: the query path, once, on the device JAX finds.

    python chip_smoke.py              one TPU chip: taxi + TPC-H Q1/Q5
    python chip_smoke.py --chips 4    four-chip mesh: sharded taxi only
    python chip_smoke.py --rehearse   small sizes on whatever JAX has
                                      (CPU, Pallas in interpret mode)

One process; it touches JAX itself and starts no child. Every line it
prints is one JSON object; the last is
{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}
and is printed only when every phase ran and matched its oracle on the
device it names. Without --rehearse a platform other than "tpu" is a
failure. The seconds on the "smoke_*" keys are host-clock times of a
smoke, not a benchmark.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

TAXI_SIZES = (20_000_000, 10_000_000, 5_000_000, 2_000_000)
# 1.5M orders (SF1-shaped) runs on one v5e, but Q5 alone then takes 214 s
# a run (chip run, PR 25) and the cold script would need ~1100 of its
# 1200 s; `--tpch-orders 1500000` asks for it by hand
TPCH_SIZES = (750_000, 375_000)
# columns Q1 and Q5 read: the sqlite oracle loads these and no others
# Q1 sums float64 columns, which the MXU gate (relational.dense_mxu_ok)
# rightly keeps off the f32 one-hot kernel; Q1's own grouping with only
# its count(*) is the query that reaches matmul_groupby_sum
Q1_COUNT = """
select l_returnflag, l_linestatus, count(*) as count_order
from lineitem
where l_shipdate <= date '1998-12-01' - interval '90' day
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
"""
TPCH_ORACLE_COLS = {
    "lineitem": ["l_orderkey", "l_suppkey", "l_quantity", "l_extendedprice",
                 "l_discount", "l_tax", "l_returnflag", "l_linestatus",
                 "l_shipdate"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate"],
    "customer": ["c_custkey", "c_nationkey"],
    "supplier": ["s_suppkey", "s_nationkey"],
    "nation": ["n_nationkey", "n_name", "n_regionkey"],
    "region": ["r_regionkey", "r_name"],
}


_T0 = time.perf_counter()


def emit(**kw):
    if "ok" not in kw:
        kw["smoke_elapsed_s"] = round(time.perf_counter() - _T0, 1)
    line = json.dumps(kw, default=str)
    print(line, flush=True)
    # the chip tool shows only the end of the output: keep every line
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "chip_smoke.jsonl"),
              "a") as f:
        f.write(line + "\n")


class PhaseFailed(Exception):
    pass


def does_not_fit(e):
    """The size rule steps down only for what a smaller size can cure."""
    return "RESOURCE_EXHAUSTED" in repr(e) or "Out of memory" in repr(e) \
        or isinstance(e, MemoryError)


def check(cond, msg):
    if not cond:
        raise PhaseFailed(msg)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse", action="store_true",
                    help="small sizes; any platform; never a chip pass")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--taxi-rows", type=int, default=0,
                    help="run the taxi phase at this size only")
    ap.add_argument("--tpch-orders", type=int, default=0,
                    help="run the tpch phase at this size only")
    args = ap.parse_args()

    if args.rehearse and args.chips > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")

    import jax
    import numpy as np
    import pandas as pd

    phase = "devices"
    try:
        devs = jax.devices()
        dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
               "count": len(devs)}
        if not args.rehearse:
            check(dev["platform"] == "tpu",
                  f"no accelerator: platform is {dev['platform']!r}")
        check(len(devs) == args.chips,
              f"asked for {args.chips} device(s), JAX has {len(devs)}")

        import bodo_tpu
        from bodo_tpu.ops import pallas_kernels as PK
        from bodo_tpu.runtime import pool, resilience, xla_observatory
        from bodo_tpu.utils import tracing
        from bodo_tpu.workloads import taxi, tpch

        if args.rehearse and dev["platform"] != "tpu":
            PK.FORCE_INTERPRET = True
        bodo_tpu.set_config(result_cache=False)
        bodo_tpu.set_mesh(bodo_tpu.make_mesh())

        backend_compiles = {"n": 0, "s": 0.0}

        def _on_duration(event, duration, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                backend_compiles["n"] += 1
                backend_compiles["s"] += duration

        jax.monitoring.register_event_duration_secs_listener(_on_duration)

        def snapshot():
            o = xla_observatory.stats()
            return {"programs": o["compiles"], "compile_s": o["compile_s"],
                    "dispatches": o["dispatches"],
                    "backend_compiles": backend_compiles["n"],
                    "backend_compile_s": backend_compiles["s"]}

        def delta(a, b):
            return {k: round(b[k] - a[k], 3) for k in a}

        def device_bytes(key):
            return [(d.memory_stats() or {}).get(key) for d in devs]

        def peak_bytes():
            return device_bytes("peak_bytes_in_use")

        def twice(name, fn):
            """Run a query twice with the result cache off; both runs
            must reach the device and the second must compile nothing."""
            runs = []
            out = None
            for _ in range(2):
                s0 = snapshot()
                t0 = time.perf_counter()
                out = fn()
                dt = time.perf_counter() - t0
                d = delta(s0, snapshot())
                runs.append(dict(d, smoke_host_s=round(dt, 3)))
            emit(phase=name, smoke_first_run=runs[0], smoke_second_run=runs[1],
                 second_run_served_by_result_cache=runs[1]["dispatches"] == 0)
            check(runs[1]["dispatches"] > 0,
                  f"{name}: second run dispatched nothing (a cache hit)")
            check(runs[1]["programs"] == 0
                  and runs[1]["backend_compiles"] == 0,
                  f"{name}: second run compiled {runs[1]}")
            return out

        data_dir = os.path.join(ROOT, ".smoke_data")
        os.makedirs(data_dir, exist_ok=True)

        # ---------------------------------------------------------- taxi
        phase = "taxi"
        sizes = ((args.taxi_rows,) if args.taxi_rows
                 else (100_000,) if args.rehearse else TAXI_SIZES)
        pq = os.path.join(data_dir, "trips.parquet")
        csv = os.path.join(data_dir, "weather.csv")
        for n in sizes:
            taxi.gen_taxi_data(n, pq, csv, seed=args.seed)
            try:
                got = twice(f"taxi[{n}]",
                            lambda: taxi.frontend_pipeline(pq, csv))
                break
            except Exception as e:  # noqa: BLE001 - size rule: try smaller
                if not does_not_fit(e) or n == sizes[-1]:
                    raise
                emit(phase="taxi", rows=n, did_not_fit=repr(e)[:2000])
        if args.chips > 1:
            # rows resident on each device after a normal frontend read
            import bodo_tpu.pandas_api as bd
            before = device_bytes("bytes_in_use")
            t = bd.read_parquet(pq)._execute()
            arr = next(iter(t.columns.values())).data
            shard_bytes = [int(s.data.nbytes) for s in
                           arr.addressable_shards]
            emit(phase="taxi", rows=n, distribution=str(t.distribution),
                 one_column_shard_bytes=shard_bytes,
                 bytes_in_use_before_read=before,
                 bytes_in_use_after_read=device_bytes("bytes_in_use"))
            check(len(shard_bytes) == args.chips
                  and min(shard_bytes) > 0
                  and max(shard_bytes) <= 2 * min(shard_bytes),
                  f"table is not spread over the devices: {shard_bytes}")
            del t, arr
        exp = taxi.pandas_pipeline(pq, csv)
        check(len(got) == len(exp),
              f"groups: {len(got)} vs pandas {len(exp)}")
        check(list(got.columns) == list(exp.columns), "columns differ")
        for c in got.columns:
            if c == "avg_miles":
                np.testing.assert_allclose(got[c].to_numpy(float),
                                           exp[c].to_numpy(float),
                                           rtol=1e-9, atol=0)
            else:
                check((got[c].to_numpy() == exp[c].to_numpy()).all(),
                      f"column {c} differs from pandas")
        taxi_counts = dict(PK.trace_counts)
        emit(phase="taxi", rows=n, groups=len(got), equal_to_pandas=True,
             peak_bytes_in_use=peak_bytes(), trace_counts=taxi_counts)
        if dev["platform"] == "tpu" or PK.FORCE_INTERPRET:
            check(taxi_counts["decode"] > 0,
                  "parquet read engaged no decode kernel")

        # ---------------------------------------------------------- tpch
        q_sizes = None
        if args.chips == 1:
            phase = "tpch"
            q_sizes = ((args.tpch_orders,) if args.tpch_orders
                       else (3_000,) if args.rehearse else TPCH_SIZES)
            from bodo_tpu.sql import BodoSQLContext
            queries = {"q1": tpch.QUERIES[1], "q1_count": Q1_COUNT,
                       "q5": tpch.QUERIES[5]}
            for n_orders in q_sizes:
                data = tpch.gen_tpch(n_orders=n_orders, seed=args.seed)
                try:
                    ctx = BodoSQLContext(data)
                    before = PK.trace_counts["groupby"]
                    res = {}
                    for q, sql in queries.items():
                        res[q] = twice(
                            f"tpch[{n_orders}].{q}",
                            lambda: ctx.sql(sql).to_pandas())
                    if dev["platform"] == "tpu" or PK.FORCE_INTERPRET:
                        check(PK.trace_counts["groupby"] > before,
                              "q1_count did not reach matmul_groupby_sum")
                    break
                except Exception as e:  # noqa: BLE001 - size rule
                    if not does_not_fit(e) or n_orders == q_sizes[-1]:
                        raise
                    emit(phase="tpch", n_orders=n_orders,
                         did_not_fit=repr(e)[:2000])
            conn = tpch.sqlite_connection(
                {t: data[t][cols] for t, cols in TPCH_ORACLE_COLS.items()})
            # without indexes sqlite needs 35 minutes for Q5 at 1.5M orders
            conn.executescript(
                "create index i_l on lineitem(l_orderkey);"
                "create index i_o on orders(o_orderkey);"
                "create index i_oc on orders(o_custkey);"
                "create index i_c on customer(c_custkey);"
                "create index i_s on supplier(s_suppkey); analyze;")
            for q, g in res.items():
                e = pd.read_sql_query(tpch.to_sqlite(queries[q]), conn)
                check(len(g) == len(e), f"{q}: {len(g)} vs {len(e)} rows")
                g = g.copy()
                g.columns = list(e.columns)
                for c in e.columns:
                    if e[c].dtype.kind == "f" or g[c].dtype.kind == "f":
                        np.testing.assert_allclose(
                            g[c].astype(float), e[c].astype(float),
                            rtol=1e-6, atol=1e-6, err_msg=f"{q} col {c}")
                    else:
                        check(list(g[c].astype(str)) == list(e[c].astype(str)),
                              f"{q} column {c} differs from sqlite")
            emit(phase="tpch", n_orders=n_orders,
                 lineitem_rows=len(data["lineitem"]), queries=list(queries),
                 equal_to_sqlite=True, peak_bytes_in_use=peak_bytes(),
                 trace_counts=dict(PK.trace_counts))

        # ------------------------------------------------ what ran where
        phase = "report"
        rs = resilience.stats()
        degraded = sum(rs["degraded_stages"].values())
        retries = sum(rs["retries"].values()) + rs["gang_retries"]
        coll = {}
        if args.chips > 1:
            for rec in xla_observatory.registry_dump():
                for prim in (rec.get("progcheck") or {}).get(
                        "collectives", ()):
                    coll[prim] = coll.get(prim, 0) + rec["dispatches"]
        emit(phase="report", device=dev, rehearsal=args.rehearse,
             pallas_interpret=PK.FORCE_INTERPRET,
             result_cache=bodo_tpu.config.result_cache,
             compile_cache_dir=jax.config.jax_compilation_cache_dir,
             compile_cache=tracing.compile_cache_stats(),
             totals=snapshot(), trace_counts=dict(PK.trace_counts),
             peak_bytes_in_use=peak_bytes(), degraded_stages=degraded,
             retries=retries, has_native_pool=pool.has_native_pool(),
             collectives_dispatched=coll)
        check(degraded == 0, f"degraded stages: {rs['degraded_stages']}")
        if args.chips > 1:
            check(coll.get("all_to_all", 0) > 0,
                  f"no all_to_all dispatched: {coll}")
    except BaseException as e:  # noqa: BLE001 - name the phase, exit 1
        import traceback
        traceback.print_exc()
        emit(failed_phase=phase, error=repr(e)[:4000])
        return 1
    finally:
        obs = sys.modules.get("bodo_tpu.runtime.xla_observatory")
        if obs is not None:
            emit(phase="report", slowest_compiles=[
                {"program": f"{r['subsystem']}:{r['base']}",
                 "compile_s": round(r["compile_s"], 1)}
                for r in obs.top_programs(15, "compile_s")])
    emit(ok=True, device=dev)
    return 0


if __name__ == "__main__":
    sys.exit(main())
