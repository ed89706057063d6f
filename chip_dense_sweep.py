"""Where `relational.DENSE_REDUCE_MAX_SLOTS` comes from: the Q1-shaped
dense aggregate tail (3,000,064 rows, TPC-H Q1's eight specs over
float64 measures) through `dense_agg_tail`'s reduce route and its
scatter route at a ladder of slot counts, device seconds a call read
from the profiler's trace. The constant is the largest swept slot count
at which the reduce route is still at least twice as fast.

    python chip_dense_sweep.py            # on the chip; table to stdout
    JAX_PLATFORMS=cpu python chip_dense_sweep.py --rehearse --rows 20096

Exits non-zero unless it ran on a TPU or was told `--rehearse` (whose
table has no device seconds: a CPU time is never a device metric). Each
route's answer is also compared with numpy's float64 on the host, so
the table says what each route's accumulation order costs in precision.
"""

import argparse
import glob
import json
import os
import re
import sys
import tempfile
import time

import numpy as np

Q1_SPECS = ("sumnull",) * 4 + ("mean",) * 3 + ("size",)


def make_inputs(rows, n_slots, seed):
    """Q1's tail as the fused stage hands it over: one int32 key of
    `n_slots` codes, seven float64 measures, an int64 column for
    `size`, and a filter mask that keeps 98% of the rows."""
    rng = np.random.default_rng(seed)
    tree = {"k": (rng.integers(0, n_slots, rows).astype(np.int32), None)}
    for i in range(7):
        tree[f"v{i}"] = (rng.uniform(0.0, 1e5, rows), None)
    tree["v7"] = (np.ones(rows, np.int64), None)
    return tree, rng.random(rows) < 0.98


def host_answer(tree, live, n_slots):
    k = tree["k"][0][live]
    cnt = np.bincount(k, minlength=n_slots)
    outs = []
    for i, op in enumerate(Q1_SPECS):
        if op == "size":
            outs.append(cnt[cnt > 0])
            continue
        s = np.bincount(k, weights=tree[f"v{i}"][0][live],
                        minlength=n_slots)
        outs.append((s / np.maximum(cnt, 1) if op == "mean" else s)[cnt > 0])
    return outs


def rel_gap(got, want):
    gap = 0.0
    for (g, _), w in zip(got, want):
        g = np.asarray(g)[:len(w)]
        if w.dtype.kind == "f":
            gap = max(gap, float(np.max(np.abs(g - w) / np.abs(w))))
        elif not np.array_equal(g, w):
            return float("inf")
    return gap


def module_seconds(log_dir):
    """Seconds on device 0 by program, from the `XLA Modules` line of
    the trace under `log_dir`; {} where the trace has no TPU plane."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    name = re.sub(r"\(\d+\)$", "", ev.name)
                    out[name] = out.get(name, 0.0) + ev.duration_ns / 1e9
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=3_000_064)
    ap.add_argument("--slots", default="6,25,64,256,1024")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="chiprun_out/dense_sweep.json")
    args = ap.parse_args()

    import jax
    import bodo_tpu  # noqa: F401 - x64 on, as the engine runs
    from bodo_tpu import relational as R
    from bodo_tpu.utils.kernel_cache import named_jit

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse:
        sys.exit(f"no TPU here (platform {platform}): --rehearse to "
                 "drive the paths without device seconds")

    def tail(route, n_slots):
        # the route is the tail's own choice by shape; the sweep steers
        # it the way a test would, by the constant, while it traces
        def body(tree, live):
            return R.dense_agg_tail(tree, live, ["k"],
                                    [f"v{i}" for i in range(8)], Q1_SPECS,
                                    (n_slots,), (0,), n_slots, False)
        fn = named_jit(f"tail_{route}_{n_slots}", body)

        def call(tree, live):
            old = R.DENSE_REDUCE_MAX_SLOTS
            R.DENSE_REDUCE_MAX_SLOTS = n_slots if route == "reduce" else 0
            try:
                return fn(tree, live)
            finally:
                R.DENSE_REDUCE_MAX_SLOTS = old
        return call

    table = []
    for n_slots in (int(s) for s in args.slots.split(",")):
        tree, live = make_inputs(args.rows, n_slots, args.seed)
        want = host_answer(tree, live, n_slots)
        dtree, dlive = jax.device_put((tree, live))
        row = {"n_slots": n_slots, "rows": args.rows}
        calls = {}
        for route in ("reduce", "scatter"):
            calls[route] = tail(route, n_slots)
            t0 = time.perf_counter()
            _, vals, ng = jax.block_until_ready(calls[route](dtree, dlive))
            row[f"{route}_first_call_s"] = time.perf_counter() - t0
            assert int(ng) == len(want[0]), (route, int(ng))
            row[f"{route}_rel_gap_to_host_f64"] = rel_gap(vals, want)
        with tempfile.TemporaryDirectory() as log_dir:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=options)
            try:
                for route, call in calls.items():
                    t0 = time.perf_counter()
                    for _ in range(args.reps):
                        jax.block_until_ready(call(dtree, dlive))
                    row[f"{route}_host_s"] = \
                        (time.perf_counter() - t0) / args.reps
            finally:
                jax.profiler.stop_trace()
            secs = module_seconds(log_dir)
        for route in calls:
            s = secs.get(f"jit_tail_{route}_{n_slots}")
            row[f"{route}_device_s"] = s / args.reps if s else None
        print(json.dumps(row), flush=True)
        table.append(row)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"platform": platform,
                   "device_kind": jax.devices()[0].device_kind,
                   "rehearsal": platform != "tpu", "table": table}, f,
                  indent=1)
    print(f"platform {platform}; table at {args.out}")


if __name__ == "__main__":
    main()
