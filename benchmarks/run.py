#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the device JAX finds.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process; it holds the chip itself and starts no child. It makes the
cell's data from --seed, loads it, compiles and runs every query of the
cell's mix once (all of that is `setup_s`), drives the mix through the
engine's front door as a closed loop of one client for --seconds, then
compares every answer the window returned with the plain reference.
Every line it prints is one JSON object; the last is the result:
  {"correct", "attempted", "failed", "metrics", "device", ["breakdown"],
   "compared"}
--trace 0 gives the end-to-end metrics, --trace 1 the per-layer metrics
from a profiler trace of the first seconds of the window, taken with the
Python tracer off: under it a taxi query ran 11% slower, 24% on four chips.

A platform other than "tpu", or another number of devices than the cell's
`chips`, is a failure with no result line, unless `--rehearse <fraction>`
is given: that runs the cell at that fraction of its scale on whatever
JAX has (Pallas interpreted off the TPU), and its last line says
"rehearsal" first and is never a result.
"""

import argparse
import contextlib
import json
import os
import resource
import shutil
import sys
import time
import types

_T0 = time.perf_counter()

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, ROOT)

# the compile cache: where the environment says, else a fixed place in the
# checkout (the path is part of the cache's key); every program is kept
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

# a traced run profiles the window's first seconds, up to a query's end
TRACE_SECONDS = 5.0


def emit(**kw):
    print(json.dumps(kw, default=str), flush=True)


def host_peak_bytes():
    """The process's peak resident set so far (Linux counts it in KiB): a
    configuration's size rule asks what the host had to hold."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def scaled(params, fraction):
    """The configuration's scale keys at a fraction (rehearsal only)."""
    out = dict(params)
    for k in params.get("scale_keys", ()):
        out[k] = max(1000, int(params[k] * fraction))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=float, default=0.0,
                    help="fraction of the cell's scale; any platform; "
                         "never a result")
    args = ap.parse_args()

    from harness import compare, mix, roofline, spec
    cell = spec.Cell(args.workload)
    if args.rehearse and cell.chips > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}")

    import jax
    import numpy as np

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse and device["platform"] != "tpu":
        sys.exit(f"no accelerator: platform is {device['platform']!r}")
    if len(devs) != cell.chips:
        sys.exit(f"cell {cell.name} asks for {cell.chips} device(s), "
                 f"JAX has {len(devs)}")
    if not args.rehearse:
        roofline.peaks(device["kind"])     # an unknown device is an error

    from harness.engine import Engine
    engine = Engine(interpret_pallas=bool(args.rehearse)
                    and device["platform"] != "tpu")

    # ------------------------------------------------------------- set-up
    params = cell.config
    if args.rehearse:
        params = scaled(params, args.rehearse)
    data_dir = os.path.join(ROOT, ".bench_data", cell.name)
    t = time.perf_counter()
    inputs = cell.generator.generate(params, args.seed, data_dir)
    gen_s = time.perf_counter() - t
    t = time.perf_counter()
    engine.load(inputs, cell.queries.values())
    load_s = time.perf_counter() - t
    warm = {}
    for name, q in cell.queries.items():
        t = time.perf_counter()
        engine.collect(q, engine.plan(q))
        warm[name] = time.perf_counter() - t
    at_setup = engine.counters()
    setup_s = time.perf_counter() - _T0
    emit(phase="setup", seed=args.seed, rows=inputs["rows"],
         generate_s=gen_s, load_s=load_s, warm_query_s=warm,
         setup_s=setup_s, host_peak_bytes=host_peak_bytes(),
         compile_cache_dir=jax.config.jax_compilation_cache_dir,
         **at_setup)

    # ------------------------------------------------------------- window
    trace_dir = os.path.join(ROOT, ".bench_data", cell.name + ".trace")
    spans = contextlib.ExitStack()      # open while the profiler traces
    traced = {"queries": 0, "seconds": 0.0}

    def span(name):
        if not args.trace:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    def stop_trace():
        spans.close()
        jax.profiler.stop_trace()
        traced.update(queries=len(answers), seconds=last_end - t_start)

    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        spans.enter_context(span("bench:window"))
    tracing = bool(args.trace)

    order = mix.sequence(cell.traffic, args.seed)
    answers = []          # (query name, answer or None)
    latencies, frontend = [], []
    t_start = time.perf_counter()
    last_end = t_start
    while time.perf_counter() - t_start < args.seconds:
        name = next(order)
        q = cell.queries[name]
        t0 = time.perf_counter()
        try:
            with span("bench:frontend"):
                lazy = engine.plan(q)
            t1 = time.perf_counter()
            with span("bench:execute+to_pandas"):
                got = engine.collect(q, lazy)
        except Exception as e:  # noqa: BLE001 - a failed query is counted
            emit(phase="window", query=name, error=repr(e)[:2000])
            got, t1 = None, time.perf_counter()
        last_end = time.perf_counter()
        answers.append((name, got))
        latencies.append(last_end - t0)
        frontend.append(t1 - t0)
        # the profiler traces the first seconds, up to a query's end
        if tracing and last_end - t_start >= TRACE_SECONDS:
            stop_trace()
            tracing = False
    if tracing:
        stop_trace()
    at_end = engine.counters()
    window_s = last_end - t_start
    done = [g for _, g in answers if g is not None]
    peak = engine.peak_bytes()
    in_window = {k: at_end[k] - at_setup[k] for k in at_end}
    emit(phase="window", queries_completed=len(done), window_s=window_s,
         query_seconds=latencies, peak_bytes_in_use=peak,
         host_peak_bytes=host_peak_bytes(), **in_window,
         **engine.report())

    # ------------------------------------------------------------ metrics
    # what the readers under layer_metrics/ are given
    run = types.SimpleNamespace(
        cell=cell, chips=cell.chips, device_kind=device["kind"],
        queries=len(done), window_s=window_s, latencies=latencies,
        frontend=frontend, counters=in_window, rows=inputs["rows"],
        bytes_per_query=float(np.mean(
            [roofline.query_bytes(cell.queries[n].reads, inputs["rows"])
             for n, _ in answers])) if answers else 0.0,
        traced_queries=traced["queries"], trace=None)

    metrics, breakdown = {}, None
    if not args.trace:
        values = {
            "setup_s": setup_s,
            "query_s": window_s / len(done) if done else None}
        for m in cell.end_to_end():
            if values.get(m["name"]) is None:
                sys.exit(f"end-to-end metric {m['name']} has no value")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        from harness.trace import Reduction, find_xplane
        t = time.perf_counter()
        run.trace = Reduction(find_xplane(trace_dir))
        patterns = []
        readers = {}
        for m in cell.bench["per_layer"]:
            readers[m["name"]] = spec.load_module("layer_metrics", m["name"])
            patterns += getattr(readers[m["name"]], "PATTERNS", [])
        for m in cell.per_layer():
            value = readers[m["name"]].read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        device["busy_s"] = run.trace.busy_mean_s()
        device["window_s"] = run.trace.window_s
        breakdown = {"device_ops": run.trace.device_ops(patterns),
                     "idle_gaps": run.trace.idle_gaps()}
        emit(phase="trace", file_bytes=os.path.getsize(run.trace.path),
             reduce_s=time.perf_counter() - t,
             traced_queries=traced["queries"],
             traced_s=traced["seconds"], window_from=run.trace.window_from,
             busy_s_by_device=run.trace.busy,
             programs=sorted(run.trace.module_seconds().items(),
                             key=lambda kv: -kv[1])[:40])
    device["memory_peak_bytes"] = max((p for p in peak if p is not None),
                                      default=None)

    # ------------------------------------------- correct (host, after all)
    t = time.perf_counter()
    refs = {n: q.reference().answer(inputs) for n, q in cell.queries.items()}
    gaps = [compare.answer_gap(got, refs[n]) for n, got in answers]
    limits = {}
    for q in cell.queries.values():
        for k, v in q.limits.items():
            limits[k] = min(v, limits.get(k, v))
    correct, compared = compare.judge(
        gaps, sum(g is None for _, g in answers), limits)
    emit(phase="compare", answers_compared=len(gaps),
         reference_and_compare_s=time.perf_counter() - t,
         host_peak_bytes=host_peak_bytes())

    result = {"correct": bool(correct), "attempted": len(answers),
              "failed": len(answers) - len(done), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    if args.rehearse:
        result = {"rehearsal": f"on {device['platform']} at "
                               f"{args.rehearse} of the cell's scale: "
                               "not a result", **result}
    for c in compared:
        print(f"compared {c['name']} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct = {bool(correct)}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
