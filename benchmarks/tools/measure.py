#!/usr/bin/env python3
"""The sets of runs a bound is set from, for one cell, in one call.

    python3 benchmarks/tools/measure.py <cell> [--seconds 51] [--sets 2]
        [--runs 6] [--traced 3] [--short 3] [--first-seed 2147484000]
        [--rehearse <fraction>]

Runs `benchmarks/run.py` as the driver does, one process a run, never
touching JAX itself: `--sets` sets of `--runs` runs with the same seeds
in every set, then `--traced` runs with --trace 1 and `--short` runs of
15 s, each on a seed of its own. Every run's output goes to
chiprun_out/measure/<cell>/, every result line to results.jsonl there,
and the spreads (interquartile distance over the median, by
`statistics.quantiles(n=4)`) are printed at the end. Beside each run: its
whole seconds from process start to exit and the peak resident set its
last line but one reports (the size rule of a configuration's ladder asks
for both). `--rehearse` passes
a fraction of the cell's scale on to every run, for trying a ladder's
lower steps on the chip: such lines are rehearsals, never results.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def phase(lines, name):
    return next((json.loads(x) for x in lines
                 if f'"phase": "{name}"' in x), {})


def one(cell, seed, seconds, trace, tag, out_dir, rehearse=0.0):
    t = time.time()
    cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    if rehearse:
        cmd += ["--rehearse", str(rehearse)]
    p = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT)
    with open(os.path.join(out_dir, f"{tag}.out"), "w") as f:
        f.write(p.stdout)
    with open(os.path.join(out_dir, f"{tag}.err"), "w") as f:
        f.write(p.stderr[-20000:])
    lines = p.stdout.strip().splitlines()
    rec = {"tag": tag, "seed": seed, "seconds": seconds, "trace": trace,
           "rc": p.returncode, "wall_s": time.time() - t}
    try:
        rec["result"] = json.loads(lines[-1])
        rec["setup"] = phase(lines, "setup")
        rec["queries"] = phase(lines, "window").get("queries_completed")
        rec["compare"] = phase(lines, "compare")
    except (IndexError, ValueError):
        rec["result"] = None
    with open(os.path.join(out_dir, "results.jsonl"), "a") as f:
        f.write(json.dumps(rec) + "\n")
    r = rec["result"] or {}
    print(tag, "rc", p.returncode, "correct", r.get("correct"),
          {k: v["value"] for k, v in r.get("metrics", {}).items()},
          [c["value"] for c in r.get("compared", [])
           if c["name"] == "float_rel_gap"],
          {k: rec.get("setup", {}).get(k)
           for k in ("generate_s", "load_s", "warm_query_s")},
          "queries", rec.get("queries"),
          {k: rec.get("compare", {}).get(k)
           for k in ("reference_and_compare_s", "host_peak_bytes")},
          "device_peak", r.get("device", {}).get("memory_peak_bytes"),
          f"{rec['wall_s']:.1f}s", flush=True)
    return rec


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seconds", type=int, default=51)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--traced", type=int, default=3)
    ap.add_argument("--short", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147484000)
    ap.add_argument("--rehearse", type=float, default=0.0)
    a = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out", "measure", a.cell)
    os.makedirs(out_dir, exist_ok=True)
    seeds = [a.first_seed + 7919 * i for i in range(a.runs)]
    sets = []
    for s in range(a.sets):
        sets.append([one(a.cell, seed, a.seconds, 0, f"set{s}.run{i}",
                         out_dir, a.rehearse)
                     for i, seed in enumerate(seeds)])
    nxt = a.first_seed + 7919 * a.runs
    for i in range(a.traced):
        one(a.cell, nxt + 7919 * i, 20, 1, f"traced{i}", out_dir, a.rehearse)
    nxt += 7919 * a.traced
    for i in range(a.short):
        one(a.cell, nxt + 7919 * i, 15, 0, f"short{i}", out_dir, a.rehearse)
    names = sorted({k for st in sets for r in st if r["result"]
                    for k in r["result"]["metrics"]})
    for name in names:
        row = {"metric": name}
        for s, st in enumerate(sets):
            v = [r["result"]["metrics"][name]["value"] for r in st
                 if r["result"]]
            # the first run of the first set may compile: set-up apart
            if name == "setup_s" and s == 0:
                row["first_run"] = v[0]
                v = v[1:]
            if len(v) >= 2:
                row[f"set{s}"] = {"median": statistics.median(v),
                                  "spread": spread(v), "values": v}
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
