#!/usr/bin/env python3
"""Readings of the control at a cell's own size: the plain reference in
float32 put in the program's place, judged as a run's answers are.

    python3 benchmarks/tools/control.py <cell> <seed> [<seed> ...]

Host only (pandas, numpy): it touches neither JAX nor the chip. One JSON
line a seed and query; `correct` has to read false on each.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))


def main():
    from harness import compare, spec
    cell = spec.Cell(sys.argv[1])
    for seed in map(int, sys.argv[2:]):
        inputs = cell.generator.generate(
            cell.config, seed,
            os.path.join(ROOT, ".bench_data", cell.name + ".control"))
        for name, q in cell.queries.items():
            ref = q.reference().answer(inputs)
            low = q.reference().answer(inputs, precision="float32")
            ok, compared = compare.judge([compare.answer_gap(low, ref)], 0,
                                         q.limits)
            print(json.dumps({"cell": cell.name, "query": name, "seed": seed,
                              "control": "float32 reference",
                              "correct": ok, "compared": compared}),
                  flush=True)


if __name__ == "__main__":
    main()
