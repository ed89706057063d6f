"""The comparison that decides `correct` has been shown to fail.

Each case drives `fault_driver.py` in a process of its own (a run holds
JAX, and four virtual devices need XLA_FLAGS before JAX starts): the rest
of a run with the timed path broken underneath has to print
`"correct": false`, and the same drive with nothing planted `true`.
Run by hand: `JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q`.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

CASES = [
    # fault, cell, fraction of scale, correct
    # tpch_q1 stands at 15,000,000 orders: 7,500 here, as before PR 30
    ("none", "tpch_q1", "0.0005", True),
    ("answer", "tpch_q1", "0.0005", False),
    ("count", "tpch_q1", "0.0005", False),
    ("half", "tpch_q1", "0.0005", False),
    # the same query on tpch_750k: 7,500 orders again
    ("none", "tpch_q1_750k", "0.01", True),
    ("answer", "tpch_q1_750k", "0.01", False),
    ("count", "tpch_q1_750k", "0.01", False),
    ("half", "tpch_q1_750k", "0.01", False),
    ("none", "tpch_q5", "0.01", True),
    ("answer", "tpch_q5", "0.01", False),
    ("half", "tpch_q5", "0.01", False),
    ("none", "taxi_1chip", "0.02", True),
    ("answer", "taxi_1chip", "0.02", False),
    ("count", "taxi_1chip", "0.02", False),
    ("half", "taxi_1chip", "0.02", False),
    # the shuffle path is taken from about 100,000 rows a device
    ("none", "taxi_4chip", "0.05", True),
    ("exchange", "taxi_4chip", "0.05", False),
    ("half", "taxi_4chip", "0.05", False),
]


def cells():
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        return {w["name"] for w in json.load(f)["workloads"]}


@pytest.mark.parametrize("fault,cell,fraction,correct", CASES)
def test_fault_reads_not_correct(fault, cell, fraction, correct):
    if cell not in cells():
        pytest.skip(f"{cell} is not a cell of BENCHMARK.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "fault_driver.py"), fault, cell,
         fraction], env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert "rehearsal" in last
    assert last["correct"] is correct, last["compared"]
