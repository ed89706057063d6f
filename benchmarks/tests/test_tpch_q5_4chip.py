"""Cell `tpch_q5_4chip` (PR 37): the cell rehearsed on four virtual
devices with the engine's two row thresholds cut as the scale is, so
that the rehearsal's plan is the cell's; planted faults on that drive;
and the three readers that say how a join's rows crossed chips
(`join_shuffle_routes`, `exchange_host_ms`, `shuffle_device_ms`) on
made-up spans and programs. The plain rehearsals and the configuration's
case are `test_selftests.py`'s, which finds new cells by itself.
Run by hand: `JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q`.
"""

import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
from harness import spec  # noqa: E402
from test_spans import made_up  # noqa: E402

CELL = "tpch_q5_4chip"
# 30,000 orders whatever step of the ladder the cell stands on
ORDERS = 30000


def scaled_env(fraction):
    """The environment of a rehearsal whose plan is the cell's: a table
    shards from 100,000 rows up and a build side is broadcast up to 2^20
    rows at the cell's scale, so both thresholds shrink as the rows do."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               BODO_TPU_SHARD_MIN_ROWS=str(max(1, int(100_000 * fraction))),
               BODO_TPU_BCAST_JOIN_THRESHOLD=str(int((1 << 20) * fraction)))
    env.pop("XLA_FLAGS", None)
    return env


def fraction():
    return ORDERS / spec.Cell(CELL).config["orders"]


def test_rehearsal_takes_the_cells_plan_and_reports_every_metric():
    f = fraction()
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147483999", "--seconds", "2", "--trace", "1",
         "--rehearse", repr(f)],
        env=scaled_env(f), capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert list(last)[0] == "rehearsal"
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"] == {**last["device"], "platform": "cpu",
                              "count": 4}
    cell = spec.Cell(CELL)
    # hbm_roofline_pct needs a device of peaks.json: the chip's alone
    want = {m["name"] for m in cell.per_layer()} - {"hbm_roofline_pct"}
    assert want <= set(last["metrics"]), want - set(last["metrics"])
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert m["compiles_in_window"] == 0
    # three tables are sharded, so rows cross chips for a join: a build
    # side replicated through the host, and no table scattered again
    assert m["exchange_host_ms"] > 0 and m["dist_host_ms"] > 0
    assert m["join_shuffle_routes"] >= 0 and m["shuffle_device_ms"] >= 0
    assert m["join_sort_routes"] >= 1
    gaps = dict(last["breakdown"]["idle_gaps"])
    assert not any("bodo:dist.shard" in k for k in gaps), gaps


@pytest.mark.parametrize("fault,correct", [("none", True), ("answer", False),
                                           ("half", False)])
def test_fault_reads_not_correct(fault, correct):
    f = fraction()
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "fault_driver.py"), fault, CELL,
         repr(f)], env=scaled_env(f), capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert "rehearsal" in last
    assert last["correct"] is correct, last["compared"]


# ------------------------------------------------------------ the readers
def run_of(names, starts, ends, programs=None, queries=2):
    trace = made_up(names, starts, ends, (0.0, 10e9))
    trace.module_seconds = lambda device=None: dict(programs or {})
    return types.SimpleNamespace(trace=trace, traced_queries=queries)


@pytest.fixture(scope="module")
def readers():
    return {n: spec.load_module("layer_metrics", n)
            for n in ("join_shuffle_routes", "exchange_host_ms",
                      "shuffle_device_ms")}


def test_readers_read_nothing_from_a_program_without_the_spans(readers):
    run = run_of(["bodo:query", "bodo:join.sort", "bodo:shuffle_by_key",
                  "bodo:dist.gather", "bodo:fused_join_group"],
                 [0, 1e9, 1.2e9, 3e9, 4e9], [9e9, 3e9, 1.8e9, 3.5e9, 5e9],
                 programs={"jit_join_sharded": 1.0})
    for name, reader in readers.items():
        assert reader.read(run) is None, name
    # the parent's program shuffles without saying so: its device time
    # is there to read, the spans are not
    run = run_of(["bodo:shuffle_by_key"], [1e9], [2e9],
                 programs={"jit_shuffle_by_key": 0.5})
    assert readers["shuffle_device_ms"].read(run) == pytest.approx(250.0)
    assert readers["join_shuffle_routes"].read(run) is None
    assert readers["exchange_host_ms"].read(run) is None


def test_zero_is_a_reading_where_every_join_broadcasts(readers):
    run = run_of(["bodo:query", "bodo:exchange.broadcast",
                  "bodo:dist.gather", "bodo:exchange.broadcast"],
                 # the second broadcast moves nothing: a span of no length
                 [0, 1e9, 1.1e9, 3e9], [9e9, 1.5e9, 1.4e9, 3e9],
                 programs={"jit_join_sharded": 2.0, "jit_fusedjoin": 0.5})
    assert readers["join_shuffle_routes"].read(run) == 0.0
    assert readers["shuffle_device_ms"].read(run) == 0.0
    assert readers["exchange_host_ms"].read(run) == pytest.approx(250.0)


def test_readers_on_a_query_that_shuffles(readers):
    run = run_of(
        ["bodo:exchange.shuffle", "bodo:shuffle_by_key",
         "bodo:shuffle_by_key", "bodo:exchange.broadcast",
         "bodo:exchange.shuffle", "bodo:exchange.shuffle",
         "bodo:dist.shard"],
        # the second shuffle starts before the window, the third after
        # it; nested spans count once
        [1e9, 1.1e9, 1.6e9, 3e9, -1e9, 11e9, 5e9],
        [2e9, 1.5e9, 1.9e9, 3.4e9, 0.5e9, 12e9, 6e9],
        programs={"jit_shuffle_by_key": 0.3,
                  "jit_shuffle_by_key(123)": 0.1,
                  "jit_join_sharded": 2.0})
    assert readers["join_shuffle_routes"].read(run) == pytest.approx(1 / 2)
    # [0, 0.5] + [1, 2] + [3, 3.4] seconds of the window, two queries
    assert readers["exchange_host_ms"].read(run) == pytest.approx(950.0)
    assert readers["shuffle_device_ms"].read(run) == pytest.approx(200.0)
    none = run_of(["bodo:exchange.shuffle"], [1e9], [2e9], queries=0)
    assert all(r.read(none) is None for r in readers.values())
