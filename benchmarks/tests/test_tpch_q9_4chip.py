"""Cell `tpch_q9_4chip`: the cell rehearsed on four virtual devices with
the engine's two row thresholds cut as the scale is, so that the
rehearsal's plan is the cell's, the first whose join hash-shuffles both
of its sides between chips; planted faults on that drive, the exchange
between chips among them; and the two readers of what a join's shuffle
recorded on its span (`shuffle_ici_pct`, `shuffle_skew`) on made-up
spans and programs. The plain rehearsals and the configuration's case
are `test_selftests.py`'s, which finds new cells by itself.
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
from test_tpch_q5_4chip import scaled_env  # noqa: E402

CELL = "tpch_q9_4chip"
# 30,000 orders whatever step of the ladder the cell stands on
ORDERS = 30000
# what only a chip's trace gives: peaks.json has the TPU's alone
ON_CHIP = {"hbm_roofline_pct", "shuffle_ici_pct"}


def fraction():
    return ORDERS / spec.Cell(CELL).config["orders"]


def test_rehearsal_shuffles_by_the_plans_rules_and_reports_every_metric():
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
    want = {m["name"] for m in spec.Cell(CELL).per_layer()} - ON_CHIP
    assert want <= set(last["metrics"]), want - set(last["metrics"])
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert m["compiles_in_window"] == 0
    # the pair join: neither side four times the other, so no broadcast
    assert m["join_shuffle_routes"] >= 1 and m["shuffle_device_ms"] > 0
    assert 1.0 <= m["shuffle_skew"] < 1.5


@pytest.mark.parametrize("fault,correct", [("none", True), ("answer", False),
                                           ("half", False),
                                           ("exchange", False)])
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
def _records(rows, columns, held):
    """What `relational.shuffle_by_key` writes on its span."""
    return {"rows": rows, "rows_crossed": rows * 3 // 4,
            "a2a_bytes": 16 * rows * 8, "rows_max": max(held),
            "rows_mean": sum(held) / len(held), "columns": columns}


def run_of(events, programs=None, platform="tpu", queries=2):
    """events: (name, start, end, arguments) of host spans."""
    names, starts, ends, _ = zip(*events) if events else ((), (), (), ())
    trace = made_up(list(names), list(starts), list(ends), (0.0, 10e9))
    trace.span_args = list(events)
    trace.platform = platform
    trace.module_seconds = lambda device=None: dict(programs or {})
    return types.SimpleNamespace(
        trace=trace, traced_queries=queries, cell=spec.Cell(CELL), chips=4,
        device_kind="TPU v5 lite")


@pytest.fixture(scope="module")
def readers():
    return {n: spec.load_module("layer_metrics", n)
            for n in ("shuffle_ici_pct", "shuffle_skew")}


LINEITEM = "t0__l_partkey t0__l_suppkey t0__l_quantity"
PARTSUPP = "t5__ps_partkey t5__ps_suppkey t5__ps_supplycost $f3"


def test_readers_read_nothing_without_a_joins_records(readers):
    # the parent's shuffle: `rows` and `keys` on its span, no records
    bare = run_of([("bodo:exchange.shuffle", 1e9, 3e9, {}),
                   ("bodo:shuffle_by_key", 1.1e9, 1.5e9, {"rows": 10})],
                  programs={"jit_shuffle_by_key": 0.5})
    # records, but of a shuffle outside any join (a group-by's), and of
    # one before the window
    apart = run_of([("bodo:shuffle_by_key", 1.1e9, 1.5e9,
                     _records(1000, LINEITEM, [250] * 4)),
                    ("bodo:exchange.shuffle", -2e9, -1e9, {}),
                    ("bodo:shuffle_by_key", -1.9e9, -1.5e9,
                     _records(1000, LINEITEM, [250] * 4))],
                   programs={"jit_shuffle_by_key": 0.5})
    for name, reader in readers.items():
        assert reader.read(bare) is None, name
        assert reader.read(apart) is None, name
        assert reader.read(run_of([])) is None, name


def test_readers_on_a_join_that_shuffles(readers):
    events = [("bodo:exchange.shuffle", 1e9, 3e9, {}),
              ("bodo:shuffle_by_key", 1.1e9, 1.5e9,
               _records(1_000_000, LINEITEM,
                        [240_000, 260_000, 250_000, 250_000])),
              ("bodo:shuffle_by_key", 1.6e9, 2.5e9,
               _records(3_200_000, PARTSUPP, [800_000] * 3 + [1_000_000]))]
    run = run_of(events, programs={"jit_shuffle_by_key": 0.01,
                                   "jit_join_sharded": 2.0})
    # the largest of the two: 1,000,000 over a mean of 850,000
    assert readers["shuffle_skew"].read(run) == pytest.approx(1e6 / 8.5e5)
    # (1.0M rows x 24 B + 3.2M x 32 B: `$f3`, no source column, counts
    # 8) x 3/4 to cross, over four chips at 200 GB/s each: 118.5 us of
    # the 10 ms the shuffle's programs took
    assert readers["shuffle_ici_pct"].read(run) == pytest.approx(1.185)
    # a fuller bucket is not more work: the share reads the rows given
    padded = [(n, s, e, {**a, "a2a_bytes": 2 * a.get("a2a_bytes", 0)})
              for n, s, e, a in events]
    assert readers["shuffle_ici_pct"].read(run_of(
        padded, programs={"jit_shuffle_by_key": 0.01})) == \
        pytest.approx(1.185)
    # off the chip there is no interconnect's peak to read against
    cpu = run_of(events, programs={"jit_shuffle_by_key": 0.01},
                 platform="host-standin")
    assert readers["shuffle_ici_pct"].read(cpu) is None
    assert readers["shuffle_skew"].read(cpu) == pytest.approx(1e6 / 8.5e5)
