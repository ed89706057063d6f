"""Self-tests of the three readers of the engine's host reads and
executor spans: `host_syncs`, `sync_idle_ms` and `exec_host_ms`, on
made-up intervals (a reduction's host half and one device's operations,
without a file), and on the two traces recorded on the chip under
`selftest/`, which a program without those spans wrote.
Run by hand: `JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q`.
"""

import gzip
import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
from harness import spec, trace  # noqa: E402

READERS = ("host_syncs", "sync_idle_ms", "exec_host_ms")


def made_up(host, ops, window, queries=1):
    """A run whose trace holds the host spans `host` [(name, start,
    end)] and one device whose operations cover `ops` [(start, end)],
    in seconds."""
    r = trace.Reduction.__new__(trace.Reduction)
    names, s, e = zip(*host) if host else ((), (), ())
    r.host = (np.asarray(names, object), np.asarray(s, float) * 1e9,
              np.asarray(e, float) * 1e9)
    r.window_ns = (window[0] * 1e9, window[1] * 1e9)
    os_, oe = (np.asarray([a for a, _ in ops], float) * 1e9,
               np.asarray([b for _, b in ops], float) * 1e9)
    r.devices = {0: {"ops": (["op"] * len(ops), os_, oe)}}
    r.busy = {0: trace.union_ns(*r._clip(os_, oe))[0] / 1e9}
    return types.SimpleNamespace(trace=r, traced_queries=queries)


def read(name, run):
    return spec.load_module("layer_metrics", name).read(run)


def test_host_syncs_counts_the_spans_that_start_inside_the_window():
    run = made_up([("bodo:sync.key_range", 0.5, 1.5),   # starts before
                   ("bodo:sync.key_range", 2.0, 2.1),
                   ("bodo:sync.probe_count", 3.0, 3.2),
                   ("bodo:sync.build_dup", 9.5, 9.6),   # starts after
                   ("bodo:query", 1.5, 9.0)],
                  [(1.0, 2.0)], (1.0, 9.0), queries=2)
    assert read("host_syncs", run) == pytest.approx(1.0)
    # a program that writes exec spans and no sync reads 0
    run = made_up([("bodo:exec.observe", 2.0, 2.5)], [(1.0, 2.0)],
                  (1.0, 9.0))
    assert read("host_syncs", run) == 0.0


def test_sync_idle_ms_counts_gaps_a_sync_ends_in_up_to_the_answer():
    host = [
        # gap [2, 4]: the sync ends at 2.5, the answer's span opens at 3
        ("bodo:sync.probe_count", 1.5, 2.5),
        ("bodo:to_pandas", 3.0, 3.5),
        # gap [5, 6]: no sync ends in it: nothing counted
        ("bodo:exec.observe", 5.2, 5.4),
        # gap [7, 9.5] runs past the window's end at 9: a sync ends at
        # 7.5, nothing else opens, so [7, 9] counts
        ("bodo:sync.build_dup", 6.8, 7.5),
        # a sync ending inside the device's work is no idle of its own
        ("bodo:sync.key_range", 0.5, 1.2),
    ]
    run = made_up(host, [(0.0, 2.0), (4.0, 5.0), (6.0, 7.0)], (0.5, 9.0),
                  queries=2)
    assert read("sync_idle_ms", run) == pytest.approx(
        1e3 * ((3.0 - 2.0) + (9.0 - 7.0)) / 2)
    # a gap that opens under a span read elsewhere counts nothing,
    # whichever of the three it is
    for other in ("bodo:to_pandas", "bodo:plan.optimize", "bench:frontend"):
        run = made_up([(other, 1.9, 3.0), ("bodo:sync.x", 1.0, 2.5)],
                      [(0.0, 2.0), (4.0, 5.0)], (0.0, 5.0))
        assert read("sync_idle_ms", run) == 0.0, other
    # no sync anywhere, an exec span: a reading of 0
    run = made_up([("bodo:exec.observe", 2.5, 3.0)],
                  [(0.0, 2.0), (4.0, 5.0)], (0.0, 5.0))
    assert read("sync_idle_ms", run) == 0.0


def test_exec_host_ms_is_a_union():
    run = made_up([("bodo:exec.replan", 1.0, 3.0),
                   ("bodo:exec.observe", 2.0, 4.0),     # overlaps: once
                   ("bodo:exec.observe", 6.0, 7.0),
                   ("bodo:exec.observe", 8.5, 9.5),     # cut at 9
                   ("bodo:sync.x", 5.0, 5.5)],
                  [(0.0, 1.0)], (0.0, 9.0), queries=4)
    assert read("exec_host_ms", run) == pytest.approx(1e3 * 4.5 / 4)
    run = made_up([("bodo:sync.x", 5.0, 5.5)], [(0.0, 1.0)], (0.0, 9.0))
    assert read("exec_host_ms", run) == 0.0


@pytest.mark.parametrize("expected", ["expected.json", "expected_taxi.json"])
def test_nothing_to_read_in_a_trace_without_the_spans(expected, tmp_path):
    with open(os.path.join(BENCH, "selftest", expected)) as f:
        want = json.load(f)
    path = str(tmp_path / "recorded.xplane.pb")
    with gzip.open(os.path.join(BENCH, "selftest", want["file"])) as f, \
            open(path, "wb") as out:
        out.write(f.read())
    run = types.SimpleNamespace(trace=trace.Reduction(path),
                                traced_queries=want.get("traced_queries", 1))
    for name in READERS:
        assert read(name, run) is None, name
