"""Self-tests of the readers that read the engine's own spans and program
names (PR 27): `harness/spans.py` on made-up intervals, and the six
readers plus `idle_gaps()` on a trace recorded on the chip
(`selftest/taxi_rehearsal_v5e.xplane.pb.gz`) against values summed by
hand, without the harness (`selftest/expected_taxi.json`).
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
from harness import spans, spec, trace  # noqa: E402


def made_up(names, starts, ends, window):
    """A reduction's host half without a file."""
    r = trace.Reduction.__new__(trace.Reduction)
    r.host = (np.asarray(names, object), np.asarray(starts, float),
              np.asarray(ends, float))
    r.window_ns = window
    return r


def test_span_seconds_is_a_union_inside_the_window():
    r = made_up(["bodo:scan.fetch", "bodo:scan.split", "bodo:scan.column",
                 "bodo:scan.column", "bodo:to_pandas", "PjitFunction(f)"],
                [0e9, 1e9, 5e9, 9e9, 7e9, 0e9],
                [4e9, 2e9, 6e9, 12e9, 8e9, 12e9], (0.5e9, 10e9))
    # fetch holds split (nested, once); the last column is cut at the
    # window's end: [0.5, 4] + [5, 6] + [9, 10]
    assert spans.span_seconds(r, r"bodo:scan\.") == pytest.approx(5.5)
    assert spans.span_seconds(r, r"bodo:to_pandas$") == pytest.approx(1.0)
    # a program that writes no such span gives nothing to read
    assert spans.span_seconds(r, r"bodo:dist\.") is None
    assert spans.span_seconds(made_up([], [], [], (0, 1)), r"bodo:") is None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    with open(os.path.join(BENCH, "selftest", "expected_taxi.json")) as f:
        want = json.load(f)
    path = str(tmp_path_factory.mktemp("trace") / "recorded.xplane.pb")
    with gzip.open(os.path.join(BENCH, "selftest", want["file"])) as f, \
            open(path, "wb") as out:
        out.write(f.read())
    return want, trace.Reduction(path)


def test_new_readers_on_the_recorded_taxi_trace(recorded):
    want, r = recorded
    assert r.platform == "tpu" and sorted(r.devices) == want["devices"]
    assert r.window_from == trace.WINDOW_SPAN
    assert r.busy[r.busiest()] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r.window_s == pytest.approx(want["window_s"], rel=1e-9)
    run = types.SimpleNamespace(trace=r,
                                traced_queries=want["traced_queries"])
    for name, value in want["readers"].items():
        got = spec.load_module("layer_metrics", name).read(run)
        if value is None:       # dist_host_ms: nothing to read on one chip
            assert got is None, name
        else:
            assert got == pytest.approx(value, rel=1e-9), name
    # the families claim the device: what no family claims is small
    patterns = []
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        for m in json.load(f)["per_layer"]:
            patterns += getattr(spec.load_module("layer_metrics", m["name"]),
                                "PATTERNS", [])
    mods = r.module_seconds()
    for name, s in want["module_seconds"].items():
        assert mods[name] == pytest.approx(s, rel=1e-9)
    assert not [m for m in mods if m in (
        "jit_body", "jit_fused", "jit_sharded", "jit_rep", "jit_fn",
        "jit_bbody", "jit_pbody")]
    assert r.other_seconds(patterns) < 0.05 * sum(mods.values())


def test_idle_gaps_carry_the_engines_spans(recorded):
    want, r = recorded
    gaps = r.idle_gaps()
    assert [g[0] for g in gaps] == [g[0] for g in want["idle_gaps"]]
    # the hand sum breaks ties among equally long gaps at the cut of the
    # 400 longest in another order, which moves a microsecond at most
    for (_, got), (_, value) in zip(gaps, want["idle_gaps"]):
        assert got == pytest.approx(value, abs=2e-6)
    assert any("bodo:scan." in name for name, _ in gaps)
    labelled = sum(s for name, s in gaps if "bodo:" in name)
    assert 0 < labelled <= want["idle_s_labelled_bodo"] + 2e-6
