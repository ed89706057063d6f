"""Self-tests of the yardstick: the trace reduction on a recorded trace,
the roofline's bytes, what BENCHMARK.json names against the files, the
generators' shapes and recorded frames, the mix, and each plain reference
against the engine at rehearsal size on the CPU.
Run by hand: `JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q`.
"""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
from harness import mix, roofline, spec, trace  # noqa: E402


def benchmark_json():
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        return json.load(f)


# ------------------------------------------------------------------ trace
def test_union_of_intervals():
    ns, merged = trace.union_ns(np.array([0., 5., 20., 22.]),
                                np.array([10., 8., 25., 30.]))
    assert ns == 20.0
    assert merged.tolist() == [[0., 10.], [20., 30.]]
    assert trace.union_ns(np.array([]), np.array([]))[0] == 0.0


def test_short_names_and_self_time():
    hlo = ("%fusion.5 = (u32[384]{0:T(1024)S(1)}, u32[384]{0:T(1024)}) "
           "fusion(u32[384]{0:T(1024)S(1)} %all-to-all.2), kind=kCustom")
    assert trace.short_op(hlo) == "%fusion.5 fusion"
    # an operand that is a collective does not make its consumer one
    assert not trace.COLLECTIVE.search(trace.short_op(hlo))
    assert trace.COLLECTIVE.search(trace.short_op(
        "%all-to-all.3 = u32[4,128]{1,0} all-to-all(u32[4,128]{1,0} %p)"))
    assert trace.short_op("sort.0") == "sort.0"
    own = trace.self_ns(np.array([0., 10., 20., 200.]),
                        np.array([100., 20., 60., 300.]))
    assert own.tolist() == [50., 10., 40., 100.]


def test_reduction_on_recorded_trace(tmp_path):
    import gzip
    with open(os.path.join(BENCH, "selftest", "expected.json")) as f:
        want = json.load(f)
    path = str(tmp_path / "recorded.xplane.pb")
    with gzip.open(os.path.join(BENCH, "selftest", want["file"])) as f, \
            open(path, "wb") as out:
        out.write(f.read())
    r = trace.Reduction(path)
    assert r.platform == "tpu"
    assert sorted(r.devices) == want["devices"]
    busy = r.busy[r.busiest()]
    assert 0 < busy <= r.window_s
    assert busy == pytest.approx(want["busy_s"], rel=1e-9)
    assert r.window_s == pytest.approx(want["window_s"], rel=1e-9)
    mods = r.module_seconds()
    for name, s in want["module_seconds"].items():
        assert mods[name] == pytest.approx(s, rel=1e-9)
    fam = r.family_seconds(want["family_patterns"])
    other = r.other_seconds(want["family_patterns"])
    assert fam == pytest.approx(want["family_seconds"], rel=1e-9)
    # no program is dropped: family + other is every program's time
    assert fam + other == pytest.approx(sum(mods.values()), rel=1e-9)
    assert r.family_seconds(["no_such_program"]) is None
    gaps = r.idle_gaps()
    assert gaps and all(s > 0 for _, s in gaps)
    assert sum(s for _, s in gaps) <= r.window_s - busy + 1e-9
    ops = r.device_ops(want["family_patterns"])
    assert len(ops) <= 10 and any("other" in n for n, _ in ops)
    # operations' own times add up to the busy union: nothing twice
    assert sum(r.op_seconds().values()) == pytest.approx(busy, rel=1e-6)
    assert r.collective_seconds() is None


# --------------------------------------------------------------- roofline
def test_query_bytes_from_the_query_files():
    rows = {"lineitem": 1000, "orders": 100, "customer": 10, "supplier": 5,
            "nation": 25, "region": 5, "trips": 1000, "weather": 181}
    assert roofline.query_bytes(spec.Query("tpch_q1").reads, rows) \
        == 1000 * (4 + 4 + 8 * 5)
    assert roofline.query_bytes(spec.Query("tpch_q5").reads, rows) \
        == 1000 * 32 + 100 * 24 + 10 * 16 + 5 * 16 + 25 * 20 + 5 * 12
    assert roofline.query_bytes(spec.Query("taxi_weather").reads, rows) \
        == 1000 * (4 + 8 * 4) + 181 * 16


def test_peaks_unknown_device_raises():
    assert roofline.peaks("TPU v5 lite")["hbm_gb_per_s"] == 819.0
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    assert roofline.least_seconds(819e9, 1, "TPU v5 lite") \
        == pytest.approx(1.0)
    assert roofline.least_seconds(819e9, 4, "TPU v5 lite") \
        == pytest.approx(0.25)


def test_readers_state_what_benchmark_json_states():
    for m in benchmark_json()["per_layer"]:
        reader = spec.load_module("layer_metrics", m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) \
            == (m["layer"], m["unit"], m["moves"], m["source"]), m["name"]
        assert callable(reader.read)


# ------------------------------------------- BENCHMARK.json and the files
@pytest.mark.parametrize("config", [c["name"]
                                    for c in benchmark_json()["configs"]])
def test_configuration_file_says_what_it_runs(config):
    bench = benchmark_json()
    entry = {c["name"]: c for c in bench["configs"]}[config]
    path = os.path.join(BENCH, "..", entry["file"])
    assert os.path.isfile(path), entry["file"]
    with open(path) as f:
        conf = json.load(f)
    assert conf["name"] == config
    # the scale that runs is the step the ladder's rule chose
    for key in conf["scale_keys"]:
        cut = conf["cuts"][key]
        assert conf[key] == cut["here"], (key, conf[key], cut["here"])
        assert cut["here"] in cut["ladder"], (key, cut)
        assert cut["here"] <= cut["source"]
        # `reduced` names a scale key exactly where the source's is cut
        assert (key in entry["reduced"]) == (cut["here"] < cut["source"])
    assert set(entry["reduced"]) <= set(conf["scale_keys"])
    # no configuration is left without a cell
    assert any(w["config"] == config for w in bench["workloads"])


def test_every_name_in_benchmark_json_is_there():
    bench = benchmark_json()
    configs = {c["name"] for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        assert w["config"] in configs, w
        assert os.path.isfile(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json")), w
        assert spec.Cell(w["name"], bench).queries
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", ())) <= cells, m["name"]
    for m in bench["per_layer"]:
        assert m["moves"] in end_to_end, m["name"]


# ------------------------------------------------------------- generators
def frame_digest(df):
    h = hashlib.sha256()
    h.update(repr([(c, str(t)) for c, t in df.dtypes.items()]).encode())
    h.update(pd.util.hash_pandas_object(df, index=True).to_numpy().tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", [1, 2147483777, 2200000001])
def test_tpch_frames_are_the_recorded_ones(seed):
    """`gen/tpch.py` may be made leaner, never to draw other values: the
    six frames of configuration `tpch` cut to 7,500 orders, from three
    seeds, against digests recorded from the generator as PR 28 left it."""
    with open(os.path.join(BENCH, "selftest", "tpch_gen_hashes.json")) as f:
        want = json.load(f)
    with open(os.path.join(BENCH, "configs", want["config"] + ".json")) as f:
        params = dict(json.load(f), orders=want["orders"])
    frames = spec.load_module("gen", "tpch").generate(params, seed)["frames"]
    assert {t: frame_digest(df) for t, df in frames.items()} \
        == want["frames"][str(seed)]


def test_tpch_shapes_do_not_depend_on_the_seed():
    gen = spec.load_module("gen", "tpch")
    ref5 = spec.load_module("reference", "tpch_q5")
    p = {"orders": 20000, "structure_seed": 3}
    a, b = gen.generate(p, 1), gen.generate(p, 2147483659)
    assert a["rows"] == b["rows"]
    assert a["rows"]["lineitem"] == sum(1 + i % 7 for i in range(20000))
    la, lb = a["frames"]["lineitem"], b["frames"]["lineitem"]
    assert not la["l_extendedprice"].equals(lb["l_extendedprice"])
    # keys and dates are structure: the joins see the same keys
    for c in ("l_orderkey", "l_suppkey", "l_shipdate"):
        assert la[c].equals(lb[c])
    assert not la["l_returnflag"].equals(lb["l_returnflag"])
    assert la["l_orderkey"].is_monotonic_increasing
    for t in ("orders", "customer", "supplier", "nation", "region"):
        assert a["frames"][t].equals(b["frames"][t])
    ra, rb = ref5.answer(a), ref5.answer(b)
    assert len(ra) == len(rb) == 5
    assert not np.allclose(ra["revenue"], rb["revenue"])
    assert gen.generate(p, 1)["frames"]["lineitem"].equals(la)


def test_taxi_shapes_do_not_depend_on_the_seed(tmp_path):
    gen = spec.load_module("gen", "taxi")
    ref = spec.load_module("reference", "taxi_weather")
    p = {"rows": 50000, "structure_seed": 3}
    a = gen.generate(p, 1, str(tmp_path / "a"))
    b = gen.generate(p, 2147483659, str(tmp_path / "b"))
    assert a["rows"] == b["rows"] == {"trips": 50000, "weather": 181}
    ra, rb = ref.answer(a), ref.answer(b)
    assert len(ra) == len(rb)
    assert int(ra["trip_count"].sum()) == 50000
    assert not np.allclose(ra["avg_miles"], rb["avg_miles"])
    assert sorted(ra["trip_count"]) == sorted(rb["trip_count"])


def test_mix_gives_every_seed_the_same_multiset():
    traffic = {"loop": "closed", "clients": 1,
               "queries": [{"query": "a", "weight": 3},
                           {"query": "b", "weight": 1}]}
    for seed in (0, 5, 2147483659):
        it = mix.sequence(traffic, seed)
        block = [next(it) for _ in range(8)]
        assert sorted(block[:4]) == sorted(block[4:]) == ["a", "a", "a", "b"]
    with pytest.raises(SystemExit):
        next(mix.sequence({"loop": "open", "clients": 1, "queries": []}, 0))


# ------------------------------------------ references against the engine
def cells():
    return [w["name"] for w in benchmark_json()["workloads"]]


# fraction of a cell's scale that keeps its rehearsal at some thousands
# of orders or tens of thousands of rows (tpch_q1: 15,000 of 15,000,000)
REHEARSE = {"tpch_q1": "0.001"}


@pytest.mark.parametrize("cell", cells())
@pytest.mark.parametrize("trace_on", [0, 1])
def test_rehearsal_is_correct_and_never_a_result(cell, trace_on):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "2147483999", "--seconds", "2", "--trace", str(trace_on),
         "--rehearse", REHEARSE.get(cell, "0.02")],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert list(last)[0] == "rehearsal" and list(last)[-1] == "compared"
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    if trace_on:
        assert last["device"]["busy_s"] > 0
        assert "hbm_roofline_pct" not in last["metrics"]
        assert len(last["breakdown"]["device_ops"]) <= 10


def test_no_accelerator_is_a_failure_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         cells()[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "correct" not in out.stdout
