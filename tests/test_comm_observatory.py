"""Communication observatory tests: per-collective accounting against
known shuffle sizes, arrival-skew straggler attribution (in-process and
across a real spawned gang with an injected latency fault), rank-aware
critical-path analysis over a synthetic merged trace, the EXPLAIN
ANALYZE comm-vs-compute split, doctor comm triage, the
swallowed-collective lint rule, and live /metrics exposure of the
``bodo_tpu_comm_*`` family.

NOTE: the tier-1 runner executes modules in shared processes (this one
is isolated in runtests.py), and every test here restores the global
comm/tracing/telemetry state it touches.
"""

import json
import os
import re
import textwrap
import time

import numpy as np
import pandas as pd
import pytest

from bodo_tpu.config import config, set_config
from bodo_tpu.parallel import comm


@pytest.fixture(scope="module", autouse=True)
def _unpin_executables():
    """This module compiles sharded shuffle/groupby/gather programs on
    top of a suite that already runs near XLA:CPU's pinned-executable
    cliff (see runtests.py docstring); in a full single-process run the
    extra programs push test_tpch's 22-query compile set over it. Drop
    every jit cache on the way out so later modules recompile into a
    fresh budget instead of segfaulting."""
    yield
    import gc

    import jax

    from bodo_tpu.plan import fusion, physical
    physical._result_cache.clear()
    fusion.clear_programs()
    jax.clear_caches()
    gc.collect()


@pytest.fixture(autouse=True)
def _restore_config():
    """Tests arm shard_min_rows=0 (so tiny fixture tables shard) and
    tracing; in a shared-process suite run those knobs must not leak
    into later modules — sharding tiny tables flips their execution
    paths and output ordering."""
    prev_shard = config.shard_min_rows
    prev_tracing = config.tracing_level
    yield
    set_config(shard_min_rows=prev_shard, tracing_level=prev_tracing)


@pytest.fixture
def comm_reset():
    comm.reset()
    yield comm
    comm.reset()


def _sharded_table(n=4096, keys=16):
    from bodo_tpu.plan import physical
    from bodo_tpu.table.table import Table
    df = pd.DataFrame({"k": np.arange(n, dtype=np.int64) % keys,
                       "v": np.arange(n, dtype=np.float64)})
    return physical._maybe_shard(Table.from_pandas(df))


# ------------------------------------------------------- accounting

class TestAccounting:
    def test_shuffle_by_key_accounts_known_sizes(self, mesh8,
                                                 comm_reset):
        """The shuffle row's bytes match the governor's sizing of the
        actual input/output tables — gang accounting is real data, not
        an estimate."""
        from bodo_tpu import relational
        set_config(shard_min_rows=0)
        t = _sharded_table()
        out = relational.shuffle_by_key(t, ["k"])
        st = comm.stats()
        rows = {k: v for k, v in st["sites"].items()
                if k.startswith("shuffle_by_key@")}
        assert len(rows) == 1, st["sites"]
        r = next(iter(rows.values()))
        assert r["count"] == 1
        assert r["bytes_in"] == comm.table_bytes(t) > 0
        assert r["bytes_out"] == comm.table_bytes(out) > 0
        assert r["wall_s"] > 0

    def test_dispatcher_row_is_count_only(self, mesh8, comm_reset):
        """Relational dispatchers account count + input bytes + wait
        but no wall: the whole-op wall is compute-dominated and would
        corrupt the comm share."""
        from bodo_tpu import relational
        set_config(shard_min_rows=0)
        t = _sharded_table()
        relational.groupby_agg(t, ["k"], [("v", "sum", "vs")])
        ops = comm.per_op()
        assert "groupby_agg" in ops
        r = ops["groupby_agg"]
        assert r["count"] == 1
        assert r["bytes_in"] > 0
        assert r["wall_s"] == 0.0

    def test_gather_span_accounts_output(self, mesh8, comm_reset):
        from bodo_tpu import relational
        set_config(shard_min_rows=0)
        t = _sharded_table()
        g = relational.groupby_agg(t, ["k"], [("v", "sum", "vs")])
        if g.distribution != "1D":
            pytest.skip("groupby result not sharded on this mesh")
        out = g.gather()
        r = comm.per_op()["gather"]
        assert r["count"] == 1
        assert r["bytes_out"] == comm.table_bytes(out) > 0
        assert r["wall_s"] > 0

    def test_off_switch_is_total(self, mesh8, comm_reset, monkeypatch):
        """comm_accounting=False: no rows, no trace spans, and the
        span CM yields an inert dict (the <2%% overhead story)."""
        monkeypatch.setattr(config, "comm_accounting", False)
        comm.record("psum", bytes_in=123)
        with comm.collective_span("gather", bytes_in=9) as sp:
            sp["bytes_out"] = 9
        assert comm.stats()["dispatches"] == 0
        assert comm.stats()["sites"] == {}

    def test_skew_head_shape(self, comm_reset):
        comm.record("psum", site="q.py:1", bytes_in=10, wait_s=0.5)
        comm.record("psum", site="q.py:1", bytes_in=10, wait_s=0.1)
        comm.record("gather", site="q.py:2", bytes_out=10,
                    wall_s=0.2)
        h = comm.skew_head()
        assert h["dispatches"] == 3
        assert h["max_wait_s"] == 0.5
        assert h["max_wait_site"] == "psum@q.py:1"
        assert 0 < h["wait_frac"] < 1
        assert h["last_op"] == "gather" and h["last_seq"] == 3
        json.dumps(h)

    def test_profile_has_comm_rows(self, mesh8, comm_reset):
        """tracing.profile() synthesizes comm:<op> rows from the
        synced gauges — the per-query console view shows the comm
        bill next to the operator bill."""
        from bodo_tpu import relational
        from bodo_tpu.utils import tracing
        set_config(tracing_level=1, shard_min_rows=0)
        try:
            tracing.reset()
            t = _sharded_table()
            relational.shuffle_by_key(t, ["k"])
            prof = tracing.profile()
            row = prof.get("comm:shuffle_by_key")
            assert row, sorted(prof)
            assert row["count"] >= 1
            assert row["bytes_in"] > 0 and row["bytes_out"] > 0
        finally:
            set_config(tracing_level=0)
            tracing.reset()


# ---------------------------------------------- critical path (unit)

def _synthetic_trace():
    """Deterministic 2-rank merged trace: rank 0 is the straggler (its
    scan runs 100us while rank 1 finishes in 30us and then waits 70us
    at the shuffle rendezvous)."""
    def ev(name, rank, ts, dur, **args):
        args.setdefault("query_id", "q1")
        return {"name": name, "ph": "X", "ts": ts, "dur": dur,
                "pid": rank, "tid": 0, "args": args}
    return {
        "ranks": [0, 1],
        "query_ids": ["q1"],
        "traceEvents": [
            ev("scan", 0, 0, 100),
            ev("scan", 1, 0, 30),
            ev("comm:shuffle_by_key", 0, 100, 20, wait_s=0.0,
               site="q.py:5", bytes_in=1000, bytes_out=1000),
            ev("comm:shuffle_by_key", 1, 30, 90, wait_s=0.07,
               site="q.py:5", bytes_in=1000, bytes_out=1000),
            ev("agg", 0, 121, 30),
            ev("agg", 1, 125, 50),
        ],
    }


class TestCriticalPath:
    def test_chain_hops_ranks(self):
        from bodo_tpu.analysis import critical_path
        cp = critical_path.critical_path(_synthetic_trace(), "q1")
        names = [(p["name"], p["rank"]) for p in cp["path"]]
        # ends at rank 1's agg (175us), routes through rank 0's comm
        # span (later start than rank 1's at the same end time), back
        # to rank 0's slow scan
        assert names == [("scan", 0), ("comm:shuffle_by_key", 0),
                         ("agg", 1)]
        assert cp["wall_us"] == 175.0
        assert cp["comm_us"] == 20.0
        assert cp["compute_us"] == 150.0
        assert 0 < cp["comm_frac"] < 1

    def test_straggler_is_min_wait_rank(self):
        from bodo_tpu.analysis import critical_path
        st = critical_path.straggler(_synthetic_trace())
        assert st["straggler_rank"] == 0  # everyone waits FOR rank 0
        assert st["confident"]
        assert st["skew_s"] == pytest.approx(0.07)
        assert st["dominant_site"] == "shuffle_by_key@q.py:5"

    def test_analyze_bundle_shape(self):
        from bodo_tpu.analysis import critical_path
        a = critical_path.analyze(_synthetic_trace())
        assert "q1" in a["queries"]
        assert a["overall"]["n_events"] == 6
        assert a["comm_ops"]["shuffle_by_key"]["count"] == 2
        assert a["straggler"]["straggler_rank"] == 0
        json.dumps(a)

    def test_empty_and_single_rank(self):
        from bodo_tpu.analysis import critical_path
        assert critical_path.critical_path({"traceEvents": []}) is None
        one = {"traceEvents": [
            {"name": "comm:psum", "ph": "X", "ts": 0, "dur": 5,
             "pid": 0, "args": {"wait_s": 0.5}}]}
        assert critical_path.straggler(one) is None  # needs 2 ranks


class TestCriticalPathDegenerate:
    """Degenerate triage inputs must yield a compute-only verdict (or
    None), never raise — doctor runs over whatever a dying gang managed
    to flush."""

    def test_single_rank_compute_only(self):
        from bodo_tpu.analysis import critical_path
        tr = {"ranks": [0], "query_ids": ["q1"], "traceEvents": [
            {"name": "scan", "ph": "X", "ts": 0, "dur": 40, "pid": 0,
             "args": {"query_id": "q1"}},
            {"name": "agg", "ph": "X", "ts": 40, "dur": 10, "pid": 0,
             "args": {"query_id": "q1"}},
        ]}
        cp = critical_path.critical_path(tr, "q1")
        assert cp["comm_us"] == 0.0
        assert cp["comm_frac"] == 0.0
        assert all(p["kind"] == "compute" for p in cp["path"])
        a = critical_path.analyze(tr)
        assert a["straggler"] is None       # one rank: nothing to skew
        assert a["comm_ops"] == {}
        assert a["overall"]["comm_frac"] == 0.0
        json.dumps(a)

    def test_zero_comm_spans_multi_rank(self):
        from bodo_tpu.analysis import critical_path
        tr = {"ranks": [0, 1], "traceEvents": [
            {"name": "scan", "ph": "X", "ts": 0, "dur": 30, "pid": 0},
            {"name": "scan", "ph": "X", "ts": 0, "dur": 35, "pid": 1},
        ]}
        a = critical_path.analyze(tr)
        assert a["straggler"] is None       # no comm spans, no waits
        assert a["overall"]["comm_us"] == 0.0
        assert a["overall"]["comm_frac"] == 0.0

    def test_zero_duration_events(self):
        from bodo_tpu.analysis import critical_path
        tr = {"traceEvents": [
            {"name": "mark", "ph": "X", "ts": 5, "dur": 0, "pid": 0}]}
        cp = critical_path.critical_path(tr)
        assert cp is not None
        assert cp["comm_frac"] == 0.0       # total==0 guard, no divide
        assert cp["wall_us"] == 0.0

    def test_unknown_query_id(self):
        from bodo_tpu.analysis import critical_path
        tr = _synthetic_trace()
        assert critical_path.critical_path(tr, "nope") is None
        tr2 = dict(tr, query_ids=["q1", "nope"])
        a = critical_path.analyze(tr2)
        assert set(a["queries"]) == {"q1"}  # absent query just skipped

    def test_two_field_lockstep_lines_no_comm_triage(self, tmp_path):
        """Legacy 2-field `seq\\tfingerprint` lockstep lines carry no
        arrival stamps: fingerprint triage still works, arrival-skew
        attribution degrades to None instead of raising."""
        from bodo_tpu import doctor
        d = str(tmp_path / "bundle_2f")
        os.makedirs(d)
        for rank in (0, 1):
            with open(os.path.join(d, f"lockstep_{rank}.log"),
                      "w") as f:
                f.write("1\tpsum@q.py:7\n2\tall_gather@q.py:9\n"
                        "garbage line without tabs\n"
                        "notanint\tx@y:1\n")
        logs, arrivals = doctor._parse_lockstep_logs(d)
        assert logs[0] == {1: "psum@q.py:7", 2: "all_gather@q.py:9"}
        assert arrivals == {0: {}, 1: {}}
        assert doctor._triage_comm(logs, arrivals) is None
        t = doctor.triage(d)
        assert t["comm"] is None
        assert t["lockstep"]["head"] == 2


# ------------------------------------------------- EXPLAIN ANALYZE

class TestExplainComm:
    def test_comm_split_and_critical_marker(self, mesh8):
        import bodo_tpu.pandas_api as bd
        from bodo_tpu.plan import explain
        from bodo_tpu.utils import tracing
        set_config(tracing_level=1, shard_min_rows=0)
        comm.reset()
        try:
            tracing.reset()
            df = pd.DataFrame({"k": np.arange(2048) % 8,
                               "v": np.arange(2048.0)})
            b = bd.from_pandas(df)
            b.groupby("k", as_index=False).agg(
                s=("v", "sum")).to_pandas()
            txt = explain.explain_analyze()
            assert "EXPLAIN ANALYZE" in txt
            # the aggregate dispatched a collective: its node carries
            # the comm-wait vs compute split
            assert re.search(
                r"comm=\d+\.\d+s/compute=\d+\.\d+s", txt), txt
            # exactly one root-to-leaf chain is marked
            marked = [ln for ln in txt.splitlines()
                      if "on critical path" in ln]
            assert marked, txt
            chain = explain.critical_path()
            assert chain and chain[0] == "0"
            assert len(marked) == len(chain)
        finally:
            set_config(tracing_level=0)
            tracing.reset()
            comm.reset()


# ------------------------------------------------- doctor comm triage

def _write_bundle(d, *, delay=0.2, seqs=4, stamped=True):
    """Bundle whose rank-1 lockstep log arrives `delay` late at every
    dispatch (3-field lines); stamped=False writes legacy 2-field
    lines."""
    os.makedirs(d, exist_ok=True)
    ops = ["psum@q.py:7", "all_gather@q.py:9"]
    base = 1000.0
    for rank in (0, 1):
        with open(os.path.join(d, f"lockstep_{rank}.log"), "w") as f:
            for seq in range(1, seqs + 1):
                fp = ops[(seq - 1) % len(ops)]
                if stamped:
                    ts = base + seq + (delay if rank == 1 else 0.0)
                    f.write(f"{seq}\t{fp}\t{ts:.6f}\n")
                else:
                    f.write(f"{seq}\t{fp}\n")
    with open(os.path.join(d, "manifest.json"), "w") as f:
        json.dump({"reason": "chaos_probe", "iso_time": "t",
                   "faults_armed": [f"collective@1=latency:{delay}"]},
                  f)
    return d


class TestDoctorComm:
    def test_names_straggler_and_dominant_site(self, tmp_path):
        from bodo_tpu import doctor
        d = _write_bundle(str(tmp_path / "bundle_skew"))
        t = doctor.triage(d)
        cm = t["comm"]
        assert cm["straggler_rank"] == 1  # arrives last everywhere
        assert cm["confident"]
        assert cm["n_skewed_dispatches"] == 4
        assert cm["straggler_late_s"] == pytest.approx(0.8, abs=1e-3)
        # both ops skewed equally often; deterministic max tie-break
        assert cm["dominant_site"] in ("psum@q.py:7",
                                       "all_gather@q.py:9")
        rep = doctor.render(t)
        assert "STRAGGLER: rank 1" in rep
        assert "dominant collective:" in rep

    def test_legacy_two_field_logs_degrade(self, tmp_path):
        from bodo_tpu import doctor
        d = _write_bundle(str(tmp_path / "bundle_old"), stamped=False)
        t = doctor.triage(d)
        assert t["comm"] is None  # no stamps, no attribution
        assert t["lockstep"]["head"] == 4  # fingerprints still parse

    def test_merged_trace_embeds_critical_path(self, tmp_path):
        from bodo_tpu import doctor
        d = _write_bundle(str(tmp_path / "bundle_trace"))
        with open(os.path.join(d, "trace_merged.json"), "w") as f:
            json.dump(_synthetic_trace(), f)
        t = doctor.triage(d)
        cp = t["critical_path"]
        assert cp["straggler"]["straggler_rank"] == 0
        rep = doctor.render(t)
        assert "critical path:" in rep
        assert "trace straggler: rank 0" in rep


# ------------------------------------------------- lint: swallowed

_LINT_FIXTURE = textwrap.dedent('''
    def bad(t):
        try:
            out = shuffle_by_key(t, ["k"])
        except Exception:
            out = t
        return out

    def bad_bare(x):
        try:
            return psum(x, "shard")
        except:
            return x

    def ok_reraise(t):
        try:
            out = shuffle_by_key(t, ["k"])
        except Exception:
            cleanup()
            raise
        return out

    def ok_narrow(t):
        try:
            out = shuffle_by_key(t, ["k"])
        except ValueError:
            out = t
        return out

    def ok_exit(x):
        import os
        try:
            return psum(x, "shard")
        except BaseException:
            os._exit(137)

    def ok_suppressed(t):
        try:
            # shardcheck: ignore[swallowed-collective]
            out = shuffle_by_key(t, ["k"])
        except Exception:
            out = t
        return out
''')


class TestSwallowedCollectiveLint:
    def _lint(self, tmp_path, src):
        from bodo_tpu.analysis import lint
        p = tmp_path / "fix.py"
        p.write_text(src)
        return lint.lint_file(str(p), root=str(tmp_path))

    def test_fixture_matrix(self, tmp_path):
        fs = self._lint(tmp_path, _LINT_FIXTURE)
        hits = [f for f in fs if f.rule == "swallowed-collective"]
        assert sorted(f.func for f in hits) == ["bad", "bad_bare"], \
            [f.render() for f in fs]
        assert all("LockstepError" in f.message for f in hits)

    def test_package_triage_is_clean(self):
        """The engine keeps collectives out of broad exception traps
        (triage result, pinned): a new swallowing site fails here and
        the CI lint gate."""
        from bodo_tpu.analysis import lint
        fs = [f for f in lint.lint_package()
              if f.rule == "swallowed-collective"]
        assert fs == [], "\n".join(f.render() for f in fs)


# ------------------------------------------- live metrics / healthz

class TestMetricsExposure:
    def test_comm_family_in_exposition(self, mesh8, comm_reset):
        from bodo_tpu.utils import metrics
        comm.record("psum", site="q.py:1", bytes_in=1 << 20,
                    wait_s=0.05)
        comm.record("gather", site="q.py:2", bytes_out=1 << 10,
                    wall_s=0.2)
        text = metrics.expose_text()
        assert metrics.check_exposition(text) == [], \
            metrics.check_exposition(text)[:5]
        for fam in ("bodo_tpu_comm_dispatches_total",
                    "bodo_tpu_comm_bytes_total",
                    "bodo_tpu_comm_seconds_total",
                    "bodo_tpu_comm_max_wait_seconds",
                    "bodo_tpu_comm_dispatch_bytes",
                    "bodo_tpu_comm_dispatch_seconds"):
            assert fam in text, fam
        line = [ln for ln in text.splitlines() if ln.startswith(
            'bodo_tpu_comm_bytes_total{op="psum",direction="in"}')]
        assert line and float(line[0].split()[1]) == float(1 << 20)

    def test_healthz_and_sampler_carry_skew_head(self, mesh8,
                                                 comm_reset):
        from bodo_tpu.runtime import telemetry
        comm.record("psum", site="q.py:1", wait_s=0.4)
        doc = telemetry.health()
        assert doc["comm"]["max_wait_site"] == "psum@q.py:1"
        s = telemetry.sample()
        assert s["comm"]["dispatches"] == 1
        json.dumps(doc), json.dumps(s)

    def test_live_scrape(self, mesh8, comm_reset):
        import urllib.request
        from bodo_tpu.runtime import telemetry
        from bodo_tpu.utils import metrics
        comm.record("psum", site="q.py:1", bytes_in=64, wait_s=0.01)
        telemetry.shutdown_server()
        addr = telemetry.serve(0)
        try:
            with urllib.request.urlopen(
                    f"http://{addr}/metrics", timeout=10) as r:
                body = r.read().decode()
            assert metrics.check_exposition(body) == []
            assert "bodo_tpu_comm_dispatches_total" in body
            with urllib.request.urlopen(
                    f"http://{addr}/healthz", timeout=10) as r:
                doc = json.loads(r.read().decode())
            assert doc["comm"]["dispatches"] >= 1
        finally:
            telemetry.shutdown_server()


# --------------------------------------------------- chaos (gang)

@pytest.mark.slow_spawn
def test_chaos_latency_fault_attributed_and_doctored(monkeypatch,
                                                     tmp_path):
    """Acceptance: a latency fault injected at rank 1's collective
    dispatch point shows up as (a) peer-wait on rank 0 in the
    observatory (straggler = the rank with the SMALLEST own wait) and
    (b) a doctor comm triage naming rank 1 and the dominant collective
    site from the bundle's 3-field lockstep logs."""
    from bodo_tpu import doctor
    from bodo_tpu.spawn import run_spmd
    monkeypatch.setattr(config, "flight_dir", str(tmp_path))
    monkeypatch.setenv("BODO_TPU_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("BODO_TPU_LOCKSTEP", "1")

    def worker(rank):
        from bodo_tpu.analysis import lockstep
        from bodo_tpu.config import set_config as _set
        from bodo_tpu.parallel import comm as _comm
        from bodo_tpu.runtime import resilience, telemetry
        # same host-level sequence the relational dispatchers run:
        # fault point -> lockstep rendezvous -> comm accounting
        _set(faults="collective@1=latency:0.25:1:0")
        for op in ("groupby_agg", "sort_table", "groupby_agg"):
            resilience.maybe_inject("collective")
            wait = lockstep.pre_collective(op)
            _comm.record(op, bytes_in=1 << 16, wait_s=wait)
        # final rendezvous so rank 1's log is complete before rank 0
        # snapshots the shared gang dir into a bundle
        lockstep.pre_collective("barrier")
        bundle = None
        if rank == 0:
            bundle = telemetry.dump_bundle(
                "chaos_probe",
                gang_dir=os.environ["BODO_TPU_LOCKSTEP_DIR"])
        return {"rank": rank, "stats": _comm.stats(),
                "bundle": bundle}

    results = run_spmd(worker, 2, timeout=240)
    waits = [r["stats"]["wait_s"] for r in results]
    # rank 0 burned the injected delays as peer-wait; rank 1 (the
    # injected straggler) waited for nobody
    assert waits[0] > 3 * 0.25 * 0.8, waits
    assert waits[1] < waits[0] / 2, waits
    assert min(range(2), key=lambda r: waits[r]) == 1

    bundle = results[0]["bundle"]
    assert bundle and os.path.isdir(bundle)
    t = doctor.triage(bundle)
    cm = t["comm"]
    assert cm is not None, "no comm triage from bundle logs"
    assert cm["straggler_rank"] == 1
    assert cm["confident"]
    assert "dominant_site" in cm
    rep = doctor.render(t)
    assert "STRAGGLER: rank 1" in rep
    assert "dominant collective:" in rep
