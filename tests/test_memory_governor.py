"""Memory governor: auto-derived device budgets, admission control, and
the OOM-retry envelope (runtime/memory_governor.py + plan/physical.py).

These tests set NO `stream_device_budget_mb` — the point of the governor
is that spill engages by itself when the (artificially lowered, via the
`set_probe_for_testing` hook) derived budget is exceeded. The grant
floor `_MIN_GRANT` is lowered alongside so the tests stay small/fast.
"""

import threading

import numpy as np
import pandas as pd
import pytest

from bodo_tpu.config import config, set_config
from bodo_tpu.table.table import Table


@pytest.fixture
def fresh_gov():
    """Default config (governor on, no legacy budget), fresh governor."""
    from bodo_tpu.runtime.memory_governor import reset_governor
    set_config(stream_device_budget_mb=0, mem_governor=True)
    reset_governor()
    yield
    reset_governor()


@pytest.fixture
def tiny_floor(monkeypatch):
    """Shrink the forward-progress grant floor so budgets in the MiB
    range (not 16 MiB+) exercise the spill paths with small test data."""
    from bodo_tpu.runtime import memory_governor as mg
    monkeypatch.setattr(mg, "_MIN_GRANT", 1 << 20)
    yield


def test_derived_budget_nonzero_by_default(mesh8, fresh_gov):
    """Acceptance: with default config the governor derives a real,
    nonzero device budget (no knob set anywhere)."""
    from bodo_tpu.runtime.memory_governor import governor
    assert config.mem_governor and not config.stream_device_budget_mb
    gov = governor()
    b = gov.derived_budget()
    assert b > 0, "probe must yield a budget on CPU (host-RAM fraction)"
    assert gov.operator_budget() > 0
    s = gov.stats()
    assert s["enabled"] and s["derived_budget_bytes"] == b


def test_sort_spills_under_derived_budget(mesh8, fresh_gov, tiny_floor):
    """A sort whose state exceeds the (lowered) derived budget completes
    via governed run-parking — with NO stream_device_budget_mb set."""
    from bodo_tpu.plan.streaming_sharded import (ShardedStreamSort,
                                                 table_batches_sharded)
    from bodo_tpu.runtime.memory_governor import governor
    governor().set_probe_for_testing(4 << 20)  # op grant lands ~1.7 MiB
    r = np.random.default_rng(11)
    n = 200_000  # ~3.2 MB of int64+float64 state: exceeds the grant
    df = pd.DataFrame({"k": r.permutation(n).astype(np.int64),
                       "x": r.normal(size=n)})
    ss = ShardedStreamSort(["k"], [True], True)
    assert 0 < ss.budget < (4 << 20)
    for b in table_batches_sharded(Table.from_pandas(df).shard(), 8192):
        assert ss.push(b)
    assert ss.runs, "derived budget must force parked runs"
    out = ss.finish().to_pandas()
    assert len(out) == n
    np.testing.assert_array_equal(out["k"].to_numpy(),
                                  np.arange(n, dtype=np.int64))
    np.testing.assert_allclose(out["x"].to_numpy(),
                               df.sort_values("k")["x"].to_numpy())
    ops = governor().stats()["operators"]
    assert ops["stream_sort"]["n_spills"] >= 1, ops
    assert ops["stream_sort"]["spilled_bytes"] > 0


def test_join_spills_under_derived_budget(mesh8, fresh_gov, tiny_floor):
    """A partitioned join whose build side exceeds the derived budget
    spills build chunks and still drains the correct result."""
    from bodo_tpu.plan.streaming_sharded import (ShardedPartitionedJoin,
                                                 table_batches_sharded)
    from bodo_tpu.runtime.memory_governor import governor
    governor().set_probe_for_testing(4 << 20)
    r = np.random.default_rng(12)
    nb = 150_000
    build = pd.DataFrame({"k": r.permutation(nb).astype(np.int64),
                          "w": r.normal(size=nb)})
    probe = pd.DataFrame({"k": r.integers(0, 2 * nb, 5000)
                          .astype(np.int64),
                          "y": r.normal(size=5000)})
    pj = ShardedPartitionedJoin(["k"], ["k"], "inner", ("_x", "_y"))
    for b in table_batches_sharded(Table.from_pandas(build).shard(), 8192):
        assert pj.push_build(b)
    assert pj.spilling, "derived budget must force spilled build chunks"
    outs = []
    for b in table_batches_sharded(Table.from_pandas(probe).shard(), 2048):
        out = pj.probe(b)
        if out is not None:
            outs.append(out.to_pandas())
    for out in pj.drain():
        outs.append(out.to_pandas())
    got = pd.concat(outs, ignore_index=True)
    exp = probe.merge(build, on="k", how="inner")
    assert len(got) == len(exp)
    g = got.sort_values(["k", "y"]).reset_index(drop=True)
    e = exp.sort_values(["k", "y"]).reset_index(drop=True)
    np.testing.assert_allclose(g["w"].to_numpy(), e["w"].to_numpy())
    ops = governor().stats()["operators"]
    assert ops["stream_join"]["n_spills"] >= 1, ops


def test_oom_retry_reruns_stage(mesh8, fresh_gov):
    """Acceptance: a RESOURCE_EXHAUSTED from a pipeline stage is caught
    at the stage boundary, the fattest grant is halved, and the stage
    re-runs to completion (injected through the resilience fault
    registry — the same `stage.boundary` point chaos runs arm via
    BODO_TPU_FAULTS, replacing the old _exec_inner monkeypatch)."""
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import physical
    from bodo_tpu.runtime import memory_governor as mg
    from bodo_tpu.runtime import resilience

    gov = mg.governor()
    gov.set_probe_for_testing(256 << 20)
    hold = gov.admit("victim_state")  # the grant handle_oom will shrink
    try:
        assert hold.budget > mg._MIN_GRANT
        before = hold.budget
        # another file's chaos test may share this worker and leave
        # its own `stage.boundary` firing in the process-wide count
        resilience.reset_stats()
        set_config(faults="stage.boundary=raise:RESOURCE_EXHAUSTED:1:1")
        physical._result_cache.clear()
        df = pd.DataFrame({"k": [3, 1, 2], "v": [1.0, 2.0, 3.0]})
        out = bd.from_pandas(df).sort_values("k").to_pandas()
        assert out["k"].tolist() == [1, 2, 3]
        assert resilience.stats()["faults_fired"]["stage.boundary"] == 1, \
            "stage must have been attempted with the fault armed"
        assert gov.n_oom_retries >= 1
        assert hold.budget == before // 2, "fattest grant must be halved"
        assert gov.stats()["n_oom_retries"] >= 1
    finally:
        set_config(faults="")
        resilience.reset_stats()
        hold.release()


def test_oom_retry_gives_up_without_progress(mesh8, fresh_gov,
                                             monkeypatch):
    """When nothing is left to shrink or spill, the OOM is re-raised
    instead of looping."""
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import physical
    from bodo_tpu.runtime import memory_governor as mg

    mg.governor().set_probe_for_testing(256 << 20)

    def always_oom(node):
        raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory.")

    monkeypatch.setattr(physical, "_exec_inner", always_oom)
    physical._result_cache.clear()
    df = pd.DataFrame({"k": [2, 1]})
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        bd.from_pandas(df).sort_values("k").to_pandas()


def test_non_oom_errors_pass_through(mesh8, fresh_gov, monkeypatch):
    """Ordinary stage errors must not be swallowed or retried."""
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import physical

    calls = [0]

    def broken(node):
        calls[0] += 1
        raise ValueError("schema mismatch")

    monkeypatch.setattr(physical, "_exec_inner", broken)
    physical._result_cache.clear()
    df = pd.DataFrame({"k": [2, 1]})
    with pytest.raises(ValueError, match="schema mismatch"):
        bd.from_pandas(df).sort_values("k").to_pandas()
    assert calls[0] == 1, "non-OOM errors must not be retried"


def test_admission_reduced_grant_under_pressure(fresh_gov):
    """When active grants oversubscribe the budget, a new request gets
    the remaining slice (forcing its spill mode) instead of blocking."""
    from bodo_tpu.runtime import memory_governor as mg
    gov = mg.governor()
    gov.set_probe_for_testing(160 << 20)  # derived 136 MiB, op slice 68
    op = gov.operator_budget()
    g1 = gov.admit("op_a")
    assert g1.budget == op
    g2 = gov.admit("op_b", want=op // 2)
    assert g2.budget == op // 2
    g3 = gov.admit("op_c")  # only op//2 left: reduced grant
    assert mg._MIN_GRANT <= g3.budget < op
    g1.release(); g2.release(); g3.release()
    g4 = gov.admit("op_d")  # releases restored the full slice
    assert g4.budget == op
    g4.release()
    g4.release()  # idempotent


def test_admission_queues_then_proceeds(fresh_gov, monkeypatch):
    """A fully oversubscribed request queues and wakes on release."""
    from bodo_tpu.runtime import memory_governor as mg
    monkeypatch.setattr(mg, "_ADMIT_TIMEOUT_S", 10.0)
    gov = mg.governor()
    gov.set_probe_for_testing(40 << 20)  # derived 34 MiB, op slice 17
    g1 = gov.admit("op_a")
    g2 = gov.admit("op_b")  # free now < _MIN_GRANT
    got = {}

    def admit_blocked():
        got["g"] = gov.admit("op_c")

    t = threading.Thread(target=admit_blocked)
    t.start()
    threading.Timer(0.2, g1.release).start()
    t.join(timeout=8.0)
    assert not t.is_alive(), "queued admit must wake on release"
    assert got["g"].budget >= mg._MIN_GRANT
    assert gov.n_queued >= 1
    got["g"].release()
    g2.release()


def test_legacy_budget_still_wins(fresh_gov):
    """An explicit stream_device_budget_mb bypasses the governor with
    the exact legacy grant."""
    from bodo_tpu.runtime.memory_governor import governor, reserve
    set_config(stream_device_budget_mb=3)
    try:
        g = governor().admit("x", want=1 << 30)
        assert g.budget == 3 << 20
        g.release()
        with reserve("y", 1 << 30) as r:
            assert r is None  # reserve() is a no-op under a legacy budget
    finally:
        set_config(stream_device_budget_mb=0)


def test_governor_off_is_unbounded(fresh_gov):
    """mem_governor=False restores the old default: budget 0, no park."""
    from bodo_tpu.runtime.memory_governor import governor
    set_config(mem_governor=False)
    try:
        g = governor().admit("x")
        assert g.budget == 0
        assert not g.over_budget(1 << 40)
        g.release()
        s = governor().stats()
        assert not s["enabled"]
    finally:
        set_config(mem_governor=True)


def test_stats_account_grant_lifecycle(fresh_gov):
    """Peak/spill accounting survives release into the retired table and
    shows up in the tracing profile as mem:<operator> rows."""
    from bodo_tpu.runtime.memory_governor import governor
    from bodo_tpu.utils import tracing
    gov = governor()
    gov.set_probe_for_testing(160 << 20)
    g = gov.admit("probe_op")
    g.update(5 << 20)
    g.record_spill(5 << 20)
    g.update(2 << 20)
    g.release()
    m = gov.stats()["operators"]["probe_op"]
    assert m["peak"] == 5 << 20
    assert m["spilled_bytes"] == 5 << 20
    assert m["n_spills"] == 1 and m["count"] == 1
    prof = tracing.profile()
    assert prof["mem:probe_op"]["spilled_bytes"] == 5 << 20
