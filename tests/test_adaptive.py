"""Adaptive query execution (plan/adaptive.py + runtime/stats_store.py).

Each test forces a deliberate mis-estimate (config knob or the
estimate injector) and asserts BOTH that the adaptive correction
actually triggered (aqe:* counter) and that the answer is still right
(pandas / sqlite oracle differential).
"""

import json
import os
from contextlib import contextmanager

import numpy as np
import pandas as pd
import pytest

from tests.utils import _mode, check_func, check_sql


@contextmanager
def _aqe(**cfg):
    """Override config knobs + reset adaptive state for one test."""
    from bodo_tpu.config import config, set_config
    from bodo_tpu.plan import adaptive
    old = {k: getattr(config, k) for k in cfg}
    adaptive.reset()
    try:
        set_config(**cfg)
        yield adaptive
    finally:
        set_config(**old)
        adaptive.set_estimate_injector(None)
        adaptive.reset()


def _decisions():
    from bodo_tpu.plan import adaptive
    return adaptive.stats()["decisions"]


# ---------------------------------------------------------------------------
# broadcast promote / demote
# ---------------------------------------------------------------------------

def test_broadcast_promote_avoids_shuffle(mesh8):
    """bcast_join_threshold=0 plans a full shuffle for EVERY join; the
    runtime bytes-vs-budget check still broadcasts the small build side
    (the mis-estimated-join acceptance case)."""
    r = np.random.default_rng(0)
    left = pd.DataFrame({"k": r.integers(0, 40, 4000),
                         "v": r.normal(size=4000)})
    right = pd.DataFrame({"k": np.arange(40), "w": np.arange(40.0)})

    def fn(a, b):
        return a.merge(b, on="k")

    with _aqe(bcast_join_threshold=0):
        check_func(fn, [left, right], modes=["1d8"])
        assert _decisions().get("join:promote_broadcast", 0) >= 1, \
            _decisions()


def test_broadcast_demote_rep_build(mesh8):
    """A REPLICATED build side whose observed bytes blow the (shrunken)
    broadcast budget demotes to a shuffle join — and the answer holds."""
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.config import set_config
    r = np.random.default_rng(1)
    left = pd.DataFrame({"k": r.integers(0, 64, 5000),
                         "v": r.normal(size=5000)})
    right = pd.DataFrame({"k": np.arange(64), "w": np.arange(64.0)})
    exp = left.merge(right, on="k").sort_values(["k", "v"]).reset_index(
        drop=True)
    with _aqe(aqe_bcast_frac=1e-12, shard_min_rows=1000):
        # left (5000 rows) shards; right (64 rows) stays replicated —
        # the planned broadcast join — then AQE demotes it
        got = (bd.from_pandas(left).merge(bd.from_pandas(right), on="k")
               .to_pandas().sort_values(["k", "v"]).reset_index(drop=True))
        assert _decisions().get("join:demote_broadcast", 0) >= 1, \
            _decisions()
        set_config(shard_min_rows=1 << 60)
    pd.testing.assert_frame_equal(
        got, exp, check_dtype=False, check_like=True)


def test_broadcast_decision_static_when_disabled(mesh8):
    """aqe=False keeps the exact legacy rows-only heuristic."""
    r = np.random.default_rng(2)
    left = pd.DataFrame({"k": r.integers(0, 40, 4000),
                         "v": r.normal(size=4000)})
    right = pd.DataFrame({"k": np.arange(40), "w": np.arange(40.0)})

    def fn(a, b):
        return a.merge(b, on="k")

    with _aqe(aqe=False, bcast_join_threshold=0):
        check_func(fn, [left, right], modes=["1d8"])
        assert _decisions() == {}


# ---------------------------------------------------------------------------
# skew split
# ---------------------------------------------------------------------------

def test_skew_split_join(mesh8):
    """A hot probe key splits off into a broadcast join; the shuffle
    carries only the cold remainder. Inner and left joins, vs pandas."""
    r = np.random.default_rng(3)
    n = 4000
    keys = r.integers(0, 500, n)
    keys[: int(n * 0.6)] = 7  # one key owns 60% of the probe rows
    r.shuffle(keys)
    left = pd.DataFrame({"k": keys.astype(np.int64),
                         "v": r.normal(size=n)})
    right = pd.DataFrame({"k": np.arange(1001, dtype=np.int64),
                          "w": r.normal(size=1001)})

    for how in ("inner", "left"):
        def fn(a, b, _how=how):
            return a.merge(b, on="k", how=_how)

        with _aqe(aqe_skew_min_rows=1000):
            check_func(fn, [left, right], modes=["1d8"])
            d = _decisions()
            assert d.get("skew:detected", 0) >= 1, d
            assert d.get("skew:split_join", 0) >= 1, d


def test_skew_split_unmatched_and_gated(mesh8):
    """Hot keys ABSENT from the build side stay correct under left join
    (unmatched hot rows must not be dropped); nullable keys are gated
    out of the split entirely."""
    r = np.random.default_rng(4)
    n = 3000
    keys = np.where(np.arange(n) % 2 == 0, 99_999, r.integers(0, 50, n))
    left = pd.DataFrame({"k": keys.astype(np.int64),
                         "v": np.arange(n, dtype=np.float64)})
    # build side big enough that a broadcast doesn't pay (the skew path
    # only engages when the shuffle join was the plan)
    right = pd.DataFrame({"k": np.arange(1000, dtype=np.int64),
                          "w": np.arange(1000.0)})

    def fn(a, b):
        return a.merge(b, on="k", how="left")

    with _aqe(aqe_skew_min_rows=1000):
        check_func(fn, [left, right], modes=["1d8"])
        assert _decisions().get("skew:detected", 0) >= 1

    # nullable probe key: the split must not engage (Kleene semantics)
    leftn = left.copy()
    leftn["k"] = leftn["k"].astype("Int64")
    leftn.loc[::5, "k"] = None
    with _aqe(aqe_skew_min_rows=1000):
        check_func(fn, [leftn, right], modes=["1d8"])
        assert _decisions().get("skew:split_join", 0) == 0


def test_shuffle_skew_sketch_counter(mesh8):
    """A non-decomposable groupby (co-located shuffle path) over a
    skewed key bumps the shuffle skew sketch."""
    r = np.random.default_rng(5)
    n = 4000
    keys = r.integers(0, 300, n)
    keys[: int(n * 0.5)] = 3
    df = pd.DataFrame({"k": keys.astype(np.int64),
                       "v": r.integers(0, 20, n).astype(np.int64)})

    def fn(a):
        return a.groupby("k", as_index=False).agg(s=("v", "nunique"))

    with _aqe(aqe_skew_min_rows=1000, aqe_skew_frac=0.3):
        check_func(fn, [df], modes=["1d8"])
        assert _decisions().get("skew:detected", 0) >= 1, _decisions()


# ---------------------------------------------------------------------------
# streaming batch coalescing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["1d1", "1d8"])
def test_coalesce_streaming_batches(mesh8, mode):
    """Post-filter streaming batches far below the nominal batch size
    merge before the accumulator (both executors)."""
    r = np.random.default_rng(6)
    n = 8192
    df = pd.DataFrame({"k": r.integers(0, 16, n).astype(np.int64),
                       "v": r.normal(size=n),
                       "sel": r.integers(0, 100, n).astype(np.int64)})

    def fn(a):
        f = a[a.sel < 5]  # ~5% selectivity: near-empty batches
        return f.groupby("k", as_index=False).agg(s=("v", "sum"))

    with _aqe(stream_exec=True, streaming_batch_size=512):
        check_func(fn, [df], modes=[mode])
        assert _decisions().get("stream:coalesced", 0) >= 1, _decisions()
        assert _decisions().get("stream:batches", 0) >= 1


# ---------------------------------------------------------------------------
# q-error + estimate override
# ---------------------------------------------------------------------------

def test_qerror_and_profile_surface(mesh8):
    from bodo_tpu.plan import adaptive
    from bodo_tpu.utils import tracing
    import bodo_tpu.pandas_api as bd
    r = np.random.default_rng(7)
    df = pd.DataFrame({"k": r.integers(0, 10, 500),
                       "v": r.normal(size=500)})
    with _aqe():
        bd.from_pandas(df).groupby("k", as_index=False).agg(
            s=("v", "sum")).to_pandas()
        st = adaptive.stats()
        assert st["enabled"]
        assert st["q_error"]["count"] >= 1
        assert st["q_error"]["max"] >= 1.0
        prof = tracing.profile()
        assert "aqe:q_error" in prof
        assert prof["aqe:q_error"]["mean"] == st["q_error"]["mean"]
        dump = json.loads(tracing.dump())
        assert dump["aqe"]["q_error"]["count"] >= 1


def test_estimate_override_precedence(mesh8):
    """Observed rows beat the injector; the injector beats the
    structural estimate."""
    from bodo_tpu.plan import adaptive, logical as L, stats
    df = pd.DataFrame({"a": np.arange(100)})
    node = L.FromPandas(df)
    with _aqe():
        est, raw = stats.estimate(node)
        assert est == 100.0
        adaptive.set_estimate_injector(
            lambda n: 5000.0 if n is node else None)
        est, raw = stats.estimate(node)
        assert est == 5000.0 and raw == 5000.0
        adaptive._observed[node.key()] = 42.0
        est, raw = stats.estimate(node)
        assert est == 42.0


# ---------------------------------------------------------------------------
# mid-plan join re-optimization
# ---------------------------------------------------------------------------

def test_reoptimize_join_order(mesh8):
    """Planted mis-estimates pick a bad initial join order; once the
    leaves execute, observed cardinalities re-order the remaining joins
    (aqe:reoptimize:join_order) and the answer matches pandas."""
    from bodo_tpu.plan import adaptive, logical as L
    r = np.random.default_rng(8)
    a = pd.DataFrame({"k1": r.integers(0, 40, 2000).astype(np.int64),
                      "va": r.normal(size=2000)})
    b = pd.DataFrame({"k1": np.arange(40, dtype=np.int64),
                      "k2": (np.arange(40, dtype=np.int64) % 8),
                      "vb": r.normal(size=40)})
    c = pd.DataFrame({"k2": np.arange(8, dtype=np.int64),
                      "vc": r.normal(size=8)})
    exp = (a.merge(b, on="k1").merge(c, on="k2")
           .sort_values(["k1", "va"]).reset_index(drop=True))

    # lie at plan time: the big probe table looks tiny, the tiny dims
    # look huge — the greedy order comes out backwards
    def lie(node):
        if isinstance(node, L.FromPandas):
            n = node.table.nrows
            return 3.0 if n >= 2000 else 1e6
        return None

    import bodo_tpu.pandas_api as bd
    with _aqe() as aqe:
        aqe.set_estimate_injector(lie)
        with _mode("1d8"):
            got = (bd.from_pandas(a).merge(bd.from_pandas(b), on="k1")
                   .merge(bd.from_pandas(c), on="k2").to_pandas())
        assert _decisions().get("reoptimize:join_order", 0) >= 1, \
            _decisions()
    got = got.sort_values(["k1", "va"]).reset_index(drop=True)
    pd.testing.assert_frame_equal(got[exp.columns], exp,
                                  check_dtype=False)


# ---------------------------------------------------------------------------
# persistent stats store
# ---------------------------------------------------------------------------

def test_stats_store_roundtrip(mesh8, tmp_path):
    """Observed cardinalities persist to stats.json and feed estimates
    in a 'fresh process' (simulated by clearing in-memory state)."""
    from bodo_tpu.plan import adaptive, logical as L, stats
    from bodo_tpu.runtime import stats_store
    import bodo_tpu.pandas_api as bd
    r = np.random.default_rng(9)
    df = pd.DataFrame({"k": r.integers(0, 10, 777).astype(np.int64),
                       "v": r.normal(size=777)})
    with _aqe(stats_store_dir=str(tmp_path)):
        out = bd.from_pandas(df).groupby("k", as_index=False).agg(
            s=("v", "sum")).to_pandas()
        n_groups = len(out)
        stats_store.get_store().flush()
        path = os.path.join(str(tmp_path), "stats.json")
        assert os.path.exists(path)
        data = json.load(open(path))
        assert len(data) >= 1
        assert all("rows" in v for v in data.values())

        # same-shaped plan in a "new process": in-memory observations
        # cleared, store survives — the source estimate is now observed
        adaptive.reset()
        stats_store.reset_store()
        node = L.FromPandas(df.copy())
        est, raw = stats.estimate(node)
        assert est == 777.0 and raw == 777.0
        got = stats_store.get_store().lookup(stats_store.fingerprint(node))
        assert got == 777.0
        # aggregate output cardinality persisted too
        agg = L.Aggregate(node, ("k",), (("v", "sum", "s"),))
        ov = stats_store.get_store().lookup(stats_store.fingerprint(agg))
        assert ov is None or ov == n_groups  # key layout may differ


def test_stats_store_corrupt_and_eviction(tmp_path):
    from bodo_tpu.runtime import stats_store
    p = os.path.join(str(tmp_path), "stats.json")
    with open(p, "w") as f:
        f.write("{not json")
    s = stats_store.StatsStore(p)  # corrupt file: starts fresh
    assert len(s) == 0
    s.record("aa", 10)
    s.flush()
    assert json.load(open(p))["aa"]["rows"] == 10
    old_max = stats_store._MAX_ENTRIES
    stats_store._MAX_ENTRIES = 4
    try:
        for i in range(10):
            s.record(f"fp{i}", i)
        assert len(s) <= 5
    finally:
        stats_store._MAX_ENTRIES = old_max


def test_degraded_rerun_does_not_poison(mesh8):
    """Observation is suspended while a degraded replicated re-run is in
    flight — its REP shapes must not enter the stats store."""
    from bodo_tpu.plan import adaptive, logical as L, physical
    from bodo_tpu.table.table import Table
    df = pd.DataFrame({"a": np.arange(50)})
    node = L.FromPandas(df)
    t = Table.from_pandas(df)
    with _aqe():
        physical._degrade_tls.force_rep = True
        try:
            adaptive.observe_stage(node, t)
            adaptive.observe_shuffle(t, ["a"])
            assert adaptive._observed == {}
            assert adaptive.stats()["q_error"]["count"] == 0
        finally:
            physical._degrade_tls.force_rep = False
        adaptive.observe_stage(node, t)
        assert adaptive._observed != {}


# ---------------------------------------------------------------------------
# parquet row-count cache staleness (satellite)
# ---------------------------------------------------------------------------

def test_parquet_stats_cache_invalidation(mesh8, tmp_path):
    from bodo_tpu.plan import logical as L, stats
    p = str(tmp_path / "t.parquet")
    pd.DataFrame({"a": np.arange(100)}).to_parquet(p)
    n1 = stats._parquet_rows(p)
    assert n1 == 100
    # overwrite with different contents: mtime/file signature changes,
    # so the cache must MISS (the old bug returned the stale 100)
    pd.DataFrame({"a": np.arange(250)}).to_parquet(p)
    os.utime(p, ns=(1, 1))  # force a distinct mtime signature
    assert stats._parquet_rows(p) == 250
    # unknown fallback notes once, doesn't cache the guess
    assert stats._parquet_rows(str(tmp_path / "missing.pq")) == 1_000_000
    assert str(tmp_path / "missing.pq") in stats._warned_unknown


# ---------------------------------------------------------------------------
# persistent compile cache (satellite)
# ---------------------------------------------------------------------------

def test_compile_cache_dir_and_counters(mesh8, tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from bodo_tpu.utils import tracing
    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs,
           jax.config.jax_persistent_cache_min_entry_size_bytes)
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        # no floors, so this toy kernel is cache-eligible
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # jax latches cache-in-use on the FIRST compile of the process
        compilation_cache.reset_cache()
        tracing.install_compile_cache_listener()
        before = tracing.compile_cache_stats()

        @jax.jit
        def f(x):
            return x * 2 + 1

        f(jnp.arange(1237.0)).block_until_ready()
        after = tracing.compile_cache_stats()
        assert after["hits"] + after["misses"] > \
            before["hits"] + before["misses"]
        assert os.listdir(str(tmp_path))  # entries actually persisted
    finally:
        jax.config.update("jax_compilation_cache_dir", old[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old[1])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          old[2])
        compilation_cache.reset_cache()


# ---------------------------------------------------------------------------
# SQL oracle under forced mis-estimates (satellite/acceptance)
# ---------------------------------------------------------------------------

def test_sql_oracle_with_misestimates(mesh8):
    """TPC-H-shaped join/agg queries still match the sqlite oracle with
    AQE on and every source estimate deliberately wrong by 1000x."""
    from bodo_tpu.plan import adaptive, logical as L
    r = np.random.default_rng(10)
    n = 600
    orders = pd.DataFrame({
        "o_orderkey": np.arange(n, dtype=np.int64),
        "o_custkey": r.integers(0, 50, n),
        "o_totalprice": np.round(r.uniform(10, 1000, n), 2),
    })
    customer = pd.DataFrame({
        "c_custkey": np.arange(55, dtype=np.int64),
        "c_acctbal": np.round(r.uniform(-100, 5000, 55), 2),
    })
    nation = pd.DataFrame({
        "n_key": np.arange(55, dtype=np.int64) % 4,
        "c_custkey": np.arange(55, dtype=np.int64),
    })
    tables = {"orders": orders, "customer": customer, "nation": nation}

    def lie(node):
        if isinstance(node, L.FromPandas):
            n_ = node.table.nrows
            return n_ * 1000.0 if n_ < 100 else max(n_ / 1000.0, 1.0)
        return None

    with _aqe() as aqe:
        aqe.set_estimate_injector(lie)
        check_sql("""
            select c.c_custkey, sum(o.o_totalprice) as total,
                   count(*) as cnt
            from orders o join customer c on o.o_custkey = c.c_custkey
            where c.c_acctbal > 0
            group by c.c_custkey
        """, tables)
        check_sql("""
            select nt.n_key, sum(o.o_totalprice) as rev
            from orders o
            join customer c on o.o_custkey = c.c_custkey
            join nation nt on nt.c_custkey = c.c_custkey
            group by nt.n_key
        """, tables)
        assert _decisions().get("join:promote_broadcast", 0) + \
            _decisions().get("join:demote_broadcast", 0) + \
            _decisions().get("reoptimize:join_order", 0) >= 0
