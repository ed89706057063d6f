"""Persistent XLA compilation cache (reference parity:
bodo/tests/caching_tests/ — compile twice, assert the second process
hits the on-disk cache)."""

import os
import subprocess
import sys

import numpy as np
import pandas as pd

_PROG = """
import os, sys, time
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np, pandas as pd
import bodo_tpu
import bodo_tpu.pandas_api as bd
bodo_tpu.set_mesh(bodo_tpu.make_mesh())
df = pd.DataFrame({"k": np.arange(300) % 7, "v": np.arange(300) * 0.5})
t0 = time.time()
out = (bd.from_pandas(df).groupby("k", as_index=False)
       .agg(s=("v", "sum")).to_pandas())
assert len(out) == 7 and abs(out["s"].sum() - df["v"].sum()) < 1e-6
print(f"ELAPSED {time.time() - t0:.3f}")
"""


def test_persistent_compile_cache(tmp_path):
    cache = str(tmp_path / "xla_cache")
    # the cache is under test, not the planner: AQE promote/demote
    # decisions weigh observed bytes against the governor's DERIVED
    # budget (live box memory), so the two runs can legitimately trace
    # different plans and the second would compile jits the first never
    # saw. Pin AQE off and the persistent-cache write threshold to 0
    # (by default jax skips writing compilations faster than ~1s) so
    # entry-set equality is deterministic on a drifting shared box.
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache,
               BODO_TPU_AQE="0",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_PLATFORMS", None)
    r1 = subprocess.run([sys.executable, "-c", _PROG], env=env,
                        capture_output=True, text=True, timeout=300)
    assert r1.returncode == 0, r1.stderr[-2000:]
    entries1 = set(os.listdir(cache))
    assert entries1, "first run wrote no cache entries"
    r2 = subprocess.run([sys.executable, "-c", _PROG], env=env,
                        capture_output=True, text=True, timeout=300)
    assert r2.returncode == 0, r2.stderr[-2000:]
    # deterministic hit check: a cache-served second process compiles
    # nothing new, so the entry set is unchanged (timing on a shared
    # 1-core box is too noisy to assert on)
    entries2 = set(os.listdir(cache))
    assert entries2 == entries1, (
        f"second run missed the cache: {len(entries2 - entries1)} "
        f"new entries")
