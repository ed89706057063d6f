"""Test harness: simulate an 8-device TPU mesh on CPU.

Mirrors the reference's strategy of using MPI itself as the multi-node
simulator (`mpiexec -n 3` on one machine, SURVEY.md §4): here the
simulator is XLA's host-platform device count — all collective paths
(all_to_all shuffle, psum, all_gather, ppermute halos) are exercised for
real on 8 virtual devices.
"""

import os

# Force the CPU backend with 8 virtual devices.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")  # tests run on the CPU

import numpy as np  # noqa: E402
import pandas as pd  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    import bodo_tpu
    m = bodo_tpu.make_mesh()
    bodo_tpu.set_mesh(m)
    return m


@pytest.fixture
def one_dev(mesh8):
    """A mesh of one device for the test, so tables stay replicated."""
    import jax

    import bodo_tpu
    old = bodo_tpu.parallel.mesh.get_mesh()
    bodo_tpu.set_mesh(bodo_tpu.make_mesh(jax.devices()[:1]))
    yield
    bodo_tpu.set_mesh(old)


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="module", autouse=True)
def _xla_registry_teardown():
    """Per-module program-registry teardown (armed by runtests.py via
    BODO_TPU_XLA_TEARDOWN): grouped test modules share one process, so
    evicting each module's compiled fusion/decode programs and resetting
    the observatory keeps the live-executable census bounded — the same
    leak the grouped-subprocess layout exists to contain."""
    yield
    if not os.environ.get("BODO_TPU_XLA_TEARDOWN"):
        return
    import sys
    for name, clear in (("bodo_tpu.plan.fusion", "clear_programs"),
                        ("bodo_tpu.io.device_decode", "clear_programs")):
        mod = sys.modules.get(name)
        if mod is not None:
            getattr(mod, clear)()
    obs = sys.modules.get("bodo_tpu.runtime.xla_observatory")
    if obs is not None:
        obs.reset()


def make_df(n=1000, seed=0, nulls=False):
    r = np.random.default_rng(seed)
    df = pd.DataFrame({
        "a": r.integers(0, 10, n),
        "b": r.normal(size=n),
        "c": r.choice(["x", "yy", "zzz", "w"], n),
        "d": r.integers(-1000, 1000, n).astype(np.int32),
    })
    if nulls:
        df.loc[r.random(n) < 0.1, "b"] = np.nan
        df["e"] = pd.array(r.integers(0, 5, n), dtype="Int64")
        df.loc[r.random(n) < 0.1, "e"] = pd.NA
    return df
