"""bodo_tpu — a TPU-native distributed dataframe engine.

Re-implements the capabilities of the reference engine (bodo-ai/Bodo: a
Numba+MPI+C++ distributed dataframe/SQL engine) as an idiomatic JAX/XLA
stack: columnar tables live in device HBM as padded struct-of-arrays,
relational kernels are jit-traced XLA programs (segment reductions, sorts,
Pallas hash kernels), and distribution is SPMD over a `jax.sharding.Mesh`
with lax collectives instead of MPI (see SURVEY.md §7).

Public surfaces (mirroring the reference's four, plus serving):
  - `bodo_tpu.jit`         — @jit decorator (reference bodo/decorators.py:338)
  - `bodo_tpu.pandas_api`  — lazy drop-in dataframe library
                             (reference bodo/pandas/frame.py:117)
  - `bodo_tpu.sql`         — SQL context (reference BodoSQL/bodosql/context.py:504)
  - `bodo_tpu.ml`          — distributed ML (reference bodo/ml_support/)
  - `bodo_tpu.serve`       — multi-tenant sessions over one resident gang
  - `bodo_tpu.fleet`       — one controller, many gangs, peered caches
                             (runtime/scheduler.py)
"""

import jax

# Dataframe engines need real 64-bit ints/floats; enable before any trace.
jax.config.update("jax_enable_x64", True)

from bodo_tpu.config import config, set_config, set_verbose_level  # noqa: E402

if jax.config.jax_compilation_cache_dir:
    # persistent XLA compilation cache: compiled kernels survive process
    # restarts (the reference's @bodo.jit(cache=True) Numba on-disk
    # cache, exercised by its caching_tests/). JAX itself reads
    # JAX_COMPILATION_CACHE_DIR; the library never places the cache, it
    # only lowers the write thresholds (never raises what the caller
    # set) and counts hits and misses.
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        min(jax.config.jax_persistent_cache_min_compile_time_secs, 0.1))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bodo_tpu.utils import tracing as _tracing
    _tracing.install_compile_cache_listener()
from bodo_tpu.parallel.mesh import (  # noqa: E402
    get_mesh, set_mesh, use_mesh, make_mesh, num_shards, init_runtime,
)
from bodo_tpu.table.table import Table, Column  # noqa: E402
from bodo_tpu.table import dtypes  # noqa: E402

__version__ = "0.1.0"

__all__ = [
    "config", "set_config", "set_verbose_level",
    "get_mesh", "set_mesh", "use_mesh", "make_mesh", "num_shards",
    "init_runtime", "Table", "Column", "dtypes", "jit", "wrap_python",
]


def __getattr__(name):
    # Lazy imports to keep `import bodo_tpu` light and avoid cycles.
    if name == "jit":
        from bodo_tpu.jit_compiler import jit as _jit
        return _jit
    if name == "wrap_python":
        from bodo_tpu.jit_compiler import wrap_python as _wp
        return _wp
    if name == "pandas_api":
        import bodo_tpu.pandas_api as m
        return m
    if name == "sql":
        import bodo_tpu.sql as m
        return m
    if name == "ml":
        import bodo_tpu.ml as m
        return m
    if name == "serve":
        import bodo_tpu.serve as m
        return m
    if name == "fleet":
        import bodo_tpu.fleet as m
        return m
    if name == "views":
        import bodo_tpu.views as m
        return m
    raise AttributeError(f"module 'bodo_tpu' has no attribute {name!r}")
