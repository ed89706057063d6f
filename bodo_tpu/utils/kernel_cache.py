"""Bounded LRU cache for compiled kernels.

Long sessions (and the 400+-test suite) compile thousands of distinct
jitted kernels; pinning them all forever exhausts XLA:CPU's JIT code
memory and eventually segfaults the compiler. The reference contains
the same class of leak per test *module* by running each module in its
own subprocess (bodo/runtests.py:58). Here the engine itself stays
healthy: kernel caches evict least-recently-used entries so dropped
executables are garbage-collected.

Caches constructed with a `subsystem` tag additionally report every
store/hit/eviction to the unified program registry
(runtime/xla_observatory.py): the optional `describe(key)` callback
maps a cache key to a (base_signature, facets) pair so the registry
can attribute retraces to the facet that changed.
"""

from __future__ import annotations

import functools
import time
from collections import OrderedDict

from bodo_tpu.runtime import xla_observatory as _obs

# Max compiled kernels pinned per kernel cache (LRU eviction beyond
# this — unbounded pinning exhausts XLA:CPU JIT code memory and
# segfaults the compiler after thousands of distinct compilations).
KERNEL_CACHE_SIZE = 512


class KernelCache:
    """Dict-shaped LRU with the two operations the kernel caches use
    (`get` and item assignment)."""

    def __init__(self, maxsize: int = 1024, *, subsystem=None,
                 describe=None):
        self.maxsize = maxsize
        self._d: OrderedDict = OrderedDict()
        self.evictions = 0
        self.subsystem = subsystem
        self.describe = describe
        self._handles: dict = {}  # key -> observatory handle
        self.last_handle = 0  # handle of the most recent store

    def get(self, key, default=None):
        try:
            self._d.move_to_end(key)
            v = self._d[key]
        except KeyError:
            return default
        if self.subsystem is not None:
            _obs.touch(self._handles.get(key, 0))
        return v

    def _describe(self, key):
        if self.describe is not None:
            try:
                return self.describe(key)
            except Exception:
                pass
        base = key[0] if isinstance(key, tuple) and key \
            and isinstance(key[0], str) else self.subsystem
        return str(base), _obs.facets_from_sig(key)

    def __setitem__(self, key, value):
        if key in self._d:
            self._d.move_to_end(key)
        elif self.subsystem is not None:
            base, facets = self._describe(key)
            h = _obs.register(self.subsystem, base, facets,
                              donated=bool(facets.get("donate")))
            self._handles[key] = h
            self.last_handle = h
            # every traceable program entering a registered cache gets
            # a progcheck proxy: its first dispatch through the cache
            # verifies the jaxpr (collective manifest, donation audit,
            # HBM estimate) against the real call args
            value = self._progcheck_wrap(value, base, h)
        self._d[key] = value
        while len(self._d) > self.maxsize:
            k, _ = self._d.popitem(last=False)
            self.evictions += 1
            _obs.mark_evicted(self._handles.pop(k, 0))

    def _progcheck_wrap(self, value, base, handle):
        if not hasattr(value, "trace") or not callable(value):
            return value
        from bodo_tpu.analysis import progcheck
        return progcheck.wrap_program(
            value, program=f"{self.subsystem}:{base}",
            subsystem=self.subsystem, obs_handle=handle)

    def __contains__(self, key):
        return key in self._d

    def __len__(self):
        return len(self._d)

    def handle_for(self, key) -> int:
        return self._handles.get(key, 0)

    def pop(self, key, default=None):
        _obs.mark_evicted(self._handles.pop(key, 0))
        return self._d.pop(key, default)

    def clear(self):
        for h in self._handles.values():
            _obs.mark_evicted(h)
        self._handles.clear()
        self._d.clear()


class FusionProgramCache(KernelCache):
    """LRU of compiled whole-stage fusion programs (plan/fusion.py),
    keyed by the fusion-group signature (op sequence + input schema/dict
    fingerprints + distribution + agg spec). Same eviction behavior as
    any kernel cache, plus the hit/miss/compile accounting that
    EXPLAIN ANALYZE, tracing.profile() and the metrics registry report
    per fusion boundary."""

    def __init__(self, maxsize: int = 256, *, subsystem=None,
                 describe=None):
        super().__init__(maxsize=maxsize, subsystem=subsystem,
                         describe=describe)
        self.hits = 0
        self.misses = 0
        self.compiles = 0
        self.compile_s = 0.0

    def lookup(self, key):
        """`get` with hit/miss accounting (use for dispatch lookups;
        plain `get` stays silent for introspection)."""
        fn = self.get(key)
        if fn is None:
            self.misses += 1
        else:
            self.hits += 1
        return fn

    def record_compile(self, program: str, seconds: float,
                       handle: int = None) -> None:
        """Account one program compilation (feeds the shared
        bodo_tpu_jit_compile_seconds histogram and the program
        registry's per-executable compile wall)."""
        self.compiles += 1
        self.compile_s += float(seconds)
        _obs.note_compile(self.last_handle if handle is None else handle,
                          seconds)
        from bodo_tpu.utils import metrics
        metrics.record_compile(program, seconds)

    def stats(self) -> dict:
        return {"size": len(self), "hits": self.hits,
                "misses": self.misses, "compiles": self.compiles,
                "compile_s": self.compile_s, "evictions": self.evictions}

    def reset_stats(self) -> None:
        self.hits = self.misses = self.compiles = 0
        self.compile_s = 0.0


class DecodeProgramCache(FusionProgramCache):
    """LRU of jitted parquet page-decode programs (io/device_decode.py),
    keyed by the spec of a run of same-kind pages: encoding kind x
    output dtype x power-of-two shape buckets (page bytes, value count,
    run-table length, dictionary length) x page-count bucket x column
    capacity x null handling x timestamp scale. Bucketing makes the live
    program population a function of the SCHEMA and the row-group size,
    not the page count. Shares the
    fusion cache's hit/miss/compile accounting (EXPLAIN ANALYZE, the
    metrics registry, and tracing.profile() read the same shape)."""

    def __init__(self, maxsize: int = 128, *, subsystem=None,
                 describe=None):
        super().__init__(maxsize=maxsize, subsystem=subsystem,
                         describe=describe)

    def clear(self):
        super().clear()
        self.reset_stats()


def cached_builder(subsystem: str, maxsize: int = 256):
    """Registered replacement for `@lru_cache` on program-builder
    functions (hashable static config in, compiled program out): same
    memoization, but entries live in a subsystem-tagged KernelCache so
    every built program appears in the program registry with facet
    attribution, and eviction actually frees the executable (lru_cache
    would pin all 256 forever once warm)."""
    def deco(fun):
        def _describe(key):
            args, kw = key
            return fun.__name__, _obs.facets_from_sig(
                (fun.__name__,) + tuple(args) + tuple(v for _, v in kw))

        cache = KernelCache(maxsize=maxsize, subsystem=subsystem,
                            describe=_describe)

        @functools.wraps(fun)
        def wrapper(*args, **kwargs):
            key = (args, tuple(sorted(kwargs.items())))
            fn = cache.get(key)
            if fn is None:
                cache[key] = fun(*args, **kwargs)
                # hand back the cache's entry (the progcheck proxy for
                # traceable programs) so even the building call's first
                # dispatch is verified
                fn = cache.get(key)
            return fn

        wrapper.cache = cache
        wrapper.cache_clear = cache.clear
        return wrapper
    return deco


def _leaf_key(x):
    shape = getattr(x, "shape", None)
    if shape is not None and hasattr(x, "dtype"):
        return ("a", tuple(shape), str(x.dtype))
    return ("v", x)


def named_jit(name: str, fun, **jit_kwargs):
    """`jax.jit` of an operator's closure under its program family's
    name. XLA calls a program `jit_<function name>`, and the closures
    are all `body`, `fused`, `sharded`...: the name is what a device
    trace, a compile log and the persistent cache's file names tell one
    operator's program from another's by (`jit_groupby_dense`,
    `jit_fusedjoin`). Same registration duty as a bare `jax.jit`: the
    caller stores the result in a subsystem-tagged KernelCache."""
    import jax
    fun.__name__ = fun.__qualname__ = name
    # the caller registers it  # shardcheck: ignore[unregistered-jit]
    return jax.jit(fun, **jit_kwargs)


def bounded_jit(fun=None, *, static_argnames=(),
                maxsize=KERNEL_CACHE_SIZE):
    """`jax.jit` whose live compiled executables are BOUNDED.

    A module-level `jax.jit` pins one executable per distinct
    (input avals, static args) combination forever in jax's unbounded
    per-function cache; a long session (or the 490-test suite in one
    process) accumulates thousands and XLA:CPU's compiler eventually
    segfaults. This wrapper creates one `jax.jit` object per
    combination, held in a `KernelCache` LRU keyed by the call's leaf
    avals + non-array leaf values, so evicting an entry lets jax
    garbage-collect its executables. Works inside an outer trace too
    (leaves are tracers with shape/dtype; the inner jit inlines).

    Every compiled variant registers with the program registry under
    subsystem "bounded_jit", base = the wrapped function's name, with
    shape/dtype/static facets from the cache key — so retraces are
    attributed (shape-bucket churn vs dtype churn) like any other
    subsystem's.
    """
    if fun is None:
        return functools.partial(bounded_jit,
                                 static_argnames=static_argnames,
                                 maxsize=maxsize)

    def _describe(key):
        struct, leaf_keys = key
        return fun.__name__, _obs.facets_from_leaves(struct, leaf_keys)

    cache = KernelCache(maxsize=maxsize, subsystem="bounded_jit",
                        describe=_describe)

    @functools.wraps(fun)
    def wrapper(*args, **kwargs):
        import jax

        struct, leaves = None, None
        try:
            leaves, struct = jax.tree_util.tree_flatten((args, kwargs))
            key = (struct, tuple(_leaf_key(x) for x in leaves))
            hash(key)
        except TypeError:  # unhashable leaf — compile uncached
            return jax.jit(fun, static_argnames=static_argnames)(
                *args, **kwargs)
        fn = cache.get(key)
        if fn is None:
            fn = jax.jit(fun, static_argnames=static_argnames)
            cache[key] = fn
            # verify BEFORE timing so the recorded compile cost stays a
            # pure trace+lower+compile measurement
            from bodo_tpu.analysis import progcheck
            progcheck.check_jit(fn, args, kwargs,
                                program=f"bounded_jit:{fun.__name__}",
                                subsystem="bounded_jit",
                                obs_handle=cache.handle_for(key))
            # first invocation pays trace+lower+compile: record it as
            # this program's compile cost (bodo_tpu_jit_compile_seconds)
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            dt = time.perf_counter() - t0
            _obs.note_compile(cache.handle_for(key), dt)
            from bodo_tpu.utils import metrics
            metrics.record_compile(fun.__name__, dt)
            return out
        return fn(*args, **kwargs)

    wrapper.cache = cache
    return wrapper
