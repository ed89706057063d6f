"""The system under test. The only module of the benchmark that imports
`bodo_tpu`; it takes from it the front door (`pandas_api`,
`BodoSQLContext`), the mesh, and counters. Everything else in the
benchmark (data, references, the comparison, the trace reduction, peaks)
is the benchmark's own."""


class Engine:
    def __init__(self, interpret_pallas=False):
        import jax
        import bodo_tpu
        from bodo_tpu.ops import pallas_kernels as PK
        from bodo_tpu.runtime import resilience, xla_observatory
        from bodo_tpu.utils import tracing

        self._jax = jax
        self._bodo = bodo_tpu
        self._pk, self._resilience = PK, resilience
        self._obs, self._tracing = xla_observatory, tracing
        if interpret_pallas:
            PK.FORCE_INTERPRET = True
        # a repeat of a query must reach the device
        bodo_tpu.set_config(result_cache=False)
        bodo_tpu.set_mesh(bodo_tpu.make_mesh())
        self._backend = {"n": 0, "s": 0.0}

        def on_duration(event, duration, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self._backend["n"] += 1
                self._backend["s"] += duration

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        self._ctx = None
        self._inputs = None

    # ------------------------------------------------------------ counters
    def counters(self):
        o = self._obs.stats()
        cc = self._tracing.compile_cache_stats()
        return {"programs": o["compiles"], "program_compile_s": o["compile_s"],
                "dispatches": o["dispatches"],
                "backend_compiles": self._backend["n"],
                "backend_compile_s": self._backend["s"],
                "compile_cache_hits": cc["hits"],
                "compile_cache_misses": cc["misses"]}

    def report(self):
        """What ran where, for an earlier line of the output."""
        rs = self._resilience.stats()
        return {"kernel_engagement": dict(self._pk.trace_counts),
                "pallas_interpret": bool(self._pk.FORCE_INTERPRET),
                "degraded_stages": sum(rs["degraded_stages"].values()),
                "retries": sum(rs["retries"].values()) + rs["gang_retries"],
                "result_cache": bool(self._bodo.config.result_cache),
                "slowest_compiles": [
                    [f"{r['subsystem']}:{r['base']}", round(r["compile_s"], 2)]
                    for r in self._obs.top_programs(8, "compile_s")]}

    def peak_bytes(self):
        return [(d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in self._jax.devices()]

    # ---------------------------------------------------------- front door
    def load(self, inputs, queries):
        """Tables of SQL queries are registered as pandas frames, which
        puts them on the device now; a pandas_api query reads its files
        inside every execution."""
        self._inputs = inputs
        if any(q.entry == "sql" for q in queries):
            from bodo_tpu.sql import BodoSQLContext
            self._ctx = BodoSQLContext(inputs["frames"])

    def plan(self, query):
        if query.entry == "sql":
            return self._ctx.sql(query.text)
        import bodo_tpu.pandas_api as bd
        return query.module.plan(bd, self._inputs)

    def collect(self, query, lazy):
        if query.entry == "sql":
            return lazy.to_pandas()
        return query.module.collect(lazy)
