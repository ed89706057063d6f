"""SQL surface: BodoSQLContext analogue (reference
BodoSQL/bodosql/context.py:111 BodoSQLContext, :504 sql())."""

from __future__ import annotations

from typing import Dict, Optional

import pandas as pd

from bodo_tpu.plan import logical as L
from bodo_tpu.sql.parser import parse_sql
from bodo_tpu.sql.planner import Planner

__all__ = ["BodoSQLContext"]


class BodoSQLContext:
    """Register tables (pandas frames, lazy frames, or parquet paths) and
    run SQL against them. Queries lower to the same logical plan /
    executor as the dataframe frontend."""

    def __init__(self, tables: Optional[Dict] = None):
        self._tables: Dict[str, L.Node] = {}
        for name, t in (tables or {}).items():
            self.add_table(name, t)

    def add_table(self, name: str, table) -> None:
        from bodo_tpu.pandas_api.frame import BodoDataFrame
        if isinstance(table, BodoDataFrame):
            self._tables[name] = table._plan
        elif isinstance(table, pd.DataFrame):
            self._tables[name] = L.FromPandas(table)
        elif isinstance(table, str):
            self._tables[name] = L.ReadParquet(table)
        elif isinstance(table, L.Node):
            self._tables[name] = table
        else:
            raise TypeError(f"cannot register table {name}: {type(table)}")

    def remove_table(self, name: str) -> None:
        del self._tables[name]

    def _schema_sig(self) -> str:
        return repr(sorted((n, tuple(p.schema)) for n, p in
                           self._tables.items()))

    def sql(self, query: str):
        """Plan + execute; returns a lazy BodoDataFrame (DDL statements
        execute immediately and return a status/metadata frame, the
        reference's direct-DDL path: BodoSQL context.py:531)."""
        ddl = self._try_ddl(query)
        if ddl is not None:
            return ddl
        from bodo_tpu.pandas_api.frame import BodoDataFrame
        from bodo_tpu.sql import plan_cache
        from bodo_tpu.utils import tracing
        with tracing.event("plan.sql"):
            sig = self._schema_sig()
            ast = plan_cache.get(query, sig)
            if ast is None:
                ast = parse_sql(query)
                # pickle to disk BEFORE planning — the planner rewrites
                # AST nodes in place, so only cache-served objects need
                # copying
                plan_cache.put(query, sig, ast)
            else:
                import copy
                ast = copy.deepcopy(ast)
            plan, names = Planner(self._tables).plan(ast)
        return BodoDataFrame(plan)

    def _try_ddl(self, query: str):
        """Handle DDL statements (CREATE TABLE/VIEW AS, DROP TABLE,
        DESCRIBE, SHOW TABLES); None for ordinary queries."""
        import re
        q = query.strip().rstrip(";")
        up = q.upper()

        m = re.match(
            r"CREATE\s+(OR\s+REPLACE\s+)?(TABLE|VIEW)\s+(\w+)\s+AS\s+",
            q, re.IGNORECASE)
        if m:
            name = m.group(3).lower()
            if name in self._tables and not m.group(1):
                raise ValueError(f"table {name!r} already exists "
                                 f"(use CREATE OR REPLACE)")
            body = q[m.end():]
            result = self.sql(body)
            if m.group(2).upper() == "VIEW":
                # views stay lazy: re-planned against live sources
                self._tables[name] = result._plan
            else:
                # tables materialize now (CTAS snapshot semantics)
                from bodo_tpu.plan.physical import execute
                self._tables[name] = L.FromPandas(execute(result._plan))
            return pd.DataFrame(
                {"status": [f"{m.group(2).capitalize()} {name} "
                            f"successfully created."]})

        m = re.match(r"DROP\s+(TABLE|VIEW)\s+(IF\s+EXISTS\s+)?(\w+)\s*$",
                     q, re.IGNORECASE)
        if m:
            name = m.group(3).lower()
            if name not in self._tables:
                if m.group(2):
                    return pd.DataFrame(
                        {"status": [f"{name} does not exist, skipped."]})
                raise ValueError(f"table {name!r} does not exist")
            del self._tables[name]
            return pd.DataFrame(
                {"status": [f"{name} successfully dropped."]})

        m = re.match(r"(DESCRIBE|DESC)\s+(TABLE\s+)?(\w+)$", q,
                     re.IGNORECASE)
        if m:
            name = m.group(3).lower()
            if name not in self._tables:
                raise ValueError(f"table {name!r} does not exist")
            schema = self._tables[name].schema
            return pd.DataFrame({"name": list(schema),
                                 "type": [t.name for t in schema.values()]})

        if re.match(r"SHOW\s+TABLES$", up):
            return pd.DataFrame({"name": sorted(self._tables)})
        return None

    def generate_plan(self, query: str):
        """Return the optimized logical plan (EXPLAIN analogue)."""
        from bodo_tpu.plan.optimizer import optimize
        from bodo_tpu.utils import tracing
        with tracing.event("plan.sql"):
            ast = parse_sql(query)
            plan, _ = Planner(self._tables).plan(ast)
        with tracing.event("plan.optimize"):
            return optimize(plan)

    def explain(self, query: str) -> str:
        """Pretty-printed optimized plan."""
        lines = []

        def walk(n, d):
            lines.append("  " * d + repr(n))
            for c in n.children:
                walk(c, d + 1)
        walk(self.generate_plan(query), 0)
        return "\n".join(lines)

    def explain_analyze(self, query: str) -> str:
        """Plan, EXECUTE, and render the plan tree annotated with the
        observed per-node rows/bytes/wall/AQE decisions (requires
        tracing: set_config(tracing_level=1))."""
        from bodo_tpu.plan import explain
        from bodo_tpu.plan.physical import execute
        from bodo_tpu.utils import tracing
        plan = self.generate_plan(query)
        with tracing.query_span() as qid:
            execute(plan, optimize_first=False)
        return explain.explain_analyze(qid)
