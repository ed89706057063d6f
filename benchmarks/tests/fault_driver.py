#!/usr/bin/env python3
"""Drive the rest of a run with the timed path broken underneath.

    python3 benchmarks/tests/fault_driver.py <fault> <cell> [fraction]

Skips the harness's look for a chip (`--rehearse`), plants one fault in
the engine, runs `benchmarks/run.py`'s main as a run would, and leaves the
run's own last line as its last line. `correct` has to read false.

  none       nothing planted (the same drive has to read true)
  answer     one float cell of every answer altered by one part in 1e6
             where it is produced (`BodoDataFrame.to_pandas`)
  count      one integer cell of every answer altered by one there
  half       half of the rows left out where they enter the engine
             (`pandas_api.read_parquet` / `Table.from_pandas`), the
             aggregates taken over the rest
  exchange   the exchange between chips left out: `all_to_all_rows`
             returns what it was given
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def plant(fault):
    if fault == "none":
        return
    if fault in ("answer", "count"):
        from bodo_tpu.pandas_api.frame import BodoDataFrame
        real = BodoDataFrame.to_pandas

        def to_pandas(self, *a, **kw):
            df = real(self, *a, **kw).copy()
            kinds = "f" if fault == "answer" else "iu"
            col = [c for c in df.columns if df[c].dtype.kind in kinds][-1]
            v = df[col].to_numpy().copy()
            v[len(v) // 2] = (v[len(v) // 2] * (1 + 1e-6) if fault == "answer"
                              else v[len(v) // 2] + 1)
            df[col] = v
            return df

        BodoDataFrame.to_pandas = to_pandas
    elif fault == "half":
        import pandas as pd
        from bodo_tpu.table.table import Table
        real_from = Table.from_pandas

        def from_pandas(df, *a, **kw):
            if len(df) > 1000:
                df = df.iloc[: len(df) // 2]
            return real_from(df, *a, **kw)

        Table.from_pandas = staticmethod(from_pandas)
        import bodo_tpu.pandas_api as bd
        real_read = bd.read_parquet

        def read_parquet(path, *a, **kw):
            half = path + ".half.parquet"
            df = pd.read_parquet(path)
            df.iloc[: len(df) // 2].to_parquet(half)
            return real_read(half, *a, **kw)

        bd.read_parquet = read_parquet
    elif fault == "exchange":
        from bodo_tpu.parallel import collectives
        collectives.all_to_all_rows = lambda x, axis=None: x
    else:
        raise SystemExit(f"unknown fault {fault!r}")


def main():
    fault, cell = sys.argv[1], sys.argv[2]
    fraction = sys.argv[3] if len(sys.argv) > 3 else "0.01"
    import run
    sys.argv = ["run.py", "--workload", cell, "--seed", "2147483999",
                "--seconds", "2", "--trace", "0", "--rehearse", fraction]
    # plant after run.py has set the environment, before the engine traces
    import harness.engine as engine
    real_init = engine.Engine.__init__

    def init(self, *a, **kw):
        real_init(self, *a, **kw)
        plant(fault)

    engine.Engine.__init__ = init
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
