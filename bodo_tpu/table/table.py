"""Device-resident columnar tables.

TPU-native analogue of the reference's `array_info`/`table_info` columnar
core (reference: bodo/libs/_bodo_common.h:936, :1828) and the Python⇄C++
bridge (bodo/libs/array.py:242 `array_to_info`, :1993 `cpp_table_to_py_table`).

Design (SURVEY.md §7):
  - struct-of-arrays: each column is a fixed-capacity padded device array
    plus an optional validity bitmask; the number of real rows is tracked
    host-side (`nrows`, per-shard `counts` when row-sharded). Padded static
    shapes keep XLA happy; the reference's 1D_Var distribution becomes
    (padded buffer + row-count).
  - strings are dictionary-encoded; the dictionary (sorted unique strings)
    lives on host, int32 codes live on device.
  - a Table is either replicated ("REP") or row-sharded over the mesh data
    axis ("1D") — the reference's distribution lattice REP/OneD/OneD_Var
    (bodo/transforms/distributed_analysis.py:83) collapses to these two
    plus the padding counts.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

from bodo_tpu.config import config
from bodo_tpu.parallel import mesh as mesh_mod
from bodo_tpu.table import dtypes as dt
from bodo_tpu.table.dtypes import DType

REP = "REP"   # replicated: one logical copy (device or host)
ONED = "1D"   # row-sharded over the mesh data axis


def round_capacity(n: int) -> int:
    """Round a row count up to a padded, tile-friendly capacity."""
    r = config.capacity_round
    return max(r, ((n + r - 1) // r) * r)


@dataclass
class Column:
    """One column: device data + optional validity + host dictionary."""
    data: jax.Array                      # [capacity] physical values/codes
    valid: Optional[jax.Array]           # [capacity] bool, None = no nulls
    dtype: DType
    dictionary: Optional[np.ndarray] = None  # sorted unique strings (host)
    # host-known (lo, hi) bound on the PHYSICAL values (parquet footer
    # stats, static DtField ranges, literal projections). A bound, not
    # exact: row-preserving ops (filter/sort/shuffle/join gathers) keep
    # it — the dense groupby/join/pack planners then skip their exact
    # min/max device reductions (the reference gets the same shortcut
    # from parquet row-group statistics in its planner)
    vrange: Optional[tuple] = None
    # value span of a RESIDENT column (plan/stats.key_ndv_bound: the join
    # order's cap on a key's distinct values; 0 = no bound), reduced
    # once and kept here. Of this column only: no constructor of a
    # derived column passes it on, and no operator's gate reads it (a
    # fused join group asks `key_ndv_bound` whether its build side's
    # keys must repeat, which only spares a build that would refuse)
    ndv_bound: Optional[int] = field(default=None, compare=False,
                                     repr=False)

    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    def with_data(self, data, valid=None) -> "Column":
        return Column(data=data, valid=valid, dtype=self.dtype,
                      dictionary=self.dictionary)

    # ---- construction ----------------------------------------------------
    @staticmethod
    def from_numpy(arr: np.ndarray, capacity: Optional[int] = None,
                   valid: Optional[np.ndarray] = None) -> "Column":
        n = len(arr)
        cap = capacity if capacity is not None else round_capacity(n)
        if arr.dtype == object and _looks_decimal(arr):
            return _decimal_column(arr, cap, valid)
        if arr.dtype == object:
            from bodo_tpu.table import nested as _nested
            nt = _nested.infer_nested_dtype(arr)
            if nt is not None:
                vals = list(arr)
                if valid is not None:
                    vals = [v if ok else None
                            for v, ok in zip(vals, valid)]
                if nt.kind == "struct":
                    vals = [None if v is None else
                            tuple(v.get(fn) for fn, _ in nt.fields)
                            for v in vals]
                return _nested.encode_values(vals, nt, capacity=cap)
        dtype = dt.from_numpy(arr.dtype)
        dictionary = None
        if dtype is dt.STRING:
            vals = np.asarray(arr, dtype=object)
            isna = np.array([v is None or (isinstance(v, float) and np.isnan(v))
                             for v in vals], dtype=bool)
            if valid is not None:
                isna |= ~np.asarray(valid, dtype=bool)
            fill = vals[~isna]
            safe = np.where(isna, fill[0] if len(fill) else "", vals)
            dictionary, codes = np.unique(safe.astype(str), return_inverse=True)
            phys = codes.astype(np.int32)
            valid = None if not isna.any() else ~isna
        elif dtype is dt.DATETIME:
            a = np.asarray(arr).astype("datetime64[ns]")
            nat = np.isnat(a)
            phys = a.view(np.int64).copy()
            if nat.any():
                phys[nat] = 0
                valid = (~nat) if valid is None else (np.asarray(valid) & ~nat)
        elif dtype is dt.TIMEDELTA:
            a = np.asarray(arr).astype("timedelta64[ns]")
            nat = np.isnat(a)
            phys = a.view(np.int64).copy()
            if nat.any():
                phys[nat] = 0
                valid = (~nat) if valid is None else (np.asarray(valid) & ~nat)
        else:
            # NaN stays NaN in float data (pandas float semantics); no mask.
            phys = np.asarray(arr, dtype=dtype.numpy)
        padded = np.zeros((cap,) + phys.shape[1:], dtype=dtype.numpy)
        padded[:n] = phys
        vcol = None
        if valid is not None:
            v = np.zeros(cap, dtype=bool)
            v[:n] = np.asarray(valid, dtype=bool)
            vcol = jnp.asarray(v)
        return Column(data=jnp.asarray(padded), valid=vcol, dtype=dtype,
                      dictionary=dictionary)

    # ---- materialization -------------------------------------------------
    def to_numpy(self, nrows: int):
        """Decode the first `nrows` real rows to a host numpy/object array."""
        if dt.is_nested(self.dtype):
            from bodo_tpu.table import nested as _nested
            return _nested.decode_column(self, nrows)
        data = np.asarray(jax.device_get(self.data))[:nrows]
        valid = (np.asarray(jax.device_get(self.valid))[:nrows]
                 if self.valid is not None else None)
        if self.dtype is dt.STRING:
            assert self.dictionary is not None
            if len(self.dictionary) == 0:
                # empty dictionary: every row is null (e.g. the all-null
                # string column appended by an outer join with an empty
                # side)
                return np.full(len(data), None, dtype=object)
            out = self.dictionary[np.clip(data, 0, len(self.dictionary) - 1)]
            out = out.astype(object)
            if valid is not None:
                out[~valid] = None
            return out
        if self.dtype is dt.DATETIME:
            out = data.view("datetime64[ns]").copy()
            if valid is not None:
                out[~valid] = np.datetime64("NaT")
            return out
        if self.dtype is dt.TIMEDELTA:
            out = data.view("timedelta64[ns]").copy()
            if valid is not None:
                out[~valid] = np.timedelta64("NaT")
            return out
        if self.dtype is dt.DATE:
            # days-since-epoch → object array of datetime.date (what
            # pandas' .dt.date produces), None for nulls
            out = data.astype("datetime64[D]").astype(object)
            if valid is not None:
                out[~valid] = None
            return out
        if self.dtype.kind == "dec":
            import decimal as pydec
            q = pydec.Decimal(1).scaleb(-self.dtype.scale)
            out = np.array([pydec.Decimal(int(v))
                            .scaleb(-self.dtype.scale).quantize(q)
                            for v in data], dtype=object)
            if valid is not None:
                out[~valid] = None
            return out
        if valid is not None and self.dtype.kind in ("i", "u", "b"):
            return _masked_to_pandas(data, valid, self.dtype)
        if valid is not None and self.dtype.kind == "f":
            out = data.astype(self.dtype.numpy).copy()
            out[~valid] = np.nan
            return out
        return data


def _dec_isna(v) -> bool:
    import decimal as pydec
    if v is None or v is getattr(pd, "NA", None):
        return True
    if isinstance(v, float) and np.isnan(v):
        return True
    if isinstance(v, pydec.Decimal) and not v.is_finite():
        return True  # Decimal('NaN')/Decimal('Infinity') → null
    return False


def _looks_decimal(arr: np.ndarray) -> bool:
    import decimal as pydec
    for v in arr:
        if _dec_isna(v):
            continue
        return isinstance(v, pydec.Decimal)
    return False


def _decimal_column(arr: np.ndarray, cap: int, valid) -> "Column":
    """Object array of decimal.Decimal → scaled int64 column; the scale
    is the maximum fractional-digit count across the values."""
    import decimal as pydec
    isna = np.array([_dec_isna(v) for v in arr])
    if valid is not None:
        isna |= ~np.asarray(valid, dtype=bool)
    scale = 0
    for v, na in zip(arr, isna):
        if not na:
            scale = max(scale, -int(v.as_tuple().exponent))
    phys = np.zeros(len(arr), dtype=np.int64)
    mul = pydec.Decimal(10) ** scale
    for i, (v, na) in enumerate(zip(arr, isna)):
        if not na:
            phys[i] = int(v * mul)
    padded = np.zeros((cap,), dtype=np.int64)
    padded[:len(arr)] = phys
    vcol = None
    if isna.any():
        vm = np.zeros(cap, dtype=bool)
        vm[:len(arr)] = ~isna
        vcol = jnp.asarray(vm)
    return Column(jnp.asarray(padded), vcol, dt.decimal(scale), None)


def _masked_to_pandas(data, valid, dtype: DType):
    mask = ~np.asarray(valid, dtype=bool)
    if dtype.kind == "b":
        return pd.arrays.BooleanArray(
            np.where(valid, data, False).astype(bool), mask)
    vals = np.where(valid, data, dtype.numpy.type(0)).astype(dtype.numpy)
    return pd.arrays.IntegerArray(vals, mask)


@dataclass
class Table:
    """Host-level handle to device-resident columns.

    Not a pytree: jitted kernels consume/produce raw array pytrees via
    `device_data()` / `with_device_data()`; dictionaries and schema stay on
    host (avoids recompiles keyed on dictionary contents).
    """
    columns: Dict[str, Column]
    nrows: int
    distribution: str = REP
    counts: Optional[np.ndarray] = None  # per-shard real-row counts when 1D

    # ---- basic accessors -------------------------------------------------
    @property
    def names(self) -> List[str]:
        return list(self.columns.keys())

    @property
    def capacity(self) -> int:
        if not self.columns:
            return 0
        return next(iter(self.columns.values())).capacity

    @property
    def num_shards(self) -> int:
        return 1 if self.counts is None else len(self.counts)

    @property
    def shard_capacity(self) -> int:
        return self.capacity // self.num_shards

    def column(self, name: str) -> Column:
        return self.columns[name]

    def select(self, names: Sequence[str]) -> "Table":
        return Table({n: self.columns[n] for n in names}, self.nrows,
                     self.distribution, self.counts)

    def with_columns(self, columns: Dict[str, Column]) -> "Table":
        return Table(dict(columns), self.nrows, self.distribution, self.counts)

    # ---- conversion ------------------------------------------------------
    @staticmethod
    def from_pandas(df: pd.DataFrame, capacity: Optional[int] = None) -> "Table":
        n = len(df)
        cap = capacity if capacity is not None else round_capacity(n)
        cols: Dict[str, Column] = {}
        for name in df.columns:
            s = df[name]
            valid = None
            if s.isna().any():
                valid = (~s.isna()).to_numpy()
            if hasattr(s.dtype, "numpy_dtype"):
                # pandas masked extension dtype (Int64/boolean/...): keep the
                # exact physical dtype, don't let to_numpy() densify to
                # object/float64 (loses precision for large ints)
                np_dt = s.dtype.numpy_dtype
                arr = s.to_numpy(dtype=np_dt, na_value=np_dt.type(0))
            elif valid is not None and s.dtype.kind not in (
                    "O", "U", "T", "M", "m", "f"):
                arr = s.to_numpy(na_value=0)
            else:
                arr = s.to_numpy()
            cols[str(name)] = Column.from_numpy(arr, capacity=cap, valid=valid)
        return Table(cols, n, REP, None)

    def to_pandas(self) -> pd.DataFrame:
        from bodo_tpu.utils import tracing
        with tracing.event("to_pandas", rows=self.nrows):
            t = self.gather(answer=True) if self.distribution == ONED \
                else self
            out = {}
            with tracing.event("result.d2h"):
                for name, col in t.columns.items():
                    out[name] = col.to_numpy(t.nrows)
            with tracing.event("result.frame"):
                return pd.DataFrame(out)

    # ---- distribution ----------------------------------------------------
    def shard(self) -> "Table":
        """REP -> 1D: scatter rows over the mesh data axis
        (scatterv analogue, reference distributed_api.py:1299).

        Shard i owns global rows [i*per, i*per + counts[i]) — the packed
        per-shard layout coincides with the source layout, so no
        per-shard host repack is needed: single-process, the column
        pads/zero-tails ON DEVICE and `jax.device_put` against the row
        sharding moves each slice to its device; multi-process (SPMD
        pods), `jax.make_array_from_callback` materializes only the
        shards THIS host's devices own — the full table never transits
        any single host."""
        if self.distribution == ONED:
            return self
        from bodo_tpu.utils import tracing
        with tracing.event("dist.shard", rows=self.nrows):
            return self._shard_inner()

    def _shard_inner(self) -> "Table":
        m = mesh_mod.get_mesh()
        s = mesh_mod.num_shards(m)
        per = round_capacity(-(-max(self.nrows, 1) // s))
        counts = np.array(
            [max(0, min(per, self.nrows - i * per)) for i in range(s)],
            dtype=np.int64)
        sharding = mesh_mod.row_sharding(m)
        target = s * per
        nrows = self.nrows
        multi = jax.process_count() > 1

        def _scatter(arr, zero):
            if multi:
                from bodo_tpu.utils.tracing import host_read
                host = np.asarray(host_read("scatter", arr))

                def cb(idx):
                    sl = idx[0]
                    lo = sl.start or 0
                    hi = sl.stop if sl.stop is not None else target
                    piece = np.full((hi - lo,) + host.shape[1:],
                                    zero, host.dtype)
                    take = min(hi, nrows)
                    if take > lo:
                        piece[: take - lo] = host[lo:take]
                    return piece
                return jax.make_array_from_callback(
                    (target,) + host.shape[1:], sharding, cb)
            d = arr
            if d.shape[0] < target:
                pad = jnp.full((target - d.shape[0],) + d.shape[1:],
                               zero, d.dtype)
                d = jnp.concatenate([d, pad])
            elif d.shape[0] > target:
                d = d[:target]
            if d.shape[0] > nrows:  # zero the tail (old garbage rows)
                mask = jnp.arange(target) < nrows
                d = jnp.where(
                    mask.reshape((-1,) + (1,) * (d.ndim - 1)), d,
                    jnp.asarray(zero, d.dtype))
            return jax.device_put(d, sharding)

        new_cols = {}
        for name, col in self.columns.items():
            data = _scatter(col.data, 0)
            valid = (None if col.valid is None
                     else _scatter(col.valid, False))
            new_cols[name] = Column(data, valid, col.dtype, col.dictionary,
                                    col.vrange)
        return Table(new_cols, self.nrows, ONED, counts)

    def gather(self, answer: bool = False) -> "Table":
        """1D -> REP: gather shards, trim padding, repack contiguous
        (gatherv analogue, reference distributed_api.py:713). Every
        column comes to the host in one read: a host sync (`gather`),
        except where the gather is the answer's own copy (`answer`, from
        `to_pandas`)."""
        if self.distribution == REP:
            return self
        from bodo_tpu.parallel import comm
        from bodo_tpu.utils import tracing
        with tracing.event("dist.gather", rows=self.nrows), \
                comm.collective_span(
                    "gather", bytes_in=comm.table_bytes(self)) as _sp:
            tree = self.device_data()
            out = self._gather_inner(
                jax.device_get(tree) if answer
                else tracing.host_read("gather", tree))
            _sp["bytes_out"] = comm.table_bytes(out)
        return out

    def _gather_inner(self, host_tree) -> "Table":
        s = self.num_shards
        per = self.shard_capacity
        cap = round_capacity(max(self.nrows, 1))
        new_cols = {}
        for name, col in self.columns.items():
            host = np.asarray(host_tree[name][0])
            pieces = [host[i * per: i * per + int(self.counts[i])]
                      for i in range(s)]
            packed = np.concatenate(pieces) if pieces else host[:0]
            padded = np.zeros((cap,), dtype=host.dtype)
            padded[: self.nrows] = packed
            valid = None
            if col.valid is not None:
                hv = np.asarray(host_tree[name][1])
                vp = [hv[i * per: i * per + int(self.counts[i])]
                      for i in range(s)]
                vpacked = np.concatenate(vp) if vp else hv[:0]
                vpad = np.zeros((cap,), dtype=bool)
                vpad[: self.nrows] = vpacked
                valid = jnp.asarray(vpad)
            new_cols[name] = Column(jnp.asarray(padded), valid, col.dtype,
                                    col.dictionary, col.vrange)
        return Table(new_cols, self.nrows, REP, None)

    # ---- kernel interface ------------------------------------------------
    def device_data(self):
        """Pytree view for jitted kernels: {name: (data, valid_or_None)}."""
        return {n: (c.data, c.valid) for n, c in self.columns.items()}

    def counts_device(self):
        """Per-shard row counts as a device array sharded one-per-shard
        (shape [S]; inside shard_map each shard sees [1])."""
        if self.counts is None:
            return jnp.asarray(np.array([self.nrows], dtype=np.int64))
        m = mesh_mod.get_mesh()
        return jax.device_put(self.counts.astype(np.int64),
                              mesh_mod.row_sharding(m))

    def with_device_data(self, tree, nrows: Optional[int] = None,
                         counts: Optional[np.ndarray] = None,
                         dtypes: Optional[Dict[str, DType]] = None,
                         dicts: Optional[Dict[str, np.ndarray]] = None
                         ) -> "Table":
        """Rebuild a Table from a kernel-output pytree, preserving schema
        metadata for columns that still exist (host-side dictionary
        re-attachment — see module docstring).

        Column ORDER is restored from this table, not the pytree: jax
        flattens dict pytrees in sorted-key order, so a dict that round-
        tripped through a jitted kernel comes back alphabetized."""
        order = [n for n in self.columns if n in tree] + \
            [n for n in tree if n not in self.columns]
        cols = {}
        for name in order:
            data, valid = tree[name]
            if dtypes and name in dtypes:
                dtype = dtypes[name]
            elif name in self.columns:
                dtype = self.columns[name].dtype
            else:
                dtype = dt.from_numpy(np.dtype(data.dtype))
            dictionary = None
            if dicts and name in dicts:
                dictionary = dicts[name]
            elif name in self.columns:
                dictionary = self.columns[name].dictionary
            cols[name] = Column(data, valid, dtype, dictionary)
        new_dist = self.distribution if counts is None else ONED
        return Table(cols, self.nrows if nrows is None else nrows,
                     new_dist, self.counts if counts is None else counts)

    def __repr__(self) -> str:  # pragma: no cover
        schema = ", ".join(f"{n}:{c.dtype.name}" for n, c in self.columns.items())
        return (f"Table[{self.nrows} rows, cap={self.capacity}, "
                f"{self.distribution}]({schema})")
