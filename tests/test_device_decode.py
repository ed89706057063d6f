"""Device-side parquet decode (io/device_decode.py) tests.

Parity contract: for every supported encoding the device decoder must
be bit-identical to the host path (arrow_bridge.arrow_to_table over
pyarrow) — data in the live region, validity masks, and string
dictionaries. Unsupported encodings (DELTA_*, BYTE_STREAM_SPLIT) must
fall back per COLUMN, transparently, and still match the oracle.

Also covers: the raw thrift page walker + hybrid RLE/bit-packed parser
as units, multi-page/multi-row-group stitching, dict-page spill,
codecs, the BODO_TPU_DEVICE_DECODE toggle, observability counters
(io_stats / tracing.profile() / prometheus gauge), and the
distribution sweep through the frontend.

Every device read also checks the dispatch count: one decode program a
run of consecutive same-kind pages of a column chunk
(``device_decode_dispatches``), over all of the chunk's pages
(``device_decode_pages``).
"""

import itertools
import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as papq
import pytest

import bodo_tpu  # noqa: F401  (enables x64, registers mesh)
import jax
from bodo_tpu.config import config, set_config
from bodo_tpu.io import device_decode as dd
from bodo_tpu.io import read_parquet
from bodo_tpu.io.arrow_bridge import arrow_to_table
from bodo_tpu.io.parquet import clear_footer_cache, footer_metadata
from bodo_tpu.runtime import io_pool


@pytest.fixture(scope="module", autouse=True)
def _fresh_programs():
    """The module starts and ends with no decode program and the whole
    compile budget, whatever ran before it in the process."""
    dd.clear_programs()
    yield
    dd.clear_programs()


@pytest.fixture(autouse=True)
def _fresh(mesh8):
    old = (config.device_decode, config.device_decode_min_bytes)
    # test files are tiny — drop the size gate so they take the route
    set_config(device_decode_min_bytes=0)
    clear_footer_cache()
    io_pool.reset_io_stats()
    yield
    set_config(device_decode=old[0], device_decode_min_bytes=old[1])


def _np(x):
    return np.asarray(jax.device_get(x))


def _assert_col_parity(name, got, want, n):
    """Bit-parity between a device-decoded Column and the host oracle
    Column over the live region (padding is engine-internal)."""
    da, db = _np(got.data)[:n], _np(want.data)[:n]
    if da.dtype.kind == "f":
        assert np.array_equal(da, db, equal_nan=True), name
    else:
        assert np.array_equal(da, db), name
    assert (got.valid is None) == (want.valid is None), name
    if got.valid is not None:
        assert np.array_equal(_np(got.valid)[:n], _np(want.valid)[:n]), name
    assert (got.dictionary is None) == (want.dictionary is None), name
    if got.dictionary is not None:
        assert np.array_equal(np.asarray(got.dictionary),
                              np.asarray(want.dictionary)), name


def _assert_table_parity(t, path, columns=None):
    ot = arrow_to_table(papq.read_table(path, columns=columns))
    assert t.nrows == ot.nrows
    assert list(t.columns) == list(ot.columns)
    for cname in ot.columns:
        _assert_col_parity(cname, t.columns[cname], ot.columns[cname],
                           t.nrows)


def _mixed_frame(n, seed=0, nulls=False):
    rng = np.random.default_rng(seed)
    df = pd.DataFrame({
        "i64": rng.integers(-10**12, 10**12, n),
        "i32": rng.integers(-10**6, 10**6, n).astype(np.int32),
        "f64": rng.standard_normal(n),
        "f32": rng.standard_normal(n).astype(np.float32),
        "b": rng.integers(0, 2, n).astype(bool),
        "s": rng.choice(["alpha", "beta", "gamma", "delta"], n),
        "ts": pd.to_datetime(rng.integers(0, 10**18, n)),
    })
    if nulls:
        for c in ["i64", "f64", "s", "ts"]:
            df.loc[rng.random(n) < 0.15, c] = None
    return df


# ---------------------------------------------------------------------------
# thrift page walker + hybrid parser units
# ---------------------------------------------------------------------------

def test_parse_page_headers_walk(tmp_path):
    """The raw thrift walker finds every page the footer promises."""
    path = str(tmp_path / "w.parquet")
    _mixed_frame(4000, nulls=True).to_parquet(
        path, row_group_size=1500, data_page_size=2048)
    md = footer_metadata(path)
    for rg in range(md.num_row_groups):
        bundle = dd.fetch_row_group(path, rg, None, inject=False)
        nrg = md.row_group(rg).num_rows
        for rc in bundle.device_cols.values():
            assert sum(p.num_values for p in rc.pages) == nrg


def test_hybrid_rle_run():
    # one RLE run: header = count<<1, then bit_width bytes of value
    bw = 3
    buf = bytes([10 << 1, 0b101])  # 10 repeats of value 5
    rt = dd._parse_hybrid(buf, 0, len(buf), bw, 10)
    assert rt.is_rle[0] and rt.vals[0] == 5 and rt.starts[0] == 0


def test_hybrid_bitpacked_run():
    # bit-packed run: header = (groups<<1)|1, groups of 8 values
    bw = 1
    buf = bytes([(1 << 1) | 1, 0b10101010])  # 8 values 0,1,0,1,...
    rt = dd._parse_hybrid(buf, 0, len(buf), bw, 8)
    assert not rt.is_rle[0] and rt.starts[0] == 0


def test_hybrid_inexact_stream():
    """exact=False stops at stream end — dict-index and bool value
    streams store only the NON-null entries, so page num_values is an
    upper bound there."""
    buf = bytes([4 << 1, 7])  # 4 repeats, stream then ends
    rt = dd._parse_hybrid(buf, 0, len(buf), 3, 50, exact=False)
    assert rt.starts.shape[0] == 1
    with pytest.raises(dd.Unsupported):
        dd._parse_hybrid(buf, 0, len(buf), 3, 50, exact=True)


# ---------------------------------------------------------------------------
# per-encoding parity (device + host-fallback routes vs pyarrow)
# ---------------------------------------------------------------------------

def _page_runs(path):
    """(pages, runs of consecutive same-kind pages) over the chunks the
    page walker ships to the device: the decode counters' expectation."""
    md = footer_metadata(path)
    pages = runs = 0
    kinds_by_col = {}
    for rg in range(md.num_row_groups):
        bundle = dd.fetch_row_group(path, rg, None, inject=False)
        for name, rc in bundle.device_cols.items():
            kinds = [k for k, _ in itertools.groupby(
                p.kind for p in rc.pages)]
            kinds_by_col.setdefault(name, []).append(kinds)
            pages += len(rc.pages)
            runs += len(kinds)
    return pages, runs, kinds_by_col


def _check_read(path, expect_fallback=0):
    pages, runs, kinds = _page_runs(path)
    io_pool.reset_io_stats()
    t = read_parquet(path)
    _assert_table_parity(t, path)
    st = io_pool.io_stats()
    assert st["device_fallback_cols"] == expect_fallback
    if expect_fallback == 0:
        assert st["device_decode_pages"] > 0
        assert st["device_decode_frac"] == 1.0
    assert st["device_decode_errors"] == 0
    assert st["device_decode_pages"] == pages
    assert st["device_decode_dispatches"] == runs
    st["kinds"] = kinds
    return st


def _roundtrip(tmp_path, df, expect_fallback=0, **writer_kw):
    path = str(tmp_path / "t.parquet")
    df.to_parquet(path, engine="pyarrow", index=False, **writer_kw)
    return _check_read(path, expect_fallback)


# chunks of one page each: one dispatch a column chunk
SINGLE_PAGE = {
    "dictionary": (lambda: _mixed_frame(3000), {}),
    "plain": (lambda: _mixed_frame(3000).drop(columns=["s"]),
              {"use_dictionary": False}),
    "def_levels": (lambda: _mixed_frame(3000, nulls=True), {}),
}


@pytest.mark.parametrize("case", list(SINGLE_PAGE))
def test_parity_single_page(tmp_path, case):
    make, kw = SINGLE_PAGE[case]
    df = make()
    st = _roundtrip(tmp_path, df, **kw)
    assert st["device_decode_pages"] == st["device_decode_dispatches"] \
        == df.shape[1]


def _nullable_frame(n, seed):
    rng = np.random.default_rng(seed)
    miss = rng.random((4, n)) < 0.2
    return pa.table({
        "i64": pa.array(rng.integers(-10**12, 10**12, n), mask=miss[0]),
        "f64": pa.array(rng.standard_normal(n), mask=miss[1]),
        "s": pa.array(rng.choice(["alpha", "beta", "gamma", "delta"], n),
                      mask=miss[2]),
        "ts": pa.array(rng.integers(0, 10**18, n), pa.timestamp("ns"),
                       mask=miss[3]),
        "i32": rng.integers(-10**6, 10**6, n).astype(np.int32),
    })


def _dict_spill_frame(n, seed):
    # ~4,000 distinct values a column overflow a 4 KiB dictionary page
    # limit partway through the chunk: dictionary pages, then PLAIN
    rng = np.random.default_rng(seed)
    return pa.table({
        "f64": rng.integers(0, 4000, n) / 7.0,
        "i64": rng.integers(0, 4000, n),
        "ts": pa.array(rng.integers(0, 4000, n) * 10**9,
                       pa.timestamp("ns")),
    })


def _bool_frame(n, seed):
    rng = np.random.default_rng(seed)
    return pa.table({
        "b": rng.integers(0, 2, n).astype(bool),
        "runs": np.repeat([True, False, True], [n // 3, n // 3,
                                                 n - 2 * (n // 3)]),
        "bn": pa.array(rng.integers(0, 2, n).astype(bool),
                       mask=rng.random(n) < 0.2),
    })


def _time_frame(n, seed):
    rng = np.random.default_rng(seed)
    return pa.table({
        "ts_us": pa.array(rng.integers(0, 10**15, n), pa.timestamp("us"),
                          mask=rng.random(n) < 0.1),
        "ts_ms": pa.array(rng.integers(0, 10**12, n), pa.timestamp("ms")),
        "d": pa.array(rng.integers(0, 20000, n).astype(np.int32),
                      pa.date32()),
    })


def _string_frame(n, seed):
    rng = np.random.default_rng(seed)
    words = np.array([f"zone_{i:03d}" for i in range(179)])
    return pa.table({
        "s": pa.array(rng.choice(words, n), mask=rng.random(n) < 0.1),
        "lic": rng.choice(["HV0002", "HV0003", "HV0004", "HV0005"], n),
    })


# small pages, so one column chunk holds many: (table, writer options,
# the kinds of the runs every chunk must show, or None)
_PAGES = {"max_rows_per_page": 400, "write_batch_size": 100}
MANY_PAGES = {
    "dict_to_plain": (_dict_spill_frame, dict(
        _PAGES, dictionary_pagesize_limit=4096), ["dict", "plain"]),
    "nulls_v1_stats": (_nullable_frame, dict(
        _PAGES, data_page_version="1.0"), None),
    "nulls_v1_nostats": (_nullable_frame, dict(
        _PAGES, data_page_version="1.0", write_statistics=False), None),
    "nulls_v2_stats": (_nullable_frame, dict(
        _PAGES, data_page_version="2.0"), None),
    "nulls_v2_nostats": (_nullable_frame, dict(
        _PAGES, data_page_version="2.0", write_statistics=False), None),
    "bool_plain": (_bool_frame, dict(
        _PAGES, use_dictionary=False,
        column_encoding={c: "PLAIN" for c in ("b", "runs", "bn")}),
        ["boolplain"]),
    "bool_rle": (_bool_frame, dict(
        _PAGES, use_dictionary=False,
        column_encoding={c: "RLE" for c in ("b", "runs", "bn")}),
        ["boolrle"]),
    "timestamp_scale": (_time_frame, dict(_PAGES, use_dictionary=False),
                        ["plain"]),
    "strings": (_string_frame, dict(_PAGES, row_group_size=3000),
                ["dict"]),
}


def _write_many_pages(tmp_path, case, n=5000, seed=0):
    make, kw, _ = MANY_PAGES[case]
    path = str(tmp_path / f"mp_{case}_{seed}.parquet")
    papq.write_table(make(n, seed), path, **kw)
    return path


@pytest.mark.parametrize("case", list(MANY_PAGES))
def test_parity_many_pages(tmp_path, case):
    """Many pages a chunk decode in one dispatch a run of same-kind
    pages and equal pyarrow's read: nulls in v1 and v2 pages with and
    without statistics, dictionary pages that give way to PLAIN, both
    boolean encodings, scaled timestamps, strings. Each case's schema
    compiles programs of its own, so each starts from an empty cache."""
    dd.clear_programs()
    st = _check_read(_write_many_pages(tmp_path, case))
    assert st["device_decode_pages"] > 2 * st["device_decode_dispatches"]
    want = MANY_PAGES[case][2]
    if want is not None:
        for name, chunks in st["kinds"].items():
            assert all(k == want for k in chunks), (name, chunks)


def test_parity_one_page_chunk(tmp_path):
    """A chunk of exactly one page is a run of one page."""
    path = str(tmp_path / "one.parquet")
    papq.write_table(_nullable_frame(300, 1), path)
    st = _check_read(path)
    assert st["device_decode_pages"] == st["device_decode_dispatches"] == 5


def test_parity_rle_bool_v2(tmp_path):
    rng = np.random.default_rng(3)
    df = pd.DataFrame({"b": rng.integers(0, 2, 4000).astype(bool),
                       "runs": np.repeat([True, False], 2000)})
    _roundtrip(tmp_path, df, version="2.6")


def test_arrow_schema_cache_pins_metadata(tmp_path):
    """The id(md)-keyed arrow-schema cache must never serve a schema
    left by a FREED FileMetaData whose address got reused: a stale
    entry planted under this md's id (simulating reuse after the
    bounded footer cache evicts) must be ignored, and the live entry
    must pin md so its id can't be recycled while cached."""
    path = str(tmp_path / "a.parquet")
    pd.DataFrame({"x": [1, 2, 3]}).to_parquet(path, index=False)
    md = footer_metadata(path)
    stale = pa.schema([("ghost_i64", pa.int64())])
    with dd._arrow_schema_lock:
        dd._arrow_schema_cache[id(md)] = (object(), stale)
    sch = dd._arrow_schema_of(md)
    assert sch.names == ["x"]
    with dd._arrow_schema_lock:
        ent = dd._arrow_schema_cache[id(md)]
    assert ent[0] is md  # pinned: id(md) stays unique while cached
    assert dd._arrow_schema_of(md).names == ["x"]


def test_fallback_delta_binary_packed(tmp_path):
    rng = np.random.default_rng(4)
    df = pd.DataFrame({"d": np.cumsum(rng.integers(0, 9, 3000)),
                       "ok": rng.standard_normal(3000)})
    st = _roundtrip(tmp_path, df, expect_fallback=1,
                    use_dictionary=False,
                    column_encoding={"d": "DELTA_BINARY_PACKED",
                                     "ok": "PLAIN"})
    # the clean column still decoded on device
    assert st["device_decode_pages"] > 0
    assert 0.0 < st["device_decode_frac"] < 1.0


def test_fallback_byte_stream_split(tmp_path):
    rng = np.random.default_rng(5)
    df = pd.DataFrame({"f": rng.standard_normal(3000).astype(np.float32),
                       "ok": rng.integers(0, 100, 3000)})
    _roundtrip(tmp_path, df, expect_fallback=1,
               use_dictionary=False,
               column_encoding={"f": "BYTE_STREAM_SPLIT", "ok": "PLAIN"})


def test_fallback_dict_page_spill(tmp_path):
    """A dictionary page that overflows mid-chunk (tiny page limit
    forces a PLAIN spill) demotes that column to the host decoder."""
    rng = np.random.default_rng(6)
    df = pd.DataFrame({
        "s": np.array([f"key_{i:06d}" for i in
                       rng.integers(0, 4000, 6000)]),
        "i": rng.integers(0, 10, 6000)})
    st = _roundtrip(tmp_path, df, expect_fallback=1,
                    dictionary_pagesize_limit=1024)
    assert st["host_decode_bytes"] > 0


@pytest.mark.parametrize("codec", ["NONE", "gzip", "zstd"])
def test_parity_codecs(tmp_path, codec):
    _roundtrip(tmp_path, _mixed_frame(2000, nulls=True),
               compression=codec)


def test_parity_timestamp_date(tmp_path):
    rng = np.random.default_rng(7)
    n = 2000
    path = str(tmp_path / "ts.parquet")
    tbl = pa.table({
        "ts_us": pa.array(rng.integers(0, 10**15, n),
                          pa.timestamp("us")),
        "d": pa.array(rng.integers(0, 20000, n).astype(np.int32),
                      pa.date32()),
    })
    papq.write_table(tbl, path)
    t = read_parquet(path)
    _assert_table_parity(t, path)


def test_parity_multipage_multirowgroup(tmp_path):
    path = str(tmp_path / "mp.parquet")
    _mixed_frame(9000, nulls=True).to_parquet(
        path, index=False, row_group_size=2500, data_page_size=2048)
    io_pool.reset_io_stats()
    t = read_parquet(path)
    _assert_table_parity(t, path)
    md = footer_metadata(path)
    # genuinely multi-page: more device pages than columns x row groups
    st = io_pool.io_stats()
    assert st["device_decode_pages"] > md.num_columns * md.num_row_groups


def test_column_pruning(tmp_path):
    path = str(tmp_path / "p.parquet")
    _mixed_frame(2500, nulls=True).to_parquet(path, index=False)
    t = read_parquet(path, columns=["f64", "s"])
    assert list(t.columns) == ["f64", "s"]
    _assert_table_parity(t, path, columns=["f64", "s"])


# ---------------------------------------------------------------------------
# routing, toggle, observability
# ---------------------------------------------------------------------------

def test_toggle_parity_and_counters(tmp_path):
    path = str(tmp_path / "tog.parquet")
    _mixed_frame(2500, nulls=True).to_parquet(path, index=False)

    set_config(device_decode=False)
    io_pool.reset_io_stats()
    t_host = read_parquet(path)
    st = io_pool.io_stats()
    assert st["device_decode_pages"] == 0
    assert st["device_decode_frac"] == 0.0

    set_config(device_decode=True)
    io_pool.reset_io_stats()
    t_dev = read_parquet(path)
    st = io_pool.io_stats()
    assert st["device_decode_pages"] > 0
    assert st["device_decode_frac"] == 1.0
    assert getattr(t_dev, "_device_decoded", False)

    for cname in t_host.columns:
        _assert_col_parity(cname, t_dev.columns[cname],
                           t_host.columns[cname], t_host.nrows)


def test_size_gate_routes_small_reads_to_host(tmp_path):
    """Below device_decode_min_bytes the read stays on the host path
    (dispatch overhead + executable pinning aren't worth it)."""
    path = str(tmp_path / "tiny.parquet")
    _mixed_frame(500).to_parquet(path, index=False)
    set_config(device_decode_min_bytes=1 << 30)
    io_pool.reset_io_stats()
    t = read_parquet(path)
    st = io_pool.io_stats()
    assert st["device_decode_pages"] == 0
    assert st["device_decode_frac"] == 0.0
    _assert_table_parity(t, path)


def test_profile_and_gauge(tmp_path):
    from bodo_tpu.utils import metrics, tracing
    path = str(tmp_path / "obs.parquet")
    _mixed_frame(2000).to_parquet(path, index=False)
    set_config(tracing_level=1)
    tracing.reset()
    io_pool.reset_io_stats()
    try:
        read_parquet(path)
    finally:
        set_config(tracing_level=0)
    prof = tracing.profile()
    assert "io:device_decode" in prof
    assert prof["io:device_decode"]["count"] > 0
    assert prof["io:device_decode"]["frac"] == 1.0
    metrics.sync_engine_metrics()
    text = metrics.expose_text()
    assert "bodo_tpu_scan_device_decode_frac 1" in text
    assert 'event="device_decode_pages"' in text


def test_program_cache_reuse(tmp_path):
    """Same schema + page shape across files hits the decode-program
    cache instead of compiling fresh XLA programs."""
    dd.clear_programs()
    for i in range(3):
        path = str(tmp_path / f"c{i}.parquet")
        _mixed_frame(2000, seed=i).to_parquet(path, index=False)
        read_parquet(path)
    st = dd.decode_program_stats()
    assert st["hits"] > st["misses"]


def test_program_population_within_budget(tmp_path):
    """A decode program is keyed by its run's page-count bucket and the
    column's capacity besides the page shape. Over this module's
    multi-file reads (every many-page case, the single-page files, the
    codecs, many pages over row groups) the population stays inside the
    process's compile budget, so no read is demoted to the host for want
    of a program."""
    dd.clear_programs()
    io_pool.reset_io_stats()
    for case in MANY_PAGES:
        read_parquet(_write_many_pages(tmp_path, case))
    path = str(tmp_path / "rg.parquet")
    _mixed_frame(9000, nulls=True).to_parquet(
        path, index=False, row_group_size=2500, data_page_size=2048)
    read_parquet(path)
    for case, (make, kw) in SINGLE_PAGE.items():
        path = str(tmp_path / f"one_{case}.parquet")
        make().to_parquet(path, index=False, **kw)
        read_parquet(path)
    for codec in ("NONE", "gzip", "zstd"):
        path = str(tmp_path / f"codec_{codec}.parquet")
        _mixed_frame(2000, nulls=True).to_parquet(
            path, index=False, compression=codec)
        read_parquet(path)
    st = io_pool.io_stats()
    assert st["device_decode_errors"] == 0
    assert st["device_fallback_cols"] == 0
    assert dd.decode_program_stats()["budget_left"] > 0


def test_streaming_batches_flagged(tmp_path):
    """decoded_batches slices carry the _device_decoded marker that
    plan/fusion counts as device_scan_batches."""
    path = str(tmp_path / "st.parquet")
    _mixed_frame(6000, nulls=True).to_parquet(
        path, index=False, row_group_size=2000)
    rows = 0
    nb = 0
    for b in dd.decoded_batches(dd.raw_bundles(path, None), 1000):
        assert getattr(b, "_device_decoded", False)
        rows += b.nrows
        nb += 1
    assert rows == 6000 and nb >= 6


def test_read_units_unsupported_returns_none(tmp_path):
    """A wholly exotic file makes the device route bow out (None) so
    io/parquet.py falls through to the host reader."""
    rng = np.random.default_rng(9)
    df = pd.DataFrame({"d": np.cumsum(rng.integers(0, 9, 1000))})
    path = str(tmp_path / "ex.parquet")
    df.to_parquet(path, index=False, use_dictionary=False,
                  column_encoding={"d": "DELTA_BINARY_PACKED"})
    t = read_parquet(path)  # full read still works via fallback
    _assert_table_parity(t, path)
    st = io_pool.io_stats()
    assert st["device_decode_frac"] == 0.0


# ---------------------------------------------------------------------------
# frontend distribution sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["rep", "1d8", "1d1"])
def test_frontend_sweep(tmp_path, mode):
    from bodo_tpu import pandas_api as bpd
    from tests.utils import _mode

    df = _mixed_frame(4000, nulls=True)
    path = str(tmp_path / f"sweep_{mode}.parquet")
    df.to_parquet(path, index=False, row_group_size=1500)
    expect = pd.read_parquet(path)
    with _mode(mode):
        got = bpd.read_parquet(path).to_pandas()
    pd.testing.assert_frame_equal(
        got.reset_index(drop=True), expect.reset_index(drop=True),
        check_dtype=False)
