"""Round-5 SQL kernel tranche: regexp full set, JSON extract/variant,
TO_CHAR/TRY_CAST, LATERAL FLATTEN — differential-tested against Python
re/json/pandas oracles (reference:
BodoSQL/bodosql/kernels/regexp_array_kernels.py,
json_array_kernels.py, casting_array_kernels.py, lateral.py)."""

import json
import re

import numpy as np
import pandas as pd
import pytest

from bodo_tpu.sql import BodoSQLContext


@pytest.fixture
def ctx(mesh8):
    r = np.random.default_rng(4)
    n = 300
    words = ["alpha beta", "Gamma-7 delta", "x999y", "no match here",
             "a1b2c3", "", "Beta BETA beta"]
    t = pd.DataFrame({
        "i": np.arange(n, dtype=np.int64),
        "s": [words[i % len(words)] for i in range(n)],
        "x": np.round(r.normal(size=n) * 100, 3),
        "d": pd.Timestamp("2024-01-15 10:30:00")
        + pd.to_timedelta(r.integers(0, 100_000, n), unit="m"),
        "num_s": [f"{i * 7 % 100}.5" if i % 9 else "bad" for i in range(n)],
        "j": [json.dumps({"a": i, "b": {"c": f"v{i % 5}"},
                          "arr": [i, i + 1]})
              if i % 11 else "not json" for i in range(n)],
    })
    return BodoSQLContext({"t": t}), t


def _col(ctx, sql):
    df = ctx.sql(sql).to_pandas()
    return df[df.columns[0]]


def test_regexp_substr_occurrence_group(ctx):
    c, t = ctx
    got = _col(c, "select regexp_substr(s, '[0-9]+', 1, 2) from t")
    exp = t["s"].map(lambda s: (re.findall("[0-9]+", s)[1:2] or [None])[0])
    assert got.where(got.notna(), None).tolist() == exp.tolist()
    got2 = _col(c, r"select regexp_substr(s, '([a-z])([0-9])', 1, 1,"
                   r" 'c', 2) from t")
    exp2 = t["s"].map(
        lambda s: (lambda m: m.group(2) if m else None)(
            re.search("([a-z])([0-9])", s)))
    assert got2.where(got2.notna(), None).tolist() == exp2.tolist()


def test_regexp_instr_count_replace(ctx):
    c, t = ctx
    got = _col(c, "select regexp_instr(s, '[0-9]+') from t")
    exp = t["s"].map(lambda s: (lambda m: m.start() + 1 if m else 0)(
        re.search("[0-9]+", s)))
    assert got.tolist() == exp.tolist()
    got2 = _col(c, "select regexp_count(s, '[aeiou]') from t")
    exp2 = t["s"].map(lambda s: len(re.findall("[aeiou]", s)))
    assert got2.tolist() == exp2.tolist()
    got3 = _col(c, "select regexp_replace(s, '[0-9]+', 'N', 1, 2) from t")

    def rep2(s):
        n = 0
        for m in re.finditer("[0-9]+", s):
            n += 1
            if n == 2:
                return s[:m.start()] + "N" + s[m.end():]
        return s
    assert got3.tolist() == t["s"].map(rep2).tolist()


def test_regexp_like_flags(ctx):
    c, t = ctx
    got = _col(c, "select regexp_like(s, '.*beta.*', 'i') from t")
    exp = t["s"].map(
        lambda s: re.fullmatch("(?i).*beta.*", s) is not None)
    assert got.tolist() == exp.tolist()


def test_json_extract_path_text(ctx):
    c, t = ctx

    def jx(s, path):
        try:
            v = json.loads(s)
        except Exception:
            return None
        for p in path:
            if isinstance(p, int):
                if not isinstance(v, list) or p >= len(v):
                    return None
                v = v[p]
            else:
                if not isinstance(v, dict) or p not in v:
                    return None
                v = v[p]
        if isinstance(v, (dict, list)):
            return json.dumps(v, separators=(",", ":"))
        return str(v)
    got = _col(c, "select json_extract_path_text(j, 'b.c') from t")
    exp = t["j"].map(lambda s: jx(s, ["b", "c"]))
    assert got.where(got.notna(), None).tolist() == exp.tolist()
    got2 = _col(c, "select json_extract_path_text(j, 'arr[1]') from t")
    exp2 = t["j"].map(lambda s: jx(s, ["arr", 1]))
    assert got2.where(got2.notna(), None).tolist() == exp2.tolist()
    # parse_json: canonical form, null on invalid
    got3 = _col(c, "select parse_json(j) from t")
    assert got3.isna().sum() == (t["j"] == "not json").sum()


def test_to_char_and_try_cast(ctx):
    c, t = ctx
    got = _col(c, "select to_char(i) from t")
    assert got.tolist() == t["i"].astype(str).tolist()
    got2 = _col(c, "select to_char(d, 'YYYY-MM-DD') from t")
    assert got2.tolist() == t["d"].dt.strftime("%Y-%m-%d").tolist()
    got3 = _col(c, "select try_cast(num_s as double) from t")
    exp3 = pd.to_numeric(t["num_s"], errors="coerce")
    np.testing.assert_allclose(got3.to_numpy(dtype=float),
                               exp3.to_numpy(dtype=float), equal_nan=True)
    # numeric cast to varchar via ToChar
    got4 = _col(c, "select cast(i as varchar) from t")
    assert got4.tolist() == t["i"].astype(str).tolist()


def test_strtok_insert_editdistance(ctx):
    c, t = ctx
    got = _col(c, "select strtok(s, ' -', 2) from t")

    def tok2(s):
        toks = [x for x in re.split("[ -]", s) if x]
        return toks[1] if len(toks) >= 2 else None
    exp = t["s"].map(tok2)
    assert got.where(got.notna(), None).tolist() == exp.tolist()
    got2 = _col(c, "select editdistance(s, 'alpha beta') from t")
    assert got2[t["s"] == "alpha beta"].eq(0).all()
    got3 = _col(c, "select insert(s, 1, 0, 'Z') from t")
    assert got3.tolist() == ("Z" + t["s"]).tolist()


def test_lateral_flatten(mesh8):
    t = pd.DataFrame({
        "k": [1, 2, 3, 4],
        "arr": [[10, 20], [30], [], [40, 50, 60]],
    })
    c = BodoSQLContext({"t": t})
    got = c.sql("select k, f.value, f.index from t, "
                "lateral flatten(input => arr) f").to_pandas()
    exp = [(1, 10, 0), (1, 20, 1), (2, 30, 0),
           (4, 40, 0), (4, 50, 1), (4, 60, 2)]
    assert [tuple(r) for r in got.itertuples(index=False)] == exp
    # outer => true keeps the empty-array row with nulls
    got2 = c.sql("select k, f.value from t, "
                 "lateral flatten(input => arr, outer => true) f"
                 ).to_pandas()
    assert len(got2) == 7
    assert got2[got2["k"] == 3]["value"].isna().all()
    # aggregate over exploded values
    got3 = c.sql("select k, sum(f.value) as s from t, "
                 "lateral flatten(input => arr) f group by k "
                 "order by k").to_pandas()
    assert got3["s"].tolist() == [30, 30, 150]


def test_lateral_flatten_with_join(mesh8):
    """WHERE equi-join conjuncts still form a real join around a
    FLATTEN (not a filtered cross product), and flatten-referencing
    predicates run after the explode."""
    t = pd.DataFrame({"k": [1, 2, 4], "arr": [[5, 6], [7], [8, 9]]})
    u = pd.DataFrame({"k": [1, 2, 3], "w": [100, 200, 300]})
    c = BodoSQLContext({"t": t, "u": u})
    got = c.sql(
        "select t.k, u.w, f.value from t, u, "
        "lateral flatten(input => t.arr) f "
        "where t.k = u.k and f.value > 5 order by t.k, f.value"
    ).to_pandas()
    assert [tuple(r) for r in got.itertuples(index=False)] == \
        [(1, 100, 6), (2, 200, 7)]


def test_review_fix_semantics(ctx):
    c, t = ctx
    # CHECK_JSON: NULL for valid, error text for invalid
    got = _col(c, "select check_json(j) from t")
    valid = t["j"] != "not json"
    assert got[valid.to_numpy()].isna().all()
    assert got[(~valid).to_numpy()].notna().all()
    # Spark REGEXP_EXTRACT group argument
    got2 = _col(c, "select regexp_extract(s, '([a-z])([0-9])', 2) from t")
    exp2 = t["s"].map(lambda s: (lambda m: m.group(2) if m else None)(
        re.search("([a-z])([0-9])", s)))
    assert got2.where(got2.notna(), None).tolist() == exp2.tolist()
    # 'ci' parameters: last wins -> case-insensitive
    got3 = _col(c, "select regexp_like(s, '.*beta.*', 'ci') from t")
    exp3 = t["s"].map(
        lambda s: re.fullmatch("(?i).*beta.*", s) is not None)
    assert got3.tolist() == exp3.tolist()


def test_cast_string_in_where_and_rounding(ctx):
    c, t = ctx
    # CAST of a string column inside WHERE must parse values, not codes
    got = c.sql("select i from t where try_cast(num_s as double) > 50"
                ).to_pandas()
    exp = t[pd.to_numeric(t["num_s"], errors="coerce") > 50]["i"]
    assert sorted(got["i"].tolist()) == sorted(exp.tolist())
    # string -> integer rounds half away from zero (Snowflake)
    got2 = _col(c, "select cast(num_s as integer) from t")
    nums = pd.to_numeric(t["num_s"], errors="coerce")
    exp2 = np.where(nums.notna(),
                    np.sign(nums.fillna(0))
                    * np.floor(np.abs(nums.fillna(0)) + 0.5), np.nan)
    np.testing.assert_allclose(got2.to_numpy(dtype=float), exp2,
                               equal_nan=True)


def test_json_quoted_numeric_key(mesh8):
    t = pd.DataFrame({"j": ['{"2": "x", "a.b": "y"}', "not json"]})
    c = BodoSQLContext({"t": t})
    # a row that is not JSON gives SQL NULL (sqlite's json_extract under
    # json_valid says the same), which pandas' `str` dtype carries as NaN
    got = _col(c, "select json_extract_path_text(j, '\"2\"') from t")
    assert [None if pd.isna(v) else v for v in got] == ["x", None]
    got2 = _col(c, "select json_extract_path_text(j, '\"a.b\"') from t")
    assert [None if pd.isna(v) else v for v in got2] == ["y", None]


def test_regexp_position_validation(ctx):
    c, _t = ctx
    with pytest.raises(Exception):
        c.sql("select regexp_substr(s, 'a', 0) from t").to_pandas()


def test_to_char_decimal(mesh8):
    t = pd.DataFrame({"p": [1.50, -2.25, 0.05]})
    t["p"] = t["p"].map(lambda x: __import__("decimal").Decimal(
        f"{x:.2f}"))
    c = BodoSQLContext({"t": t})
    got = _col(c, "select to_char(p) from t")
    assert got.tolist() == ["1.50", "-2.25", "0.05"]
