"""Hashable expression IR + JAX evaluator.

Analogue of the reference's expression nodes (bodo/pandas/plan.py:560-760
ColRefExpression/ArithOpExpression/ComparisonOpExpression/...). Being
frozen dataclasses, expressions are hashable and serve directly as jit
cache keys, so each distinct expression tree compiles exactly once.

String predicates evaluate against the host-side dictionary (tiny) and
become a boolean lookup-table gather on device — the dict-encoding trick
the reference uses for string-heavy workloads (bodo/libs/dict_arr_ext.py).
Null semantics follow SQL/pandas-float behavior: arithmetic propagates
nulls; comparisons with null produce null, and filters treat null as
False.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from bodo_tpu.ops import datetime as dtops
from bodo_tpu.table import dtypes as dt
from bodo_tpu.utils import tracing


class Expr:
    """Base class; all subclasses are frozen/hashable."""

    # -- operator sugar used by the frontend --------------------------------
    def _bin(self, op, other, reverse=False):
        o = other if isinstance(other, Expr) else Lit(other)
        return BinOp(op, o, self) if reverse else BinOp(op, self, o)

    def __add__(self, o): return self._bin("+", o)
    def __radd__(self, o): return self._bin("+", o, True)
    def __sub__(self, o): return self._bin("-", o)
    def __rsub__(self, o): return self._bin("-", o, True)
    def __mul__(self, o): return self._bin("*", o)
    def __rmul__(self, o): return self._bin("*", o, True)
    def __truediv__(self, o): return self._bin("/", o)
    def __rtruediv__(self, o): return self._bin("/", o, True)
    def __floordiv__(self, o): return self._bin("//", o)
    def __mod__(self, o): return self._bin("%", o)
    def __pow__(self, o): return self._bin("**", o)
    def __eq__(self, o): return self._bin("==", o)  # type: ignore[override]
    def __ne__(self, o): return self._bin("!=", o)  # type: ignore[override]
    def __lt__(self, o): return self._bin("<", o)
    def __le__(self, o): return self._bin("<=", o)
    def __gt__(self, o): return self._bin(">", o)
    def __ge__(self, o): return self._bin(">=", o)
    def __and__(self, o): return self._bin("&", o)
    def __rand__(self, o): return self._bin("&", o, True)
    def __or__(self, o): return self._bin("|", o)
    def __ror__(self, o): return self._bin("|", o, True)
    def __invert__(self): return UnOp("~", self)
    def __neg__(self): return UnOp("neg", self)
    def __abs__(self): return UnOp("abs", self)
    def key(self):
        """Structural cache key (expressions can't be dict keys directly:
        __eq__ is overloaded as the comparison *builder*)."""
        raise NotImplementedError

    def isin(self, values): return IsIn(self, tuple(values))
    def isna(self): return UnOp("isna", self)
    def notna(self): return UnOp("notna", self)
    def fillna(self, v): return Where(UnOp("isna", self), Lit(v), self)
    def astype(self, dtype): return Cast(self, dt.from_numpy(np.dtype(dtype)))


def _frozen(cls):
    return dataclass(frozen=True, eq=False, repr=True)(cls)


@_frozen
class ColRef(Expr):
    name: str
    def key(self): return ("col", self.name)


@_frozen
class Lit(Expr):
    value: Any
    def key(self): return ("lit", str(type(self.value).__name__), self.value)


@_frozen
class BinOp(Expr):
    op: str
    left: Expr
    right: Expr
    def key(self): return ("bin", self.op, self.left.key(), self.right.key())


@_frozen
class UnOp(Expr):
    op: str
    operand: Expr
    def key(self): return ("un", self.op, self.operand.key())


@_frozen
class Cast(Expr):
    operand: Expr
    to: dt.DType
    def key(self): return ("cast", self.operand.key(), self.to.name)


@_frozen
class DtField(Expr):
    field: str
    operand: Expr
    def key(self): return ("dtf", self.field, self.operand.key())


@_frozen
class IsIn(Expr):
    operand: Expr
    values: Tuple
    def key(self): return ("isin", self.operand.key(), self.values)


@_frozen
class Where(Expr):
    cond: Expr
    iftrue: Expr
    iffalse: Expr
    def key(self):
        return ("where", self.cond.key(), self.iftrue.key(), self.iffalse.key())


@_frozen
class RowUDF(Expr):
    """Compiled element-wise Python UDF (df.apply(axis=1) / Series.map).

    The callable is traced with jax.vmap over per-row scalars — the
    trace-to-XLA analogue of the reference compiling UDFs with a nested
    Numba pipeline (BodoCompilerUDF, bodo/compiler.py:705). String columns
    are withheld from the row namespace (dict codes would silently change
    semantics); a UDF touching one raises KeyError at trace time and the
    frontend falls back to pandas.
    """
    func: Any          # callable(Row) -> scalar, or callable(x) in scalar mode
    out_dtype: Any     # DType or None (trace default float64)
    operand: Any = None  # Expr → scalar mode (Series.map); None → row mode
    def key(self):
        return ("rowudf", _udf_serial(self.func),
                self.out_dtype.name if self.out_dtype else None,
                self.operand.key() if self.operand is not None else None)


_UDF_COUNTER = [0]
_UDF_SERIALS: Dict[int, Tuple] = {}  # id -> (weakref, serial)


def _udf_serial(func) -> int:
    """Stable serial per live callable — id() alone is unsafe as cache key
    (CPython reuses ids after GC; same guard as relational._dict_fp)."""
    s = getattr(func, "__bodo_tpu_udf_serial__", None)
    if s is not None:
        return s
    _UDF_COUNTER[0] += 1
    serial = _UDF_COUNTER[0]
    try:
        func.__bodo_tpu_udf_serial__ = serial
    except (AttributeError, TypeError):
        import weakref
        ent = _UDF_SERIALS.get(id(func))
        if ent is not None and ent[0]() is func:
            return ent[1]
        key = id(func)
        try:
            wr = weakref.ref(func, lambda _: _UDF_SERIALS.pop(key, None))
        except TypeError:
            wr = lambda: func  # not weakref-able: pin via closure
        _UDF_SERIALS[key] = (wr, serial)
    return serial


class _RowNS:
    """Attribute/item access over a dict of per-row scalar tracers;
    records which columns the UDF actually reads (for null propagation)."""
    __slots__ = ("_d", "_touched")

    def __init__(self, d, touched=None):
        object.__setattr__(self, "_d", d)
        object.__setattr__(self, "_touched", touched)

    def __getattr__(self, n):
        try:
            v = self._d[n]
        except KeyError:
            raise AttributeError(n)
        if self._touched is not None:
            self._touched.add(n)
        return v

    def __getitem__(self, n):
        if self._touched is not None and n in self._d:
            self._touched.add(n)
        return self._d[n]


@_frozen
class DictMap(Expr):
    """String→string transform applied to the host dictionary (substring,
    upper, lower): the device only remaps int32 codes through a host-built
    translation table — strings never reach the device (same trick as the
    reference's dict-encoded string kernels, bodo/libs/dict_arr_ext.py).
    Must sit at the top level of a projection (relational.assign_columns
    attaches the new dictionary host-side)."""
    kind: str          # substring | upper | lower | strip | replace | ...
    params: Tuple
    operand: Expr      # must reference a string column
    def key(self):
        return ("dictmap", self.kind, self.params, self.operand.key())

    def apply_host(self, s: str) -> str:
        if self.kind == "substring":
            start, length = self.params
            i = start - 1  # SQL is 1-based
            return s[i:i + length] if length is not None else s[i:]
        if self.kind == "slice":  # pandas .str.slice — 0-based, stop excl
            start, stop = self.params
            return s[start:stop]
        if self.kind == "upper":
            return s.upper()
        if self.kind == "lower":
            return s.lower()
        if self.kind == "strip":
            return s.strip(*self.params)
        if self.kind == "lstrip":
            return s.lstrip(*self.params)
        if self.kind == "rstrip":
            return s.rstrip(*self.params)
        if self.kind == "replace":
            old, new = self.params
            return s.replace(old, new)
        if self.kind == "title":
            return s.title()
        if self.kind == "capitalize":
            return s.capitalize()
        if self.kind == "zfill":
            return s.zfill(self.params[0])
        if self.kind == "lpad":
            n, fill = self.params
            if len(s) >= n:
                return s[:n]
            pad = (fill * n)[: n - len(s)] if fill else ""
            return pad + s
        if self.kind == "rpad":
            n, fill = self.params
            if len(s) >= n:
                return s[:n]
            return s + (fill * n)[: n - len(s)] if fill else s
        if self.kind == "left":
            n = self.params[0]
            return s[:n] if n > 0 else ""
        if self.kind == "right":
            n = self.params[0]
            return s[-n:] if n > 0 else ""
        if self.kind == "reverse":
            return s[::-1]
        if self.kind == "repeat":
            return s * self.params[0]
        if self.kind == "split_part":
            delim, n = self.params
            parts = s.split(delim) if delim else [s]
            return parts[n - 1] if 1 <= n <= len(parts) else ""
        if self.kind == "initcap":
            return re.sub(r"[A-Za-z0-9]+",
                          lambda m: m.group(0).capitalize(), s)
        if self.kind == "translate":
            src, dst = self.params
            return s.translate(str.maketrans(src, dst))
        if self.kind == "prepend":
            return self.params[0] + s
        if self.kind == "append":
            return s + self.params[0]
        if self.kind == "regexp_replace":
            # (pat, repl[, position, occurrence]) — occurrence 0 = all
            # (Snowflake REGEXP_REPLACE semantics,
            # bodosql/kernels/regexp_array_kernels.py)
            pat, repl = self.params[:2]
            pos = self.params[2] if len(self.params) > 2 else 1
            occ = self.params[3] if len(self.params) > 3 else 0
            head, tail = s[:pos - 1], s[pos - 1:]
            if occ == 0:
                return head + re.sub(pat, repl, tail)
            n = 0
            for m in re.finditer(pat, tail):
                n += 1
                if n == occ:
                    return (head + tail[:m.start()] + m.expand(repl)
                            + tail[m.end():])
            return s  # fewer than `occ` matches: unchanged
        if self.kind == "regexp_substr":
            # (pat[, position, occurrence, group]) — no-match rows become
            # NULL (validity handled by the assign_columns host pass,
            # relational._str_part)
            m = self._re_match(s)
            if m is None:
                return ""
            grp = self.params[3] if len(self.params) > 3 else 0
            return m.group(grp) or ""
        if self.kind == "json_extract":
            # JSON_EXTRACT_PATH_TEXT: dotted/indexed path into a JSON
            # string; missing path / bad JSON -> NULL via host_null
            # (bodosql/kernels/json_array_kernels.py)
            v = _json_path_get(s, self.params[0])
            if v is None:
                return ""
            if isinstance(v, (dict, list)):
                import json as _json
                return _json.dumps(v, separators=(",", ":"))
            if isinstance(v, bool):
                return "true" if v else "false"
            return str(v)
        if self.kind == "json_canon":
            # PARSE_JSON/TO_JSON canonical form; invalid JSON -> NULL
            import json as _json
            try:
                return _json.dumps(_json.loads(s),
                                   separators=(",", ":"))
            except Exception:
                return ""
        if self.kind == "strtok":
            # STRTOK(s[, delim, part]): split on ANY delimiter char,
            # empty tokens dropped (Snowflake)
            part = self.params[1] if len(self.params) > 1 else 1
            toks = self._strtok_tokens(s)
            return toks[part - 1] if 1 <= part <= len(toks) else ""
        if self.kind == "check_json":
            # Snowflake CHECK_JSON: NULL for valid JSON (host_null),
            # the parse-error description for invalid
            import json as _json
            try:
                _json.loads(s)
                return ""
            except Exception as exc:
                return str(exc)
        if self.kind == "insert":
            # INSERT(s, pos, len, repl) (Snowflake)
            pos, n, repl = self.params
            i = pos - 1
            return s[:i] + repl + s[i + n:]
        if self.kind == "ljust":
            n, fill = self.params
            return s.ljust(n, fill)
        if self.kind == "rjust":
            n, fill = self.params
            return s.rjust(n, fill)
        if self.kind == "center":
            n, fill = self.params
            return s.center(n, fill)
        if self.kind == "get":
            i = self.params[0]
            return s[i] if -len(s) <= i < len(s) else ""
        if self.kind == "md5":
            import hashlib
            return hashlib.md5(s.encode()).hexdigest()
        if self.kind == "sha1":
            import hashlib
            return hashlib.sha1(s.encode()).hexdigest()
        if self.kind == "sha2":
            import hashlib
            bits = self.params[0] if self.params else 256
            h = {224: hashlib.sha224, 256: hashlib.sha256,
                 384: hashlib.sha384, 512: hashlib.sha512}[bits]
            return h(s.encode()).hexdigest()
        raise ValueError(self.kind)

    def _strtok_tokens(self, s: str):
        """STRTOK tokens: split on ANY delimiter char, drop empties; an
        empty delimiter set means the whole string is one token."""
        delim = self.params[0] if self.params else " "
        if not delim:
            return [s] if s else []
        return [t_ for t_ in re.split(
            "|".join(re.escape(c) for c in delim), s) if t_]

    def _re_match(self, s: str):
        """regexp_substr match honoring (pat, position, occurrence)."""
        pat = self.params[0]
        pos = self.params[1] if len(self.params) > 1 else 1
        occ = self.params[2] if len(self.params) > 2 else 1
        n = 0
        for m in re.finditer(pat, s[pos - 1:]):
            n += 1
            if n == occ:
                return m
        return None

    def host_null(self, s: str) -> bool:
        """Whether this transform yields NULL for input `s` (applied by
        the assign_columns host pass; eval-side predicates ignore it)."""
        if self.kind == "regexp_substr":
            m = self._re_match(s)
            if m is None:
                return True
            grp = self.params[3] if len(self.params) > 3 else 0
            return m.group(grp) is None
        if self.kind == "json_extract":
            return _json_path_get(s, self.params[0]) is None
        if self.kind == "json_canon":
            import json as _json
            try:
                _json.loads(s)
                return False
            except Exception:
                return True
        if self.kind == "strtok":
            part = self.params[1] if len(self.params) > 1 else 1
            return not (1 <= part <= len(self._strtok_tokens(s)))
        if self.kind == "check_json":
            import json as _json
            try:
                _json.loads(s)
                return True   # valid JSON -> NULL (Snowflake CHECK_JSON)
            except Exception:
                return False
        if self.kind == "get":
            i = self.params[0]
            return not (-len(s) <= i < len(s))
        return False


def _json_path_get(s: str, path: str):
    """Walk a dotted/bracketed path into a JSON string; None on invalid
    JSON or a missing step (JSON_EXTRACT_PATH_TEXT / GET_PATH host
    evaluator; reference: bodosql/kernels/json_array_kernels.py)."""
    import json as _json
    try:
        v = _json.loads(s)
        parts = _split_json_path(path)
    except Exception:
        return None
    for part in parts:
        if isinstance(part, int):
            if not isinstance(v, list) or not (-len(v) <= part < len(v)):
                return None
            v = v[part]
        else:
            if not isinstance(v, dict) or part not in v:
                return None
            v = v[part]
    return v


def _split_json_path(path: str):
    """'a.b[2].c' / "a['b']" -> ['a', 'b', 2, 'c']. Quote-aware: a
    QUOTED segment is always a string key (even '\"2\"', and even when
    it contains '.' or '['); only bare bracketed integers become list
    indices. Raises ValueError on malformed paths (unclosed quote or
    bracket) — callers treat that as no-match."""
    parts: list = []
    i, n = 0, len(path)
    while i < n:
        c = path[i]
        if c == ".":
            i += 1
        elif c in "'\"":
            j = path.find(c, i + 1)
            if j < 0:
                raise ValueError(f"unclosed quote in path {path!r}")
            parts.append(path[i + 1:j])
            i = j + 1
        elif c == "[":
            j = path.find("]", i)
            if j < 0:
                raise ValueError(f"unclosed bracket in path {path!r}")
            seg = path[i + 1:j].strip()
            if seg[:1] in "'\"":
                if len(seg) < 2 or seg[-1] != seg[0]:
                    raise ValueError(f"bad quoted key in path {path!r}")
                parts.append(seg[1:-1])
            elif seg.lstrip("-").isdigit():
                parts.append(int(seg))
            else:
                parts.append(seg)
            i = j + 1
        else:
            j = i
            while j < n and path[j] not in ".[":
                j += 1
            seg = path[i:j].strip()
            if seg:
                parts.append(int(seg) if seg.lstrip("-").isdigit()
                             else seg)
            i = j
    return parts


@_frozen
class MathFn(Expr):
    """Element-wise math function on the VPU (SQL kernel library analogue
    of the reference's numeric kernels, BodoSQL/bodosql/kernels/
    numeric_array_kernels.py). kind: ceil|floor|sqrt|exp|ln|log10|log2|
    sign|sin|cos|tan|asin|acos|atan|degrees|radians|round|round_even|
    trunc. `round`/`trunc` take (digits,) in params; SQL `round` is
    half-away-from-zero, `round_even` is banker's (pandas)."""
    kind: str
    params: Tuple
    operand: Expr
    def key(self): return ("math", self.kind, self.params, self.operand.key())


@_frozen
class ToChar(Expr):
    """TO_CHAR/TO_VARCHAR of a non-string operand: the operand evaluates
    on device, values round-trip to host once, and the formatted strings
    dict-encode like any ingest (reference:
    bodosql/kernels/casting_array_kernels.py to_char). `fmt` is a
    Snowflake-style date format ('YYYY-MM-DD' etc., translated to
    strftime) or None for the canonical numeric/date rendering. Must sit
    at the top level of a projection (relational.assign_columns builds
    the new dictionary host-side, same contract as DictMap)."""
    fmt: Optional[str]
    operand: Expr

    def key(self):
        return ("tochar", self.fmt, self.operand.key())

    _FMT = (("YYYY", "%Y"), ("YY", "%y"), ("MMMM", "%B"),
            ("MON", "%b"), ("MM", "%m"), ("DD", "%d"), ("DY", "%a"),
            ("HH24", "%H"), ("HH12", "%I"), ("HH", "%H"),
            ("MI", "%M"), ("SS", "%S"), ("AM", "%p"), ("PM", "%p"))

    def strftime_fmt(self) -> Optional[str]:
        if self.fmt is None:
            return None
        out = self.fmt
        for sf, py in self._FMT:
            out = out.replace(sf, py).replace(sf.lower(), py)
        return out


@_frozen
class MaskNull(Expr):
    """Null out rows where `cond` holds (NULLIF building block): data
    passes through, validity becomes valid & ~cond."""
    cond: Expr
    operand: Expr
    def key(self): return ("masknull", self.cond.key(), self.operand.key())


def contains_expr(e, cls, stop=()) -> bool:
    """True when `e` or any sub-expression is an instance of `cls`
    (generic dataclass-field walk; tuples of Exprs are descended).
    Subtrees rooted at a `stop` node are not entered — callers use this
    to exempt nodes that consume the target legally (e.g. StrPredicate
    evaluates a CodeLUT operand at the dictionary level itself)."""
    if isinstance(e, cls):
        return True
    if stop and isinstance(e, stop):
        return False
    import dataclasses
    if not dataclasses.is_dataclass(e):
        return False
    for f in dataclasses.fields(e):
        v = getattr(e, f.name)
        for x in (v if isinstance(v, tuple) else (v,)):
            if isinstance(x, Expr) and contains_expr(x, cls, stop):
                return True
    return False


def codelut_misplaced(e, consumer_ok: bool = True) -> bool:
    """True when a CodeLUT sits in a position where evaluation would
    yield raw LUT codes with no dictionary attached.

    Legal positions: the top level of a projection, or the operand
    spine (DictMap* → CodeLUT) of a string-CONSUMING node
    (StrPredicate/StrLen/StrHostFn/StrCodes evaluate the LUT at
    dictionary level). Unlike a `stop`-pruned contains_expr walk, the
    scan continues INSIDE consumer operands, so e.g.
    StrPredicate(Where(c, CodeLUT, x)) is still reported."""
    import dataclasses
    if isinstance(e, CodeLUT):
        # a legally-consumed CodeLUT's integer operand must itself be
        # CodeLUT-free
        return (not consumer_ok) or codelut_misplaced(e.operand, False)
    if isinstance(e, (StrPredicate, StrLen, StrHostFn, StrCodes)):
        op = e.operand
        while isinstance(op, DictMap):
            op = op.operand
        return codelut_misplaced(op, True)
    if not dataclasses.is_dataclass(e):
        return False
    for f in dataclasses.fields(e):
        v = getattr(e, f.name)
        for x in (v if isinstance(v, tuple) else (v,)):
            if isinstance(x, Expr) and codelut_misplaced(x, False):
                return True
    return False


@_frozen
class CodeLUT(Expr):
    """String column from a small static vocabulary indexed by an integer
    expression (MONTHNAME/DAYNAME analogue of the reference's
    bodosql/kernels/datetime_array_kernels.py monthname). `operand` must
    produce codes in [0, len(strings)); the device only sees the
    remapping into the sorted dictionary."""
    strings: Tuple
    operand: Expr
    def key(self): return ("codelut", self.strings, self.operand.key())

    def sorted_dict(self) -> np.ndarray:
        return np.sort(np.asarray(self.strings, dtype=str))

    def rank_lut(self) -> np.ndarray:
        """rank_lut[i] = position of strings[i] in the sorted dictionary."""
        return np.argsort(np.argsort(np.asarray(self.strings, dtype=str))
                          ).astype(np.int32)


@_frozen
class StrHostFn(Expr):
    """Numeric function of a string column, evaluated per dictionary
    entry on host → device gather through the LUT (same trick as StrLen).
    kind: position(sub) 1-based 0-if-absent | ascii | to_number |
    to_date | regexp_count(pat). to_number/to_date entries that fail to
    parse become null."""
    kind: str
    params: Tuple
    operand: Expr
    def key(self): return ("strhost", self.kind, self.params,
                           self.operand.key())

    def apply_host(self, s: str):
        """Returns (value, ok)."""
        if self.kind == "position":
            return s.find(self.params[0]) + 1, True
        if self.kind == "ascii":
            return (ord(s[0]) if s else 0), True
        if self.kind == "to_number":
            try:
                return float(s), True
            except ValueError:
                return 0.0, False
        if self.kind == "to_date":
            try:
                d = np.datetime64(s.strip()[:10], "D")
            except ValueError:
                return 0, False
            if np.isnat(d):  # np.datetime64('') parses to NaT, no raise
                return 0, False
            return int(d.astype(np.int64)), True
        if self.kind == "regexp_count":
            pos = self.params[1] if len(self.params) > 1 else 1
            return len(re.findall(self.params[0], s[pos - 1:])), True
        if self.kind == "regexp_instr":
            # (pat[, position, occurrence, option]) -> 1-based match
            # start (option=0) or one past the end (option=1); 0 = no
            # match (Snowflake REGEXP_INSTR)
            pat = self.params[0]
            pos = self.params[1] if len(self.params) > 1 else 1
            occ = self.params[2] if len(self.params) > 2 else 1
            opt = self.params[3] if len(self.params) > 3 else 0
            n = 0
            for m in re.finditer(pat, s[pos - 1:]):
                n += 1
                if n == occ:
                    return (m.end() if opt else m.start()) + pos, True
            return 0, True
        if self.kind == "editdistance":
            t_ = self.params[0]
            cap = self.params[1] if len(self.params) > 1 else None
            prev = list(range(len(t_) + 1))
            for i, cs in enumerate(s, 1):
                cur = [i]
                for j, ct in enumerate(t_, 1):
                    cur.append(min(prev[j] + 1, cur[j - 1] + 1,
                                   prev[j - 1] + (cs != ct)))
                prev = cur
            d = prev[-1]
            return (min(d, cap) if cap is not None else d), True
        raise ValueError(self.kind)


@_frozen
class StrConcat(Expr):
    """Concatenation of string columns and literal fragments into one
    dict-encoded column. parts: str literals and string-producing Exprs.
    With k column parts the combined dictionary is the cross product of
    the part dictionaries (mixed-radix codes on device), gated by
    MAX_CONCAT_DICT — the dict-encoded analogue of the reference's
    concat kernel (BodoSQL/bodosql/kernels/string_array_kernels.py)."""
    parts: Tuple
    def key(self):
        return ("strcat", tuple(p if isinstance(p, str) else p.key()
                                for p in self.parts))


MAX_CONCAT_DICT = 1 << 20


@_frozen
class DateTrunc(Expr):
    """DATE_TRUNC(unit, x): start of the containing unit."""
    unit: str
    operand: Expr
    def key(self): return ("dtrunc", self.unit, self.operand.key())


@_frozen
class DateAdd(Expr):
    """DATEADD(unit, n, x) — calendar-correct for month/quarter/year
    (day-of-month clamped), tick arithmetic for fixed-width units."""
    unit: str
    amount: Expr
    operand: Expr
    def key(self): return ("dadd", self.unit, self.amount.key(),
                           self.operand.key())


@_frozen
class DateDiff(Expr):
    """DATEDIFF(unit, a, b) = boundary count from a to b (Snowflake
    semantics: year diff is year(b)-year(a), etc.)."""
    unit: str
    left: Expr
    right: Expr
    def key(self): return ("ddiff", self.unit, self.left.key(),
                           self.right.key())


@_frozen
class StrLen(Expr):
    """Per-row string length via a host dictionary LUT → device int32
    gather (same dict-encoded trick as StrPredicate; reference:
    bodo/libs/dict_arr_ext.py str_len kernel)."""
    operand: Expr

    def key(self):
        return ("strlen", self.operand.key())


@_frozen
class NestedFn(Expr):
    """Semi-structured access over nested (list/struct/map) columns
    (reference: BodoSQL/bodosql/kernels/semistructured_array_kernels.py
    GET/GET_PATH/ARRAY_SIZE). kind: list_len | list_get(i) |
    field(name). Kernels are host-dictionary LUTs gathered on device
    (table/nested.py); string-valued results attach their dictionary in
    the assign_columns host pass, so NestedFn must sit at the top level
    of a projection like DictMap."""
    kind: str
    params: Tuple
    operand: Expr
    def key(self): return ("nested", self.kind, self.params,
                           self.operand.key())


@_frozen
class StrToList(Expr):
    """str.split(expand=False) → list<string> column; the split runs
    once per distinct dictionary entry on host (table/nested.py design;
    reference: bodo/libs/dict_arr_ext.py str_split + array_item repr).
    Must sit at the top level of a projection like DictMap."""
    params: Tuple      # (pat, maxsplit)
    operand: Expr
    def key(self): return ("strtolist", self.params, self.operand.key())

    def split_host(self, s: str):
        pat, n = self.params
        return tuple(s.split(pat) if n <= 0 else s.split(pat, n))


@_frozen
class StrCodes(Expr):
    """Dictionary codes of a string column as int32 (pandas .cat.codes
    analogue; nulls become -1). The dictionary is sorted, so on a
    freshly-scanned column codes equal `astype('category')` codes; after
    a filter the full dictionary persists, so codes may be sparser than
    pandas' renumbering (see _CatAccessor docstring). Reference:
    bodo/hiframes/pd_categorical_ext.py get_categorical_arr_codes."""
    operand: Expr
    def key(self): return ("strcodes", self.operand.key())


@_frozen
class StrPredicate(Expr):
    """String predicate evaluated on the host dictionary → device LUT.
    kind: contains | startswith | endswith | match | eq_any | lower_eq"""
    kind: str
    pattern: Tuple
    operand: Expr
    def key(self):
        return ("strp", self.kind, self.pattern, self.operand.key())


# ---------------------------------------------------------------------------
# schema-level type inference (host side)
# ---------------------------------------------------------------------------

def infer_dtype(e: Expr, schema: Dict[str, dt.DType]) -> dt.DType:
    if isinstance(e, ColRef):
        return schema[e.name]
    if isinstance(e, Lit):
        v = e.value
        if isinstance(v, bool):
            return dt.BOOL
        if isinstance(v, (int, np.integer)):
            return dt.INT64
        if isinstance(v, (float, np.floating)):
            return dt.FLOAT64
        if isinstance(v, str):
            return dt.STRING
        if isinstance(v, (np.datetime64,)):
            return dt.DATETIME
        import datetime as _dtmod
        if isinstance(v, _dtmod.date) and not isinstance(v, _dtmod.datetime):
            return dt.DATE
        raise TypeError(f"unsupported literal: {v!r}")
    if isinstance(e, Cast):
        return e.to
    if isinstance(e, DtField):
        return dt.DATE if e.field == "date" else dt.INT64
    if isinstance(e, (IsIn, StrPredicate)):
        return dt.BOOL
    if isinstance(e, (DictMap, CodeLUT, StrConcat, ToChar)):
        return dt.STRING
    if isinstance(e, StrToList):
        return dt.list_of(dt.STRING)
    if isinstance(e, NestedFn):
        src = infer_dtype(e.operand, schema)
        if e.kind == "list_len":
            return dt.INT64
        if e.kind == "list_get":
            return src.elem if src.kind == "list" else dt.FLOAT64
        if e.kind == "field":
            if src.kind == "map":
                return src.value
            if src.kind == "struct":
                m = dict(src.fields)
                if e.params[0] in m:
                    return m[e.params[0]]
            return dt.FLOAT64
        raise ValueError(e.kind)
    if isinstance(e, StrLen):
        return dt.INT64
    if isinstance(e, StrCodes):
        return dt.INT32
    if isinstance(e, StrHostFn):
        if e.kind == "to_number":
            return dt.FLOAT64
        if e.kind == "to_date":
            return dt.DATE
        return dt.INT64
    if isinstance(e, MathFn):
        if e.kind == "sign":
            return dt.INT64
        if e.kind in ("ceil", "floor", "round", "round_even", "trunc"):
            src = infer_dtype(e.operand, schema)
            if dt.is_decimal(src):
                return dt.FLOAT64
            return src if src.kind in ("i", "u") else dt.FLOAT64
        return dt.FLOAT64
    if isinstance(e, MaskNull):
        return infer_dtype(e.operand, schema)
    if isinstance(e, DateTrunc):
        return infer_dtype(e.operand, schema)
    if isinstance(e, DateAdd):
        src = infer_dtype(e.operand, schema)
        if src is dt.DATE and e.unit in ("hour", "minute", "second"):
            return dt.DATETIME
        return src
    if isinstance(e, DateDiff):
        return dt.INT64
    if isinstance(e, RowUDF):
        if e.out_dtype is not None:
            return e.out_dtype
        return dt.FLOAT64
    if isinstance(e, UnOp):
        if e.op in ("isna", "notna", "~"):
            return dt.BOOL
        return infer_dtype(e.operand, schema)
    if isinstance(e, Where):
        t = infer_dtype(e.iftrue, schema)
        f = infer_dtype(e.iffalse, schema)
        if t is f:
            return t
        if dt.is_numeric(t) and dt.is_numeric(f):
            return dt.common_numeric(t, f)
        return t
    if isinstance(e, BinOp):
        if e.op in ("==", "!=", "<", "<=", ">", ">=", "&", "|"):
            return dt.BOOL
        if e.op in ("max2", "min2"):
            lt = infer_dtype(e.left, schema)
            rt = infer_dtype(e.right, schema)
            if dt.is_decimal(lt) or dt.is_decimal(rt):
                ls = lt.scale if dt.is_decimal(lt) else 0
                rs = rt.scale if dt.is_decimal(rt) else 0
                return dt.decimal(max(ls, rs))
            if dt.is_numeric(lt) and dt.is_numeric(rt):
                return dt.common_numeric(lt, rt)
            return lt
        lt = infer_dtype(e.left, schema)
        rt = infer_dtype(e.right, schema)
        if dt.is_decimal(lt) or dt.is_decimal(rt):
            ls = lt.scale if dt.is_decimal(lt) else None
            rs = rt.scale if dt.is_decimal(rt) else None
            float_side = (ls is None and lt.kind == "f") or \
                (rs is None and rt.kind == "f")
            if float_side or e.op == "/":
                return dt.FLOAT64
            if e.op == "*":
                return dt.decimal((ls or 0) + (rs or 0))
            return dt.decimal(max(ls or 0, rs or 0))
        if e.op == "/":
            return dt.FLOAT64 if lt.numpy.itemsize == 8 or rt.numpy.itemsize == 8 \
                else dt.FLOAT32
        if dt.is_numeric(lt) and dt.is_numeric(rt):
            return dt.common_numeric(lt, rt)
        return lt
    raise TypeError(f"cannot infer dtype of {e}")


def expr_columns(e: Expr) -> set:
    """Free column references (for projection pushdown)."""
    if isinstance(e, ColRef):
        return {e.name}
    if isinstance(e, Lit):
        return set()
    if isinstance(e, BinOp):
        return expr_columns(e.left) | expr_columns(e.right)
    if isinstance(e, RowUDF):
        if e.operand is not None:
            return expr_columns(e.operand)
        return {"*"}  # may touch any column — disables pruning above it
    if isinstance(e, (UnOp, Cast, DtField, IsIn, StrPredicate, DictMap,
                      StrLen, MathFn, StrHostFn, CodeLUT, DateTrunc,
                      StrCodes, StrToList, NestedFn, ToChar)):
        return expr_columns(e.operand)
    if isinstance(e, Where):
        return (expr_columns(e.cond) | expr_columns(e.iftrue)
                | expr_columns(e.iffalse))
    if isinstance(e, MaskNull):
        return expr_columns(e.cond) | expr_columns(e.operand)
    if isinstance(e, DateAdd):
        return expr_columns(e.amount) | expr_columns(e.operand)
    if isinstance(e, DateDiff):
        return expr_columns(e.left) | expr_columns(e.right)
    if isinstance(e, StrConcat):
        out = set()
        for p in e.parts:
            if isinstance(p, Expr):
                out |= expr_columns(p)
        return out
    return set()


# ---------------------------------------------------------------------------
# static value-range inference (host side; feeds Column.vrange)
# ---------------------------------------------------------------------------

# fields with fixed output ranges regardless of input
_FIELD_RANGES = {"month": (1, 12), "hour": (0, 23), "day": (1, 31),
                 "dayofweek": (0, 6), "weekday": (0, 6),
                 "quarter": (1, 4), "minute": (0, 59), "second": (0, 59),
                 "week": (1, 53), "weekofyear": (1, 53),
                 "dayofyear": (1, 366)}


def expr_range(e: Expr, columns) -> Optional[tuple]:
    """Host-known (lo, hi, tight) bound on the physical values of `e`,
    or None. `columns` maps name -> Column (for source vranges).
    `tight` means refinement (an exact device min/max) would not shrink
    the bound enough to matter — parquet scan stats and literals are
    tight, fixed field ranges (month in 1..12) are loose. Conservative:
    returns None unless the bound is certain."""
    if isinstance(e, ColRef):
        c = columns.get(e.name)
        return c.vrange if c is not None else None
    if isinstance(e, Lit):
        v = e.value
        if isinstance(v, (bool, np.bool_)):
            return (int(v), int(v), True)
        if isinstance(v, (int, np.integer)):
            return (int(v), int(v), True)
        return None
    if isinstance(e, DtField):
        if e.field in _FIELD_RANGES:
            lo, hi = _FIELD_RANGES[e.field]
            return (lo, hi, False)
        src = expr_range(e.operand, columns)
        if src is None:
            return None
        lo, hi = src[0], src[1]
        tight = len(src) > 2 and bool(src[2])
        if e.field == "date":       # monotone in ticks
            day = 86_400_000_000_000
            return (int(lo) // day, int(hi) // day, tight)
        if e.field == "year":       # monotone in ticks
            return (int(np.datetime64(int(lo), "ns").astype(
                        "datetime64[Y]").astype(int)) + 1970,
                    int(np.datetime64(int(hi), "ns").astype(
                        "datetime64[Y]").astype(int)) + 1970, tight)
        return None
    if isinstance(e, Where):
        a = expr_range(e.iftrue, columns)
        b = expr_range(e.iffalse, columns)
        if a is None or b is None:
            return None
        return (min(a[0], b[0]), max(a[1], b[1]),
                (len(a) > 2 and bool(a[2])) and
                (len(b) > 2 and bool(b[2])))
    if isinstance(e, Cast):
        if e.to.kind in ("i", "u"):
            r = expr_range(e.operand, columns)
            if r is None:
                return None
            # a narrowing cast (int64 → int32/int8) wraps values that
            # exceed the target type, so the operand's bound is only
            # sound when it fits entirely within the target's range —
            # otherwise dense-groupby planners would trust a violated
            # bound and silently mis-slot rows
            info = np.iinfo(e.to.numpy)
            if info.min <= r[0] and r[1] <= info.max:
                return r
            return None
        return None
    if isinstance(e, MaskNull):
        return expr_range(e.operand, columns)
    return None


# ---------------------------------------------------------------------------
# evaluation (device side, traced)
# ---------------------------------------------------------------------------

_CMP = {"==": jnp.equal, "!=": jnp.not_equal, "<": jnp.less,
        "<=": jnp.less_equal, ">": jnp.greater, ">=": jnp.greater_equal}


def eval_expr(e: Expr, tree: Dict[str, Tuple], dicts: Dict[str, np.ndarray],
              schema: Dict[str, dt.DType]):
    """Evaluate to (data, valid_or_None). `tree` maps column name to
    (data, valid); `dicts` holds host dictionaries for string columns."""
    if isinstance(e, ColRef):
        return tree[e.name]
    if isinstance(e, Lit):
        v = e.value
        if isinstance(v, str):
            raise TypeError(
                "string literal outside a string predicate — wrap string "
                "comparisons in StrPredicate (frontend does this)")
        if isinstance(v, np.datetime64):
            # match the DATETIME physical repr (int64 ns ticks)
            return jnp.asarray(np.int64(v.astype("datetime64[ns]")
                                        .astype(np.int64))), None
        import datetime as _dtmod
        if isinstance(v, _dtmod.date) and not isinstance(v, _dtmod.datetime):
            # match the DATE physical repr (int32 days since epoch)
            return jnp.asarray(np.int32(
                (np.datetime64(v, "D") - np.datetime64(0, "D"))
                .astype(np.int32))), None
        return jnp.asarray(v), None
    if isinstance(e, Cast):
        d, v = eval_expr(e.operand, tree, dicts, schema)
        src = infer_dtype(e.operand, schema)
        if e.to is dt.STRING:
            raise TypeError("cast to string not supported on device")
        if src.kind == "f" and e.to.kind in ("i", "u"):
            nan = jnp.isnan(d)
            v = (~nan) if v is None else (v & ~nan)
            d = jnp.where(nan, 0, d)
        return d.astype(e.to.numpy), v
    if isinstance(e, DtField):
        d, v = eval_expr(e.operand, tree, dicts, schema)
        if infer_dtype(e.operand, schema) is dt.DATE:
            # DATE stores days; field kernels expect ns ticks
            d = d.astype(jnp.int64) * dtops.NS_PER_DAY
        return dtops.FIELDS[e.field](d), v
    if isinstance(e, UnOp):
        if e.op in ("isna", "notna"):
            d, v = eval_expr(e.operand, tree, dicts, schema)
            isna = jnp.zeros(d.shape, dtype=bool)
            if v is not None:
                isna = ~v
            if jnp.issubdtype(d.dtype, jnp.floating):
                isna = isna | jnp.isnan(d)
            return (isna if e.op == "isna" else ~isna), None
        d, v = eval_expr(e.operand, tree, dicts, schema)
        if e.op == "~":
            return jnp.logical_not(d), v
        if e.op == "neg":
            return jnp.negative(d), v
        if e.op == "abs":
            return jnp.abs(d), v
        raise ValueError(f"unknown unop {e.op}")
    if isinstance(e, IsIn):
        d, v = eval_expr(e.operand, tree, dicts, schema)
        src = infer_dtype(e.operand, schema)
        if src is dt.STRING:
            return eval_expr(StrPredicate("eq_any", tuple(e.values),
                                          e.operand), tree, dicts, schema)
        acc = jnp.zeros(d.shape, dtype=bool)
        for val in e.values:
            acc = acc | (d == val)
        return acc, v
    if isinstance(e, RowUDF):
        import jax
        if e.operand is not None:  # scalar mode (Series.map)
            d, v = eval_expr(e.operand, tree, dicts, schema)
            out = jax.vmap(e.func)(d)
            if e.out_dtype is not None:
                out = out.astype(e.out_dtype.numpy)
            return out, v
        # row mode: withhold string/temporal columns — their physical repr
        # (dict codes, int ticks) would silently change meaning; a UDF
        # touching one fails the trace → frontend falls back to pandas
        numeric = {n: d for n, (d, v) in tree.items()
                   if schema.get(n) is not None
                   and schema[n].kind in ("i", "u", "f", "b")}
        # discover which columns the UDF reads (abstract pre-trace), so
        # null masks propagate only from consumed columns
        touched: set = set()
        jax.eval_shape(
            lambda row: e.func(_RowNS(row, touched)),
            {n: jax.ShapeDtypeStruct((), d.dtype) for n, d in numeric.items()})

        def one_row(row_vals):
            return e.func(_RowNS(row_vals))
        out = jax.vmap(one_row)(numeric)
        if e.out_dtype is not None:
            out = out.astype(e.out_dtype.numpy)
        valid = None
        for n in sorted(touched):
            v = tree[n][1]
            if v is not None:
                valid = v if valid is None else (valid & v)
        return out, valid
    if isinstance(e, MathFn):
        d, v = eval_expr(e.operand, tree, dicts, schema)
        src = infer_dtype(e.operand, schema)
        if dt.is_decimal(src):
            d = d.astype(jnp.float64) / (10.0 ** src.scale)
            src = dt.FLOAT64
        k = e.kind
        if k == "sign":
            return jnp.sign(d).astype(jnp.int64), v
        if k in ("ceil", "floor", "round", "round_even", "trunc"):
            if src.kind in ("i", "u") and k in ("ceil", "floor"):
                return d, v
            digits = int(e.params[0]) if e.params else 0
            mul = np.float64(10.0 ** digits)
            x = d.astype(jnp.float64) * mul
            if k == "ceil":
                r = jnp.ceil(d.astype(jnp.float64))
            elif k == "floor":
                r = jnp.floor(d.astype(jnp.float64))
            elif k == "round":     # SQL: half away from zero
                r = jnp.sign(x) * jnp.floor(jnp.abs(x) + 0.5) / mul
            elif k == "round_even":  # pandas/IEEE: half to even
                r = jnp.round(x) / mul
            else:                   # trunc: toward zero
                r = jnp.trunc(x) / mul
            if src.kind in ("i", "u"):
                return r.astype(src.numpy), v
            return r, v
        x = d.astype(jnp.float64)
        fns = {"sqrt": jnp.sqrt, "exp": jnp.exp, "ln": jnp.log,
               "log10": jnp.log10, "log2": jnp.log2, "sin": jnp.sin,
               "cos": jnp.cos, "tan": jnp.tan, "asin": jnp.arcsin,
               "acos": jnp.arccos, "atan": jnp.arctan,
               "degrees": jnp.degrees, "radians": jnp.radians}
        if k not in fns:
            raise ValueError(f"unknown math fn {k}")
        return fns[k](x), v
    if isinstance(e, MaskNull):
        c, cv = eval_expr(e.cond, tree, dicts, schema)
        d, v = eval_expr(e.operand, tree, dicts, schema)
        hit = c if cv is None else (c & cv)  # null cond does not mask
        valid = (~hit) if v is None else (v & ~hit)
        return d, valid
    if isinstance(e, CodeLUT):
        d, v = eval_expr(e.operand, tree, dicts, schema)
        lut = jnp.asarray(e.rank_lut())
        codes = lut[jnp.clip(d.astype(jnp.int32), 0, len(e.strings) - 1)]
        return codes, v
    if isinstance(e, DateTrunc):
        d, v = eval_expr(e.operand, tree, dicts, schema)
        src = infer_dtype(e.operand, schema)
        if src is dt.DATE:
            ns = d.astype(jnp.int64) * dtops.NS_PER_DAY
            out = dtops.trunc(e.unit, ns)
            return jnp.floor_divide(out, dtops.NS_PER_DAY
                                    ).astype(jnp.int32), v
        return dtops.trunc(e.unit, d), v
    if isinstance(e, DateAdd):
        d, v = eval_expr(e.operand, tree, dicts, schema)
        n, nv = eval_expr(e.amount, tree, dicts, schema)
        src = infer_dtype(e.operand, schema)
        out_dt = infer_dtype(e, schema)
        ns = d.astype(jnp.int64) * dtops.NS_PER_DAY if src is dt.DATE \
            else d.astype(jnp.int64)
        n = n.astype(jnp.int64)
        if e.unit in ("month", "quarter", "year"):
            mult = {"month": 1, "quarter": 3, "year": 12}[e.unit]
            out = dtops.add_months(ns, n * mult)
        else:
            step = {"week": dtops.NS_PER_DAY * 7, "day": dtops.NS_PER_DAY,
                    "hour": dtops.NS_PER_HOUR, "minute": dtops.NS_PER_MIN,
                    "second": dtops.NS_PER_SEC}[e.unit]
            out = ns + n * step
        if out_dt is dt.DATE:
            out = jnp.floor_divide(out, dtops.NS_PER_DAY).astype(jnp.int32)
        valid = None
        if v is not None or nv is not None:
            valid = (v if v is not None else jnp.ones(out.shape, bool)) & \
                    (nv if nv is not None else jnp.ones(out.shape, bool))
        return out, valid
    if isinstance(e, DateDiff):
        la, lv = eval_expr(e.left, tree, dicts, schema)
        ra, rv = eval_expr(e.right, tree, dicts, schema)
        lt = infer_dtype(e.left, schema)
        rt = infer_dtype(e.right, schema)
        lns = la.astype(jnp.int64) * dtops.NS_PER_DAY if lt is dt.DATE \
            else la.astype(jnp.int64)
        rns = ra.astype(jnp.int64) * dtops.NS_PER_DAY if rt is dt.DATE \
            else ra.astype(jnp.int64)
        u = e.unit
        if u == "year":
            out = dtops.year(rns) - dtops.year(lns)
        elif u == "quarter":
            out = (dtops.year(rns) * 4 + (dtops.quarter(rns) - 1)) - \
                  (dtops.year(lns) * 4 + (dtops.quarter(lns) - 1))
        elif u == "month":
            out = dtops.month_index(rns) - dtops.month_index(lns)
        elif u == "week":
            out = jnp.floor_divide(dtops.days_from_ns(rns) -
                                   dtops.dayofweek(rns), 7) - \
                jnp.floor_divide(dtops.days_from_ns(lns) -
                                 dtops.dayofweek(lns), 7)
        else:
            step = {"day": dtops.NS_PER_DAY, "hour": dtops.NS_PER_HOUR,
                    "minute": dtops.NS_PER_MIN, "second": dtops.NS_PER_SEC}[u]
            out = jnp.floor_divide(rns, step) - jnp.floor_divide(lns, step)
        valid = None
        if lv is not None or rv is not None:
            valid = (lv if lv is not None else jnp.ones(out.shape, bool)) & \
                    (rv if rv is not None else jnp.ones(out.shape, bool))
        return out.astype(jnp.int64), valid
    if isinstance(e, StrCodes):
        d, v = eval_expr(e.operand, tree, dicts, schema)
        codes = d.astype(jnp.int32)
        if v is not None:
            codes = jnp.where(v, codes, np.int32(-1))
        return codes, None
    if isinstance(e, (StrLen, StrHostFn)):
        col = e.operand
        transforms = []
        while isinstance(col, DictMap):
            transforms.append(col)
            col = col.operand
        base_codes = None
        if isinstance(col, CodeLUT):
            vals = list(col.sorted_dict())
            base_codes = eval_expr(col, tree, dicts, schema)
        elif isinstance(col, ColRef):
            dic = dicts.get(col.name)
            if dic is None:
                raise TypeError(f"column {col.name} has no dictionary")
            vals = list(dic)
            base_codes = tree[col.name]
        else:
            raise TypeError("string functions must apply to a string column")
        for tr in reversed(transforms):
            vals = [tr.apply_host(s) for s in vals]
        d, v = base_codes
        if isinstance(e, StrLen):
            lut = jnp.asarray(np.array([len(s) for s in vals] or [0],
                                       dtype=np.int64))
            return lut[jnp.clip(d, 0, len(vals) - 1 if vals else 0)], v
        pairs = [e.apply_host(s) for s in vals] or [(0, True)]
        out_np = np.asarray([p[0] for p in pairs])
        if e.kind == "to_number":
            out_np = out_np.astype(np.float64)
        elif e.kind == "to_date":
            out_np = out_np.astype(np.int32)
        else:
            out_np = out_np.astype(np.int64)
        lut = jnp.asarray(out_np)
        codes = jnp.clip(d, 0, len(vals) - 1 if vals else 0)
        out = lut[codes]
        ok = np.asarray([p[1] for p in pairs], dtype=bool)
        if not ok.all():
            okv = jnp.asarray(ok)[codes]
            v = okv if v is None else (v & okv)
        return out, v
    if isinstance(e, StrPredicate):
        col = e.operand
        transforms = []
        while isinstance(col, DictMap):  # compose host transforms
            transforms.append(col)
            col = col.operand
        if isinstance(col, CodeLUT):
            dic = list(col.sorted_dict())
            d, v = eval_expr(col, tree, dicts, schema)
        elif isinstance(col, ColRef):
            dic0 = dicts.get(col.name)
            if dic0 is None:
                raise TypeError(f"column {col.name} has no dictionary")
            dic = list(dic0)
            d, v = tree[col.name]
        else:
            raise TypeError("string predicates must apply to a column")
        if transforms:
            for tr in reversed(transforms):
                dic = [tr.apply_host(s) for s in dic]
        lut = np.zeros(max(len(dic), 1), dtype=bool)
        pats = [p for p in e.pattern]
        # a host loop as long as the dictionary: it runs while a program
        # is traced, and the compiled program keeps the LUT as a constant
        with tracing.event("strpred.lut", kind=e.kind, dict_size=len(dic)):
            for i, s in enumerate(dic):
                if e.kind == "contains":
                    lut[i] = pats[0] in s
                elif e.kind == "startswith":
                    lut[i] = s.startswith(tuple(pats))
                elif e.kind == "endswith":
                    lut[i] = s.endswith(tuple(pats))
                elif e.kind == "match":
                    lut[i] = re.match(pats[0], s) is not None
                elif e.kind == "fullmatch":
                    lut[i] = re.fullmatch(pats[0], s) is not None
                elif e.kind == "eq_any":
                    lut[i] = s in pats
                elif e.kind == "lower_eq":
                    lut[i] = s.lower() == pats[0]
                else:
                    raise ValueError(f"unknown str predicate {e.kind}")
        res = jnp.asarray(lut)[jnp.clip(d, 0, len(dic) - 1)]
        return res, v
    if isinstance(e, Where):
        c, cv = eval_expr(e.cond, tree, dicts, schema)
        t, tv = eval_expr(e.iftrue, tree, dicts, schema)
        f, fv = eval_expr(e.iffalse, tree, dicts, schema)
        rdt = infer_dtype(e, schema)
        if rdt is dt.STRING:
            raise TypeError("string Where requires frontend dict rewrite")
        t = jnp.asarray(t).astype(rdt.numpy)
        f = jnp.asarray(f).astype(rdt.numpy)
        cond = c if cv is None else (c & cv)
        out = jnp.where(cond, t, f)
        valid = None
        if tv is not None or fv is not None:
            tvv = tv if tv is not None else jnp.ones(out.shape, bool)
            fvv = fv if fv is not None else jnp.ones(out.shape, bool)
            valid = jnp.where(cond, tvv, fvv)
        return out, valid
    if isinstance(e, BinOp):
        if e.op in ("&", "|"):
            ld, lv = eval_expr(e.left, tree, dicts, schema)
            rd, rv = eval_expr(e.right, tree, dicts, schema)
            # null-as-False three-valued logic collapse (filter semantics)
            if lv is not None:
                ld = ld & lv
            if rv is not None:
                rd = rd & rv
            return (ld & rd if e.op == "&" else ld | rd), None
        ld, lv = eval_expr(e.left, tree, dicts, schema)
        rd, rv = eval_expr(e.right, tree, dicts, schema)
        lt = infer_dtype(e.left, schema)
        rt = infer_dtype(e.right, schema)
        # DATE (days) vs DATETIME (ns) physical coercion
        if lt is dt.DATE and rt is dt.DATETIME:
            ld = ld.astype(jnp.int64) * dtops.NS_PER_DAY
        elif lt is dt.DATETIME and rt is dt.DATE:
            rd = rd.astype(jnp.int64) * dtops.NS_PER_DAY
        if lt is dt.STRING or rt is dt.STRING:
            raise TypeError(
                "string comparison must be rewritten to dict codes by the "
                "frontend (StrPredicate / code-space compare)")
        # decimal fixed-point coercion (scaled int64, exact where possible)
        if dt.is_decimal(lt) or dt.is_decimal(rt):
            ls = lt.scale if dt.is_decimal(lt) else None
            rs = rt.scale if dt.is_decimal(rt) else None
            float_side = (ls is None and lt.kind == "f") or \
                (rs is None and rt.kind == "f")
            if float_side or e.op == "/":
                # mixed float / division: leave fixed point
                ld = ld.astype(jnp.float64) / (10.0 ** ls) \
                    if ls is not None else ld.astype(jnp.float64)
                rd = rd.astype(jnp.float64) / (10.0 ** rs) \
                    if rs is not None else rd.astype(jnp.float64)
            elif e.op == "*":
                # dec(sa)·dec(sb) → dec(sa+sb): plain int64 product;
                # int sides carry scale 0
                ld = ld.astype(jnp.int64)
                rd = rd.astype(jnp.int64)
            else:
                # +,-,cmp: align both sides to the larger scale exactly
                s = max(ls or 0, rs or 0)
                ld = ld.astype(jnp.int64) * np.int64(10 ** (s - (ls or 0)))
                rd = rd.astype(jnp.int64) * np.int64(10 ** (s - (rs or 0)))
        valid = None
        if lv is not None or rv is not None:
            valid = (lv if lv is not None else jnp.ones(ld.shape, bool)) & \
                    (rv if rv is not None else jnp.ones(rd.shape, bool))
        if e.op in _CMP:
            return _CMP[e.op](ld, rd), valid
        if e.op == "max2":   # GREATEST/LEAST (null if either side null)
            return jnp.maximum(ld, rd), valid
        if e.op == "min2":
            return jnp.minimum(ld, rd), valid
        if e.op == "+":
            return ld + rd, valid
        if e.op == "-":
            return ld - rd, valid
        if e.op == "*":
            return ld * rd, valid
        if e.op == "/":
            rdt = infer_dtype(e, schema)
            return ld.astype(rdt.numpy) / rd.astype(rdt.numpy), valid
        if e.op == "//":
            return jnp.floor_divide(ld, jnp.where(rd == 0, 1, rd)), valid
        if e.op == "%":
            return jnp.mod(ld, jnp.where(rd == 0, 1, rd)), valid
        if e.op == "**":
            return jnp.power(ld, rd), valid
        raise ValueError(f"unknown binop {e.op}")
    raise TypeError(f"cannot evaluate {e}")
