"""Unified typed metrics registry: counter / gauge / histogram with labels.

The substrate PRs (memory governor, resilience, AQE, I/O pool,
shardcheck) each grew an ad-hoc ``stats()`` dict with its own shape;
`tracing.profile()` then hand-translated five shapes into ``mem:`` /
``resil:`` / ``aqe:`` / ``io:`` / ``lint:`` / ``lockstep:`` rows. This
module is the one place those translations live now: a typed registry
(the reference analogue is the per-operator metric types of
bodo/libs/_query_profile_collector.h — TIMER/STAT/BLOB — crossed with a
Prometheus-style exposition for the future serving layer,
runtime/scheduler.py, which will scrape it per session/tenant).

Three metric kinds, all label-aware and thread-safe:

  * :class:`Counter` — monotonically increasing (``.inc(n)``)
  * :class:`Gauge` — set-to-current-value (``.set(v)``)
  * :class:`Histogram` — bucketed observations (``.observe(v)``)

``sync_engine_metrics()`` pulls every subsystem's stats snapshot into
canonically named metrics (``bodo_tpu_*``); ``expose_text()`` renders
the whole registry in the Prometheus text exposition format;
``snapshot()`` returns the same data as a JSON-safe dict (embedded in
tracing dumps). Query-scoped operator counters
(labelled ``query=...``/``op=...``) are synthesized from the tracing
layer's per-query aggregates, so per-query accounting needs no extra
bookkeeping on the hot event path.
"""

from __future__ import annotations

import math
import os
import re
import sys
import threading
from typing import Dict, List, Optional, Sequence, Tuple

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")

# default histogram buckets: latency-shaped (seconds), 1ms .. ~2min
DEFAULT_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0,
                   30.0, 120.0)


class _Child:
    """One labelled series of a metric (what ``.labels(...)`` returns)."""

    __slots__ = ("_metric", "_key")

    def __init__(self, metric: "_Metric", key: Tuple[str, ...]):
        self._metric = metric
        self._key = key

    def inc(self, n: float = 1.0) -> None:
        self._metric._inc(self._key, n)

    def set(self, v: float) -> None:
        self._metric._set(self._key, v)

    def observe(self, v: float) -> None:
        self._metric._observe(self._key, v)

    def get(self) -> float:
        return self._metric.value(*self._key)


class _Metric:
    kind = ""

    def __init__(self, name: str, help: str = "",
                 labelnames: Sequence[str] = ()):
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name: {name!r}")
        for ln in labelnames:
            if not _LABEL_RE.match(ln):
                raise ValueError(f"invalid label name: {ln!r}")
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._mu = threading.Lock()
        self._values: Dict[Tuple[str, ...], float] = {}

    # -- label resolution ----------------------------------------------------

    def labels(self, *args, **kwargs) -> _Child:
        if args and kwargs:
            raise ValueError("pass labels positionally OR by name")
        if kwargs:
            try:
                vals = tuple(str(kwargs[ln]) for ln in self.labelnames)
            except KeyError as e:
                raise ValueError(
                    f"{self.name}: missing label {e.args[0]!r} "
                    f"(expects {self.labelnames})") from None
            if len(kwargs) != len(self.labelnames):
                extra = set(kwargs) - set(self.labelnames)
                raise ValueError(f"{self.name}: unknown labels {extra}")
        else:
            if len(args) != len(self.labelnames):
                raise ValueError(
                    f"{self.name}: expected {len(self.labelnames)} label "
                    f"values {self.labelnames}, got {len(args)}")
            vals = tuple(str(a) for a in args)
        return _Child(self, vals)

    def _unlabelled(self) -> Tuple[str, ...]:
        if self.labelnames:
            raise ValueError(
                f"{self.name} has labels {self.labelnames}; "
                f"use .labels(...)")
        return ()

    # -- value ops (overridden per kind) -------------------------------------

    def _inc(self, key, n) -> None:
        raise TypeError(f"{self.kind} does not support inc()")

    def _set(self, key, v) -> None:
        raise TypeError(f"{self.kind} does not support set()")

    def _observe(self, key, v) -> None:
        raise TypeError(f"{self.kind} does not support observe()")

    def value(self, *labelvals) -> float:
        with self._mu:
            return self._values.get(tuple(str(v) for v in labelvals), 0.0)

    def series(self) -> Dict[Tuple[str, ...], float]:
        with self._mu:
            return dict(self._values)

    def clear(self) -> None:
        with self._mu:
            self._values.clear()


class Counter(_Metric):
    kind = "counter"

    def inc(self, n: float = 1.0) -> None:
        self._inc(self._unlabelled(), n)

    def _inc(self, key, n) -> None:
        if n < 0:
            raise ValueError(f"{self.name}: counters only go up ({n})")
        with self._mu:
            self._values[key] = self._values.get(key, 0.0) + n

    def get(self) -> float:
        return self.value()


class Gauge(_Metric):
    kind = "gauge"

    def set(self, v: float) -> None:
        self._set(self._unlabelled(), v)

    def inc(self, n: float = 1.0) -> None:
        self._inc(self._unlabelled(), n)

    def _set(self, key, v) -> None:
        with self._mu:
            self._values[key] = float(v)

    def _inc(self, key, n) -> None:
        with self._mu:
            self._values[key] = self._values.get(key, 0.0) + n

    def get(self) -> float:
        return self.value()


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name: str, help: str = "",
                 labelnames: Sequence[str] = (),
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        super().__init__(name, help, labelnames)
        b = sorted(float(x) for x in buckets)
        if not b:
            raise ValueError("histogram needs at least one bucket")
        self.buckets = tuple(b) + (math.inf,)
        # per-series state: [counts per bucket] + sum + count
        self._hist: Dict[Tuple[str, ...], dict] = {}

    def observe(self, v: float) -> None:
        self._observe(self._unlabelled(), v)

    def _observe(self, key, v) -> None:
        v = float(v)
        with self._mu:
            h = self._hist.get(key)
            if h is None:
                h = self._hist[key] = {
                    "counts": [0] * len(self.buckets),
                    "sum": 0.0, "count": 0}
            for i, ub in enumerate(self.buckets):
                if v <= ub:
                    h["counts"][i] += 1
                    break
            h["sum"] += v
            h["count"] += 1
            # keep _values in sync so snapshot() has a scalar view
            self._values[key] = h["sum"]

    def series_hist(self) -> Dict[Tuple[str, ...], dict]:
        with self._mu:
            return {k: {"counts": list(h["counts"]), "sum": h["sum"],
                        "count": h["count"]}
                    for k, h in self._hist.items()}

    def clear(self) -> None:
        with self._mu:
            self._values.clear()
            self._hist.clear()


class Registry:
    """Named metric store. ``counter``/``gauge``/``histogram`` are
    get-or-create (re-registration with a different kind or labelset is
    an error — two call sites silently disagreeing about a metric's
    meaning is exactly the bug a registry exists to prevent)."""

    def __init__(self):
        self._mu = threading.Lock()
        self._metrics: Dict[str, _Metric] = {}

    def _get_or_create(self, cls, name: str, help: str,
                       labelnames: Sequence[str], **kw) -> _Metric:
        with self._mu:
            m = self._metrics.get(name)
            if m is not None:
                if not isinstance(m, cls):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{m.kind}, not {cls.kind}")
                if tuple(labelnames) != m.labelnames:
                    raise ValueError(
                        f"metric {name!r} already registered with labels "
                        f"{m.labelnames}, not {tuple(labelnames)}")
                return m
            m = cls(name, help, labelnames, **kw)
            self._metrics[name] = m
            return m

    def counter(self, name: str, help: str = "",
                labelnames: Sequence[str] = ()) -> Counter:
        return self._get_or_create(Counter, name, help, labelnames)

    def gauge(self, name: str, help: str = "",
              labelnames: Sequence[str] = ()) -> Gauge:
        return self._get_or_create(Gauge, name, help, labelnames)

    def histogram(self, name: str, help: str = "",
                  labelnames: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
        return self._get_or_create(Histogram, name, help, labelnames,
                                   buckets=buckets)

    def get(self, name: str) -> Optional[_Metric]:
        with self._mu:
            return self._metrics.get(name)

    def metrics(self) -> List[_Metric]:
        with self._mu:
            return sorted(self._metrics.values(), key=lambda m: m.name)

    def unregister(self, name: str) -> None:
        with self._mu:
            self._metrics.pop(name, None)

    def reset(self) -> None:
        """Drop every metric (tests)."""
        with self._mu:
            self._metrics.clear()

    # -- output --------------------------------------------------------------

    def snapshot(self) -> Dict[str, dict]:
        """JSON-safe dump: {name: {kind, help, values: {label-expr or
        "": value}}} (histograms additionally carry sum/count/buckets)."""
        out: Dict[str, dict] = {}
        for m in self.metrics():
            entry: dict = {"kind": m.kind, "values": {}}
            if m.help:
                entry["help"] = m.help
            for key, v in sorted(m.series().items()):
                entry["values"][_labelexpr(m.labelnames, key)] = (
                    round(v, 6) if isinstance(v, float) else v)
            if isinstance(m, Histogram):
                entry["histogram"] = {
                    _labelexpr(m.labelnames, key): {
                        "count": h["count"], "sum": round(h["sum"], 6)}
                    for key, h in sorted(m.series_hist().items())}
            out[m.name] = entry
        return out

    def expose_text(self) -> str:
        """Prometheus text exposition (the contract the future
        runtime/scheduler.py serving layer scrapes)."""
        lines: List[str] = []
        for m in self.metrics():
            if m.help:
                lines.append(f"# HELP {m.name} {_esc_help(m.help)}")
            lines.append(f"# TYPE {m.name} {m.kind}")
            if isinstance(m, Histogram):
                for key, h in sorted(m.series_hist().items()):
                    acc = 0
                    for ub, c in zip(m.buckets, h["counts"]):
                        acc += c
                        le = "+Inf" if ub == math.inf else _fmt(ub)
                        lines.append(
                            f"{m.name}_bucket"
                            f"{_promlabels(m.labelnames, key, le=le)}"
                            f" {acc}")
                    lines.append(f"{m.name}_sum"
                                 f"{_promlabels(m.labelnames, key)}"
                                 f" {_fmt(h['sum'])}")
                    lines.append(f"{m.name}_count"
                                 f"{_promlabels(m.labelnames, key)}"
                                 f" {h['count']}")
                continue
            series = sorted(m.series().items())
            if not series and not m.labelnames:
                series = [((), 0.0)]
            for key, v in series:
                lines.append(f"{m.name}{_promlabels(m.labelnames, key)}"
                             f" {_fmt(v)}")
        return "\n".join(lines) + "\n"


def _fmt(v: float) -> str:
    # the exposition format spells non-finite values +Inf/-Inf/NaN —
    # repr() would emit python's inf/nan, which scrapers reject
    if isinstance(v, float):
        if math.isinf(v):
            return "+Inf" if v > 0 else "-Inf"
        if math.isnan(v):
            return "NaN"
        if v.is_integer():
            return str(int(v))
    return repr(v)


def _esc(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _esc_help(v: str) -> str:
    # HELP text escapes backslash and newline only (quotes stay raw)
    return v.replace("\\", "\\\\").replace("\n", "\\n")


def _promlabels(names: Sequence[str], vals: Sequence[str],
                le: Optional[str] = None) -> str:
    parts = [f'{n}="{_esc(v)}"' for n, v in zip(names, vals)]
    if le is not None:
        parts.append(f'le="{le}"')
    return "{" + ",".join(parts) + "}" if parts else ""


def _labelexpr(names: Sequence[str], vals: Sequence[str]) -> str:
    if not names:
        return ""
    return ",".join(f"{n}={v}" for n, v in zip(names, vals))


# ---------------------------------------------------------------------------
# exposition-format checker (the /metrics compliance gate)
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?P<labels>\{[^{}]*\})?"
    r" (?P<value>[^ ]+)"
    r"( (?P<ts>-?\d+))?$")
_LABEL_PAIR_RE = re.compile(
    r'^(?P<name>[a-zA-Z_][a-zA-Z0-9_]*)="(?P<value>(?:[^"\\]|\\["\\n])*)"$')
_VALUE_RE = re.compile(r"^(\+Inf|-Inf|NaN|[-+]?(\d+\.?\d*|\.\d+)"
                       r"([eE][-+]?\d+)?)$")
_HELP_RE = re.compile(r"^# HELP ([a-zA-Z_:][a-zA-Z0-9_:]*) (.*)$")
_TYPE_RE = re.compile(r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) "
                      r"(counter|gauge|histogram|summary|untyped)$")


def _split_labels(body: str) -> Optional[List[str]]:
    """Split the inside of a {...} label block on unescaped/unquoted
    commas. Returns None when the quoting is broken."""
    parts: List[str] = []
    cur: List[str] = []
    in_str = False
    esc = False
    for ch in body:
        if esc:
            cur.append(ch)
            esc = False
            continue
        if ch == "\\" and in_str:
            cur.append(ch)
            esc = True
            continue
        if ch == '"':
            in_str = not in_str
            cur.append(ch)
            continue
        if ch == "," and not in_str:
            parts.append("".join(cur))
            cur = []
            continue
        cur.append(ch)
    if in_str or esc:
        return None
    if cur or parts:
        parts.append("".join(cur))
    return [p for p in parts if p]


def _base_family(name: str) -> str:
    for suffix in ("_bucket", "_sum", "_count"):
        if name.endswith(suffix):
            return name[: -len(suffix)]
    return name


def check_exposition(text: str) -> List[str]:
    """Validate a Prometheus text-exposition payload line by line;
    returns a list of problems (empty = compliant). Checks: sample-line
    grammar, numeric values (incl. +Inf/-Inf/NaN spellings), label
    name/escaping rules, HELP/TYPE well-formedness and uniqueness,
    TYPE-before-samples ordering, histogram families carrying _bucket
    (with le), _sum and _count with count == the +Inf bucket."""
    problems: List[str] = []
    typed: Dict[str, str] = {}
    helped: set = set()
    sampled: set = set()
    # histogram family -> {"inf": value, "count": value, "sum": seen}
    hist: Dict[str, dict] = {}
    for i, line in enumerate(text.split("\n"), 1):
        if line == "":
            continue
        if line != line.strip():
            problems.append(f"line {i}: leading/trailing whitespace")
            continue
        if line.startswith("#"):
            mh = _HELP_RE.match(line)
            mt = _TYPE_RE.match(line)
            if mh:
                name = mh.group(1)
                if name in helped:
                    problems.append(f"line {i}: duplicate HELP {name}")
                helped.add(name)
                body = mh.group(2)
                if re.search(r"(?<!\\)\\(?![\\n])", body):
                    problems.append(
                        f"line {i}: HELP {name}: stray backslash "
                        f"escape in help text")
            elif mt:
                name = mt.group(1)
                if name in typed:
                    problems.append(f"line {i}: duplicate TYPE {name}")
                if name in sampled:
                    problems.append(
                        f"line {i}: TYPE {name} after its samples")
                typed[name] = mt.group(2)
            elif line.startswith(("# HELP", "# TYPE")):
                problems.append(f"line {i}: malformed comment: "
                                f"{line[:80]!r}")
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            problems.append(f"line {i}: unparseable sample: "
                            f"{line[:80]!r}")
            continue
        name = m.group("name")
        sampled.add(_base_family(name))
        sampled.add(name)
        if not _VALUE_RE.match(m.group("value")):
            problems.append(f"line {i}: {name}: bad value "
                            f"{m.group('value')!r}")
        labels: Dict[str, str] = {}
        if m.group("labels"):
            body = m.group("labels")[1:-1]
            pairs = _split_labels(body)
            if pairs is None:
                problems.append(
                    f"line {i}: {name}: broken label quoting")
                pairs = []
            for pair in pairs:
                ml = _LABEL_PAIR_RE.match(pair)
                if not ml:
                    problems.append(
                        f"line {i}: {name}: bad label pair "
                        f"{pair[:60]!r}")
                    continue
                if ml.group("name") in labels:
                    problems.append(
                        f"line {i}: {name}: duplicate label "
                        f"{ml.group('name')}")
                labels[ml.group("name")] = ml.group("value")
        fam = _base_family(name)
        if typed.get(fam) == "histogram":
            key = tuple(sorted((k, v) for k, v in labels.items()
                               if k != "le"))
            h = hist.setdefault(fam, {}).setdefault(
                key, {"inf": None, "count": None, "sum": False,
                      "buckets": False, "line": i})
            if name.endswith("_bucket"):
                h["buckets"] = True
                if "le" not in labels:
                    problems.append(
                        f"line {i}: {name}: _bucket without le label")
                elif labels["le"] == "+Inf":
                    h["inf"] = m.group("value")
            elif name.endswith("_sum"):
                h["sum"] = True
            elif name.endswith("_count"):
                h["count"] = m.group("value")
    for fam, series in hist.items():
        for key, h in series.items():
            where = f"histogram {fam}{dict(key) if key else ''}"
            if not h["buckets"]:
                problems.append(f"{where}: no _bucket series")
            elif h["inf"] is None:
                problems.append(f"{where}: no le=\"+Inf\" bucket")
            if not h["sum"]:
                problems.append(f"{where}: missing _sum")
            if h["count"] is None:
                problems.append(f"{where}: missing _count")
            elif h["inf"] is not None and h["count"] != h["inf"]:
                problems.append(
                    f"{where}: _count {h['count']} != +Inf bucket "
                    f"{h['inf']}")
    return problems


# ---------------------------------------------------------------------------
# process-global registry + module-level conveniences
# ---------------------------------------------------------------------------

_registry = Registry()


def registry() -> Registry:
    return _registry


def counter(name: str, help: str = "",
            labelnames: Sequence[str] = ()) -> Counter:
    return _registry.counter(name, help, labelnames)


def gauge(name: str, help: str = "",
          labelnames: Sequence[str] = ()) -> Gauge:
    return _registry.gauge(name, help, labelnames)


def histogram(name: str, help: str = "",
              labelnames: Sequence[str] = (),
              buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
    return _registry.histogram(name, help, labelnames, buckets=buckets)


def snapshot() -> Dict[str, dict]:
    sync_engine_metrics()
    return _registry.snapshot()


def expose_text() -> str:
    sync_engine_metrics()
    return _registry.expose_text()


def reset() -> None:
    _registry.reset()


# ---------------------------------------------------------------------------
# engine metric sync: the one place the legacy stats() shapes map onto
# canonical metric names
# ---------------------------------------------------------------------------

# compile-side series are fed LIVE (kernel_cache.record_compile), not
# synced — declare them eagerly so an exposition before any compile
# still shows the metric families ROADMAP item 1 is judged against
JIT_COMPILE_SECONDS = "bodo_tpu_jit_compile_seconds"
PALLAS_TRACED = "bodo_tpu_pallas_traced_into_pipeline"


def record_compile(program: str, seconds: float) -> None:
    """Per-program jit compile seconds (called by kernel_cache on every
    cache-miss first invocation — trace+lower+compile wall time)."""
    histogram(JIT_COMPILE_SECONDS,
              "wall seconds of jit trace+compile per program",
              ("program",)).labels(program=program).observe(seconds)


class _GangBound:
    """Gauge façade that injects a constant ``gang`` label value into
    every labels()/set() call (fleet gang processes only)."""

    def __init__(self, g: Gauge, gid: str):
        self._g, self._gid = g, gid

    def labels(self, **kw) -> _Child:
        kw["gang"] = self._gid
        return self._g.labels(**kw)

    def set(self, v: float) -> None:
        self._g.labels(gang=self._gid).set(v)


def _gang_gauge(name: str, help: str = "",
                labelnames: Sequence[str] = ()):
    """Gauge that grows a ``gang`` label when this process is a fleet
    gang (BODO_TPU_GANG_ID set at spawn): the controller's scrapes then
    attribute per-gang series unambiguously. Outside fleet mode the
    series keeps its classic shape — the env is set for the process's
    whole life, so the label set never flips mid-registry."""
    gid = os.environ.get("BODO_TPU_GANG_ID", "")
    if not gid:
        return gauge(name, help, labelnames)
    return _GangBound(gauge(name, help, tuple(labelnames) + ("gang",)),
                      gid)


def sync_engine_metrics() -> None:
    """Pull every subsystem's stats snapshot into the registry. Cheap
    (a few dict copies); called by snapshot()/expose_text() and by
    tracing.profile()/dump() so readers always see current values."""
    # -- memory governor -----------------------------------------------------
    try:
        from bodo_tpu.runtime.memory_governor import governor
        mem = governor().stats()
        gauge("bodo_tpu_mem_derived_budget_bytes",
              "memory governor derived per-device budget").set(
            mem.get("derived_budget_bytes", 0))
        gauge("bodo_tpu_mem_oom_retries_total",
              "stage re-runs after RESOURCE_EXHAUSTED").set(
            mem.get("n_oom_retries", 0))
        g = gauge("bodo_tpu_mem_operator_bytes",
                  "per-operator granted/peak/spilled bytes",
                  ("op", "kind"))
        ge = gauge("bodo_tpu_mem_operator_events",
                   "per-operator grant count / spill count",
                   ("op", "kind"))
        for name, m in mem.get("operators", {}).items():
            g.labels(op=name, kind="granted").set(m.get("granted", 0))
            g.labels(op=name, kind="peak").set(m.get("peak", 0))
            g.labels(op=name, kind="spilled").set(
                m.get("spilled_bytes", 0))
            ge.labels(op=name, kind="count").set(m.get("count", 0))
            ge.labels(op=name, kind="n_spills").set(m.get("n_spills", 0))
    except Exception:  # pragma: no cover - governor unavailable pre-mesh
        pass
    # -- resilience ----------------------------------------------------------
    try:
        from bodo_tpu.runtime import resilience
        rs = resilience.stats()
        g = gauge("bodo_tpu_resil_faults_fired_total",
                  "armed faults fired per injection point", ("point",))
        for point, n in rs.get("faults_fired", {}).items():
            g.labels(point=point).set(n)
        g = gauge("bodo_tpu_resil_retries_total",
                  "retry-envelope retries per label", ("label",))
        for label, n in rs.get("retries", {}).items():
            g.labels(label=label).set(n)
        g = gauge("bodo_tpu_resil_degraded_stages_total",
                  "stages re-executed replicated", ("stage",))
        for stage, n in rs.get("degraded_stages", {}).items():
            g.labels(stage=stage).set(n)
        gauge("bodo_tpu_resil_gang_retries_total",
              "whole-gang spawn retries").set(rs.get("gang_retries", 0))
    except Exception:  # pragma: no cover
        pass
    # -- adaptive execution --------------------------------------------------
    try:
        from bodo_tpu.plan import adaptive
        aq = adaptive.stats()
        g = gauge("bodo_tpu_aqe_decisions_total",
                  "adaptive-execution decisions", ("decision",))
        for decision, n in aq.get("decisions", {}).items():
            g.labels(decision=decision).set(n)
        qe = aq.get("q_error", {})
        if qe.get("count"):
            gauge("bodo_tpu_aqe_q_error_count",
                  "first-observation estimates scored").set(
                qe.get("count", 0))
            gauge("bodo_tpu_aqe_q_error_mean",
                  "mean q-error of first-observation estimates").set(
                qe.get("mean", 0.0))
            gauge("bodo_tpu_aqe_q_error_p50",
                  "median q-error of first-observation estimates").set(
                qe.get("p50", 0.0))
            gauge("bodo_tpu_aqe_q_error_p90",
                  "p90 q-error of first-observation estimates").set(
                qe.get("p90", 0.0))
            gauge("bodo_tpu_aqe_q_error_max",
                  "worst q-error of first-observation estimates").set(
                qe.get("max", 0.0))
    except Exception:  # pragma: no cover
        pass
    # -- pipelined I/O -------------------------------------------------------
    try:
        from bodo_tpu.runtime import io_pool
        ios = io_pool.io_stats()
        g = gauge("bodo_tpu_io_events_total", "io pipeline counters",
                  ("event",))
        for key in ("prefetch_hits", "prefetch_streams", "prefetch_depth",
                    "stalls", "footer_hits", "footer_misses",
                    "parallel_units", "parallel_reads", "decode_batches",
                    "decode_bytes", "device_decode_pages",
                    "device_decode_cols", "device_fallback_cols",
                    "device_decode_errors", "device_decode_bytes",
                    "host_decode_bytes", "raw_bytes"):
            g.labels(event=key).set(ios.get(key, 0))
        g = gauge("bodo_tpu_io_seconds", "io pipeline time split",
                  ("phase",))
        for phase in ("decode_s", "stall_s", "overlap_s",
                      "device_decode_s"):
            g.labels(phase=phase[:-2]).set(ios.get(phase, 0.0))
        gauge("bodo_tpu_io_overlap_ratio",
              "decode time hidden behind consumer compute").set(
            ios.get("overlap_ratio", 0.0))
        gauge("bodo_tpu_scan_device_decode_frac",
              "fraction of decoded scan bytes produced on device").set(
            ios.get("device_decode_frac", 0.0))
    except Exception:  # pragma: no cover
        pass
    # -- shardcheck (plan validator / lint / lockstep) -----------------------
    try:
        from bodo_tpu.analysis import lint, lockstep, plan_validator
        pv = plan_validator.stats()
        gauge("bodo_tpu_plans_validated_total",
              "plans checked by the plan validator").set(
            pv.get("plans", 0))
        gauge("bodo_tpu_plan_violations_total",
              "plan invariant violations raised").set(
            pv.get("violations", 0))
        gauge("bodo_tpu_lint_findings_total",
              "shardcheck lint findings").set(
            lint.stats().get("findings", 0))
        ls = lockstep.stats()
        gauge("bodo_tpu_lockstep_collectives_total",
              "host-level collective dispatches fingerprinted").set(
            ls.get("collectives", 0))
        gauge("bodo_tpu_lockstep_mismatches_total",
              "lockstep divergences detected").set(
            ls.get("mismatches", 0))
        gauge("bodo_tpu_lockstep_timeouts_total",
              "lockstep peer-wait timeouts").set(ls.get("timeouts", 0))
        gauge("bodo_tpu_lockstep_wait_seconds",
              "cumulative peer-wait seconds").set(ls.get("wait_s", 0.0))
        gauge("bodo_tpu_lockstep_max_wait_seconds",
              "worst single peer-wait seconds").set(
            ls.get("max_wait_s", 0.0))
    except Exception:  # pragma: no cover
        pass
    # -- progcheck (jaxpr-level SPMD program verifier; lazy-module rule:
    # nothing to report until a registration point has imported it) ----------
    pc = sys.modules.get("bodo_tpu.analysis.progcheck")
    if pc is not None:
        try:
            ps = pc.stats()
            gauge("bodo_tpu_progcheck_programs_total",
                  "programs statically verified at registration").set(
                ps.get("programs", 0))
            gauge("bodo_tpu_progcheck_violations_total",
                  "program invariant violations found").set(
                ps.get("violations", 0))
            gauge("bodo_tpu_progcheck_skipped_total",
                  "programs whose trace could not be reproduced").set(
                ps.get("skipped", 0))
            gauge("bodo_tpu_progcheck_check_seconds",
                  "cumulative verification wall seconds").set(
                ps.get("check_s", 0.0))
            gauge("bodo_tpu_progcheck_max_check_seconds",
                  "worst single program verification seconds").set(
                ps.get("max_check_s", 0.0))
            gauge("bodo_tpu_progcheck_manifests_total",
                  "collective manifests extracted and registered").set(
                ps.get("manifests", 0))
            gauge("bodo_tpu_progcheck_hbm_peak_bytes_max",
                  "largest static HBM peak estimate across programs").set(
                ps.get("hbm_peak_bytes_max", 0))
            gauge("bodo_tpu_progcheck_rank_variant_programs",
                  "programs with a collective under rank-derived "
                  "control flow").set(
                ps.get("rank_variant_programs", 0))
            gauge("bodo_tpu_progcheck_enforce",
                  "1 when violations raise instead of warn").set(
                ps.get("enforce", 0))
        except Exception:  # pragma: no cover
            pass
    # -- communication observatory (parallel/comm.py is stdlib-safe) ---------
    try:
        from bodo_tpu.parallel import comm
        g = gauge("bodo_tpu_comm_dispatches_total",
                  "collective dispatches accounted per op", ("op",))
        gb = gauge("bodo_tpu_comm_bytes_total",
                   "bytes through collective dispatches",
                   ("op", "direction"))
        gw = gauge("bodo_tpu_comm_seconds_total",
                   "cumulative collective host wall / peer-wait seconds",
                   ("op", "kind"))
        for op, r in comm.per_op().items():
            g.labels(op=op).set(r["count"])
            gb.labels(op=op, direction="in").set(r["bytes_in"])
            gb.labels(op=op, direction="out").set(r["bytes_out"])
            gw.labels(op=op, kind="wall").set(r["wall_s"])
            gw.labels(op=op, kind="wait").set(r["wait_s"])
        sk = comm.skew_head()
        gauge("bodo_tpu_comm_max_wait_seconds",
              "worst single collective peer-wait (arrival skew)").set(
            sk.get("max_wait_s", 0.0))
        gauge("bodo_tpu_comm_wait_frac",
              "peer-wait share of total comm time").set(
            sk.get("wait_frac", 0.0))
    except Exception:  # pragma: no cover
        pass
    # -- compile cache + pallas engagement -----------------------------------
    try:
        from bodo_tpu.utils import tracing
        cc = tracing.compile_cache_stats()
        g = gauge("bodo_tpu_compile_cache_total",
                  "persistent jit-cache lookups", ("result",))
        g.labels(result="hit").set(cc["hits"])
        g.labels(result="miss").set(cc["misses"])
    except Exception:  # pragma: no cover
        pass
    # -- semantic result cache (lazy-module rule: nothing to report
    # until the executor has loaded it anyway) -------------------------------
    rc = sys.modules.get("bodo_tpu.runtime.result_cache")
    if rc is not None:
        try:
            rs_ = rc.stats()
            g = _gang_gauge("bodo_tpu_result_cache_events_total",
                            "semantic result cache events", ("event",))
            for k in ("hits", "misses", "q_hits", "q_misses",
                      "q_incremental", "evictions", "invalidations",
                      "incremental_fallbacks", "spills", "rehydrations",
                      "rejected", "sig_uncacheable", "pressure_sheds",
                      "peer_hits", "peer_misses", "peer_serves",
                      "invalidations_remote"):
                g.labels(event=k).set(rs_.get(k, 0))
            gb = _gang_gauge("bodo_tpu_result_cache_bytes",
                             "resident result-cache bytes per tier",
                             ("tier",))
            gb.labels(tier="device").set(rs_.get("device_bytes", 0))
            gb.labels(tier="host").set(rs_.get("host_bytes", 0))
            ge2 = _gang_gauge("bodo_tpu_result_cache_entries",
                              "resident result-cache entries per tier",
                              ("tier",))
            ge2.labels(tier="device").set(rs_.get("device_entries", 0))
            ge2.labels(tier="host").set(rs_.get("host_entries", 0))
            _gang_gauge("bodo_tpu_result_cache_saved_seconds",
                        "wall seconds saved by serving cached "
                        "results").set(rs_.get("saved_wall_s", 0.0))
            _gang_gauge("bodo_tpu_result_cache_budget_bytes",
                        "device-byte budget of the result cache "
                        "(admission reads occupancy = "
                        "bytes/budget)").set(rs_.get("budget_bytes", 0))
            gs = _gang_gauge("bodo_tpu_result_cache_session_events_total",
                             "per-session result cache events",
                             ("session", "event"))
            gsb = _gang_gauge("bodo_tpu_result_cache_session_bytes",
                              "per-session resident device bytes",
                              ("session",))
            for sid, row in rs_.get("by_session", {}).items():
                for ev in ("q_hits", "q_misses", "evicted", "records"):
                    gs.labels(session=sid, event=ev).set(row.get(ev, 0))
                gsb.labels(session=sid).set(row.get("device_bytes", 0))
        except Exception:  # pragma: no cover
            pass
    # -- materialized views (lazy-module rule: a registry only exists
    # once views were created) -----------------------------------------------
    vw = sys.modules.get("bodo_tpu.runtime.views")
    if vw is not None:
        try:
            vs_ = vw.stats()
            if vs_.get("n_views"):
                g = _gang_gauge("bodo_tpu_view_events_total",
                                "materialized-view maintenance events",
                                ("event",))
                for k in ("refreshes_incremental", "refreshes_full",
                          "ticks", "detected_stale", "flagged_stale",
                          "refresh_scheduled", "refresh_rejected"):
                    g.labels(event=k).set(vs_.get(k, 0))
                _gang_gauge("bodo_tpu_view_count",
                            "registered materialized views").set(
                    vs_.get("n_views", 0))
                _gang_gauge("bodo_tpu_view_subscriptions",
                            "live continuous-query subscriptions").set(
                    vs_.get("subscriptions", 0))
                _gang_gauge("bodo_tpu_view_fanout_depth",
                            "depth of the materialized-view DAG").set(
                    vs_.get("dag_depth", 0))
                _gang_gauge("bodo_tpu_view_refresh_ratio",
                            "incremental refresh wall relative to "
                            "full-recompute wall").set(
                    vs_.get("refresh_ratio", 0.0))
                _gang_gauge("bodo_tpu_view_staleness_p99_seconds",
                            "p99 change-to-refresh staleness across "
                            "views").set(vs_.get("staleness_p99_s",
                                                 0.0))
        except Exception:  # pragma: no cover
            pass
    # -- sql plan cache (sql/plan_cache.py is stdlib-safe) -------------------
    try:
        from bodo_tpu.sql import plan_cache
        pc = plan_cache.stats()
        g = gauge("bodo_tpu_sql_plan_cache_total",
                  "persistent SQL plan cache lookups", ("result",))
        g.labels(result="hit").set(pc.get("hits", 0))
        g.labels(result="miss").set(pc.get("misses", 0))
        gps = gauge("bodo_tpu_sql_plan_cache_session_total",
                    "per-session SQL plan cache lookups",
                    ("session", "result"))
        for sid, row in pc.get("by_session", {}).items():
            gps.labels(session=sid, result="hit").set(row.get("hits", 0))
            gps.labels(session=sid, result="miss").set(
                row.get("misses", 0))
    except Exception:  # pragma: no cover
        pass
    # -- query scheduler (lazy-module rule: nothing to serve until the
    # serving layer has loaded it anyway) ------------------------------------
    sch = sys.modules.get("bodo_tpu.runtime.scheduler")
    if sch is not None:
        try:
            ss = sch.stats()
            if ss is not None:
                _gang_gauge("bodo_tpu_serve_sessions",
                            "open serving sessions").set(
                    ss.get("sessions", 0))
                _gang_gauge("bodo_tpu_serve_queued",
                            "requests queued across all sessions").set(
                    ss.get("queued", 0))
                _gang_gauge("bodo_tpu_serve_running",
                            "requests executing on the gang").set(
                    ss.get("running", 0))
                _gang_gauge("bodo_tpu_serve_workers",
                            "live scheduler worker threads").set(
                    ss.get("workers", 0))
                _gang_gauge("bodo_tpu_serve_completed_total",
                            "queries completed by the serving layer").set(
                    ss.get("completed", 0))
                _gang_gauge("bodo_tpu_serve_failed_total",
                            "queries delivered as typed failures").set(
                    ss.get("failed", 0))
                gd = _gang_gauge("bodo_tpu_serve_decisions_total",
                                 "admission decisions by action",
                                 ("action",))
                for action, n in ss.get("decisions", {}).items():
                    gd.labels(action=action).set(n)
        except Exception:  # pragma: no cover
            pass
    # pallas_kernels imports jax — only read the counter if the module
    # is already loaded (never force a jax import from a metrics scrape)
    pk = sys.modules.get("bodo_tpu.ops.pallas_kernels")
    if pk is not None:
        gauge(PALLAS_TRACED,
              "pallas kernels traced into compiled pipelines").set(
            getattr(pk, "trace_count", 0))
    # -- whole-stage fusion (same lazy-module rule: fusion imports jax) ------
    fz = sys.modules.get("bodo_tpu.plan.fusion")
    if fz is not None:
        try:
            fs = fz.stats()
            g = gauge("bodo_tpu_fusion_events_total",
                      "whole-stage fusion events", ("kind",))
            for k in ("groups_planned", "groups_executed",
                      "stream_chains", "partial_agg", "fallbacks",
                      "donated", "device_scan_batches", "hits",
                      "misses", "compiles", "evictions"):
                g.labels(kind=k).set(fs.get(k, 0))
            gauge("bodo_tpu_fusion_compile_seconds",
                  "cumulative fused-program compile wall seconds").set(
                fs.get("compile_s", 0.0))
            gauge("bodo_tpu_fusion_programs_cached",
                  "compiled fusion programs resident in the LRU").set(
                fs.get("size", 0))
        except Exception:  # pragma: no cover
            pass
    # -- compile & device-memory observatory (stdlib-only module, but
    # the same lazy rule keeps a bare metrics scrape from loading it) --------
    ob = sys.modules.get("bodo_tpu.runtime.xla_observatory")
    if ob is not None:
        try:
            os_ = ob.stats()
            g = gauge("bodo_tpu_xla_executables",
                      "registered XLA executables", ("subsystem",))
            gc_ = gauge("bodo_tpu_xla_compile_seconds",
                        "cumulative compile wall seconds",
                        ("subsystem",))
            gd = gauge("bodo_tpu_xla_dispatches_total",
                       "dispatches of registered executables",
                       ("subsystem",))
            for sub, sv in os_["by_subsystem"].items():
                g.labels(subsystem=sub).set(sv["executables"])
                gc_.labels(subsystem=sub).set(sv["compile_s"])
                gd.labels(subsystem=sub).set(sv["dispatches"])
            gauge("bodo_tpu_xla_budget_remaining",
                  "unified compile-budget units left (-1 unlimited)"
                  ).set(os_["budget"]["remaining"])
            gr = gauge("bodo_tpu_xla_retraces_total",
                       "retraces by attributed cause", ("cause",))
            for cause, n in os_["retraces"].items():
                gr.labels(cause=cause).set(n)
            led = os_["ledger"]
            gb = gauge("bodo_tpu_device_bytes_live",
                       "live device bytes by creating operator",
                       ("operator",))
            for op, ov in led["by_op"].items():
                gb.labels(operator=op).set(
                    ov["created_bytes"] - ov["freed_bytes"])
            gauge("bodo_tpu_device_bytes_created_total",
                  "device bytes created (ledger)").set(
                led["created_bytes"])
            gauge("bodo_tpu_device_bytes_freed_total",
                  "device bytes freed (ledger)").set(led["freed_bytes"])
            gauge("bodo_tpu_device_buffers_live",
                  "live tracked device buffers").set(
                led["live_buffers"])
            gdn = gauge("bodo_tpu_xla_donation_total",
                        "donated dispatches by verification result",
                        ("result",))
            gdn.labels(result="verified").set(
                led["donation"]["verified"])
            gdn.labels(result="copied").set(led["donation"]["copied"])
        except Exception:  # pragma: no cover
            pass
    # -- telemetry sampler (same lazy-module rule) ---------------------------
    tl = sys.modules.get("bodo_tpu.runtime.telemetry")
    if tl is not None:
        try:
            tl.sync_gauges()
        except Exception:  # pragma: no cover
            pass
    # -- tracing layer (events buffer + per-query operator counters) ---------
    try:
        from bodo_tpu.utils import tracing
        gauge("bodo_tpu_trace_events_dropped_total",
              "trace events dropped by the ring buffer").set(
            tracing.dropped_events())
        cs = counter("bodo_tpu_operator_seconds_total",
                     "operator wall seconds per query", ("op", "query"))
        cc2 = counter("bodo_tpu_operator_calls_total",
                      "operator invocations per query", ("op", "query"))
        cr = counter("bodo_tpu_operator_rows_total",
                     "operator output rows per query", ("op", "query"))
        # counters must be monotonic: set absolute values via the raw
        # series (tracing's per-query agg IS the source of truth)
        for (qid, op), a in tracing.query_agg().items():
            key = (str(op), str(qid or "-"))
            with cs._mu:
                cs._values[key] = a["total_s"]
            with cc2._mu:
                cc2._values[key] = float(a["count"])
            with cr._mu:
                cr._values[key] = float(a["rows"])
    except Exception:  # pragma: no cover
        pass
