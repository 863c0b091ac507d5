"""Process-wide metrics registry (ISSUE 2 tentpole part 2).

Counter / Gauge / Histogram with labels, a ``snapshot()``/JSON dump for
programmatic readers, and Prometheus text exposition
(https://prometheus.io/docs/instrumenting/exposition_formats/) so a
node-local scraper can pull serving, comms, memory, and compile metrics
from a training or serving host.

Naming follows Prometheus conventions: ``_total`` counters,
``_seconds``/``_bytes`` units, e.g. ``ds_serving_decoded_tokens_total``,
``ds_compile_seconds_total{phase="backend_compile"}``. The full
metric table is in docs/observability.md.
"""

from __future__ import annotations

import json
import math
import threading
from typing import Any, Iterable, Optional

LabelKey = tuple  # tuple of sorted (k, v) pairs

# default latency buckets: 0.5 ms .. 60 s, roughly log-spaced
DEFAULT_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                   0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


def _label_key(labels: dict) -> LabelKey:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _escape(v: str) -> str:
    """Label-VALUE escaping per the text-format spec: backslash first
    (escaping the escapes), then quote and newline. Now that
    request-derived label values exist (trace ids, outcome strings,
    component names fed from serving state), every value goes through
    here — a stray quote or newline must not break a scrape."""
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _escape_help(v: str) -> str:
    """HELP-text escaping: the spec escapes backslash and newline only
    (quotes are legal in help text — escaping them would corrupt it)."""
    return v.replace("\\", "\\\\").replace("\n", "\\n")


def _fmt_labels(key: LabelKey, extra: Iterable[tuple[str, str]] = ()) -> str:
    pairs = list(key) + list(extra)
    if not pairs:
        return ""
    return "{" + ",".join(f'{k}="{_escape(v)}"' for k, v in pairs) + "}"


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._lock = threading.Lock()
        self._values: dict[LabelKey, Any] = {}

    def label_sets(self) -> list[dict]:
        with self._lock:
            return [dict(k) for k in self._values]


class Counter(_Metric):
    """Monotonically increasing value (per label set)."""

    kind = "counter"

    def inc(self, amount: float = 1.0, **labels) -> None:
        if amount < 0:
            raise ValueError(f"counter {self.name}: negative inc {amount}")
        k = _label_key(labels)
        with self._lock:
            self._values[k] = self._values.get(k, 0.0) + amount

    def set_total(self, total: float, **labels) -> None:
        """Mirror an external monotonic counter (e.g. an engine's
        serving_stats entry): sets the exposed total directly, refusing
        to go backwards so scrapes never see a counter reset."""
        k = _label_key(labels)
        with self._lock:
            self._values[k] = max(self._values.get(k, 0.0), float(total))

    def value(self, **labels) -> float:
        return self._values.get(_label_key(labels), 0.0)


class Gauge(_Metric):
    """Point-in-time value (per label set)."""

    kind = "gauge"

    def set(self, value: float, **labels) -> None:
        self._values[_label_key(labels)] = float(value)

    def inc(self, amount: float = 1.0, **labels) -> None:
        k = _label_key(labels)
        with self._lock:
            self._values[k] = self._values.get(k, 0.0) + amount

    def dec(self, amount: float = 1.0, **labels) -> None:
        self.inc(-amount, **labels)

    def value(self, **labels) -> float:
        return self._values.get(_label_key(labels), 0.0)


class _HistState:
    __slots__ = ("bucket_counts", "sum", "count", "exemplars")

    def __init__(self, n_buckets: int):
        self.bucket_counts = [0] * n_buckets  # non-cumulative per bucket
        self.sum = 0.0
        self.count = 0
        # bucket index -> (trace_id, value): the most recent exemplar
        # observed into that bucket (OpenMetrics exemplar semantics —
        # a p99 bucket links to a concrete request trace)
        self.exemplars: dict[int, tuple[str, float]] = {}


class Histogram(_Metric):
    """Bucketed distribution (per label set). Buckets are upper bounds;
    an implicit +Inf bucket catches the tail."""

    kind = "histogram"

    def __init__(self, name: str, help: str = "",
                 buckets: tuple = DEFAULT_BUCKETS):
        super().__init__(name, help)
        self.buckets = tuple(sorted(float(b) for b in buckets))

    def observe(self, value: float, *, exemplar: Optional[str] = None,
                **labels) -> None:
        """Record one observation. ``exemplar`` attaches a trace id to
        the bucket the value lands in (most recent wins), emitted in
        OpenMetrics exemplar syntax by :meth:`MetricsRegistry.\
prometheus_text` so a tail bucket names a concrete trace."""
        value = float(value)
        k = _label_key(labels)
        with self._lock:
            st = self._values.get(k)
            if st is None:
                st = self._values[k] = _HistState(len(self.buckets) + 1)
            i = 0
            for i, ub in enumerate(self.buckets):
                if value <= ub:
                    break
            else:
                i = len(self.buckets)
            st.bucket_counts[i] += 1
            st.sum += value
            st.count += 1
            if exemplar is not None:
                st.exemplars[i] = (str(exemplar), value)

    def exemplars(self, **labels) -> dict:
        """{bucket upper bound (inf for the tail): (trace_id, value)}"""
        st = self._values.get(_label_key(labels))
        if st is None:
            return {}
        ubs = list(self.buckets) + [math.inf]
        return {ubs[i]: ex for i, ex in st.exemplars.items()}

    def summary(self, **labels) -> dict:
        """{count, sum, mean, buckets: {le: cumulative_count}}"""
        st = self._values.get(_label_key(labels))
        if st is None:
            return {"count": 0, "sum": 0.0, "mean": 0.0, "buckets": {}}
        cum, out = 0, {}
        for ub, c in zip(self.buckets, st.bucket_counts):
            cum += c
            out[ub] = cum
        out[math.inf] = st.count
        return {"count": st.count, "sum": st.sum,
                "mean": st.sum / max(st.count, 1), "buckets": out}


class MetricsRegistry:
    """Name -> metric map with typed, idempotent getters: asking twice
    for the same name returns the same object; asking with a different
    type raises (one name, one meaning)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: dict[str, _Metric] = {}

    def _get(self, cls, name: str, help: str, **kw) -> _Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, help, **kw)
            elif not isinstance(m, cls):
                raise TypeError(
                    f"metric {name!r} already registered as {m.kind}, "
                    f"requested {cls.kind}")
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(Gauge, name, help)

    def histogram(self, name: str, help: str = "",
                  buckets: tuple = DEFAULT_BUCKETS) -> Histogram:
        return self._get(Histogram, name, help, buckets=buckets)

    def get(self, name: str) -> Optional[_Metric]:
        return self._metrics.get(name)

    def names(self) -> list[str]:
        return sorted(self._metrics)

    def clear(self) -> None:
        with self._lock:
            self._metrics.clear()

    # ------------------------------------------------------------------
    def snapshot(self) -> dict:
        """JSON-friendly dump of every metric and label set."""
        out = {}
        for name in self.names():
            m = self._metrics[name]
            entries = []
            for labels in m.label_sets():
                if isinstance(m, Histogram):
                    s = m.summary(**labels)
                    entry = {
                        "labels": labels, "count": s["count"],
                        "sum": s["sum"], "mean": s["mean"],
                        "buckets": {("+Inf" if math.isinf(k) else k): v
                                    for k, v in s["buckets"].items()}}
                    exs = m.exemplars(**labels)
                    if exs:
                        entry["exemplars"] = {
                            ("+Inf" if math.isinf(k) else k):
                                {"trace_id": t, "value": v}
                            for k, (t, v) in exs.items()}
                    entries.append(entry)
                else:
                    entries.append({"labels": labels,
                                    "value": m.value(**labels)})
            out[name] = {"type": m.kind, "help": m.help,
                         "values": entries}
        return out

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.snapshot(), indent=indent)

    def dump_json(self, path: str, indent: int = 1) -> str:
        with open(path, "w") as f:
            f.write(self.to_json(indent=indent))
        return path

    def prometheus_text(self, exemplars: bool = True) -> str:
        """Prometheus text exposition. HELP text and label values are
        escaped per the 0.0.4 spec; with ``exemplars=True`` (default)
        histogram buckets holding one carry it in OPENMETRICS exemplar
        syntax (``... # {trace_id="..."} value``) so a tail bucket
        links to a concrete request trace. Exemplars are an
        OpenMetrics extension — strict 0.0.4 parsers reject mid-line
        ``#``, so pass ``exemplars=False`` when feeding one (the
        in-repo consumer, ``telemetry_report.parse_prometheus``,
        strips the suffix)."""
        lines: list[str] = []
        for name in self.names():
            m = self._metrics[name]
            if m.help:
                lines.append(f"# HELP {name} {_escape_help(m.help)}")
            lines.append(f"# TYPE {name} {m.kind}")
            for labels in m.label_sets():
                key = _label_key(labels)
                if isinstance(m, Histogram):
                    s = m.summary(**labels)
                    exs = m.exemplars(**labels) if exemplars else {}
                    for ub, cum in s["buckets"].items():
                        le = "+Inf" if math.isinf(ub) else repr(ub)
                        line = (f"{name}_bucket"
                                f"{_fmt_labels(key, [('le', le)])} {cum}")
                        ex = exs.get(ub)
                        if ex is not None:
                            line += (f' # {{trace_id="{_escape(ex[0])}"}}'
                                     f" {ex[1]}")
                        lines.append(line)
                    lines.append(f"{name}_sum{_fmt_labels(key)} "
                                 f"{s['sum']}")
                    lines.append(f"{name}_count{_fmt_labels(key)} "
                                 f"{s['count']}")
                else:
                    v = m.value(**labels)
                    lines.append(f"{name}{_fmt_labels(key)} {v}")
        return "\n".join(lines) + "\n"

    def dump_prometheus(self, path: str) -> str:
        with open(path, "w") as f:
            f.write(self.prometheus_text())
        return path

    # ------------------------------------------------------------------
    def events_for_monitor(self, step: int, prefix: str = "Telemetry") \
            -> list[tuple[str, float, int]]:
        """Flatten scalar metrics into monitor event tuples so CSV /
        TensorBoard / W&B backends chart the registry. Histograms emit
        ``_count``/``_sum``/``_mean`` scalars; labeled metrics append
        ``/k=v`` segments to the event name."""
        events: list[tuple[str, float, int]] = []
        for name in self.names():
            m = self._metrics[name]
            for labels in m.label_sets():
                suffix = "".join(f"/{k}={v}"
                                 for k, v in sorted(labels.items()))
                base = f"{prefix}/{name}{suffix}"
                if isinstance(m, Histogram):
                    s = m.summary(**labels)
                    if s["count"]:
                        events += [(f"{base}_count", float(s["count"]),
                                    step),
                                   (f"{base}_sum", s["sum"], step),
                                   (f"{base}_mean", s["mean"], step)]
                else:
                    events.append((base, m.value(**labels), step))
        return events


# --- module-level current registry (wired by telemetry.configure) -------

_REGISTRY: Optional[MetricsRegistry] = None


def get_registry() -> Optional[MetricsRegistry]:
    return _REGISTRY


def set_registry(reg: Optional[MetricsRegistry]) -> None:
    global _REGISTRY
    _REGISTRY = reg
