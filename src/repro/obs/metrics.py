"""Metrics registry: counters, gauges, histograms, series.

Metrics (``inc``/``gauge``/``observe``/``sample``) are recorded
unconditionally by this module but every call site gates on
``obs.enabled()`` first (the tracer's GC hook exists only while the
tracer is on), so with tracing off no metric call is even reached — that
is the zero-cost contract, pinned in tests/test_obs.py.  Host time by
phase is not kept here: it is the self time of the tracer's spans.

A counter may carry tags (``inc("gc_s", dt, generation=0)``): each tag
set is a counter of its own, exported as one row with the tags as
fields.

``sample`` feeds the metrics JSONL stream (``obs.export``): a bounded
list of ``{"name", "value", "step", ...tags}`` rows for time-series like
live-lane occupancy and per-bucket pack widths.  ``observe`` feeds
histograms (staleness, store write latency) summarized at export time.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

# Safety valve so a pathological run cannot grow the series list without
# bound; 1M rows is far beyond any smoke/bench sweep (which emit ~1e3).
SERIES_LIMIT = 1_000_000


def _percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile on an already-sorted list."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


class MetricsRegistry:
    """Process-wide metric store (singleton at :data:`registry`)."""

    def __init__(self):
        self._counters: Dict[str, float] = {}
        self._tagged: Dict[Tuple[str, tuple], float] = {}
        self._gauges: Dict[str, float] = {}
        self._hists: Dict[str, List[float]] = {}
        self._series: List[Dict[str, Any]] = []

    # ---- recording (call sites gate on obs.enabled()) -----------------

    def inc(self, name: str, value: float = 1.0, **tags):
        if tags:
            key = (name, tuple(sorted(tags.items())))
            self._tagged[key] = self._tagged.get(key, 0.0) + value
            return
        self._counters[name] = self._counters.get(name, 0.0) + value

    def gauge(self, name: str, value: float):
        self._gauges[name] = float(value)

    def observe(self, name: str, value: float):
        self._hists.setdefault(name, []).append(float(value))

    def sample(self, name: str, value: float, step: Optional[int] = None,
               **tags):
        if len(self._series) >= SERIES_LIMIT:
            return
        row: Dict[str, Any] = {"name": name, "value": float(value)}
        if step is not None:
            row["step"] = int(step)
        if tags:
            row.update(tags)
        self._series.append(row)

    # ---- accessors ----------------------------------------------------

    def counter_value(self, name: str, **tags) -> float:
        if tags:
            return self._tagged.get((name, tuple(sorted(tags.items()))),
                                    0.0)
        return self._counters.get(name, 0.0)

    def counters(self) -> Dict[str, float]:
        """The untagged counters."""
        return dict(self._counters)

    def tagged_counters(self) -> List[Dict[str, Any]]:
        """The tagged counters, one ``{"name", "value", **tags}`` row per
        tag set, sorted by name and tags."""
        return [{"name": name, "value": value, **dict(tags)}
                for (name, tags), value in sorted(self._tagged.items())]

    def gauges(self) -> Dict[str, float]:
        return dict(self._gauges)

    def series(self, name: Optional[str] = None) -> List[Dict[str, Any]]:
        if name is None:
            return list(self._series)
        return [r for r in self._series if r["name"] == name]

    def histogram_summary(self, name: str) -> Dict[str, float]:
        vals = sorted(self._hists.get(name, []))
        if not vals:
            return {"count": 0}
        return {
            "count": len(vals),
            "min": vals[0],
            "max": vals[-1],
            "mean": sum(vals) / len(vals),
            "p50": _percentile(vals, 0.50),
            "p90": _percentile(vals, 0.90),
            "p99": _percentile(vals, 0.99),
        }

    def histograms(self) -> Dict[str, Dict[str, float]]:
        return {n: self.histogram_summary(n) for n in sorted(self._hists)}

    def snapshot(self) -> Dict[str, Any]:
        """Everything at once — what the benchmark and exporters read."""
        return {
            "counters": self.counters(),
            "tagged_counters": self.tagged_counters(),
            "gauges": self.gauges(),
            "histograms": self.histograms(),
            "n_series": len(self._series),
        }

    def reset(self):
        self._counters.clear()
        self._tagged.clear()
        self._gauges.clear()
        self._hists.clear()
        self._series.clear()


registry = MetricsRegistry()
