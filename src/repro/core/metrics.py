"""Typed metric instruments + the cluster metrics pipeline.

The observability plane (ROADMAP item 3) in one module:

  * `Counter` / `Gauge` / `Histogram` -- the three instrument kinds.
    Histograms use FIXED log-spaced bucket bounds with mergeable state
    (per-bucket counts + sum + count), so worker-side observations fold
    into head-side aggregates by pure element-wise addition: merge is
    associative and commutative (property-tested in
    tests/test_observability.py), and a wire delta is just the counts
    that changed since the last confirmed send.
  * `MetricsRegistry` -- instruments keyed by (name, labels). The
    scheduler owns one; the head's `MetricsHub` shares it so sojourn
    histograms, worker-folded histograms and router gauges land in one
    place.
  * `SpanRing` / `SPANS` -- the fourth instrument: timed spans, one per
    call or request, in a process-wide fixed-size ring (the served
    path's flight recorder), on `time.perf_counter()` and, once JAX is
    loaded, on the profiler trace's host plane as well.
  * `TimeSeries` / `MetricsHub` -- head-side ring-buffer history keyed
    by (metric, label): every `metrics` op snapshot is recorded, so
    dashboards get history without a second collection path.
  * `render_prometheus` -- Prometheus text exposition format (label
    escaping, `_bucket`/`_sum`/`_count` layout, `+Inf`), golden-tested.
  * `render_dashboards` -- Grafana-style dashboard JSON for the four
    boards operators actually watch: serve, drain, dataplane, tenancy.
  * `build_cluster_metrics` -- the ONE builder that turns ground truth
    (store.stats, scheduler stats/registry, worker delta aggregates,
    router-fed serve gauges) into the flat `metrics`-op reply. The head
    and `SimCluster.export_metrics` both call it, and the chaos
    conformance checker (tests/_invariants.py) asserts its output
    against the raw sources -- metrics that disagree with reality are a
    test failure, not a dashboard surprise.

Quantile estimates are bucket-bounded: `Histogram.quantile(q)` returns
the upper bound of the bucket holding the q-th order statistic, so the
estimate is never below the exact sample and never more than one bucket
above it.
"""
from __future__ import annotations

import bisect
import itertools
import math
import sys
import threading
from time import perf_counter
from typing import (Any, Callable, Dict, Iterable, List, NamedTuple, Optional,
                    Tuple)


def log_buckets(lo: float, hi: float, factor: float = 2.0) -> Tuple[float, ...]:
    """Fixed log-spaced bucket upper bounds: lo, lo*factor, ... >= hi.
    Fixed (not adaptive) so every producer of a histogram name shares
    the same bounds and merge stays a pure element-wise add."""
    assert lo > 0 and factor > 1.0 and hi >= lo
    out = [float(lo)]
    while out[-1] < hi:
        out.append(out[-1] * factor)
    return tuple(out)


# well-known bounds: seconds (1ms .. ~1.1h), queue depths, byte sizes.
# Wire deltas carry bucket indices only, so the sender and the head MUST
# agree on bounds per histogram name -- register new names here.
TIME_BUCKETS = log_buckets(0.001, 4096.0)
DEPTH_BUCKETS = log_buckets(0.25, 4096.0)
SIZE_BUCKETS = log_buckets(256.0, float(1 << 32), factor=4.0)

BOUNDS_BY_NAME: Dict[str, Tuple[float, ...]] = {
    "syndeo_task_sojourn_seconds": TIME_BUCKETS,
    "syndeo_worker_poll_seconds": TIME_BUCKETS,
    "syndeo_router_queue_depth": DEPTH_BUCKETS,
    "syndeo_router_shed_depth": DEPTH_BUCKETS,
}


class Counter:
    """Monotone counter. `inc` only; exported value is `.value`."""

    __slots__ = ("value",)
    kind = "counter"

    def __init__(self):
        self.value = 0

    def inc(self, n: int = 1):
        assert n >= 0, "counters are monotone"
        self.value += n


class Gauge:
    """Point-in-time value; `set` replaces, `add` adjusts."""

    __slots__ = ("value",)
    kind = "gauge"

    def __init__(self):
        self.value = 0.0

    def set(self, v: float):
        self.value = float(v)

    def add(self, dv: float):
        self.value += float(dv)


class Histogram:
    """Fixed-bound log-bucket histogram with mergeable state.

    `counts[i]` counts observations v with v <= bounds[i] (and
    > bounds[i-1]); `counts[-1]` is the overflow bucket. State is
    (counts, sum, count) -- element-wise addable, so merge is
    associative and commutative and a wire delta is sparse counts plus
    scalar sum/count deltas."""

    __slots__ = ("bounds", "counts", "sum", "count")
    kind = "histogram"

    def __init__(self, bounds: Tuple[float, ...] = TIME_BUCKETS):
        self.bounds = tuple(float(b) for b in bounds)
        assert list(self.bounds) == sorted(set(self.bounds)), \
            "histogram bounds must be strictly increasing"
        self.counts = [0] * (len(self.bounds) + 1)
        self.sum = 0.0
        self.count = 0

    def bucket_index(self, v: float) -> int:
        return bisect.bisect_left(self.bounds, float(v))

    def observe(self, v: float):
        self.counts[self.bucket_index(v)] += 1
        self.sum += float(v)
        self.count += 1

    def merge(self, other: "Histogram") -> "Histogram":
        """Pure merge: a NEW histogram holding both states (the
        associativity/commutativity property the tests pin)."""
        assert self.bounds == other.bounds, "cannot merge mismatched bounds"
        out = Histogram(self.bounds)
        out.counts = [a + b for a, b in zip(self.counts, other.counts)]
        out.sum = self.sum + other.sum
        out.count = self.count + other.count
        return out

    def __eq__(self, other: object) -> bool:
        if not isinstance(other, Histogram):
            return NotImplemented
        return (self.bounds == other.bounds
                and self.counts == other.counts
                and self.count == other.count
                and math.isclose(self.sum, other.sum,
                                 rel_tol=1e-9, abs_tol=1e-9))

    def __hash__(self):  # pragma: no cover -- dict-key use is a bug
        raise TypeError("histograms are mutable; not hashable")

    def quantile(self, q: float) -> float:
        """Bucket-bounded quantile estimate: the upper bound of the
        bucket containing the ceil(q*count)-th order statistic (overflow
        clamps to the top bound). >= the exact order statistic, and at
        most one bucket above it."""
        if self.count <= 0:
            return 0.0
        rank = max(1, math.ceil(min(max(q, 0.0), 1.0) * self.count))
        cum = 0
        for i, c in enumerate(self.counts):
            cum += c
            if cum >= rank:
                return self.bounds[min(i, len(self.bounds) - 1)]
        return self.bounds[-1]

    # -- wire deltas (the worker -> head piggyback path) ----------------------

    def to_delta(self, base: "Histogram") -> Dict[str, Any]:
        """Sparse JSON-safe delta since `base` (the last confirmed
        send): bucket-index -> count delta, plus sum/count deltas."""
        assert self.bounds == base.bounds
        return {"counts": {str(i): a - b
                           for i, (a, b) in enumerate(zip(self.counts,
                                                          base.counts))
                           if a != b},
                "sum": self.sum - base.sum,
                "count": self.count - base.count}

    def apply_delta(self, delta: Dict[str, Any]):
        """Fold a wire delta in (head-side aggregation, and the sender's
        base advance after a confirmed send). Hot path: the head folds
        one of these per worker poll, so skip the zero fields."""
        counts = delta.get("counts")
        if counts:
            cs, n = self.counts, len(self.counts)
            for k, v in counts.items():
                i = int(k)
                if 0 <= i < n:
                    cs[i] += int(v)
        s = delta.get("sum")
        if s:
            self.sum += float(s)
        c = delta.get("count")
        if c:
            self.count += int(c)


_FACTORIES = {"counter": Counter, "gauge": Gauge}


class MetricsRegistry:
    """Instruments keyed by (name, sorted label items). Thread-safe
    lookup; instrument mutation is GIL-atomic dict/int work (the
    threaded head additionally serializes writers under its cluster
    lock)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._families: Dict[str, Dict[Tuple[Tuple[str, str], ...], Any]] = {}

    def _get(self, kind: str, name: str, labels: Dict[str, str],
             factory: Callable[[], Any]):
        key = tuple(sorted((str(k), str(v)) for k, v in labels.items()))
        with self._lock:
            fam = self._families.setdefault(name, {})
            inst = fam.get(key)
            if inst is None:
                inst = fam[key] = factory()
            assert inst.kind == kind, \
                f"metric {name!r} is a {inst.kind}, not a {kind}"
            return inst

    def counter(self, name: str, **labels) -> Counter:
        return self._get("counter", name, labels, Counter)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get("gauge", name, labels, Gauge)

    def histogram(self, name: str,
                  bounds: Optional[Tuple[float, ...]] = None,
                  **labels) -> Histogram:
        b = bounds or BOUNDS_BY_NAME.get(name, TIME_BUCKETS)
        return self._get("histogram", name, labels, lambda: Histogram(b))

    def family(self, name: str) -> Dict[Tuple[Tuple[str, str], ...], Any]:
        with self._lock:
            return dict(self._families.get(name, {}))

    def samples(self) -> Iterable[Tuple[str, Dict[str, str], Any]]:
        with self._lock:
            flat = [(name, key, inst)
                    for name, fam in sorted(self._families.items())
                    for key, inst in sorted(fam.items())]
        for name, key, inst in flat:
            yield name, dict(key), inst


# -- spans: the flight recorder ------------------------------------------------

class Span(NamedTuple):
    """One finished interval. `start`/`end` are `time.perf_counter()`
    seconds; `thread` is `threading.get_ident()` of the recording thread;
    `parent` is the id of the span open on that thread when this one
    began (None at the top); `attrs` holds its identifiers (`call` for an
    actor call, `req` for a request) and small integer counts."""
    id: int
    name: str
    start: float
    end: float
    thread: int
    parent: Optional[int]
    attrs: Dict[str, Any]


class _Stack(threading.local):
    def __init__(self):
        self.open: List["_OpenSpan"] = []


class _OpenSpan:
    """A span being timed by `SpanRing.span`; its `attrs` may be written
    until it closes. With no ring (recording off) it does nothing."""

    __slots__ = ("_ring", "name", "attrs", "id", "parent", "start", "_stack",
                 "_note")

    def __init__(self, ring: Optional["SpanRing"], name: str,
                 attrs: Dict[str, Any]):
        self._ring = ring
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_OpenSpan":
        ring = self._ring
        if ring is None:
            return self
        self._stack = stack = ring._stack.open
        self.parent = stack[-1].id if stack else None
        self.id = next(ring._ids)
        stack.append(self)
        note = ring._note_cls or ring._annotation()
        if note is not None and note.is_enabled():   # a profiler is tracing
            self._note = note(self.name)
            self._note.__enter__()
        else:
            self._note = None
        self.start = perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        ring = self._ring
        if ring is None:
            return False
        end = perf_counter()
        if self._note is not None:
            self._note.__exit__(None, None, None)
        self._stack.pop()
        ring._put((self.id, self.name, self.start, end,
                   threading.get_ident(), self.parent, self.attrs))
        return False


class SpanRing:
    """Spans of the served path in a fixed-size ring, newest kept.

    Two ways in: `span(name, **attrs)`, a context manager timing the
    block it wraps on the calling thread, and `record(name, start, end,
    **attrs)`, an interval already measured whose two ends fall in
    different threads or handlers (a queue wait, a result held for its
    caller). A span's parent is the innermost span open on the recording
    thread. `add(key, n)` adds to the count `key` of every open span on
    this thread that declared it, so a count is taken where the work
    happens.

    Once `jax` is imported, every `span()` opened while a profiler
    traces is also a `jax.profiler.TraceAnnotation` of its name, so it
    lands on the host plane of the trace beside the device's events.
    `record()` intervals stay in memory only. This module never imports
    JAX itself.

    Writers take no lock: ids and slots come from `itertools.count`,
    whose `next` is atomic under the interpreter lock, and a slot is
    written by one store. `horizon` is the end of the latest span the
    ring has overwritten (-inf until it wraps): every span that ended
    after it is still held, so a reader of the spans that start at or
    after `t` has them all while `horizon < t`. `enabled` is on by
    default (a flight recorder); turning it off makes `span`, `record`
    and `add` do nothing."""

    # a whole run of the chat cell (set-up, a 51 s window, the drain)
    # records about 3,800 spans
    capacity = 65536

    def __init__(self):
        self.enabled = True
        self.horizon = -math.inf
        # Span fields as plain tuples: a NamedTuple costs ~5x to build
        self._buf: List[Optional[tuple]] = [None] * self.capacity
        self._slots = itertools.count()
        self._ids = itertools.count(1)
        self._stack = _Stack()
        self._note_cls: Optional[Any] = None

    def _annotation(self) -> Optional[Any]:
        """`jax.profiler.TraceAnnotation` once JAX has loaded it."""
        profiler = sys.modules.get("jax.profiler")
        self._note_cls = getattr(profiler, "TraceAnnotation", None)
        return self._note_cls

    def _put(self, fields: tuple) -> None:
        i = next(self._slots) % self.capacity
        old = self._buf[i]
        self._buf[i] = fields
        if old is not None and old[3] > self.horizon:
            self.horizon = old[3]

    def span(self, name: str, **attrs) -> _OpenSpan:
        return _OpenSpan(self if self.enabled else None, name, attrs)

    def record(self, name: str, start: float, end: float, **attrs) -> None:
        if not self.enabled:
            return
        stack = self._stack.open
        self._put((next(self._ids), name, start, end, threading.get_ident(),
                   stack[-1].id if stack else None, attrs))

    def add(self, key: str, n: int = 1) -> None:
        for sp in self._stack.open:
            if key in sp.attrs:
                sp.attrs[key] += n

    def spans(self) -> List[Span]:
        """Every span held, by start time."""
        return sorted((Span._make(f) for f in list(self._buf)
                       if f is not None), key=lambda s: s.start)


# the process-wide recorder the served path writes to
SPANS = SpanRing()


class TimeSeries:
    """Fixed-capacity ring buffer of (t, value) points."""

    __slots__ = ("capacity", "_buf", "_next", "_len")

    def __init__(self, capacity: int = 512):
        self.capacity = max(1, int(capacity))
        self._buf: List[Tuple[float, float]] = [(0.0, 0.0)] * self.capacity
        self._next = 0
        self._len = 0

    def record(self, t: float, v: float):
        self._buf[self._next] = (float(t), float(v))
        self._next = (self._next + 1) % self.capacity
        self._len = min(self._len + 1, self.capacity)

    def __len__(self) -> int:
        return self._len

    def points(self) -> List[Tuple[float, float]]:
        if self._len < self.capacity:
            return self._buf[:self._len]
        return self._buf[self._next:] + self._buf[:self._next]

    @property
    def latest(self) -> Optional[Tuple[float, float]]:
        return self._buf[self._next - 1] if self._len else None


class MetricsHub:
    """Head-side aggregation point: one shared registry (histograms the
    workers fold into, the scheduler's sojourn family) plus ring-buffer
    time series keyed by (metric, label) fed from each flat `metrics`
    snapshot -- dashboards read history, the HPA reads the latest."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 capacity: int = 512):
        self.registry = registry if registry is not None else MetricsRegistry()
        self.capacity = capacity
        self.series: Dict[Tuple[str, str], TimeSeries] = {}
        self._lock = threading.Lock()

    def _series(self, name: str, label: str = "") -> TimeSeries:
        with self._lock:
            ts = self.series.get((name, label))
            if ts is None:
                ts = self.series[(name, label)] = TimeSeries(self.capacity)
            return ts

    def ingest(self, now: float, flat: Dict[str, Any]):
        """Record one flat metrics snapshot: scalar values get one
        series; dict-valued metrics (per-tenant shares, per-link bytes,
        per-worker aggregates) get one series per label key."""
        for name, v in flat.items():
            if isinstance(v, bool) or name == "ok":
                continue
            if isinstance(v, (int, float)):
                self._series(name).record(now, float(v))
            elif isinstance(v, dict):
                for label, sub in v.items():
                    if isinstance(sub, (int, float)) \
                            and not isinstance(sub, bool):
                        self._series(name, str(label)).record(now, float(sub))

    def history(self, name: str, label: str = "") -> List[Tuple[float, float]]:
        with self._lock:
            ts = self.series.get((name, label))
        return ts.points() if ts is not None else []


# -- Prometheus text exposition ------------------------------------------------

def _escape_label(v: str) -> str:
    return (str(v).replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _fmt(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return f"{f:.10g}"


def _labels_str(labels: Dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{_escape_label(v)}"'
                     for k, v in sorted(labels.items()))
    return "{" + inner + "}"


def _sanitize(name: str) -> str:
    return "".join(c if c.isalnum() or c in "_:" else "_" for c in name)


def render_prometheus(registry: Optional[MetricsRegistry] = None,
                      flat: Optional[Dict[str, Any]] = None) -> str:
    """Prometheus text exposition of a registry plus a flat snapshot.

    Registry histograms emit the standard cumulative `_bucket{le=...}`
    series (closing with `le="+Inf"`), `_sum` and `_count`. Flat scalars
    emit as gauges; flat dict-valued metrics emit one sample per entry
    under a `key` label (tenant ids, worker ids, "src->dst" links --
    escaped, since ids are operator-controlled strings)."""
    lines: List[str] = []
    seen_types: set = set()

    def type_line(name: str, kind: str):
        if name not in seen_types:
            seen_types.add(name)
            lines.append(f"# TYPE {name} {kind}")

    for name, labels, inst in (registry.samples() if registry else ()):
        name = _sanitize(name)
        if inst.kind == "histogram":
            type_line(name, "histogram")
            cum = 0
            for i, b in enumerate(inst.bounds):
                cum += inst.counts[i]
                bl = dict(labels, le=_fmt(b))
                lines.append(f"{name}_bucket{_labels_str(bl)} {cum}")
            bl = dict(labels, le="+Inf")
            lines.append(f"{name}_bucket{_labels_str(bl)} {inst.count}")
            lines.append(f"{name}_sum{_labels_str(labels)} {_fmt(inst.sum)}")
            lines.append(f"{name}_count{_labels_str(labels)} {inst.count}")
        else:
            type_line(name, inst.kind)
            lines.append(f"{name}{_labels_str(labels)} {_fmt(inst.value)}")
    for name, v in sorted((flat or {}).items()):
        if isinstance(v, bool) or name == "ok":
            continue
        name = _sanitize(name)
        if isinstance(v, (int, float)):
            type_line(name, "gauge")
            lines.append(f"{name} {_fmt(v)}")
        elif isinstance(v, dict):
            type_line(name, "gauge")
            for label, sub in sorted(v.items()):
                if isinstance(sub, (int, float)) \
                        and not isinstance(sub, bool):
                    ls = _labels_str({"key": str(label)})
                    lines.append(f"{name}{ls} {_fmt(sub)}")
    return "\n".join(lines) + "\n"


def parse_prometheus(text: str) -> Dict[Tuple[str, str], float]:
    """Minimal exposition parser (the conformance checker's read-back
    path): {(metric_name, labels_str): value}. Handles escaped label
    values by keeping the raw label block as the key."""
    out: Dict[Tuple[str, str], float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        body, _, val = line.rpartition(" ")
        if "{" in body:
            name, _, rest = body.partition("{")
            labels = "{" + rest
        else:
            name, labels = body, ""
        out[(name, labels)] = (math.inf if val == "+Inf" else float(val))
    return out


# -- Grafana-style dashboard JSON ---------------------------------------------

def _panel(pid: int, title: str, exprs: List[str], x: int, y: int,
           kind: str = "timeseries") -> Dict[str, Any]:
    return {"id": pid, "title": title, "type": kind,
            "datasource": {"type": "prometheus", "uid": "syndeo"},
            "gridPos": {"h": 8, "w": 12, "x": x, "y": y},
            "targets": [{"expr": e, "refId": chr(ord("A") + i)}
                        for i, e in enumerate(exprs)]}


def render_dashboards() -> Dict[str, Dict[str, Any]]:
    """The four boards the planes need watched. Panel exprs reference
    exactly the names `build_cluster_metrics` / `render_prometheus`
    export, so a renamed metric breaks the dashboard test, not the 2am
    page."""
    boards: Dict[str, Dict[str, Any]] = {}

    def board(uid: str, title: str,
              panels: List[Tuple[str, List[str], str]]) -> Dict[str, Any]:
        out = {"uid": f"syndeo-{uid}", "title": title, "tags": ["syndeo"],
               "schemaVersion": 39, "refresh": "10s",
               "time": {"from": "now-1h", "to": "now"},
               "panels": [_panel(i + 1, t, exprs, 12 * (i % 2),
                                 8 * (i // 2), kind)
                          for i, (t, exprs, kind) in enumerate(panels)]}
        boards[uid] = out
        return out

    board("serve", "Syndeo / Serving plane", [
        ("Request rate / shed", ["rate(syndeo_serve_requests[1m])",
                                 "rate(syndeo_serve_shed[1m])"],
         "timeseries"),
        ("p99 latency (ms)", ["syndeo_serve_p99_ms"], "timeseries"),
        ("Live replicas", ["syndeo_replica_count"], "stat"),
        ("Router queue depth",
         ["histogram_quantile(0.99, "
          "rate(syndeo_router_queue_depth_bucket[5m]))"], "timeseries"),
    ])
    board("drain", "Syndeo / Drain plane", [
        ("Moves committed / aborted", ["rate(syndeo_moves_committed[5m])",
                                       "rate(syndeo_moves_aborted[5m])"],
         "timeseries"),
        ("Relay fallbacks", ["rate(syndeo_relay_fallbacks[5m])"],
         "timeseries"),
        ("Head-relayed bytes", ["rate(syndeo_head_relayed_bytes[5m])"],
         "timeseries"),
        ("Drain push bytes (workers)",
         ["rate(syndeo_worker_drain_pushed_bytes[5m])"], "timeseries"),
    ])
    board("dataplane", "Syndeo / Data plane", [
        ("Per-link bytes (top 10)",
         ["topk(10, syndeo_link_bytes)"], "timeseries"),
        ("Worker blob serves / receives",
         ["rate(syndeo_worker_blob_serves[5m])",
          "rate(syndeo_worker_blob_receives[5m])"], "timeseries"),
        ("Broadcast rounds / tree edges / batched moves",
         ["syndeo_broadcast_rounds", "syndeo_tree_edges",
          "syndeo_batched_moves"], "timeseries"),
        ("Spill tier: bytes saved / promotions",
         ["syndeo_delta_spill_bytes_saved", "syndeo_promotions"],
         "timeseries"),
    ])
    board("tenancy", "Syndeo / Tenancy", [
        ("Dominant share by tenant",
         ["syndeo_tenant_dominant_share"], "timeseries"),
        ("Quota pressure by tenant",
         ["syndeo_tenant_quota_fraction"], "timeseries"),
        ("Sojourn p99 by tenant (s)",
         ["syndeo_tenant_sojourn_p99_s"], "timeseries"),
        ("Backlog by tenant", ["backlog_by_tenant"], "timeseries"),
    ])
    return boards


# -- the one metrics builder ---------------------------------------------------

def build_cluster_metrics(store, scheduler,
                          worker_metrics: Optional[Dict[str, Dict[str, int]]]
                          = None,
                          serve_stats: Optional[Dict[str, float]] = None,
                          replica_count: Optional[int] = None,
                          workers: Optional[int] = None,
                          busy: Optional[int] = None,
                          backlog: Optional[int] = None,
                          backlog_by_tenant: Optional[Dict[str, int]] = None,
                          shares: Optional[Dict[str, float]] = None
                          ) -> Dict[str, Any]:
    """Build the flat cluster-metrics snapshot from ground truth. The
    threaded head passes its lock-snapshotted scheduler values; the
    simulator (single-threaded) lets the defaults read the scheduler
    directly. Every key here is cross-checked against the raw sources by
    `tests/_invariants.check_metrics_conformance` at the end of every
    chaos scenario."""
    from repro.core.task_graph import TaskState
    if workers is None:
        alive = [w for w in scheduler.workers.values() if w.alive]
        workers = len(alive)
        busy = sum(1 for w in alive if w.running)
    if backlog is None:
        backlog = sum(1 for t in scheduler.graph.tasks.values()
                      if t.state in (TaskState.READY, TaskState.PENDING))
    if backlog_by_tenant is None:
        backlog_by_tenant = scheduler.backlog_by_tenant()
    if shares is None:
        shares = scheduler.tenant_shares()
    if replica_count is None:
        replica_count = len(scheduler.actors)
    wm_by_id = {str(k): dict(v)
                for k, v in (worker_metrics or {}).items()}
    wm = list(wm_by_id.values())
    serve = dict(serve_stats or {})
    n = max(workers, 1)
    store_stats = store.stats
    out: Dict[str, Any] = {
        "ok": True, "workers": workers, "busy": busy, "backlog": backlog,
        "syndeo_backlog_per_worker": backlog / n,
        "syndeo_busy_fraction": (busy or 0) / n,
        "backlog_by_tenant": backlog_by_tenant,
        "syndeo_tenant_dominant_share": shares,
        "syndeo_tenant_quota_fraction": {
            t: store.tenant_quota_fraction(t)
            for t in sorted(set(shares) | store.quota_tenants())},
        # per-worker delta aggregates, exported raw so the conformance
        # checker can hold each worker's aggregate against that worker's
        # own live counters (the lost-delta regression check)
        "per_worker": wm_by_id,
    }
    # drain-plane health counters + data-plane throughput layer (store
    # directory stats; worker-local shares arrive via piggybacked deltas)
    for k in ("moves_started", "moves_committed", "moves_aborted",
              "relay_fallbacks", "head_relayed_bytes", "replica_gc",
              "broadcast_rounds", "tree_edges"):
        out[f"syndeo_{k}"] = int(store_stats.get(k, 0))
    out["syndeo_batched_moves"] = int(store_stats.get("batched_moves", 0)) \
        + sum(m.get("batched_moves", 0) for m in wm)
    spill = store.spill_tier_stats()
    for k in ("delta_spill_bytes_saved", "promotions"):
        out[f"syndeo_{k}"] = spill[k] + sum(m.get(k, 0) for m in wm)
    # worker blob-plane aggregates (p2p bytes that never touch the head)
    for wire_k, src_k in (("worker_blob_serves", "serves"),
                          ("worker_blob_receives", "receives"),
                          ("worker_served_bytes", "served_bytes"),
                          ("worker_drain_pushed_blobs", "drain_pushed_blobs"),
                          ("worker_drain_pushed_bytes", "drain_pushed_bytes")):
        out[f"syndeo_{wire_k}"] = sum(m.get(src_k, 0) for m in wm)
    # per-link flow gauges off the store's byte accounting
    out["syndeo_link_bytes"] = {f"{src}->{dst}": int(v)
                                for (src, dst), v
                                in store.link_snapshot().items()}
    # per-tenant sojourn percentiles (submit -> result) from the
    # scheduler's mergeable histograms
    registry = getattr(scheduler, "metrics", None)
    soj_count: Dict[str, int] = {}
    soj_p50: Dict[str, float] = {}
    soj_p99: Dict[str, float] = {}
    if registry is not None:
        for key, hist in registry.family("syndeo_task_sojourn_seconds"
                                         ).items():
            tenant = dict(key).get("tenant", "default")
            soj_count[tenant] = hist.count
            soj_p50[tenant] = hist.quantile(0.50)
            soj_p99[tenant] = hist.quantile(0.99)
        poll_fam = registry.family("syndeo_worker_poll_seconds")
        polls = None
        for _key, hist in poll_fam.items():
            polls = hist if polls is None else polls.merge(hist)
        out["syndeo_worker_poll_count"] = polls.count if polls else 0
        out["syndeo_worker_poll_p99_s"] = (polls.quantile(0.99)
                                           if polls else 0.0)
    out["syndeo_tenant_sojourn_count"] = soj_count
    out["syndeo_tenant_sojourn_p50_s"] = soj_p50
    out["syndeo_tenant_sojourn_p99_s"] = soj_p99
    # serving-plane gauges (router-fed via stats_sink)
    out["syndeo_serve_requests"] = int(serve.get("requests", 0))
    out["syndeo_serve_shed"] = int(serve.get("shed", 0))
    out["syndeo_serve_p99_ms"] = float(serve.get("p99_ms", 0.0))
    out["syndeo_replica_count"] = int(replica_count)
    return out
