"""Metrics registry: counters, gauges, timers with 3-level naming.

Capability parity with the reference metrics SPI
(ratis-metrics-api/src/main/java/org/apache/ratis/metrics/):
``MetricRegistryInfo`` (app/component/name 3-level naming),
``RatisMetricRegistry`` (counter/gauge/timer accessors),
``Timekeeper`` (timer contexts), and the ``MetricRegistries`` process-global
singleton that creates/removes registries and serves reporters (the
reference discovers the implementation via ServiceLoader,
MetricRegistries.java; here the in-process implementation is direct).

TPU-first note: metrics are plain host-side Python — they observe the
asyncio runtime and kernel-dispatch cadence, never device code.  Timers
keep a bounded reservoir so p50/p99 snapshots are O(1) memory, matching
what the dropwizard histogram gives the reference.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Dict, Iterable, Optional


@dataclasses.dataclass(frozen=True)
class MetricRegistryInfo:
    """3-level metric naming (MetricRegistryInfo.java): app.component.name."""

    prefix: str          # e.g. a group-member id ("s0@group-1234")
    application: str     # "ratis"
    component: str       # "server", "log_worker", "leader_election", ...
    name: str            # metrics class name

    @property
    def full_name(self) -> str:
        return ".".join((self.application, self.component, self.prefix,
                         self.name))


class Counter:
    """Monotonic (but resettable) counter.

    Lock-free on purpose: hot paths (append handling, apply loop) inc these
    thousands of times per second from the event loop, and profiling at
    1024 groups showed a per-inc Lock costing ~5% of total runtime.  A
    bare ``+=`` is GIL-coherent; the worst cross-thread race loses an
    occasional increment, which is an accepted trade for observability
    counters (the reference's dropwizard LongAdder makes the same
    accuracy-for-speed trade in reverse)."""

    __slots__ = ("_value",)

    def __init__(self) -> None:
        self._value = 0

    def inc(self, n: int = 1) -> None:
        self._value += n

    def dec(self, n: int = 1) -> None:
        self._value -= n

    @property
    def count(self) -> int:
        return self._value


class TallyCounter(Counter):
    """A counter that also counts into ``total``: a server's sum of its
    divisions' counts, read without walking them."""

    __slots__ = ("_total",)

    def __init__(self, total: Counter) -> None:
        super().__init__()
        self._total = total

    def inc(self, n: int = 1) -> None:
        self._value += n
        self._total._value += n

    def dec(self, n: int = 1) -> None:
        self._value -= n
        self._total._value -= n


def labeled(name: str, **labels: str) -> str:
    """Canonical registry name for a labeled metric: ``name{k="v",...}``
    with keys sorted.  The Prometheus renderer splits this form back into
    base name + label set and merges the registry's ``member`` label in."""
    if not labels:
        return name
    inner = ",".join(f'{k}="{labels[k]}"' for k in sorted(labels))
    return f"{name}{{{inner}}}"


class Timekeeper:
    """Timer with count/total and a bounded reservoir for percentiles
    (reference Timekeeper + dropwizard Timer)."""

    RESERVOIR = 512

    def __init__(self) -> None:
        self._count = 0
        self._total_s = 0.0
        self._max_s = 0.0
        self._samples: list[float] = []

    class Context:
        __slots__ = ("_timer", "_start")

        def __init__(self, timer: "Timekeeper") -> None:
            self._timer = timer
            self._start = time.perf_counter()

        def stop(self) -> float:
            elapsed = time.perf_counter() - self._start
            self._timer.update(elapsed)
            return elapsed

        def __enter__(self) -> "Timekeeper.Context":
            return self

        def __exit__(self, *exc) -> None:
            self.stop()

    def time(self) -> "Timekeeper.Context":
        return Timekeeper.Context(self)

    def update(self, elapsed_s: float) -> None:
        # Lock-free for the same reason as Counter.inc (hot-path cost);
        # cross-thread races at worst skew the bounded reservoir slightly.
        self._count += 1
        self._total_s += elapsed_s
        if elapsed_s > self._max_s:
            self._max_s = elapsed_s
        if len(self._samples) < self.RESERVOIR:
            self._samples.append(elapsed_s)
        else:  # Vitter's algorithm R — uniform over the stream
            import random
            j = random.randrange(self._count)
            if j < self.RESERVOIR:
                self._samples[j] = elapsed_s

    @property
    def count(self) -> int:
        return self._count

    @property
    def mean_s(self) -> float:
        return self._total_s / self._count if self._count else 0.0

    def percentile_s(self, q: float) -> float:
        samples = list(self._samples)  # snapshot vs concurrent updates
        if not samples:
            return 0.0
        ordered = sorted(samples)
        idx = min(len(ordered) - 1, int(q * len(ordered)))
        return ordered[idx]

    def snapshot(self) -> dict:
        return {"count": self._count, "mean_s": self.mean_s,
                "max_s": self._max_s, "p50_s": self.percentile_s(0.50),
                "p99_s": self.percentile_s(0.99)}


class Histogram(Timekeeper):
    """Value histogram over the same bounded reservoir: batch sizes, queue
    depths — dimensionless quantities, not durations (the snapshot keys
    carry no ``_s`` suffix and the Prometheus renderer emits no unit)."""

    def snapshot(self) -> dict:
        return {"count": self._count, "mean": self.mean_s,
                "max": self._max_s, "p50": self.percentile_s(0.50),
                "p99": self.percentile_s(0.99)}


class RatisMetricRegistry:
    """One named registry of counters/gauges/timers/histograms
    (RatisMetricRegistry.java / impl/RatisMetricRegistryImpl.java)."""

    def __init__(self, info: MetricRegistryInfo) -> None:
        self.info = info
        self._counters: Dict[str, Counter] = {}
        self._timers: Dict[str, Timekeeper] = {}
        self._histograms: Dict[str, Histogram] = {}
        self._gauges: Dict[str, Callable[[], object]] = {}
        self._lock = threading.Lock()

    def counter(self, name: str, total: Optional[Counter] = None
                ) -> Counter:
        """``total``: a counter this one also counts into."""
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = (Counter() if total is None
                                            else TallyCounter(total))
            return c

    def timer(self, name: str) -> Timekeeper:
        with self._lock:
            return self._timers.setdefault(name, Timekeeper())

    def histogram(self, name: str) -> Histogram:
        with self._lock:
            return self._histograms.setdefault(name, Histogram())

    def gauge(self, name: str, supplier: Callable[[], object]) -> None:
        with self._lock:
            self._gauges[name] = supplier

    def remove(self, name: str) -> bool:
        with self._lock:
            return (self._counters.pop(name, None) is not None
                    or self._timers.pop(name, None) is not None
                    or self._histograms.pop(name, None) is not None
                    or self._gauges.pop(name, None) is not None)

    def metric_names(self) -> list[str]:
        with self._lock:
            return sorted([*self._counters, *self._timers,
                           *self._histograms, *self._gauges])

    def snapshot(self) -> dict:
        """Flat {metric: value} view (console/JMX reporter analog)."""
        return {name: value for name, (_kind, value)
                in self.typed_snapshot().items()}

    def typed_snapshot(self) -> dict:
        """{metric: (kind, value)} where kind is one of counter/timer/
        histogram/gauge — the Prometheus renderer needs the kind (counters
        get the ``_total`` suffix, histogram quantiles carry no unit)."""
        out: dict = {}
        with self._lock:
            counters = dict(self._counters)
            timers = dict(self._timers)
            histograms = dict(self._histograms)
            gauges = dict(self._gauges)
        for name, c in counters.items():
            out[name] = ("counter", c.count)
        for name, t in timers.items():
            out[name] = ("timer", t.snapshot())
        for name, h in histograms.items():
            out[name] = ("histogram", h.snapshot())
        for name, g in gauges.items():
            try:
                out[name] = ("gauge", g())
            except Exception as e:  # gauge suppliers must never break reports
                out[name] = ("gauge", f"<error: {e}>")
        return out


class MetricRegistries:
    """Process-global registry-of-registries (MetricRegistries.global())."""

    _global: Optional["MetricRegistries"] = None
    _global_lock = threading.Lock()

    def __init__(self) -> None:
        self._registries: Dict[MetricRegistryInfo, RatisMetricRegistry] = {}
        self._lock = threading.Lock()
        self._reporters: list[Callable[[RatisMetricRegistry], None]] = []
        self._stop_reporters: list[Callable[[RatisMetricRegistry], None]] = []

    @classmethod
    def global_registries(cls) -> "MetricRegistries":
        with cls._global_lock:
            if cls._global is None:
                cls._global = MetricRegistries()
            return cls._global

    def create(self, info: MetricRegistryInfo) -> RatisMetricRegistry:
        with self._lock:
            reg = self._registries.get(info)
            if reg is None:
                reg = RatisMetricRegistry(info)
                self._registries[info] = reg
                for reporter in self._reporters:
                    reporter(reg)
            return reg

    def remove(self, info: MetricRegistryInfo) -> bool:
        with self._lock:
            reg = self._registries.pop(info, None)
            if reg is not None:
                for stop in self._stop_reporters:
                    stop(reg)
            return reg is not None

    def get(self, info: MetricRegistryInfo) -> Optional[RatisMetricRegistry]:
        with self._lock:
            return self._registries.get(info)

    def get_registry_infos(self) -> Iterable[MetricRegistryInfo]:
        with self._lock:
            return list(self._registries)

    def add_reporter_registration(
            self, reporter: Callable[[RatisMetricRegistry], None],
            stop_reporter: Callable[[RatisMetricRegistry], None]) -> None:
        with self._lock:
            self._reporters.append(reporter)
            self._stop_reporters.append(stop_reporter)

    def clear(self) -> None:
        with self._lock:
            self._registries.clear()

    def snapshot_all(self) -> dict:
        with self._lock:
            regs = dict(self._registries)
        return {info.full_name: reg.snapshot() for info, reg in regs.items()}
