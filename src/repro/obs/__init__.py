"""Observability layer: metrics (counters / gauges / log-bucket histograms),
engine phase spans on the profiler's clock plus request-lifecycle tracing
(Chrome/Perfetto trace_event JSON), and a retrace watchdog over the
engines' jitted functions.  Needs nothing beyond JAX; see
docs/OBSERVABILITY.md for the metric catalog and span taxonomy.

``Obs`` is the bundle the engines, the trainer, and launch/serve.py accept:

    obs = Obs(trace=True, routing=True)      # everything on
    eng = ContinuousEngine(cfg, params, obs=obs, ...)
    ...
    print(obs.metrics.render())
    obs.tracer.export("trace.json")          # load in ui.perfetto.dev

Engines construct a default ``Obs()`` when none is injected: metrics stay on
(they are the source of per-tick telemetry and cost ~µs/tick), the tracer is
disabled (its phase spans are then profiler annotations only), and per-tick
routing-stats collection is off (it changes the decode step's jitted
signature, so it is an explicit opt-in).  ``Obs.disabled()`` turns the metrics off too — the benchmark
baseline for the overhead guard."""
from __future__ import annotations

from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.retrace import RetraceWatchdog, jit_cache_size
from repro.obs.trace import Tracer

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "RetraceWatchdog", "jit_cache_size", "Tracer", "Obs",
]


class Obs:
    """Bundle of the three instruments plus collection knobs.

    ``routing=True`` makes the engines' decode step (and the trainer's step
    when asked) return jit-computed per-layer ``RoutingStats`` — per-expert
    token counts, dropped-token fraction, gate entropy, f·P imbalance —
    aggregated host-side each tick/step (paper §3/§5: expert load balance is
    THE MoE-specific signal)."""

    def __init__(self, metrics: MetricsRegistry = None, tracer: Tracer = None,
                 watchdog: RetraceWatchdog = None, routing: bool = False,
                 trace: bool = False):
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer(enabled=trace)
        self.watchdog = watchdog if watchdog is not None else RetraceWatchdog()
        self.routing = routing

    @classmethod
    def disabled(cls) -> "Obs":
        """Everything off — registry included.  Benchmark baseline."""
        return cls(metrics=MetricsRegistry(enabled=False),
                   tracer=Tracer(enabled=False),
                   watchdog=_InertWatchdog(), routing=False)


class _InertWatchdog(RetraceWatchdog):
    """Watchdog that never samples (Obs.disabled baseline)."""

    def register(self, name, fn, aux=False):  # noqa: D102
        pass

    def tick(self) -> int:  # noqa: D102
        return 0
