"""End-to-end inference telemetry (DESIGN.md §telemetry).

Three layers, one rule — **observability must be data, not structure**:

* :mod:`repro.telemetry.trace` — the engine's phase spans
  (``trace.span``: always a ``jax.profiler.TraceAnnotation`` on the
  profiler's clock) and the host-side span/event recorder they also
  fill (bounded ring buffer, simulated- or wall-clock) with
  Chrome-trace/Perfetto export; instruments the request lifecycle
  queue admit → pack decision → dispatch → device step(s) →
  materialization → finish plus compile events.
* :mod:`repro.telemetry.taps` — on-device scalar taps threaded as extra
  **data** outputs through ``make_packed_step_fn`` (per-request eps
  norm, realized cache replay drift ``‖h_fresh − h_replay‖``, the
  kernel ledger's attention block counts). No host callbacks, no
  ``debug.print``, no recompiles: DCE of the tap outputs recovers the
  untapped jaxpr bit-for-bit (asserted in ``analysis/jaxpr_audit.py``).
* :mod:`repro.telemetry.export` — the ``[metrics]`` structured log
  line over ``ServingMetrics`` summaries and tap aggregates
  (duck-typed: this module never imports the engine).

``Telemetry`` bundles a recorder + tap aggregator for the serving
engine; device values cross to the host only inside
``TapAggregator.aggregate()`` / trace export — never on the dispatch
path.
"""
from repro.telemetry.taps import TapAggregator, TapSample  # noqa: F401
from repro.telemetry.trace import SpanRecorder, TraceEvent  # noqa: F401


class Telemetry:
    """One serving session's telemetry bundle.

    ``taps=False`` keeps the engine on the untapped step family (spans
    only); ``taps=True`` routes dispatches through the tapped runners —
    same latents bit-for-bit, plus per-dispatch tap samples.

    ``profile=True`` adds the compiled-cost registry + per-request
    attribution ledger (DESIGN.md §profiling): the engine then measures
    dispatch wall-clock (one ``block_until_ready`` per dispatch —
    measurement overhead, latents and jaxprs unchanged) and splits it
    across requests with exact conservation. ``watchdog`` /
    ``postmortem_dir`` wire the SLO detector bank and crash flight
    recorder; passing only ``postmortem_dir`` builds a default-config
    watchdog.
    """

    def __init__(self, clock=None, taps: bool = False,
                 max_events: int = 65536, max_samples: int = 4096,
                 profile: bool = False, watchdog=None,
                 postmortem_dir=None):
        self.recorder = SpanRecorder(clock=clock, max_events=max_events)
        self.taps = TapAggregator(max_samples=max_samples)
        self.taps_enabled = bool(taps)
        self.profile = None
        self.attribution = None
        if profile:
            # lazy: profile.py imports jax + model costing; the plain
            # spans+taps bundle must stay importable without them
            from repro.telemetry.attribution import AttributionLedger
            from repro.telemetry.profile import CompiledCostRegistry
            self.profile = CompiledCostRegistry()
            self.attribution = AttributionLedger()
        if watchdog is None and postmortem_dir is not None:
            from repro.telemetry.watchdog import Watchdog
            watchdog = Watchdog()
        self.watchdog = watchdog
        if self.watchdog is not None:
            self.watchdog.recorder = self.recorder
            if postmortem_dir is not None:
                self.watchdog.postmortem_dir = postmortem_dir

    @property
    def profiling(self) -> bool:
        return self.profile is not None

    def bind_clock(self, clock) -> None:
        """Adopt the engine's clock (simulated or wall) if the recorder
        was built before the engine existed."""
        self.recorder.clock = clock

    def snapshot(self) -> dict:
        """JSON-friendly view: tap aggregates + recorder counters."""
        out = {"taps_enabled": self.taps_enabled,
               "tap_aggregates": self.taps.aggregate(),
               "events_recorded": self.recorder.events_recorded,
               "events_dropped": self.recorder.events_dropped,
               "span_occupancy": self.recorder.occupancy}
        if self.attribution is not None:
            out["attribution"] = self.attribution.snapshot()
        if self.watchdog is not None:
            out["alerts"] = [a.as_dict() for a in self.watchdog.alerts]
        return out
