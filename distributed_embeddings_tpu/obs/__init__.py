"""Unified observability layer (docs/design.md §15).

One instrumentation contract across training and serving, replacing the
per-component ``stats()`` islands with two shared primitives:

- ``obs.trace``: one vocabulary for where a step's time goes.  HOST
  SPANS thread through the host side of the step — CSR build and feed
  queue wait, cold-tier pre-pass/fetch/write-back, auditor calls,
  checkpoint save/restore, and the per-request
  submit->enqueue->dispatch->demux path in serving — into a
  Chrome-trace-event JSON (Perfetto / ``chrome://tracing``) and, as
  ``TraceAnnotation``s, into the JAX profiler's own trace.  DEVICE
  PHASES (``obs.trace.phase`` = ``jax.named_scope``) name the sections
  of the compiled step — route, exchange, lookup/combine, head, and the
  sparse apply's dedup/read/update/write per table group — on the
  profiler's device ops.  ``obs.trace.profile(dir)`` captures both;
  ``tools/trace_report.py`` turns either file into the per-step phase
  breakdown, ``--profile`` into device ms per phase.
- ``obs.metrics``: a process-global registry of counters / gauges /
  fixed-bucket histograms under ONE documented name schema
  (``REGISTERED_METRICS``), with periodic snapshots journaled through
  the existing ``resilience.journal`` sink and a Prometheus-text
  exporter.

Spans and metrics are DISABLED by default and their disabled path is a
single flag check returning a shared no-op; device phases are metadata
of the compiled program and insert zero operations, so the instrumented
program is program-identical to the uninstrumented one (``bench.py``
journals the measured on/off ``obs_overhead_pct``).

Every span name must come from ``REGISTERED_SPANS``, every phase from
``REGISTERED_PHASES`` and every metric
name from ``REGISTERED_METRICS`` — pinned by the source-scan tests in
``tests/test_obs.py`` (the same schema discipline as
``resilience.REGISTERED_EVENTS``): a typo'd phase name fails tier-1
instead of silently vanishing from every report.
"""

from distributed_embeddings_tpu.obs import devprof, metrics, trace
from distributed_embeddings_tpu.obs.metrics import REGISTERED_METRICS
from distributed_embeddings_tpu.obs.trace import (REGISTERED_PHASES,
                                                  REGISTERED_SPANS)


def enable(trace_path=None):
  """Arm both layers (idempotent): span tracing (buffered; write with
  ``trace.save()``) and the metrics registry."""
  trace.enable(path=trace_path)
  metrics.enable()


def disable():
  """Disarm both layers; buffered state stays readable
  (``trace.events()`` / ``metrics.snapshot()``) until ``reset``."""
  trace.disable()
  metrics.disable()


def reset():
  """Disarm AND drop all buffered events/instrument state (clears any
  ``trace.enable(pin=True)`` re-entrancy pins — reset is the hard
  teardown; plain ``disable()`` respects pins)."""
  trace.disable(force=True)
  trace.clear()
  metrics.disable()
  metrics.reset()


def measure_overhead(step_ms: float, reps: int = 2000) -> dict:
  """DIRECT per-step instrumentation cost, the same honesty rule the
  audit A/B settled on (design §13): a two-arm window subtraction on a
  noisy host launders noise into the claim, so the headline
  ``obs_overhead_pct`` is the measured wall of the per-step obs
  operations (one span + one counter, emitted for real and then
  truncated back out of the buffer) amortized against ``step_ms``.
  Arms both layers for the measurement and restores their prior
  state.  Caveat: with the trace buffer already at its bound the
  measured cost is the (cheaper) drop path, so the reported overhead
  is a lower bound there — the truncate below restores the dropped
  counter either way, so the scaffolding never reads as lost spans."""
  import time as _time
  was_trace, was_metrics = trace.enabled(), metrics.enabled()
  trace.enable()
  metrics.enable()
  n0, d0 = trace.event_count(), trace.dropped()
  t0 = _time.perf_counter()
  for _ in range(reps):
    with trace.span('train/step', step=-1):
      metrics.inc('train.steps')
  per_call_us = (_time.perf_counter() - t0) / reps * 1e6
  # scaffolding events never reach a saved trace (thread labels kept)
  trace.truncate(n0, dropped_to=d0)
  metrics.inc('train.steps', -reps)  # undo the scaffolding counts
  if not was_trace:
    trace.disable()
  if not was_metrics:
    metrics.disable()
  return {
      'obs_step_call_us': round(per_call_us, 3),
      'obs_overhead_pct': round(per_call_us / 1000.0 / step_ms * 100.0,
                                4) if step_ms > 0 else None,
  }


__all__ = ['trace', 'metrics', 'devprof', 'REGISTERED_SPANS',
           'REGISTERED_PHASES',
           'REGISTERED_METRICS', 'enable', 'disable', 'reset']
