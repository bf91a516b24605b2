"""Metrics registry: one named schema over every runtime counter.

The aggregation half of the observability layer (docs/design.md §15).
Before it, runtime visibility lived in per-component ``stats()`` dicts
(``CsrFeed``, ``ColdFetchPipeline``, ``DynamicBatcher``,
``ServingEngine``) plus inline ``perf_counter`` timings — four
disjoint vocabularies nobody could join.  This module holds:

- the process-global registry: counters / gauges / fixed-bucket
  histograms under the documented ``REGISTERED_METRICS`` schema,
  updated through ``inc``/``set_gauge``/``observe`` (each a single
  flag check when the registry is disabled — the default), snapshot
  through ``snapshot()`` / ``prometheus_text()`` /
  ``journal_snapshot()`` (the existing ``resilience.journal`` sink,
  event kind ``metrics_snapshot``);
- the shared LOCAL primitives the components' ``stats()`` are built
  on (``OverlapStat``, ``LatencyWindow``, ``Histogram``): the three
  hand-rolled blocked-time/overlap implementations (csr_feed,
  coldtier, serving batcher) now share one accounting, with every
  pre-existing ``stats()`` key bit-compatible (pinned by the existing
  tests).  Local primitives are always live — they ARE the component
  stats — while the global registry mirror engages only when enabled.

Metric-name discipline: runtime call sites must use names from
``REGISTERED_METRICS`` (typed in ``METRIC_TYPES``); ``inc`` & co
raise on an unknown name so a typo fails the first test that crosses
it, and tests/test_obs.py source-scans every literal.
"""

from __future__ import annotations

import hashlib
import json
import threading

from typing import Any, Dict, Iterable, List, Optional, Tuple

import numpy as np

from distributed_embeddings_tpu.utils import resilience

# The complete metric schema: name -> instrument type.  ``*_ms`` names
# are millisecond histograms over DEFAULT_MS_BUCKETS; counters are
# monotone totals; gauges are last-written values.  Add a name HERE in
# the same change that introduces the call site (docs/design.md §15).
METRIC_TYPES: Dict[str, str] = {
    # training driver (parallel/grad.py fit)
    'train.steps': 'counter',
    'train.anomalies': 'counter',
    'train.rollbacks': 'counter',
    'train.loss': 'gauge',
    'train.sync_ms': 'histogram',
    # a packed token batch (models/hybrid_ssm.count_batch)
    'train.tokens': 'counter',
    'train.documents': 'counter',
    'train.loss_positions': 'counter',
    # which path each call of models/hybrid_ssm.blocked_attention took,
    # counted as a step is traced
    'attention.kernel_layers': 'counter',
    'attention.blocked_layers': 'counter',
    # gated short-convolution layers a compiled step holds, one a call
    # at trace time (models/moe_lm.short_conv)
    'mixer.short_conv_layers': 'counter',
    # routed blocks whose output the backward pass keeps for the norm
    # after it, one a block at trace time (models/moe_lm.layer)
    'moe.kept_outputs': 'counter',
    # a routed layer's load (models/moe_lm.record_routing_stats)
    'moe.assignments_held': 'gauge',
    'moe.load_max_over_mean': 'gauge',
    'moe.overflow_rows': 'gauge',
    # host CSR feed (parallel/csr_feed.py)
    'feed.batches': 'counter',
    'feed.skipped': 'counter',
    'feed.io_retries': 'counter',
    'feed.respawns': 'counter',
    'feed.queue_dropped': 'counter',
    'feed.queue_depth': 'gauge',
    'feed.build_ms': 'histogram',
    'feed.blocked_ms': 'histogram',
    # cold tier (parallel/coldtier.py)
    'coldtier.batches': 'counter',
    'coldtier.fetch_rows': 'counter',
    'coldtier.prepass_ms': 'histogram',
    'coldtier.blocked_ms': 'histogram',
    # state-integrity auditor (parallel/audit.py)
    'audit.calls': 'counter',
    'audit.findings': 'counter',
    'audit.call_ms': 'histogram',
    # checkpoints (parallel/checkpoint.py)
    'ckpt.saves': 'counter',
    'ckpt.restores': 'counter',
    'ckpt.save_ms': 'histogram',
    'ckpt.restore_ms': 'histogram',
    # serving (serving/batcher.py + serving/engine.py)
    'serve.submitted': 'counter',
    'serve.completed': 'counter',
    'serve.batches': 'counter',
    'serve.batch_fill': 'gauge',
    'serve.latency_ms': 'histogram',
    # admission -> dispatch, the 'serve/enqueue' span's own measurement
    'serve.queue_wait_ms': 'histogram',
    # pipelined dispatch stages (design §16)
    'serve.merge_ms': 'histogram',
    'serve.demux_ms': 'histogram',
    # SLO-aware overload layer (serving/batcher.py + serving/pool.py,
    # design §23): per-class latency histograms, shed/degraded/failover
    # counters and the pool's routing-depth gauge
    'serve.latency_high_ms': 'histogram',
    'serve.latency_low_ms': 'histogram',
    'serve.shed': 'counter',
    'serve.degraded': 'counter',
    'serve.failover': 'counter',
    'serve.failover_ms': 'histogram',
    'serve.pool_depth': 'gauge',
    'engine.lookups': 'counter',
    'engine.samples': 'counter',
    # bucket-ladder padding accounting (design §16): rows the compiled
    # rung launched vs the sentinel rows among them
    'engine.rows_launched': 'counter',
    'engine.pad_rows': 'counter',
    'engine.lookup_ms': 'histogram',
    # device-time attribution (obs/devprof.py, design §19)
    'devprof.runs': 'counter',
    'devprof.phase_ms': 'histogram',
    # per-device exchange imbalance (parallel/hotcache.py, design §19):
    # skew gauges over the per-source-device exchanged-row counters
    'exchange.rows_max': 'gauge',
    'exchange.rows_mean': 'gauge',
    # hierarchical DCNxICI exchange (design §20): rows crossing each
    # link class per step, and the within-slice dedup leverage —
    # ici_rows / dcn_rows (>1 whenever slices hold cross-chip
    # duplicates; ==1 when every id is unique within its slice)
    'exchange.dcn_rows': 'gauge',
    'exchange.ici_rows': 'gauge',
    'exchange.dcn_dedup_ratio': 'gauge',
}

REGISTERED_METRICS = frozenset(METRIC_TYPES)

# Component ``stats()`` key schema (docs/design.md §17): every string
# key a runtime component's ``stats()`` method emits must be registered
# here — the same rename-kills-every-consumer hazard as the metric
# names, now under the detlint registry-schema pass instead of nothing.
# Add the key HERE in the same change that introduces it.
REGISTERED_STATS_KEYS = frozenset({
    # shared overlap accounting (CsrFeed / ColdFetchPipeline / batcher)
    'batches', 'build_ms', 'blocked_ms', 'overlap_pct',
    # CsrFeed (parallel/csr_feed.py)
    'builder', 'skipped', 'fast_forwarded', 'io_retries', 'respawns',
    'queue_depth', 'queue_dropped',
    # DynamicBatcher (serving/batcher.py)
    'submitted', 'completed', 'max_batch', 'max_delay_ms', 'batch_fill',
    'p50_ms', 'p99_ms', 'queue_wait_p50_ms', 'queue_wait_p99_ms',
    'bucket_ladder', 'buckets', 'bucket_launches',
    'rows_launched', 'pad_rows', 'pad_waste_pct', 'pipeline',
    'merge_demux_ms', 'csr_feed',
    # SLO-aware admission + replica pool (serving/batcher.py,
    # serving/pool.py; design §23): the per-class ledger, the
    # per-reason shed block and the pool's failover/degraded counters
    'p999_ms', 'classes', 'shed', 'admitted', 'served', 'depth',
    'low_queue_depth', 'high', 'low', 'deadline', 'queue_full',
    'closed', 'replicas', 'live_replicas', 'quarantined', 'failovers',
    'retried', 'degraded', 'degraded_served', 'degraded_enters',
    'degraded_exits', 'degraded_drop_pct', 'watermark_high',
    'watermark_low',
    # ServingEngine (serving/engine.py)
    'batches_served', 'samples_served', 'batch_size', 'world_size',
    'hot_cache', 'cold_tier', 'table_dtype', 'fused_exchange',
    'wire_dtype',
})

# Bench-artifact key schema: the keys tests/test_bench_artifact.py pins
# against the journaled artifact.  The detlint registry-schema pass
# asserts every key here is still PRODUCED by a string literal
# somewhere in the runtime sources, so a silent producer rename breaks
# tier-1 at the registry instead of at a stale dashboard.
REGISTERED_ARTIFACT_KEYS = frozenset({
    # core artifact line (bench.py)
    'metric', 'value', 'unit', 'vs_baseline', 'comparable', 'warmup_s',
    'window_ms', 'loadavg', 'sha',
    # hot-cache counters (parallel/hotcache.py)
    'alltoall_rows_sent', 'alltoall_rows_sent_off', 'unique_cold_rows',
    'hot_hit_rate', 'cold_occurrence_fraction', 'scatter_rows_per_step',
    'scatter_rows_per_step_off', 'total_id_occurrences',
    # chunked-exchange block (parallel/overlap.py)
    'a2a_overlap_pct', 'overlap_chunks', 'a2a_group_chunks',
    'a2a_off_ms', 'a2a_on_ms', 'a2a_exchange_ms',
    # quantized storage + cold tier (parallel/quantization.py, coldtier.py)
    'table_bytes_per_row', 'table_scale_bytes_per_row',
    'table_total_bytes_per_row', 'table_payload_bytes',
    'table_scale_bytes', 'table_rows',
    'cold_tier_fetch_rows', 'cold_tier_fetch_bytes',
    'cold_tier_fetch_scale_bytes', 'cold_tier_fetch_rows_per_group',
    'cold_tier_row_bytes_per_group', 'cold_tier_resident_bytes',
    'cold_tier_host_bytes',
    # serving three-arm A/B (serving/bench.py)
    'serve_p50_ms', 'serve_p99_ms', 'serve_qps', 'serve_batches',
    'serve_batch_fill', 'serve_requests', 'serve_batch',
    'serve_max_delay_ms', 'serve_concurrency', 'serve_buckets',
    'serve_bucket_launches', 'serve_rows_launched', 'serve_pad_rows',
    'serve_pad_waste_pct', 'serve_pipeline_overlap_pct',
    'serve_pipeline_merge_demux_ms', 'serve_pipeline_blocked_ms',
    'serve_mono_p50_ms', 'serve_mono_p99_ms', 'serve_mono_qps',
    'serve_mono_batches', 'serve_mono_batch_fill',
    'serve_mono_pad_waste_pct', 'serve_nobatch_p50_ms',
    'serve_nobatch_p99_ms', 'serve_nobatch_qps',
    'serve_nobatch_pad_waste_pct', 'serve_p999_ms',
    # overload arm (serving/bench.py measure_overload; design §23):
    # per-class latency tails, shed accounting, degraded-mode serves
    # and the failover drill counters the perf sentinel guards
    'serve_over_requests', 'serve_over_served', 'serve_over_shed',
    'serve_over_shed_rate', 'serve_over_offered_qps', 'serve_over_qps',
    'serve_over_deadline_ms', 'serve_over_priority_mix',
    'serve_over_replicas', 'serve_over_high_p50_ms',
    'serve_over_high_p99_ms', 'serve_over_high_p999_ms',
    'serve_over_low_p50_ms', 'serve_over_low_p99_ms',
    'serve_over_low_p999_ms', 'serve_over_high_shed',
    'serve_over_low_shed', 'serve_over_shed_deadline',
    'serve_over_shed_queue_full', 'serve_over_degraded_served',
    'serve_over_degraded_enters', 'serve_over_degraded_exits',
    'serve_over_failovers', 'serve_over_quarantined',
    # observability block (bench.obs_block)
    'obs_trace', 'obs_trace_path', 'obs_trace_events', 'obs_off_ms',
    'obs_on_ms', 'obs_window_delta_pct', 'obs_metrics_digest',
    'obs_step_call_us', 'obs_overhead_pct',
    # static-analysis gate counts (bench.lint_block; design §17)
    'lint_findings', 'lint_waivers',
    # IR-analysis gate counts (bench.graphlint_block; design §18)
    'graphlint_findings', 'graphlint_donation_ok',
    'graphlint_retraces', 'graphlint_peak_hbm_bytes',
    # cross-rank protocol gate counts (bench.commlint_block; design
    # §22): unwaived findings (0 on a healthy tree), the active waived
    # true-positive count, and how many program schedules the emission
    # pass PREDICTED from the plans — a drop below the catalog size
    # means a plan/ledger divergence rode in under an allowance
    'commlint_findings', 'commlint_waivers',
    'commlint_schedules_predicted',
    # fused-exchange counters (bench.graphlint_block, design §21):
    # collective counts of the fused vs per-group twin programs plus
    # the fused programs' summed on-wire payload, all counted from the
    # graphlint schedule; the traced leg/wire views ride alongside
    # (parallel/hotcache.py fused_leg_bytes, coldtier.py
    # cold_exchange_leg_bytes)
    'exchange_collectives_fwd', 'exchange_collectives_fwd_pergroup',
    'exchange_collectives_bwd', 'exchange_collectives_bwd_pergroup',
    'fused_exchange_bytes', 'fused_leg_bytes',
    'cold_exchange_leg_bytes',
    # wire-dtype compression counters (parallel/hotcache.py,
    # coldtier.py; design §24): the traced schedule's on-wire totals,
    # the compute-dtype counterfactual, their ratio, and the per-leg
    # dtype ledgers that prove which legs narrowed
    'wire_bytes', 'wire_payload_bytes', 'wire_compression_ratio',
    'wire_leg_dtypes', 'cold_exchange_leg_dtypes', 'wire_dtype',
    # off/bf16/int8-passthrough wire A/B (bench.py --wire_ab, design
    # §24): measured wire bytes over the codec-targeted row legs per
    # arm, the off/on ratios the acceptance bars gate, the forward
    # parity drift per arm (int8 passthrough must be 0.0) and the
    # never-fatal error tag
    'wire_ab_bytes_off', 'wire_ab_bytes_bf16', 'wire_ab_bytes_int8',
    'wire_ab_ratio_bf16', 'wire_ab_ratio_int8', 'wire_ab_drift_bf16',
    'wire_ab_drift_int8', 'wire_ab_error',
    # artifact schema + host-pressure gauges (bench.py; design §19 —
    # the perf sentinel's comparability/noise inputs)
    'schema_version', 'available_mem_mb',
    # per-device imbalance accounting (parallel/hotcache.py, design §19)
    'alltoall_rows_sent_per_device', 'alltoall_rows_sent_off_per_device',
    'hot_hit_rate_per_device', 'total_id_occurrences_per_device',
    'scatter_rows_per_device', 'exchange_rows_max', 'exchange_rows_mean',
    'hottest_shard',
    # hierarchical DCNxICI exchange (parallel/hotcache.py, design §20):
    # per-link row counts, the flat-exchange counterfactual, the dedup
    # leverage, per-slice breakdowns, and the mesh shape tag that keeps
    # perf_sentinel comparisons like-for-like across topologies
    'dcn_rows', 'dcn_rows_off', 'ici_rows', 'dcn_dedup_ratio',
    'dcn_rows_per_slice', 'dcn_rows_off_per_slice', 'mesh_shape',
    # the flat-vs-hierarchical bench A/B arm (bench.py, design §20)
    'dcn_sharding', 'dcn_ab_flat_ms', 'dcn_ab_hier_ms',
    'dcn_ab_mesh_shape', 'dcn_ab_error',
    # device-time attribution block (obs/devprof.py, design §19)
    'devprof_phase_ms', 'devprof_step_ms', 'devprof_coverage_pct',
    'devprof_cost', 'devprof_cost_ok', 'devprof_serve_rung_ms',
    # dcn/ici sub-lanes of the exchange phases (design §20)
    'devprof_dcn_lane_ms',
})

# ~x2-2.5 geometric ladder, 10 us .. 60 s: percentile estimates from
# bucket counts are bounded by one bucket's width (the resolution
# contract tests/test_obs.py pins against exact NumPy percentiles).
DEFAULT_MS_BUCKETS: Tuple[float, ...] = (
    0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0, 2.0, 5.0, 10.0, 20.0, 50.0,
    100.0, 200.0, 500.0, 1000.0, 2000.0, 5000.0, 10000.0, 30000.0,
    60000.0)


class Histogram:
  """Fixed-bucket histogram: ``buckets`` are ascending upper bounds
  (one overflow bucket rides implicitly).  Percentiles resolve to the
  containing bucket under the inverted-CDF rank convention, so the
  exact sample percentile always lies inside ``percentile_bounds``."""

  __slots__ = ('buckets', 'counts', 'count', 'sum', '_min', '_max')

  def __init__(self, buckets: Iterable[float] = DEFAULT_MS_BUCKETS):
    self.buckets = tuple(float(b) for b in buckets)
    if list(self.buckets) != sorted(set(self.buckets)):
      raise ValueError('histogram buckets must be strictly ascending')
    self.counts = [0] * (len(self.buckets) + 1)
    self.count = 0
    self.sum = 0.0
    self._min = None
    self._max = None

  def observe(self, value: float):
    v = float(value)
    i = int(np.searchsorted(self.buckets, v, side='left'))
    self.counts[i] += 1
    self.count += 1
    self.sum += v
    self._min = v if self._min is None else min(self._min, v)
    self._max = v if self._max is None else max(self._max, v)

  def percentile_bounds(self, p: float) -> Optional[Tuple[float, float]]:
    """(lo, hi) of the bucket holding the p-th percentile (inverted-CDF
    rank), tightened by the observed min/max; None when empty."""
    if not self.count:
      return None
    rank = min(self.count, max(1, int(np.ceil(p / 100.0 * self.count))))
    cum = 0
    for i, c in enumerate(self.counts):
      cum += c
      if cum >= rank:
        lo = self.buckets[i - 1] if i > 0 else 0.0
        hi = self.buckets[i] if i < len(self.buckets) else self._max
        return (max(lo, self._min), min(hi, self._max))
    return (self._min, self._max)  # unreachable; defensive

  def percentile(self, p: float) -> Optional[float]:
    """Point estimate: the containing bucket's upper bound (clamped to
    observed extremes) — error bounded by that bucket's width."""
    b = self.percentile_bounds(p)
    return None if b is None else b[1]

  def to_dict(self) -> Dict[str, Any]:
    return {
        'count': self.count,
        'sum': round(self.sum, 6),
        'min': self._min,
        'max': self._max,
        'p50': self.percentile(50),
        'p99': self.percentile(99),
        'buckets': [[le, c] for le, c in zip(self.buckets, self.counts)
                    if c] + ([['+Inf', self.counts[-1]]]
                             if self.counts[-1] else []),
    }

  def reset(self):
    self.counts = [0] * (len(self.buckets) + 1)
    self.count = 0
    self.sum = 0.0
    self._min = None
    self._max = None


class OverlapStat:
  """The ONE blocked-time/overlap accounting (previously hand-rolled
  three times): ``build_ms`` is producer work wall, ``blocked_ms`` the
  consumer's wait for it — i.e. producer time NOT hidden behind the
  consumer's own work; ``overlap_frac`` is the hidden share."""

  __slots__ = ('batches', 'build_ms', 'blocked_ms')

  def __init__(self):
    self.reset()

  def reset(self):
    self.batches = 0
    self.build_ms = 0.0
    self.blocked_ms = 0.0

  def add_build(self, ms: float):
    self.build_ms += ms

  def add_blocked(self, ms: float):
    self.blocked_ms += ms

  def count_batch(self, n: int = 1):
    self.batches += n

  def overlap_frac(self) -> float:
    """Hidden share in [0, 1]; 0.0 with no recorded build."""
    if self.build_ms <= 0:
      return 0.0
    return min(1.0, max(0.0, 1.0 - self.blocked_ms / self.build_ms))

  def overlap_pct(self) -> Optional[float]:
    """Hidden share as a percentage; None with no recorded build (the
    ``CsrFeed.stats()`` convention)."""
    if self.build_ms <= 0:
      return None
    return 100.0 * max(0.0, self.build_ms - self.blocked_ms) \
        / self.build_ms


class LatencyWindow:
  """Bounded exact-latency recorder (the serving batcher's accounting):
  keeps the most recent latencies, trimming ``cap`` down to ``keep``,
  and answers percentiles with exact ``np.percentile`` over the
  window."""

  __slots__ = ('cap', 'keep', '_values')

  def __init__(self, cap: int = 65536, keep: int = 32768):
    self.cap = int(cap)
    self.keep = int(keep)
    self._values: List[float] = []

  def extend(self, values: Iterable[float]):
    self._values.extend(values)
    if len(self._values) > self.cap:
      del self._values[:-self.keep]

  def record(self, value: float):
    self.extend((value,))

  def __len__(self):
    return len(self._values)

  def values(self) -> np.ndarray:
    return np.asarray(self._values, np.float64)

  def percentile(self, p: float) -> Optional[float]:
    if not self._values:
      return None
    return float(np.percentile(self.values(), p))


# --------------------------------------------------------------------------
# process-global registry
# --------------------------------------------------------------------------

_enabled = False
_lock = threading.Lock()
_counters: Dict[str, float] = {}
_gauges: Dict[str, float] = {}
_histograms: Dict[str, Histogram] = {}


def _check(name: str, kind: str):
  t = METRIC_TYPES.get(name)
  if t is None:
    raise KeyError(
        f'unregistered metric {name!r}: add it to '
        'obs.metrics.METRIC_TYPES in the same change that introduces '
        'the call site (docs/design.md §15)')
  if t != kind:
    raise TypeError(f'metric {name!r} is a {t}, not a {kind}')


def enabled() -> bool:
  return _enabled


def enable():
  global _enabled
  _enabled = True


def disable():
  global _enabled
  _enabled = False


def reset():
  """Drop every instrument's state (flag untouched)."""
  with _lock:
    _counters.clear()
    _gauges.clear()
    _histograms.clear()


def inc(name: str, value: float = 1.0):
  if not _enabled:
    return
  _check(name, 'counter')
  with _lock:
    _counters[name] = _counters.get(name, 0.0) + value


def set_gauge(name: str, value: float):
  if not _enabled:
    return
  _check(name, 'gauge')
  with _lock:
    _gauges[name] = float(value)


def observe(name: str, value: float):
  if not _enabled:
    return
  _check(name, 'histogram')
  with _lock:
    h = _histograms.get(name)
    if h is None:
      h = _histograms[name] = Histogram()
    h.observe(value)


def snapshot() -> Dict[str, Any]:
  """One JSON-ready dict of everything recorded: counters/gauges map to
  their value, histograms to their summary dict."""
  with _lock:
    out: Dict[str, Any] = {}
    out.update({k: v for k, v in _counters.items()})
    out.update({k: v for k, v in _gauges.items()})
    out.update({k: h.to_dict() for k, h in _histograms.items()})
  return {k: out[k] for k in sorted(out)}


def snapshot_digest() -> str:
  """sha256 over the canonical-JSON snapshot — the artifact-sized
  fingerprint bench journals (two runs recording identical values
  digest identically)."""
  blob = json.dumps(snapshot(), sort_keys=True,
                    separators=(',', ':')).encode()
  return hashlib.sha256(blob).hexdigest()


def journal_snapshot(step: Optional[int] = None, **fields):
  """Journal one ``metrics_snapshot`` event through the existing
  resilience sink; a no-op (ZERO journal writes) when the registry is
  disabled."""
  if not _enabled:
    return None
  return resilience.journal('metrics_snapshot', step=step,
                            metrics=snapshot(), **fields)


def _prom_name(name: str) -> str:
  return 'det_' + name.replace('.', '_').replace('/', '_')


def prometheus_text() -> str:
  """The registry in Prometheus text exposition format (counters,
  gauges, and cumulative-bucket histograms)."""
  lines: List[str] = []
  with _lock:
    for k in sorted(_counters):
      n = _prom_name(k)
      lines += [f'# TYPE {n} counter', f'{n} {_counters[k]:g}']
    for k in sorted(_gauges):
      n = _prom_name(k)
      lines += [f'# TYPE {n} gauge', f'{n} {_gauges[k]:g}']
    for k in sorted(_histograms):
      h = _histograms[k]
      n = _prom_name(k)
      lines.append(f'# TYPE {n} histogram')
      cum = 0
      for le, c in zip(h.buckets, h.counts):
        cum += c
        lines.append(f'{n}_bucket{{le="{le:g}"}} {cum}')
      lines.append(f'{n}_bucket{{le="+Inf"}} {h.count}')
      lines.append(f'{n}_sum {h.sum:g}')
      lines.append(f'{n}_count {h.count}')
  return '\n'.join(lines) + ('\n' if lines else '')
