"""Benchmark: synthetic-model train-step time vs the reference's published
DGX-A100 numbers.

Reference baselines (`/root/reference/examples/benchmarks/synthetic_models/
README.md:69-75`, BASELINE.md): step time in ms at global batch 65536 with
Adagrad, per device count.  This script runs the same model/batch/optimizer
on the available TPU device(s) and prints ONE JSON line; ``vs_baseline`` > 1
means faster than the baseline at the nearest published device count.

It times the backend JAX gives it and refuses to time anything else: no
TPU, no measurement, exit code 1.  Any failure ends the run non-zero — an
exception in ``main()``, the wall-time watchdog, or a secondary phase that
raised (its ``*_error`` key still rides the JSON line, so the line says
which phase; the exit code says the run is not clean).
"""

import argparse
import json
import os
import sys
import time
import traceback

# Artifact schema version (design §19): bumped whenever the artifact's
# key set or semantics change, so tools/perf_sentinel.py and any other
# longitudinal consumer can tell an old-schema line from a missing key.
# v2 adds schema_version itself, available_mem_mb, the per-device
# imbalance counters and the devprof block.
SCHEMA_VERSION = 2

# Published step times, ms, by model -> device count
# (synthetic_models/README.md:69-75).
BASELINES_MS = {
    'tiny': {1: 24.433, 8: 5.537, 16: 4.867},
    'small': {1: 67.355, 8: 17.203, 16: 12.461, 32: 11.839},
    'medium': {8: 63.393, 16: 46.636, 32: 37.732, 128: 27.329},
    'large': {32: 67.57, 128: 37.934},
    'jumbo': {128: 124.3},
    'colossal': {},
    'criteo': {},
}


def obs_block(step_ms: float, on_ms: float,
              trace_path=None) -> dict:
  """Assemble the journaled obs block (design §15; keys pinned by
  tests/test_bench_artifact.py).  ``obs_overhead_pct`` is the DIRECT
  per-step instrumentation cost (``obs.measure_overhead``) amortized
  against the headline (obs-off) step; the two-arm window delta rides
  alongside, sign preserved, because on this host it lands inside
  window noise."""
  from distributed_embeddings_tpu import obs as obs_lib
  from distributed_embeddings_tpu.obs import metrics as obs_metrics
  from distributed_embeddings_tpu.obs import trace as obs_trace
  direct = obs_lib.measure_overhead(step_ms)
  saved = obs_trace.save(trace_path) if trace_path else None
  return {
      'obs_trace': bool(saved),
      'obs_trace_path': saved,
      'obs_trace_events': obs_trace.event_count(),
      'obs_off_ms': round(step_ms, 3),
      'obs_on_ms': round(on_ms, 3),
      'obs_window_delta_pct': round(
          (on_ms - step_ms) / step_ms * 100.0, 3),
      'obs_metrics_digest': obs_metrics.snapshot_digest(),
      **direct,
  }


def lint_block() -> dict:
  """The journaled static-analysis gate counts (design §17; keys
  pinned by tests/test_bench_artifact.py): ``lint_findings`` is the
  unwaived detlint finding count (0 on a healthy tree — the same gate
  tier-1 and dryrun_multichip enforce), ``lint_waivers`` the active
  rationale-bearing waiver count, so a quietly growing baseline is
  visible in the round-over-round artifact record."""
  from distributed_embeddings_tpu.analysis import run_repo
  res = run_repo(os.path.dirname(os.path.abspath(__file__)))
  return {
      'lint_findings': len(res.findings) + len(res.unverifiable),
      'lint_waivers': len(res.waived),
  }


def graphlint_block() -> dict:
  """The journaled IR-analysis gate counts (design §18; keys pinned by
  tests/test_bench_artifact.py): the flagship program catalog traced
  on THIS backend.  ``graphlint_findings`` is the unwaived finding
  count (0 on a healthy tree), ``graphlint_donation_ok`` whether every
  sparse-train-step state leaf came back input-output aliased in the
  compiled executable, ``graphlint_retraces`` the compile/retrace
  count across the monitored 3-step fit + warmed serving ladder (0 or
  a hot path is recompiling), and ``graphlint_peak_hbm_bytes`` the
  largest per-program per-device memory estimate — the journaled twin
  of the perf_notes fits ladder.

  Fused-exchange counters (design §21), counted from the graphlint
  schedule of the multi-group fused/per-group twin programs:
  ``exchange_collectives_fwd`` / ``_bwd`` are the fused programs'
  collective counts, ``_fwd_pergroup`` / ``_bwd_pergroup`` the
  unfused twins' (fused < per-group by at least the group count on a
  multi-group plan — the pinned coalescing win), and
  ``fused_exchange_bytes`` the summed on-wire payload of the fused
  programs' collectives."""
  from distributed_embeddings_tpu.analysis import graphlint
  res = graphlint.run_repo(os.path.dirname(os.path.abspath(__file__)))
  don = res.meta.get('graphlint_donation', {})
  ret = res.meta.get('graphlint_retrace', {})
  hbm = res.meta.get('graphlint_hbm', {})
  sched = res.meta.get('graphlint_schedule', {})

  def _count(name):
    return len(sched.get(name, {}).get('collectives', []))

  def _bytes(name):
    total = 0
    for op in sched.get(name, {}).get('collectives', []):
      try:
        import numpy as _np
        item = _np.dtype(op.get('dtype') or 'V0').itemsize
      except TypeError:
        item = 0
      n = 1
      for d in op.get('shape', ()):
        n *= int(d)
      total += n * item
    return total

  return {
      'graphlint_findings': len(res.findings) + len(res.unverifiable),
      'graphlint_donation_ok': bool(don) and all(
          v['aliased'] == v['expected'] for v in don.values()),
      'graphlint_retraces': sum(v['compile_count_delta']
                                for v in ret.values()),
      'graphlint_peak_hbm_bytes': max(
          (v['peak'] for v in hbm.values()), default=0),
      'exchange_collectives_fwd': _count('lookup/fused'),
      'exchange_collectives_fwd_pergroup': _count('lookup/pergroup'),
      'exchange_collectives_bwd': _count('bwd/fused'),
      'exchange_collectives_bwd_pergroup': _count('bwd/pergroup'),
      'fused_exchange_bytes': _bytes('lookup/fused') + _bytes('bwd/fused'),
  }


def commlint_block(programs=None) -> dict:
  """The journaled cross-rank protocol gate counts (design §22; keys
  pinned by tests/test_bench_artifact.py): ``commlint_findings`` is
  the unwaived finding count across the four passes (0 on a healthy
  tree), ``commlint_waivers`` the active waived true-positive count
  (the rank-variant recovery paths commsan guards at runtime), and
  ``commlint_schedules_predicted`` how many flagship program
  schedules the emission pass re-derived from the lookup plans and
  matched against the checked-in ledger — the journaled twin of the
  dryrun cross-rank stage.  Pass ``programs`` to reuse an
  already-built graphlint catalog instead of tracing a second one."""
  from distributed_embeddings_tpu.analysis import commlint
  res = commlint.run_repo(os.path.dirname(os.path.abspath(__file__)),
                          programs=programs)
  em = res.meta.get('commlint_emission', {})
  return {
      'commlint_findings': len(res.findings) + len(res.unverifiable),
      'commlint_waivers': len(res.waived),
      'commlint_schedules_predicted': sum(
          1 for v in em.values() if v.get('matched')),
  }


def pick_baseline(model: str, n_devices: int):
  """Baseline at this device count; otherwise round UP to the smallest
  published count >= ours (more devices = faster baseline = harder target,
  so vs_baseline is never overstated), falling back to the largest published
  count when we exceed them all."""
  table = BASELINES_MS.get(model, {})
  if not table:
    return None, None
  if n_devices in table:
    return table[n_devices], n_devices
  at_least = [n for n in table if n >= n_devices]
  n = min(at_least) if at_least else max(table)
  return table[n], n


def require_tpu():
  """The devices JAX gives this process — which must be TPUs.  A step
  time from any other backend says nothing about the system's users'
  hardware, so nothing is timed without one."""
  import jax
  devices = jax.devices()
  if devices[0].platform != 'tpu':
    raise SystemExit(
        f'bench.py: JAX found no TPU (platform '
        f'{devices[0].platform!r}, {len(devices)} device(s)); nothing is '
        'timed on another backend')
  return jax, devices


def finish(result):
  """Print the line; exit non-zero if a secondary phase raised.  Each
  such phase caught its exception into a ``*_error`` key so the line
  still says which one and why — but the run is not clean."""
  emit(result)
  failed = sorted(k for k in result if k.endswith('_error'))
  if failed:
    raise SystemExit('bench.py: phases raised (see their keys on the '
                     f'line above): {", ".join(failed)}')


def repo_sha():
  """Provenance of the tree being measured: a copy that is not a git
  checkout (a `git archive` extract, the chip tool's copy) can carry
  its SHA in a SNAPSHOT_SHA file written when the copy was made; a live
  checkout asks git; neither, None."""
  here = os.path.dirname(os.path.abspath(__file__))
  try:
    with open(os.path.join(here, 'SNAPSHOT_SHA')) as f:
      return f.read().strip()
  except OSError:
    pass
  try:
    import subprocess
    out = subprocess.run(['git', '-C', here, 'rev-parse', '--short', 'HEAD'],
                         capture_output=True, text=True, timeout=10)
    if out.returncode == 0:
      return out.stdout.strip()
  except Exception:
    pass
  return None


def split_windows(steps: int, windows: int):
  """Partition ``steps`` into ``windows`` contiguous measurement windows
  (the first windows absorb the remainder), at least one step each.

  The official number is the MIN over window means: a loaded driver
  host (the bench shares it with sweeps and compiles) inflates wall
  time in bursts, and a single long window averages the burst in —
  printing a phantom regression.  The min of several windows is the
  standard noise-robust estimator; the per-window list and the host
  loadavg are journaled alongside so a suspicious artifact line carries
  its own evidence."""
  windows = max(1, min(int(windows), int(steps)))
  base, rem = divmod(int(steps), windows)
  return [base + (1 if i < rem else 0) for i in range(windows)]


def host_load():
  """1/5/15-minute load averages of the bench host, for the artifact;
  None where the platform has no getloadavg."""
  try:
    return [round(x, 2) for x in os.getloadavg()]
  except (AttributeError, OSError):
    return None


def host_mem():
  """Available host memory in MiB (``MemAvailable`` from
  /proc/meminfo), the second host-pressure gauge next to loadavg
  (design §19): a bench line measured while the host was swapping
  carries its own evidence, and the perf sentinel's reader can discount
  it.  None where /proc/meminfo is absent (non-Linux)."""
  try:
    with open('/proc/meminfo', 'r', encoding='ascii') as f:
      for line in f:
        if line.startswith('MemAvailable:'):
          return round(int(line.split()[1]) / 1024.0, 1)
  except (OSError, ValueError, IndexError):
    pass
  return None


def emit(result):
  print(json.dumps(result), flush=True)


def main():
  parser = argparse.ArgumentParser()
  parser.add_argument('--model', default='tiny', choices=sorted(BASELINES_MS))
  parser.add_argument('--batch_size', type=int, default=65536)
  parser.add_argument('--steps', type=int, default=20)
  parser.add_argument('--warmup', type=int, default=4,
                      help='untimed warmup steps before the timed loop; '
                      'at least 3 always run (the compile, a second '
                      'compile if the state came back with other input '
                      'shardings, one cached call)')
  parser.add_argument('--alpha', type=float, default=1.05,
                      help='power-law exponent for ids (0=uniform)')
  parser.add_argument('--param_dtype', default='float32',
                      choices=['float32', 'bfloat16'])
  parser.add_argument('--compute_dtype', default=None,
                      choices=['float32', 'bfloat16'],
                      help='activation dtype (default: param_dtype)')
  parser.add_argument('--trainer', default='sparse',
                      choices=['sparse', 'dense'],
                      help='sparse = O(nnz) row-wise embedding updates '
                      '(parallel/sparse.py, matching the reference '
                      'IndexedSlices path); dense = autodiff + optax')
  parser.add_argument('--segwalk_apply', action='store_true',
                      help='opt into the fused segment-walk apply '
                      '(ops/pallas_segwalk.py): sorted raw stream in, '
                      'no compaction pipeline')
  parser.add_argument('--stream_dtype', default='float32',
                      choices=['float32', 'bfloat16'],
                      help='segwalk update-stream payload dtype '
                      '(bfloat16 halves stream HBM bytes/traffic)')
  parser.add_argument('--accum_dtype', default='float32',
                      choices=['float32', 'bfloat16'],
                      help='Adagrad accumulator STORAGE dtype: bfloat16 '
                      'halves accumulator HBM (the jumbo-scale lever; '
                      'arithmetic stays f32)')
  parser.add_argument('--fast_compile', action='store_true',
                      help='compile with exec_time_optimization_effort='
                      '-1.0 / memory_fitting_effort=-1.0: measured 2.75x '
                      'faster XLA compile (910->331 s host-side, round 5) '
                      'at unchanged memory/flops — for landing a step '
                      'number inside a short chip budget; the official '
                      'artifact line uses default effort')
  parser.add_argument('--row_slice', type=int, default=None,
                      help='element threshold for row-sharding big tables '
                      '(multi-chip; beyond the reference)')
  parser.add_argument('--capacity_fraction', type=float, default=0.5,
                      help='compaction capacity as a fraction of the raw '
                      'update stream (parallel/sparse.py)')
  parser.add_argument('--packed_storage',
                      action=argparse.BooleanOptionalAction, default=True,
                      help='lane-pack qualifying narrow fusion groups in '
                      'HBM (GroupSpec.storage_pack): packing exists to '
                      'kill the T(8,128) lane-padding HBM blowup of '
                      'narrow tables on TPU')
  parser.add_argument('--lookup_impl', default='auto',
                      choices=['auto', 'xla', 'pallas', 'sparsecore'],
                      help='embedding lookup dispatch; sparsecore runs '
                      'the docs/design.md §8 path (mod-sharded plan + '
                      'static CSR), through the executable emulation on '
                      'TensorCore/CPU backends — the artifact line is '
                      'labelled with the resolved backend so an '
                      'emulation number can never read as SC hardware')
  parser.add_argument('--hot_cache', action=argparse.BooleanOptionalAction,
                      default=None,
                      help='frequency-aware hot-row cache A/B + counters '
                      '(parallel/hotcache.py, design §10): replicated '
                      'hot rows served locally, cold ids sort-uniqued '
                      'before the dp->mp exchange.  Default: on exactly '
                      'for power-law workloads (--alpha > 0) with the '
                      'sparse trainer; the artifact journals the exact '
                      'exchanged-row/scatter-row counters for cache '
                      'off/on plus both step times (the headline value '
                      'stays the cache-OFF number, comparable with '
                      'prior rounds)')
  parser.add_argument('--overlap_chunks', type=int, default=None,
                      help='chunked dp<->mp exchange A/B (parallel/'
                      'overlap.py, design §11): split each subgroup\'s '
                      'exchange buffers into k static slot chunks and '
                      'software-pipeline collective against compute.  '
                      'The HEADLINE number stays the monolithic '
                      '(chunks=1, program-identical to pre-chunking) '
                      'step; the artifact journals a2a_off_ms / '
                      'a2a_on_ms / a2a_exchange_ms (directly measured '
                      'exchange-only wall) and the derived '
                      'a2a_overlap_pct.  Default: 4 for the sparse '
                      'trainer off the sparsecore path; 1 skips the A/B')
  parser.add_argument('--dcn_ab', action=argparse.BooleanOptionalAction,
                      default=None,
                      help='hierarchical DCNxICI exchange A/B (design '
                      '§20): re-measure the step on a two-axis '
                      '(2, n/2) mesh with tables flat-replicated vs '
                      'sharded over the axis product, and journal the '
                      'exact dcn_rows / dcn_rows_off / dcn_dedup_ratio '
                      'counters proving each distinct row crosses DCN '
                      'at most once per slice.  The HEADLINE number is '
                      'untouched.  Default: on for the sparse trainer '
                      'off the sparsecore path with >= 4 devices')
  parser.add_argument('--wire_ab', action=argparse.BooleanOptionalAction,
                      default=None,
                      help='wire-dtype compression A/B (design §24): '
                      'run twin forward passes with the fused-exchange '
                      'wire codec off vs on (bf16 arm and, on int8 '
                      'tables, the payload+po2-scale passthrough arm) '
                      'and journal the measured per-leg wire bytes, the '
                      'off/on byte ratios and the forward parity drift '
                      '(the passthrough arm must be bit-exact, drift '
                      '0.0).  The HEADLINE number is untouched.  '
                      'Default: on for the sparse trainer off the '
                      'sparsecore path with >= 2 devices')
  parser.add_argument('--hot_coverage', type=float, default=0.85,
                      help='per-table occurrence coverage target for the '
                      'hot set (0.85 measured: 8.5x fewer exchanged '
                      'rows, 2.6x fewer scatter rows on power-law tiny)')
  parser.add_argument('--hot_budget_mb', type=float, default=None,
                      help='per-device replication budget for the hot '
                      'rows + optimizer state (None = unbudgeted)')
  parser.add_argument('--table_dtype', default=None,
                      choices=['none', 'float32', 'int8', 'float8_e4m3'],
                      help='quantized table storage A/B (parallel/'
                      'quantization.py, design §12): per-row-scaled '
                      'int8 / float8_e4m3 payloads, dequantized at the '
                      'gather.  The HEADLINE number stays the '
                      'unquantized arm; the artifact journals '
                      'table_bytes_per_row off/on (exact byte '
                      'accounting) plus both step times.  Default: '
                      'int8 A/B for the sparse trainer off the '
                      "sparsecore path; 'none'/'float32' skips it")
  parser.add_argument('--cold_tier_budget_mb', type=float, default=None,
                      help='host-DRAM cold-tier phase (parallel/'
                      'coldtier.py, design §12): per-device HBM byte '
                      'budget the resident head must fit — the tail '
                      'rows pin in host memory and stream through the '
                      'deduplicated cold exchange, double-buffered '
                      'behind device steps.  Default: auto-size to '
                      '~60%% of the quantized arm\'s resident table '
                      'bytes so the tier is genuinely exercised (the '
                      'table does NOT fit without it); 0 skips the '
                      'phase.  Journals cold_tier_fetch_rows/bytes '
                      '(exact cross-checkable counters) and the '
                      'DIRECTLY measured cold_tier_overlap_pct')
  parser.add_argument('--audit_every', type=int, default=None,
                      help='state-integrity audit cadence for the '
                      'self-healing A/B (parallel/audit.py, design '
                      '§13): re-measure the same min-of-k windows with '
                      'a StateAuditor checking the live state every N '
                      'steps and journal audit_overhead_pct against '
                      'the headline (audit-off) arm, which stays '
                      'program-identical to pre-§13.  Default: 10 for '
                      'the sparse trainer, off otherwise; 0 disables')
  parser.add_argument('--serve', action=argparse.BooleanOptionalAction,
                      default=None,
                      help='online-serving phase (serving/, design '
                      '§14, §16): freeze the trained tables into a '
                      'lookup-only ServingEngine (int8 payload+scale '
                      'unless the plan is already quantized) and '
                      'measure the THREE-arm serving A/B (no-batch / '
                      'monolithic batcher / bucket-ladder+pipelined '
                      'dispatch) over a concurrent request stream cut '
                      'from the bench traffic — journals serve_p50_ms '
                      '/ serve_p99_ms / serve_qps / serve_batch_fill '
                      '+ the monolithic and no-batch arms, '
                      'serve_pad_waste_pct, per-bucket launch counts '
                      'and serve_pipeline_overlap_pct, all directly '
                      'measured.  Default: on for the sparse trainer')
  parser.add_argument('--serve_batch', type=int, default=256,
                      help='the LARGEST compiled serving batch — the '
                      'top ladder rung (rounded down to a device-count '
                      'multiple)')
  parser.add_argument('--serve_buckets', default=None,
                      help='comma-separated compiled-shape ladder '
                      'rungs (design §16), e.g. "32,64,128,256"; '
                      'default: the pow-2 ladder {B/8, B/4, B/2, B}. '
                      'Pass the full batch alone to serve the '
                      'monolithic single-signature engine.')
  parser.add_argument('--serve_requests', type=int, default=192,
                      help='request count per serving arm')
  parser.add_argument('--serve_max_delay_ms', type=float, default=2.0,
                      help='batcher admission deadline (oldest queued '
                      'request waits at most this long for co-riders)')
  parser.add_argument('--serve_concurrency', type=int, default=8,
                      help='closed-loop in-flight requests in the '
                      'batching arm')
  parser.add_argument('--serve_hot_coverage', type=float, default=0.95,
                      help='serving hot-cache coverage target (read-'
                      'only cache, no optimizer copies to fund — '
                      'larger than training coverage by design)')
  parser.add_argument('--serve_hot_budget_mb', type=float, default=256.0,
                      help='per-device replication budget for the '
                      'serving hot rows')
  parser.add_argument('--serve_overload', action=argparse.BooleanOptionalAction,
                      default=None,
                      help='overload arm of the serving phase (design '
                      '§23): drive a ServingEnginePool past capacity '
                      'with a mixed-priority open-loop burst and '
                      'journal the serve_over_* block (per-class '
                      'p50/p99/p99.9, shed ledger by class+reason, '
                      'degraded-mode enters/exits, failover drill when '
                      '--serve_replicas > 1).  Default: rides --serve')
  parser.add_argument('--serve_overload_qps', type=float, default=None,
                      help='paced offered load for the overload arm '
                      '(requests/s, open-loop); default None = one '
                      'unpaced burst — the worst case')
  parser.add_argument('--serve_deadline_ms', type=float, default=50.0,
                      help='per-request deadline in the overload arm; '
                      'requests past it at dispatch shed, never execute')
  parser.add_argument('--serve_priority_mix', type=float, default=0.5,
                      help='high-priority fraction of overload traffic '
                      '(deterministic error-diffusion interleave)')
  parser.add_argument('--serve_replicas', type=int, default=2,
                      help='replica engines behind the overload pool; '
                      '>1 arms the mid-stream failover drill '
                      '(replica 0 quarantined halfway through the '
                      'burst, its in-flight work retried bit-exact on '
                      'the survivors)')
  parser.add_argument('--obs', action=argparse.BooleanOptionalAction,
                      default=None,
                      help='observability A/B (obs/, design §15): '
                      're-run the same min-of-k windows with the span '
                      'tracer + metrics registry armed (one train/step '
                      'span + counter per step) and journal the obs '
                      'block — obs_overhead_pct is the DIRECTLY '
                      'measured per-step instrumentation wall '
                      'amortized against the headline step, which '
                      'stays program-identical to the obs-off build.  '
                      'Default: on for the sparse trainer')
  parser.add_argument('--devprof', action=argparse.BooleanOptionalAction,
                      default=None,
                      help='device-time attribution (obs/devprof.py, '
                      'design §19): after the measured windows, run the '
                      "step's phases (exchange, lookup/combine, "
                      'backward exchange, apply) as individually '
                      'synced sub-programs and journal per-phase '
                      'device ms + the cost-model cross-check; with '
                      'the obs arm traced, the phases land on the '
                      "trace's device lane.  NEVER runs inside a "
                      'measured headline window.  Default: rides the '
                      'obs arm for the sparse trainer')
  parser.add_argument('--trace_path', default=None,
                      help='write the obs phase trace (Chrome-trace '
                      'JSON; open in Perfetto or feed '
                      'tools/trace_report.py) to this path.  Default: '
                      'buffered + journaled by count only, no file')
  parser.add_argument('--measure_windows', type=int, default=3,
                      help='min-of-k measurement: split --steps into k '
                      'windows and report the fastest window, immunising '
                      'the official number against driver-host load '
                      'bursts (per-window times + loadavg are journaled)')
  parser.add_argument('--auto_capacity',
                      action=argparse.BooleanOptionalAction, default=True,
                      help='calibrate per-group compaction capacities from '
                      'the first generated batch (calibrate_capacity_rows) '
                      'instead of --capacity_fraction (default: on; '
                      '--no-auto_capacity reverts to the fraction)')
  args = parser.parse_args()

  jax, devices = require_tpu()
  # persistent compilation cache, placed from outside
  # (utils/compile_cache.py): the train-step programs take minutes to
  # compile, and a warm cache makes a repeat run start measuring sooner
  from distributed_embeddings_tpu.utils import compile_cache
  compile_cache.configure()
  import jax.numpy as jnp
  import numpy as np
  import optax
  from distributed_embeddings_tpu.models.synthetic import (SYNTHETIC_MODELS,
                                                           InputGenerator,
                                                           SyntheticModel)
  from distributed_embeddings_tpu.models.dlrm import bce_with_logits
  from distributed_embeddings_tpu.parallel import (SparseAdagrad, TrainState,
                                                   create_mesh,
                                                   init_hybrid_train_state,
                                                   init_train_state,
                                                   make_hybrid_train_step)

  mesh = create_mesh(devices)
  config = SYNTHETIC_MODELS[args.model]
  compute_dtype = jnp.dtype(args.compute_dtype or args.param_dtype)
  use_hot = args.hot_cache
  if use_hot is None:
    use_hot = (args.alpha > 0 and args.trainer == 'sparse'
               and args.lookup_impl != 'sparsecore')
  elif use_hot:
    # explicit --hot_cache: fail fast on unsupported combinations (before
    # any compile/measure work) rather than journaling an artifact
    # without the requested measurement
    if args.trainer != 'sparse':
      raise SystemExit('--hot_cache requires --trainer sparse (the hot '
                       'path lives in the sparse train step)')
    if args.lookup_impl == 'sparsecore':
      raise SystemExit('--hot_cache is incompatible with --lookup_impl '
                       'sparsecore (the cached forward bypasses the '
                       'SparseCore path)')
    if args.alpha <= 0:
      raise SystemExit('--hot_cache requires a power-law workload '
                       '(--alpha > 0): uniform ids have no head to '
                       'cache, and the analytic hot set would replicate '
                       'coverage*rows of every table')
  use_chunks = args.overlap_chunks
  if use_chunks is None:
    use_chunks = (4 if (args.trainer == 'sparse'
                        and args.lookup_impl != 'sparsecore') else 1)
  elif use_chunks > 1:
    # explicit --overlap_chunks: fail fast (same discipline as
    # --hot_cache) instead of journaling an artifact without the
    # requested measurement
    if args.trainer != 'sparse':
      raise SystemExit('--overlap_chunks > 1 requires --trainer sparse '
                       '(the chunked pipeline lives in the sparse '
                       'dp<->mp exchange)')
    if args.lookup_impl == 'sparsecore':
      raise SystemExit('--overlap_chunks > 1 is incompatible with '
                       '--lookup_impl sparsecore (that path pipelines '
                       'through the static-CSR host feed; design §11 '
                       'refusal matrix)')
  use_dcn_ab = args.dcn_ab
  if use_dcn_ab is None:
    use_dcn_ab = (args.trainer == 'sparse'
                  and args.lookup_impl != 'sparsecore'
                  and len(devices) >= 4 and len(devices) % 2 == 0)
  elif use_dcn_ab:
    # explicit --dcn_ab: fail fast (same discipline as --hot_cache)
    # instead of journaling an artifact without the requested A/B
    if args.trainer != 'sparse':
      raise SystemExit('--dcn_ab requires --trainer sparse (the '
                       'hierarchical exchange lives in the sparse '
                       'dp<->mp path; design §20)')
    if args.lookup_impl == 'sparsecore':
      raise SystemExit('--dcn_ab is incompatible with --lookup_impl '
                       'sparsecore (the SC path mod-shards; '
                       'hierarchical layouts need contiguous windows; '
                       'design §20 refusal matrix)')
    if len(devices) < 4 or len(devices) % 2:
      raise SystemExit('--dcn_ab needs an even device count >= 4 '
                       '(the A/B mesh is (2, n/2); design §20)')
  use_wire_ab = args.wire_ab
  if use_wire_ab is None:
    use_wire_ab = (args.trainer == 'sparse'
                   and args.lookup_impl != 'sparsecore'
                   and len(devices) >= 2)
  elif use_wire_ab:
    # explicit --wire_ab: fail fast (same discipline as --dcn_ab)
    if args.trainer != 'sparse':
      raise SystemExit('--wire_ab requires --trainer sparse (the wire '
                       'codec lives in the sparse fused exchange; '
                       'design §24)')
    if len(devices) < 2:
      raise SystemExit('--wire_ab needs >= 2 devices (a single-device '
                       'mesh has no exchange legs to compress)')
  quant_dtype = args.table_dtype
  if quant_dtype is None:
    # default: journal the int8 storage A/B for every sparse power-law
    # run off the sparsecore path (the headline number stays the
    # unquantized arm — comparable with prior rounds)
    quant_dtype = ('int8' if (args.trainer == 'sparse'
                              and args.lookup_impl != 'sparsecore'
                              and args.param_dtype == 'float32')
                   else 'none')
  elif quant_dtype not in ('none', 'float32'):
    # explicit --table_dtype: fail fast on unsupported combinations
    # (same discipline as --hot_cache) instead of journaling an
    # artifact without the requested measurement
    if args.trainer != 'sparse':
      raise SystemExit('--table_dtype requires --trainer sparse '
                       '(dense autodiff cannot differentiate through '
                       'integer payloads; design §12 refusal matrix)')
    if args.param_dtype != 'float32':
      raise SystemExit('--table_dtype requires --param_dtype float32 '
                       '(the per-row scale carries the dynamic range; '
                       'design §12 refusal matrix)')
  use_quant = quant_dtype not in ('none', 'float32')
  if args.cold_tier_budget_mb is not None and args.cold_tier_budget_mb > 0:
    # explicit budget: fail fast like --hot_cache / --table_dtype
    if args.trainer != 'sparse':
      raise SystemExit('--cold_tier_budget_mb requires --trainer sparse')
    if not use_hot:
      raise SystemExit('--cold_tier_budget_mb requires the hot cache '
                       '(the tier rides the deduplicated cold '
                       'exchange; design §12 refusal matrix) — drop '
                       '--no-hot_cache or use a power-law workload')
    if args.param_dtype != 'float32':
      raise SystemExit('--cold_tier_budget_mb requires --param_dtype '
                       'float32 (the host tier stores f32 tails; '
                       'design §12 refusal matrix)')
  use_tier = (args.trainer == 'sparse' and use_hot
              and args.lookup_impl != 'sparsecore'
              and args.param_dtype == 'float32'
              and (args.cold_tier_budget_mb is None
                   or args.cold_tier_budget_mb > 0))
  model = SyntheticModel(config,
                         mesh=mesh,
                         dp_input=True,
                         row_slice=args.row_slice,
                         param_dtype=jnp.dtype(args.param_dtype),
                         compute_dtype=compute_dtype,
                         packed_storage=args.packed_storage,
                         lookup_impl=args.lookup_impl)
  if args.lookup_impl == 'sparsecore':
    # Resolve the SC backend BEFORE any compile or measurement work: on
    # a TPU without jax-tpu-embedding this raises the §8 contract error
    # immediately (a labelled failure artifact), instead of burning the
    # full warmup+measure run and crashing at metric-build time — and
    # instead of a bf16/wide config silently measuring the XLA fallback
    # under a sparsecore label (every group can decline the SC gate).
    sc_backend = model.dist_embedding._resolve_sc_backend()
  params = model.init(0)

  gen = InputGenerator(config, args.batch_size, alpha=args.alpha,
                       num_batches=2, seed=0)
  (_, cats0), _ = gen.pool[0]  # shared by calibration + CSR measurement

  def loss_fn(p, batch):
    (numerical, cats), labels = batch
    logits = model.apply(p, numerical, list(cats))
    return bce_with_logits(logits, labels)

  def head_loss_fn(dense_params, emb_outs, batch):
    numerical, labels = batch
    logits = model.head(dense_params, numerical, emb_outs)
    return bce_with_logits(logits, labels)

  # keras Adagrad defaults (reference synthetic_models/main.py:105)
  optimizer = optax.adagrad(0.01, initial_accumulator_value=0.1, eps=1e-7)
  capacity_rows = None
  if args.auto_capacity and args.trainer == 'sparse':
    segwalk_all = False
    if args.segwalk_apply:
      # the segment-walk kernel has no compaction capacities: when it
      # serves every group on THIS backend, calibration is dead work
      from distributed_embeddings_tpu.utils.apply_eligibility import (
          segwalk_serves_all_groups)
      segwalk_all = segwalk_serves_all_groups(model.dist_embedding,
                                              args.param_dtype,
                                              accum_dtype=args.accum_dtype)
    if not segwalk_all:
      from distributed_embeddings_tpu.parallel import calibrate_capacity_rows
      capacity_rows = calibrate_capacity_rows(
          model.dist_embedding, [jnp.asarray(c) for c in cats0],
          params=params['embedding'])
  # Host-side static-CSR preprocessing cost (docs/design.md §8): the
  # per-batch transform the real SparseCore feed pays on this host —
  # the native C++ builder fanned out over the worker pool when the
  # toolchain exists, with the NumPy oracle's number (and a live
  # bit-exact parity check against it) journaled alongside — so the
  # v5p projection's "including preprocessing" term is a number, not
  # an assumption.  Caps are CALIBRATED (with margin) from batch 0 and
  # the timed padded build runs on batch 1, so the journaled
  # csr_dropped is a genuine cross-batch check of the calibration, not
  # 0 by construction.  Runs BEFORE the train loop — the first
  # donating step invalidates `params`, which the calibration forward
  # reads.  Never fatal to the artifact.
  csr_stats = None
  sc_caps = None
  if args.trainer == 'sparse':
    try:
      from distributed_embeddings_tpu.parallel import sparsecore
      sc_caps = sparsecore.calibrate_max_ids_per_partition(
          model.dist_embedding, [jnp.asarray(c) for c in cats0],
          params=params['embedding'])
      (_, cats1), _ = gen.pool[1 % len(gen.pool)]
      csr_stats = sparsecore.measure_preprocess_ms(
          model.dist_embedding, [np.asarray(c) for c in cats1],
          repeats=5, max_ids_per_partition=sc_caps)
    except Exception as e:
      csr_stats = {'csr_preprocess_error': f'{type(e).__name__}: {e}'}

  emb_opt = SparseAdagrad(learning_rate=0.01,
                          capacity_fraction=args.capacity_fraction,
                          capacity_rows=capacity_rows,
                          use_segwalk_apply=args.segwalk_apply,
                          stream_dtype=args.stream_dtype,
                          accum_dtype=args.accum_dtype)
  if args.trainer == 'sparse':
    state = init_hybrid_train_state(model.dist_embedding, params, optimizer,
                                    emb_opt)
    raw_step = make_hybrid_train_step(model.dist_embedding, head_loss_fn,
                                      optimizer, emb_opt, jit=False)
  else:
    state = init_train_state(params, optimizer)

  # Time the bare jitted step in an async-dispatch python loop: dispatches
  # queue without blocking (the sync is one scalar pull at the end), so the
  # device pipelines back-to-back steps exactly as a lax.scan would, while
  # the program stays half the compile time of a scan wrapper.  Batches
  # cycle through the generated pool so consecutive steps see distinct ids.
  def make_step():
    if args.trainer == 'sparse':
      def body(state, batch):
        (numerical, cats), labels = batch
        return raw_step(state, list(cats), (numerical, labels))
    else:
      def body(state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params)
        new_params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                                  state.params, updates)
        return TrainState(new_params, opt_state, state.step + 1), loss

    copts = ({'exec_time_optimization_effort': -1.0,
              'memory_fitting_effort': -1.0} if args.fast_compile else None)
    return jax.jit(body, donate_argnums=(0,), compiler_options=copts)

  step = make_step()
  pool = [((jnp.asarray(num), tuple(jnp.asarray(c) for c in cats)),
           jnp.asarray(lab)) for (num, cats), lab in gen.pool]

  # Every scalar pull below runs under a hung-step watchdog: a backend
  # that wedges MID-RUN makes the sync hang rather than raise, which
  # would burn the whole unattended window with nothing to show.  The
  # watchdog dumps all-thread tracebacks, journals the event, and fails
  # fast so the failure line still gets written (and the run exits
  # non-zero).  Budget: env DET_STEP_HANG_S (default 600 s — above a
  # cold full-size compile, far below the driver window).
  from distributed_embeddings_tpu.utils import resilience
  step_hang_s = float(os.environ.get('DET_STEP_HANG_S', '600'))

  def sync_loss(loss, what):
    return resilience.call_with_timeout(lambda: float(loss), step_hang_s,
                                        what=what)

  # Warm up until the program is cached: the first call compiles.  A
  # second compile on call 2 means the state's input shardings changed
  # between the calls (uncommitted initial leaves come back committed);
  # init_hybrid_train_state commits them up front, the dense trainer's
  # init_train_state does not — hence at least 3 calls.
  warm_start = time.perf_counter()
  for i in range(max(3, args.warmup)):
    state, loss = step(state, pool[i % len(pool)])
  # the scalar pull is the sync, under the hung-step watchdog
  sync_loss(loss, 'warmup step sync')
  warmup_s = time.perf_counter() - warm_start

  # Min-of-k windows (split_windows): the fastest window is the
  # official number; the full list + host load ride the artifact so a
  # loaded driver host cannot print a phantom regression unnoticed.
  window_ms = []
  i = 0
  for wsteps in split_windows(args.steps, args.measure_windows):
    t0 = time.perf_counter()
    for _ in range(wsteps):
      state, loss = step(state, pool[i % len(pool)])
      i += 1
    sync_loss(loss, f'measurement window sync at step {i}')
    window_ms.append((time.perf_counter() - t0) / wsteps * 1000)

  step_ms = min(window_ms)

  # Self-healing audit A/B (design §13): the HEADLINE windows above are
  # the off arm — zero auditor code touched them, so the official
  # number is program-identical to pre-§13.  The on arm re-runs the
  # same min-of-k loop with a StateAuditor checking the live state
  # every --audit_every steps (replicated digests, quantized row
  # contract, finiteness — the same jitted pass fit(auditor=) uses),
  # and the journaled audit_overhead_pct is the measured cost of
  # leaving SDC detection armed on an unattended run.  Never fatal.
  audit_stats = None
  audit_every = args.audit_every
  if audit_every is None:
    audit_every = 10 if args.trainer == 'sparse' else 0
  if audit_every > 0 and args.trainer == 'sparse':
    try:
      from distributed_embeddings_tpu.parallel.audit import StateAuditor
      # NO 'tier' check here: the audited main-loop state has no cold
      # tier, and constructing a tier-armed auditor would permanently
      # enable the tier's write-back digests on the shared model —
      # silently taxing every LATER measured phase of this run
      auditor = StateAuditor(model.dist_embedding, every=audit_every,
                             checks=('replicated', 'quantized',
                                     'finite'))
      # compile the audit program + prove the state healthy before the
      # timed windows (a finding here would poison the measurement)
      pre = auditor.check_state(state, step=0)
      if pre:
        raise RuntimeError('pre-measurement audit failed: '
                           + '; '.join(f.brief() for f in pre))
      audit_window_ms = []
      audit_call_ms = []
      ai = 0
      for wsteps in split_windows(args.steps, args.measure_windows):
        t0 = time.perf_counter()
        for _ in range(wsteps):
          state, loss = step(state, pool[(i + ai) % len(pool)])
          ai += 1
          if ai % audit_every == 0:
            ta = time.perf_counter()
            bad = auditor.check_state(state, step=ai)
            audit_call_ms.append((time.perf_counter() - ta) * 1000)
            if bad:
              raise RuntimeError('audit failed mid-measurement: '
                                 + '; '.join(f.brief() for f in bad))
        sync_loss(loss, f'audit-arm window sync at step {ai}')
        audit_window_ms.append((time.perf_counter() - t0) / wsteps * 1000)
      audit_on_ms = min(audit_window_ms)
      # the headline overhead is DIRECTLY measured: per-audit wall
      # (audit_call_ms, min over calls) amortized over the cadence.
      # The two-arm window subtraction also rides the artifact
      # (audit_window_delta_pct, sign preserved) but is noise-bound on
      # this host: the amortized cost (~call/cadence) sits well below
      # the window-to-window swings of either arm, so the subtraction
      # can land negative — a derived number must never launder noise
      # into a "negative overhead" claim
      call_ms = (min(audit_call_ms) if audit_call_ms else 0.0)
      audit_stats = {
          'audit_every': audit_every,
          'audit_off_ms': round(step_ms, 3),
          'audit_on_ms': round(audit_on_ms, 3),
          'audit_call_ms': round(call_ms, 3),
          'audit_overhead_pct': round(
              call_ms / audit_every / step_ms * 100.0, 3),
          'audit_window_delta_pct': round(
              (audit_on_ms - step_ms) / step_ms * 100.0, 3),
          'audits_run': auditor.audits,
          'audit_findings': auditor.findings_total,
          'audit_checks': list(auditor.checks),
          # rotating-coverage accounting: fraction of the state each
          # audit reads, and how many audits cover every row — the
          # detection window is audit_every * audit_full_coverage_audits
          'audit_coverage_frac': auditor.coverage_frac,
          'audit_full_coverage_audits': auditor.full_coverage_audits,
      }
    except Exception as e:
      audit_stats = {'audit_error': f'{type(e).__name__}: {e}'}

  # Pipelined host-feed phase (docs/design.md §8 "host feed pipeline"):
  # run the same step through a CsrFeed that builds batch N+1's padded
  # static-CSR buffers on worker threads while the device executes
  # batch N, and journal how much of the host build time the device
  # step hid.  The overlap metric is DIRECT (the feed's blocked-ms
  # accounting, not a subtraction of two noisy walls); batch 0's build
  # has no prior step to hide behind, so the feed's stats reset after
  # it and the journaled overlap is steady-state.  Never fatal.
  if args.trainer == 'sparse' and sc_caps is not None and csr_stats:
    try:
      from distributed_embeddings_tpu.parallel import run_pipelined
      from distributed_embeddings_tpu.parallel.csr_feed import CsrFeed
      k = max(args.steps, 8)
      src = ((j, gen.pool[j % len(gen.pool)]) for j in range(k))
      feed = CsrFeed(model.dist_embedding, src,
                     cats_fn=lambda it: [np.asarray(c)
                                         for c in it[1][0][1]],
                     max_ids_per_partition=sc_caps)
      # run_pipelined owns the consume/sync/steady-state-reset protocol
      # (ONE copy of the overlap accounting); the adapters map its
      # (cats, batch) contract onto the bench's prebuilt device pool
      state, _, fstats = run_pipelined(
          lambda st, _cats, j: step(st, pool[j % len(pool)]),
          state, feed, lambda fed: (None, fed.item[0]))
      csr_stats.update({
          'csr_feed_batches': fstats['batches'],
          'csr_feed_build_ms': fstats['build_ms'],
          'csr_feed_blocked_ms': fstats['blocked_ms'],
          'csr_feed_overlap_pct': fstats['overlap_pct'],
          'csr_feed_builder': fstats['builder'],
      })
    except Exception as e:
      csr_stats['csr_feed_error'] = f'{type(e).__name__}: {e}'

  # Frequency-aware hot-cache A/B + exact counters (design §10; ISSUE 5).
  # Flag-guarded, DEFAULT ON only for power-law workloads: uniform ids
  # have no head to cache.  The counters are computed host-side from the
  # id streams + plan (exact, hardware-independent); the A/B re-measures
  # the same min-of-k windows with the cache enabled.  Never fatal.
  hot_stats = None
  if use_hot:
    try:
      from distributed_embeddings_tpu.models.synthetic import expand_tables
      from distributed_embeddings_tpu.parallel import hotcache
      tables, _, _ = expand_tables(config)
      budget = (int(args.hot_budget_mb * 2**20)
                if args.hot_budget_mb else None)
      hs = hotcache.analytic_power_law_hot_sets(
          tables, args.alpha, args.hot_coverage, budget_bytes=budget)
      hot_rows = sum(h.size for h in hs.values())
      hot_mb = sum(h.size * hotcache.hot_row_bytes(tables[t].output_dim)
                   for t, h in hs.items()) / 2**20
      hot_stats = hotcache.measure_exchange_counters(
          model.dist_embedding, [np.asarray(c) for c in cats0],
          hot_sets=hs)
      hot_stats.update({
          'hot_cache': True,
          'hot_coverage': args.hot_coverage,
          'hot_rows_replicated': int(hot_rows),
          'hot_mb_per_device': round(hot_mb, 1),
      })
      # A/B: the same model/step with the cache engaged, same warmup
      # discipline (compile + donation recompile + one cached call) and
      # the same min-of-k windows as the official number
      model_hot = SyntheticModel(config,
                                 mesh=mesh,
                                 dp_input=True,
                                 row_slice=args.row_slice,
                                 param_dtype=jnp.dtype(args.param_dtype),
                                 compute_dtype=compute_dtype,
                                 packed_storage=args.packed_storage,
                                 lookup_impl=args.lookup_impl,
                                 hot_cache=hs)
      hot_params = model_hot.init(0)
      emb_opt_hot = emb_opt
      if args.auto_capacity:
        # the cached residual streams are per-(source, slot) unique —
        # recalibrate so the A/B's static scatters reflect the shrink
        import dataclasses as _dc
        from distributed_embeddings_tpu.parallel import (
            calibrate_capacity_rows)
        emb_opt_hot = _dc.replace(
            emb_opt,
            capacity_rows=calibrate_capacity_rows(
                model_hot.dist_embedding, [jnp.asarray(c) for c in cats0],
                params=hot_params['embedding']))
      hot_raw = make_hybrid_train_step(model_hot.dist_embedding,
                                       head_loss_fn, optimizer,
                                       emb_opt_hot, jit=False)
      copts = ({'exec_time_optimization_effort': -1.0,
                'memory_fitting_effort': -1.0}
               if args.fast_compile else None)
      hot_step = jax.jit(
          lambda st, batch: hot_raw(st, list(batch[0][1]),
                                    (batch[0][0], batch[1])),
          donate_argnums=(0,), compiler_options=copts)
      hstate = init_hybrid_train_state(model_hot.dist_embedding,
                                       hot_params, optimizer,
                                       emb_opt_hot)
      for i in range(max(3, args.warmup)):
        hstate, hloss = hot_step(hstate, pool[i % len(pool)])
      sync_loss(hloss, 'hot-cache warmup sync')
      hot_window_ms = []
      i = 0
      for wsteps in split_windows(args.steps, args.measure_windows):
        t0 = time.perf_counter()
        for _ in range(wsteps):
          hstate, hloss = hot_step(hstate, pool[i % len(pool)])
          i += 1
        sync_loss(hloss, f'hot-cache window sync at step {i}')
        hot_window_ms.append((time.perf_counter() - t0) / wsteps * 1000)
      hot_stats.update({
          'hot_ab_off_ms': round(step_ms, 3),
          'hot_ab_on_ms': round(min(hot_window_ms), 3),
          'hot_window_ms': [round(x, 3) for x in hot_window_ms],
      })
      del hstate
    except Exception as e:
      hot_stats = (hot_stats or {})
      hot_stats['hot_cache_error'] = f'{type(e).__name__}: {e}'

  # Chunked-exchange overlap A/B (parallel/overlap.py, design §11;
  # ISSUE 6).  Three directly-measured numbers: the OFF arm is the
  # headline step itself (overlap_chunks=1 IS the monolithic program —
  # the official number doubles as the A/B baseline, so the off arm is
  # program-identical to pre-chunking by construction); the ON arm
  # re-measures the same step built with overlap_chunks=k under the
  # same warmup discipline and min-of-k windows; the DENOMINATOR is the
  # exchange-only wall (measure_exchange_ms: the chunked id/row
  # collectives with no lookup/combine between them).  a2a_overlap_pct
  # = (off - on) / exchange — the hidden fraction of the exchange wall,
  # measured the same way csr_feed_overlap_pct prices the host build.
  # Never fatal.
  a2a_stats = None
  if use_chunks > 1 and args.trainer == 'sparse':
    try:
      from distributed_embeddings_tpu.parallel import overlap as overlap_lib
      exchange_ms = overlap_lib.measure_exchange_ms(
          model.dist_embedding, [jnp.asarray(c) for c in cats0], chunks=1)
      model_chk = SyntheticModel(config,
                                 mesh=mesh,
                                 dp_input=True,
                                 row_slice=args.row_slice,
                                 param_dtype=jnp.dtype(args.param_dtype),
                                 compute_dtype=compute_dtype,
                                 packed_storage=args.packed_storage,
                                 lookup_impl=args.lookup_impl,
                                 overlap_chunks=use_chunks)
      chk_params = model_chk.init(0)
      # chunking never changes the residual streams (bit-exact vs the
      # monolithic program), so the headline run's calibrated
      # capacities describe the chunked arm exactly — no recalibration
      chk_raw = make_hybrid_train_step(model_chk.dist_embedding,
                                       head_loss_fn, optimizer, emb_opt,
                                       jit=False)
      copts = ({'exec_time_optimization_effort': -1.0,
                'memory_fitting_effort': -1.0}
               if args.fast_compile else None)
      chk_step = jax.jit(
          lambda st, batch: chk_raw(st, list(batch[0][1]),
                                    (batch[0][0], batch[1])),
          donate_argnums=(0,), compiler_options=copts)
      cstate = init_hybrid_train_state(model_chk.dist_embedding,
                                       chk_params, optimizer, emb_opt)
      for i in range(max(3, args.warmup)):
        cstate, closs = chk_step(cstate, pool[i % len(pool)])
      sync_loss(closs, 'chunked-exchange warmup sync')
      chk_window_ms = []
      i = 0
      for wsteps in split_windows(args.steps, args.measure_windows):
        t0 = time.perf_counter()
        for _ in range(wsteps):
          cstate, closs = chk_step(cstate, pool[i % len(pool)])
          i += 1
        sync_loss(closs, f'chunked-exchange window sync at step {i}')
        chk_window_ms.append((time.perf_counter() - t0) / wsteps * 1000)
      a2a_stats = overlap_lib.a2a_overlap_stats(
          step_ms, min(chk_window_ms), exchange_ms, use_chunks,
          group_chunks=overlap_lib.group_chunk_counts(
              model_chk.dist_embedding.plan),
          window_ms=chk_window_ms)
      del cstate
    except Exception as e:
      a2a_stats = {'a2a_overlap_error': f'{type(e).__name__}: {e}'}

  # Hierarchical DCNxICI exchange A/B (parallel/planner.py
  # hierarchical_layout + dist_embedding dcn_sharding, design §20;
  # PR 16 tentpole).  Both arms run on a two-axis (2, n/2) mesh with
  # natural (pack=1) storage so the ONLY delta is the table placement:
  # the flat arm replicates tables across the dcn axis (zero exchange
  # rows cross DCN, replication pays the HBM), the hierarchical arm
  # shards over the axis product and dedups each slice's id union at
  # the slice-local representative before anything crosses DCN.  The
  # counters are EXACT host-side accounting (measure_exchange_counters
  # mirrors HierGroupLayout.map_rows): dcn_rows vs dcn_rows_off is the
  # dedup-at-the-boundary win, dcn_dedup_ratio > 1 whenever slices
  # hold cross-chip duplicates.  The HEADLINE number is untouched.
  # Never fatal.
  dcn_stats = None
  if use_dcn_ab:
    try:
      from distributed_embeddings_tpu.parallel import hotcache
      from distributed_embeddings_tpu.parallel.mesh import (
          create_mesh as _dcn_mesh)
      n_dev2 = len(devices)
      hier_mesh = _dcn_mesh((2, n_dev2 // 2))
      hostpool = [((np.asarray(num), [np.asarray(c) for c in cats]),
                   np.asarray(lab)) for (num, cats), lab in gen.pool]
      dcn_arm_ms = {}
      for arm, shard in (('flat', False), ('hier', True)):
        model_d = SyntheticModel(config,
                                 mesh=hier_mesh,
                                 dp_input=True,
                                 row_slice=args.row_slice,
                                 param_dtype=jnp.dtype(args.param_dtype),
                                 compute_dtype=compute_dtype,
                                 packed_storage=False,
                                 lookup_impl=args.lookup_impl,
                                 dcn_sharding=shard)
        if shard:
          # exact counters from the hierarchical layer's own layout
          dcn_stats = hotcache.measure_exchange_counters(
              model_d.dist_embedding,
              [np.asarray(c) for c in cats0], hot_sets={})
        d_params = model_d.init(0)
        d_raw = make_hybrid_train_step(model_d.dist_embedding,
                                       head_loss_fn, optimizer,
                                       emb_opt, jit=False)
        copts = ({'exec_time_optimization_effort': -1.0,
                  'memory_fitting_effort': -1.0}
                 if args.fast_compile else None)
        d_step = jax.jit(
            lambda st, batch, _raw=d_raw: _raw(st, list(batch[0][1]),
                                               (batch[0][0], batch[1])),
            donate_argnums=(0,), compiler_options=copts)
        dstate = init_hybrid_train_state(model_d.dist_embedding,
                                         d_params, optimizer, emb_opt)
        for i in range(max(3, args.warmup)):
          dstate, dloss = d_step(dstate, hostpool[i % len(hostpool)])
        sync_loss(dloss, f'dcn-ab {arm} warmup sync')
        arm_window_ms = []
        i = 0
        for wsteps in split_windows(args.steps, args.measure_windows):
          t0 = time.perf_counter()
          for _ in range(wsteps):
            dstate, dloss = d_step(dstate, hostpool[i % len(hostpool)])
            i += 1
          sync_loss(dloss, f'dcn-ab {arm} window sync at step {i}')
          arm_window_ms.append((time.perf_counter() - t0) / wsteps
                               * 1000)
        dcn_arm_ms[arm] = round(min(arm_window_ms), 3)
        del dstate
      dcn_stats = dcn_stats or {}
      dcn_stats.update({
          'dcn_sharding': True,
          'dcn_ab_flat_ms': dcn_arm_ms['flat'],
          'dcn_ab_hier_ms': dcn_arm_ms['hier'],
          'dcn_ab_mesh_shape': [2, n_dev2 // 2],
      })
    except Exception as e:
      dcn_stats = dcn_stats or {}
      dcn_stats['dcn_ab_error'] = f'{type(e).__name__}: {e}'

  # Wire-dtype compression A/B (parallel/dist_embedding.py wire_dtype,
  # design §24; ISSUE 20).  Four twin layers over the SAME wide tables
  # + hot sets + id streams, so the only delta per pair is the wire
  # codec: the int8 pair (stored int8, wire off vs 'table' passthrough
  # — payload + po2 scale on a packed uint8 wire, bit-exact by the §12
  # po2 identity) and the f32 pair (wire off vs 'bfloat16').  Bytes
  # are read off the traced LookupPlan legs — the codec encodes BEFORE
  # fuse_layout records the leg, so leg.nbytes IS the on-wire size and
  # leg.payload_nbytes the compute-dtype counterfactual.  Ratios are
  # over the codec-targeted row legs (id legs never narrow and ride
  # unchanged in every arm).  The HEADLINE number is untouched.  Never
  # fatal.
  wire_stats = None
  if use_wire_ab:
    try:
      from distributed_embeddings_tpu.parallel import (
          DistributedEmbedding, TableConfig, set_weights)
      from distributed_embeddings_tpu.parallel.hotcache import HotSet
      from distributed_embeddings_tpu.utils import resilience

      # one table per worker: with fewer tables the auto-slicer would
      # shred them into narrow column slices to feed every worker, and
      # the q8 wire pays its 2-byte scale exponent PER SLICE-ROW —
      # diluting the ratio to ~3.0x at width-4 slices.  Tables >= world
      # keeps rows full-width (the representative case for many-table
      # models) so the A/B measures the codec, not the slicer; fusion
      # folds same-width tables back into one group per signature
      # (docs/design.md §24).
      w_world = len(mesh.devices.flat)
      w_configs = [
          TableConfig(1024 * (1 + t % 2), 16 * (1 + t % 2), 'sum')
          for t in range(max(w_world, 2))]
      w_rng = np.random.default_rng(0)
      w_weights = [
          (w_rng.normal(size=(c.input_dim, c.output_dim)) * 0.05)
          .astype(np.float32) for c in w_configs]
      w_hot = {t: HotSet(t, np.sort(w_rng.choice(
          c.input_dim, 64, replace=False)).astype(np.int64))
               for t, c in enumerate(w_configs)}
      w_batch = 8 * w_world
      w_ids = [jnp.asarray(
          w_rng.integers(0, c.input_dim, size=(w_batch, 4)),
          dtype=jnp.int32) for c in w_configs]

      def _wire_arm(table_dtype, wire):
        d = DistributedEmbedding(w_configs, mesh=mesh, dp_input=True,
                                 hot_cache=dict(w_hot),
                                 table_dtype=table_dtype,
                                 wire_dtype=wire)
        out = [np.asarray(o) for o in d.apply(set_weights(d, w_weights),
                                              w_ids)]
        legs = [leg for lp in d._lookup_plans.values()
                for leg in lp.legs]
        return out, legs

      def _wire_leg_bytes(legs):
        # codec-targeted legs only: on a wire-on arm those carry
        # wire != None; their payload_nbytes is the f32-wire
        # counterfactual the off arm ships for the same legs
        on = sum(int(l.nbytes) for l in legs if l.wire)
        off = sum(int(l.payload_bytes) for l in legs if l.wire)
        return off, on

      out_i_off, _ = _wire_arm('int8', None)
      out_i_on, legs_i = _wire_arm('int8', 'table')
      out_f_off, _ = _wire_arm(None, None)
      out_f_on, legs_f = _wire_arm(None, 'bfloat16')
      drift_i = max(float(np.max(np.abs(a - b)))
                    for a, b in zip(out_i_off, out_i_on))
      for a, b in zip(out_i_off, out_i_on):
        # int8 table on the int8 wire is bit-exact BY CONTRACT — a
        # nonzero delta is a codec bug, not noise; refuse to journal it
        # as a mere drift number
        np.testing.assert_array_equal(a, b)
      # drift scaled by each output's max magnitude (the §24 pinned-
      # bound definition the parity tests use) — an elementwise
      # relative error would blow up on near-zero combined sums and
      # journal noise, not codec truth
      drift_f = max(
          float(np.max(np.abs(a - b)) / max(float(np.max(np.abs(a))),
                                            1e-6))
          for a, b in zip(out_f_off, out_f_on))
      off_i, on_i = _wire_leg_bytes(legs_i)
      off_f, on_f = _wire_leg_bytes(legs_f)
      if off_i != off_f:
        raise AssertionError(
            f'wire_ab arms disagree on the f32-wire baseline bytes '
            f'({off_i} vs {off_f}) — the twin id streams diverged')
      wire_stats = {
          'wire_ab_bytes_off': int(off_i),
          'wire_ab_bytes_int8': int(on_i),
          'wire_ab_bytes_bf16': int(on_f),
          'wire_ab_ratio_int8': round(off_i / max(on_i, 1), 3),
          'wire_ab_ratio_bf16': round(off_f / max(on_f, 1), 3),
          'wire_ab_drift_int8': drift_i,
          'wire_ab_drift_bf16': round(drift_f, 6),
      }
      resilience.journal('wire_ab', **wire_stats)
    except Exception as e:
      wire_stats = {'wire_ab_error': f'{type(e).__name__}: {e}'}

  # Quantized table storage A/B (parallel/quantization.py, design §12;
  # ISSUE 7).  The OFF arm is the headline step (unquantized, program-
  # identical to pre-PR); the ON arm re-measures the same model with
  # per-row-scaled int8/fp8 payloads under the same warmup discipline
  # and min-of-k windows.  The byte counters are EXACT (plan-derived
  # row-bytes accounting, hardware-independent): table_bytes_per_row is
  # payload-only with the per-row scale overhead journaled by name
  # alongside, so the honest all-in ratio is one line away.  Never
  # fatal.
  quant_stats = None
  if use_quant:
    try:
      from distributed_embeddings_tpu.parallel import (
          quantization as quant_lib)
      item = jnp.dtype(args.param_dtype).itemsize
      off_b = quant_lib.table_bytes_stats(model.dist_embedding.plan,
                                          item)
      model_q = SyntheticModel(config,
                               mesh=mesh,
                               dp_input=True,
                               row_slice=args.row_slice,
                               param_dtype=jnp.dtype(args.param_dtype),
                               compute_dtype=compute_dtype,
                               packed_storage=args.packed_storage,
                               lookup_impl=args.lookup_impl,
                               table_dtype=quant_dtype)
      on_b = quant_lib.table_bytes_stats(model_q.dist_embedding.plan,
                                         item)
      q_params = model_q.init(0)
      # quantization never changes the id streams, so the headline
      # run's calibrated capacities describe this arm exactly
      q_raw = make_hybrid_train_step(model_q.dist_embedding,
                                     head_loss_fn, optimizer, emb_opt,
                                     jit=False)
      copts = ({'exec_time_optimization_effort': -1.0,
                'memory_fitting_effort': -1.0}
               if args.fast_compile else None)
      q_step = jax.jit(
          lambda st, batch: q_raw(st, list(batch[0][1]),
                                  (batch[0][0], batch[1])),
          donate_argnums=(0,), compiler_options=copts)
      qstate = init_hybrid_train_state(model_q.dist_embedding, q_params,
                                       optimizer, emb_opt)
      for i in range(max(3, args.warmup)):
        qstate, qloss = q_step(qstate, pool[i % len(pool)])
      sync_loss(qloss, 'quantized-storage warmup sync')
      q_window_ms = []
      i = 0
      for wsteps in split_windows(args.steps, args.measure_windows):
        t0 = time.perf_counter()
        for _ in range(wsteps):
          qstate, qloss = q_step(qstate, pool[i % len(pool)])
          i += 1
        sync_loss(qloss, f'quantized-storage window sync at step {i}')
        q_window_ms.append((time.perf_counter() - t0) / wsteps * 1000)
      quant_stats = {
          'table_dtype': quant_dtype,
          'table_bytes_per_row_off': off_b['table_bytes_per_row'],
          'table_bytes_per_row': on_b['table_bytes_per_row'],
          'table_scale_bytes_per_row': on_b['table_scale_bytes_per_row'],
          'table_total_bytes_per_row': on_b['table_total_bytes_per_row'],
          'table_bytes_reduction': round(
              off_b['table_bytes_per_row'] /
              max(on_b['table_bytes_per_row'], 1e-9), 3),
          'table_rows': on_b['table_rows'],
          'quant_ab_off_ms': round(step_ms, 3),
          'quant_ab_on_ms': round(min(q_window_ms), 3),
          'quant_window_ms': [round(x, 3) for x in q_window_ms],
      }
      del qstate
    except Exception as e:
      quant_stats = {'quant_storage_error': f'{type(e).__name__}: {e}'}

  # Host-DRAM cold-tier phase (parallel/coldtier.py, design §12;
  # ISSUE 7).  The per-device HBM budget is sized (auto: ~60% of the
  # quantized arm's resident table bytes) so the tables do NOT fit
  # without the tier — the same plan with cold_tier off must REFUSE
  # with the OOM-shaped construction error, and that refusal is
  # journaled as part of the artifact.  The run streams tail rows
  # host->device through ColdFetchPipeline (the fetch pre-pass double-
  # buffered behind device steps); counters are exact per-batch row/
  # byte accounting and cold_tier_overlap_pct is DIRECTLY measured
  # from consumer blocked time (the CsrFeed accounting, never inferred
  # from a wall-clock subtraction).  Never fatal.
  tier_stats = None
  if use_tier:
    try:
      from distributed_embeddings_tpu.parallel import (
          coldtier as coldtier_lib)
      tier_dtype = quant_dtype if use_quant else None
      probe = SyntheticModel(config,
                             mesh=mesh,
                             dp_input=True,
                             row_slice=args.row_slice,
                             param_dtype=jnp.dtype(args.param_dtype),
                             compute_dtype=compute_dtype,
                             packed_storage=args.packed_storage,
                             lookup_impl=args.lookup_impl,
                             hot_cache=hs,
                             table_dtype=tier_dtype)
      full_bytes = probe.dist_embedding.plan.resident_table_bytes()
      budget = (int(args.cold_tier_budget_mb * 2**20)
                if args.cold_tier_budget_mb
                else max(int(full_bytes * 0.6),
                         probe.dist_embedding.plan.hot_buffer_bytes()
                         + 4096))
      del probe
      mk = dict(config=config, mesh=mesh, dp_input=True,
                row_slice=args.row_slice,
                param_dtype=jnp.dtype(args.param_dtype),
                compute_dtype=compute_dtype,
                packed_storage=args.packed_storage,
                lookup_impl=args.lookup_impl, hot_cache=hs,
                table_dtype=tier_dtype, device_hbm_budget=budget)
      # the off arm MUST refuse: same budget, no tier — the §12
      # OOM-shaped construction error, journaled verbatim
      try:
        SyntheticModel(**mk)
        refusal = ('MISSING: over-budget plan without cold_tier did '
                   'NOT refuse — §12 gate broken')
      except ValueError as e:
        refusal = str(e)[:200]
      model_t = SyntheticModel(**mk, cold_tier=True)
      t_params = model_t.init(0)
      emb_opt_t = emb_opt
      if args.auto_capacity:
        import dataclasses as _dc
        from distributed_embeddings_tpu.parallel import (
            calibrate_capacity_rows)
        emb_opt_t = _dc.replace(
            emb_opt,
            capacity_rows=calibrate_capacity_rows(
                model_t.dist_embedding,
                [jnp.asarray(c) for c in cats0],
                params=t_params['embedding']))
      # make_hybrid_train_step owns the tier protocol (host fetch
      # outside the jit boundary, writeback after the step) — use its
      # jitted runner directly instead of bench's own jit wrapper
      t_run = make_hybrid_train_step(model_t.dist_embedding,
                                     head_loss_fn, optimizer, emb_opt_t,
                                     jit=True, donate=False)
      tstate = init_hybrid_train_state(model_t.dist_embedding, t_params,
                                       optimizer, emb_opt_t)
      n_meas = max(args.steps, 8)
      n_warm = max(3, args.warmup)

      def cats_src():
        for j in range(n_warm + n_meas):
          yield [np.asarray(c) for c in gen.pool[j % len(gen.pool)][0][1]]

      pipe = coldtier_lib.ColdFetchPipeline(model_t.dist_embedding,
                                            cats_src())
      fetch_rows_t = 0
      fetch_bytes_t = 0
      fetch_scale_t = 0
      per_group_rows = None
      row_bytes_pg = None
      j = 0
      t0 = None
      for cats, fetch in pipe:
        (num, _), lab = gen.pool[j % len(gen.pool)]
        tstate, tloss = t_run(tstate, cats, (jnp.asarray(num),
                                             jnp.asarray(lab)),
                              cold_fetch=fetch)
        if j >= n_warm:
          fs = coldtier_lib.fetch_stats(model_t.dist_embedding, fetch)
          fetch_rows_t += fs['cold_tier_fetch_rows']
          fetch_bytes_t += fs['cold_tier_fetch_bytes']
          fetch_scale_t += fs['cold_tier_fetch_scale_bytes']
          row_bytes_pg = fs['cold_tier_row_bytes_per_group']
          pg = fs['cold_tier_fetch_rows_per_group']
          per_group_rows = (pg if per_group_rows is None else
                            [a + b for a, b in zip(per_group_rows, pg)])
        j += 1
        if j == n_warm:
          # steady state: batch 0's fetch had no prior step to hide
          # behind, and warmup compiles are not representative walls
          sync_loss(tloss, 'cold-tier warmup sync')
          pipe.reset_stats()
          t0 = time.perf_counter()
      sync_loss(tloss, 'cold-tier measurement sync')
      tier_ms = (time.perf_counter() - t0) / n_meas * 1000
      pstats = pipe.stats()
      tier_stats = coldtier_lib.tier_stats(model_t.dist_embedding)
      tier_stats.update({
          'cold_tier': True,
          'cold_tier_off_refusal': refusal,
          'cold_tier_step_ms': round(tier_ms, 3),
          'cold_tier_steps_measured': n_meas,
          'cold_tier_fetch_rows': int(fetch_rows_t),
          'cold_tier_fetch_bytes': int(fetch_bytes_t),
          'cold_tier_fetch_scale_bytes': int(fetch_scale_t),
          'cold_tier_fetch_rows_per_group': per_group_rows,
          'cold_tier_row_bytes_per_group': row_bytes_pg,
          'cold_tier_build_ms': pstats['build_ms'],
          'cold_tier_blocked_ms': pstats['blocked_ms'],
          'cold_tier_overlap_pct': pstats['overlap_pct'],
      })
      del tstate
    except Exception as e:
      tier_stats = {'cold_tier_error': f'{type(e).__name__}: {e}'}

  # Online-serving phase (serving/, design §14 + §16; ISSUES 9, 12).
  # The trained tables freeze into a lookup-only ServingEngine —
  # quantized to int8 payload+scale unless the plan already carries a
  # table_dtype, the production serving shape and 4x less host/device
  # memory for the second table copy this phase holds — with a
  # serving-sized READ-ONLY hot cache (state_copies=0: no optimizer
  # slots to fund) and the compiled-shape bucket ladder (warmup
  # AOT-compiles every rung; no arm ever eats a compile).  All THREE
  # arms are measured directly over the same request stream cut from
  # the bench traffic: per-request submit->demux latencies from the
  # batcher itself (p50/p99), sequential ladder-rung dispatches for
  # the no-batch arm, the monolithic serial batcher as the middle arm,
  # and the ladder+pipelined batcher as the headline — plus the
  # pad-waste and pipeline-overlap accounting (design §16).  Never
  # fatal.
  serve_stats = None
  use_serve = args.serve
  if use_serve is None:
    use_serve = args.trainer == 'sparse'
  if use_serve:
    try:
      from distributed_embeddings_tpu import serving as serving_lib
      from distributed_embeddings_tpu.parallel import (
          hotcache as hotcache_lib, quantization as serve_quant)
      from distributed_embeddings_tpu.parallel.checkpoint import (
          QuantizedWeight, export_tables)
      from distributed_embeddings_tpu.models.synthetic import (
          expand_tables as serve_expand)
      dist0 = model.dist_embedding
      int8 = serve_quant.resolve_table_dtype('int8')
      bundle_tables = []
      for t in export_tables(dist0, state.params['embedding']):
        # quantize f32 exports table-by-table so only one full f32
        # table is ever live beyond the export itself
        bundle_tables.append(
            t if isinstance(t, QuantizedWeight)
            else QuantizedWeight.from_values(np.asarray(t), int8))
      denom = dist0.world_size * dist0.num_slices
      sv_batch = max(denom, (int(args.serve_batch) // denom) * denom)
      serve_hot = None
      if args.alpha > 0:
        serve_cfgs, _, _ = serve_expand(config)
        serve_hot = hotcache_lib.analytic_power_law_hot_sets(
            serve_cfgs, args.alpha, coverage=args.serve_hot_coverage,
            budget_bytes=int(args.serve_hot_budget_mb * 2**20),
            state_copies=0)
      requests = serving_lib.split_requests(
          [np.asarray(c) for c in cats0], sizes=(1, 2, 4, 8),
          limit=args.serve_requests)
      sv_buckets = None
      if args.serve_buckets:
        sv_buckets = [int(b) for b in
                      str(args.serve_buckets).split(',') if b.strip()]
      engine = serving_lib.ServingEngine(
          dist0.table_configs, bundle_tables, batch_size=sv_batch,
          mesh=mesh, input_table_map=list(dist0.plan.input_table_map),
          hotness=[1 if np.asarray(c).ndim == 1 else
                   np.asarray(c).shape[1] for c in cats0],
          buckets=sv_buckets,
          hot_sets=serve_hot)
      serve_stats = serving_lib.measure_serving(
          engine, requests, max_delay_ms=args.serve_max_delay_ms,
          concurrency=args.serve_concurrency)
      serve_stats.update({
          'serve_table_dtype': (engine.dist.quant.name
                                if engine.dist.quant else None),
          'serve_hot_rows_replicated': (
              int(sum(h.size for h in serve_hot.values()))
              if serve_hot else 0),
          'serve_hot_hit_rate': (
              serving_lib.hot_hit_rate(
                  serve_hot, dist0.table_configs,
                  list(dist0.plan.input_table_map), requests)
              if serve_hot else None),
      })
      # Overload arm (design §23): the same frozen tables behind a
      # ServingEnginePool driven open-loop past capacity — per-class
      # latency under pressure, the shed ledger, the degraded-mode
      # watermark crossings and (replicas > 1) a mid-burst failover
      # drill.  Never fatal, independently of the three-arm block.
      use_overload = args.serve_overload
      if use_overload is None:
        use_overload = True
      if use_overload:
        try:
          replicas = max(1, int(args.serve_replicas))
          pool_engines = [engine]
          for _ in range(replicas - 1):
            pool_engines.append(serving_lib.ServingEngine(
                dist0.table_configs, bundle_tables, batch_size=sv_batch,
                mesh=mesh,
                input_table_map=list(dist0.plan.input_table_map),
                hotness=[1 if np.asarray(c).ndim == 1 else
                         np.asarray(c).shape[1] for c in cats0],
                buckets=sv_buckets,
                hot_sets=serve_hot))
          serve_stats.update(serving_lib.measure_overload(
              pool_engines, requests,
              max_delay_ms=args.serve_max_delay_ms,
              deadline_ms=args.serve_deadline_ms,
              priority_mix=args.serve_priority_mix,
              offered_qps=args.serve_overload_qps,
              failover_after=(len(requests) // 2
                              if replicas > 1 else None)))
          del pool_engines
        except Exception as e:
          serve_stats['serving_overload_error'] = (
              f'{type(e).__name__}: {e}')
      del engine, bundle_tables
    except Exception as e:
      serve_stats = {'serving_error': f'{type(e).__name__}: {e}'}

  # Observability A/B (obs/, design §15; ISSUE 11).  The HEADLINE
  # windows are the off arm — obs disabled is the default and its
  # entry points are single flag checks, so the official number is
  # program-identical to the obs-off build.  The on arm re-runs the
  # same min-of-k loop with the tracer + registry armed and one
  # 'train/step' span + counter per step (exactly what fit() emits).
  # The journaled obs_overhead_pct is DIRECT (the measured per-step
  # instrumentation wall amortized against the headline step, the
  # audit phase's honesty rule): the two-arm window subtraction also
  # rides the artifact, sign preserved, but is noise-bound on this
  # host.  Never fatal.
  obs_stats = None
  use_obs = args.obs
  if use_obs is None:
    use_obs = args.trainer == 'sparse'
  if use_obs:
    try:
      from distributed_embeddings_tpu import obs as obs_lib
      from distributed_embeddings_tpu.obs import metrics as obs_metrics
      from distributed_embeddings_tpu.obs import trace as obs_trace
      obs_lib.reset()
      obs_lib.enable(trace_path=args.trace_path)
      obs_window_ms = []
      oi = 0
      for wsteps in split_windows(args.steps, args.measure_windows):
        t0 = time.perf_counter()
        for _ in range(wsteps):
          with obs_trace.span('train/step', step=oi + 1):
            state, loss = step(state, pool[(i + oi) % len(pool)])
          obs_metrics.inc('train.steps')
          oi += 1
        sync_loss(loss, f'obs-arm window sync at step {oi}')
        obs_window_ms.append((time.perf_counter() - t0) / wsteps * 1000)
      obs_on_ms = min(obs_window_ms)
      # device-time attribution (obs/devprof.py, design §19): AFTER
      # every measured window (devprof is opt-in and never touches a
      # headline loop), with the tracer still armed so the per-phase
      # events land on this trace's device lane.  Never fatal to the
      # obs block.
      use_devprof = args.devprof
      if use_devprof is None:
        use_devprof = args.trainer == 'sparse'
      devprof_stats = None
      # an explicit --devprof on an unsupported combination must reach
      # devprof's own refusal (journaled as devprof_error with the
      # actionable message), never be dropped silently
      if use_devprof:
        try:
          from distributed_embeddings_tpu.obs import devprof as devprof_lib
          # profile with the HEADLINE emb optimizer (calibrated
          # capacities): the attributed apply phase is the real step's
          # apply, not a default-capacity stand-in
          prof = devprof_lib.profile_step(
              model.dist_embedding, [jnp.asarray(c) for c in cats0],
              params=state.params['embedding'], emb_optimizer=emb_opt,
              reps=3)
          devprof_stats = devprof_lib.artifact_block(prof)
        except Exception as e:
          devprof_stats = {'devprof_error': f'{type(e).__name__}: {e}'}
      # one periodic registry snapshot through the resilience sink —
      # the journaled proof the metrics path is wired end to end
      obs_metrics.journal_snapshot(step=oi, source='bench')
      obs_stats = obs_block(step_ms, obs_on_ms,
                            trace_path=args.trace_path)
      if devprof_stats:
        obs_stats.update(devprof_stats)
      obs_lib.reset()
    except Exception as e:
      obs_stats = {'obs_error': f'{type(e).__name__}: {e}'}

  # Static-analysis gate counts (design §17).  Pure host-side AST work
  # (~a second); never fatal to the artifact.
  lint_stats = None
  try:
    lint_stats = lint_block()
  except Exception as e:
    lint_stats = {'lint_error': f'{type(e).__name__}: {e}'}

  # IR-analysis gate counts (design §18): the flagship program catalog
  # traced+compiled on this backend (tiny programs; a repeat run rides
  # the persistent compile cache).  Never fatal.
  graphlint_stats = None
  try:
    graphlint_stats = graphlint_block()
  except Exception as e:
    graphlint_stats = {'graphlint_error': f'{type(e).__name__}: {e}'}

  # Cross-rank protocol gate counts (design §22): commlint's four
  # passes over this tree + the flagship ledger; the emission pass
  # re-traces the flagship catalog (same cost class as graphlint's
  # block).  Never fatal.
  commlint_stats = None
  try:
    commlint_stats = commlint_block()
  except Exception as e:
    commlint_stats = {'commlint_error': f'{type(e).__name__}: {e}'}

  n_dev = len(devices)
  backend = devices[0].platform
  # the baselines are AT global batch 65536: a reduced-batch chip run
  # (the sweep's quick ladder step) is on-chip evidence but not a
  # comparable line — never compute vs_baseline against a different batch
  full_batch = args.batch_size == 65536
  baseline, baseline_ndev = pick_baseline(args.model, n_dev)
  metric = (f'synthetic-{args.model} train step time, global batch '
            f'{args.batch_size}, Adagrad, {n_dev} {backend} chip(s)')
  if baseline is not None:
    metric += f' (baseline: {baseline_ndev}xA100 {baseline} ms)'
  if args.fast_compile:
    # a low-effort executable may run slower than the default-effort
    # one: the line must say so or it reads as the official number
    metric += ' [fast_compile: low XLA optimization effort]'
  if args.model == 'criteo':
    # DLRM-shaped model: the reference's headline metric is throughput
    # (9.16M samples/s TF32 / 10.4M AMP on 8xA100, examples/dlrm/
    # README.md:7-8); report it alongside ms/step for comparability.
    # No vs_baseline: the synthetic criteo config's 100k-row tables are
    # a shape proxy, not the Criteo-1TB vocabularies.
    metric += (f' [throughput {args.batch_size / (step_ms / 1000) / 1e6:.3f}'
               f'M samples/s; reference DLRM 8xA100 TF32: 9.158M]')
  if args.segwalk_apply and args.trainer == 'sparse':
    # without this note an A/B run can silently measure the XLA
    # fallback and read as "kernel is no faster"
    from distributed_embeddings_tpu.utils.apply_eligibility import (
        eligibility_line)
    metric += ' [' + eligibility_line(
        model.dist_embedding, args.param_dtype,
        args.segwalk_apply, accum_dtype=args.accum_dtype) + ']'
  if args.lookup_impl == 'sparsecore':
    # the resolved backend AND the engaged-group count must be on the
    # line: an emulation number must never read as SC hardware, and a
    # run whose groups all declined the SC gate (bf16, very wide) must
    # never read as a sparsecore measurement at all
    from distributed_embeddings_tpu.parallel import sparsecore as sc_lib
    plan = model.dist_embedding.plan
    engaged = len(sc_lib.engaged_groups(plan, args.param_dtype))
    metric += (f' [sparsecore backend: {sc_backend}; '
               f'{engaged}/{len(plan.groups)} groups on the SC path]')
  result = {
      'metric': metric,
      'value': round(step_ms, 3),
      'unit': 'ms/step',
      'vs_baseline': (round(baseline / step_ms, 4)
                      if baseline and full_batch else None),
      # a reduced-batch run is not comparable with the baselines:
      # flagged, instead of relying on the metric prose
      'comparable': full_batch,
      # compile+warmup wall time: how much of a driver timeout budget
      # the warmup burned; a warm compile cache shortens it
      'warmup_s': round(warmup_s, 1),
      # driver-host load hardening: every window's mean plus the host
      # load averages, so the min-of-k headline number carries its own
      # noise evidence
      'window_ms': [round(w, 3) for w in window_ms],
      'loadavg': host_load(),
      'available_mem_mb': host_mem(),
      'schema_version': SCHEMA_VERSION,
      # the headline mesh's axis sizes (design §20): perf_sentinel only
      # compares like-for-like, and a (2, 4) hierarchical line must
      # never diff against an (8,) flat one
      'mesh_shape': [int(s) for s in mesh.devices.shape],
      'packed_storage': args.packed_storage,
      'fast_compile': args.fast_compile,
      'lookup_impl': args.lookup_impl,
      'sha': repo_sha(),
  }
  if csr_stats:
    result.update(csr_stats)
  if hot_stats:
    result.update(hot_stats)
  if a2a_stats:
    result.update(a2a_stats)
  if dcn_stats:
    result.update(dcn_stats)
  if wire_stats:
    result.update(wire_stats)
  if quant_stats:
    result.update(quant_stats)
  if tier_stats:
    result.update(tier_stats)
  if audit_stats:
    result.update(audit_stats)
  if serve_stats:
    result.update(serve_stats)
  if obs_stats:
    result.update(obs_stats)
  if lint_stats:
    result.update(lint_stats)
  if graphlint_stats:
    result.update(graphlint_stats)
  if commlint_stats:
    result.update(commlint_stats)
  finish(result)


class _Watchdog(BaseException):
  # BaseException, deliberately: the alarm is one-shot, and a broad
  # `except Exception` anywhere in main()/JAX internals would otherwise
  # swallow it and leave the run unbounded — the exact driver-kill/
  # no-artifact failure this watchdog exists to prevent
  pass


def failure_line(error, **extra):
  """The JSON line of a run that measured nothing."""
  return {
      'metric': 'benchmark failed',
      'value': None,
      'unit': 'ms/step',
      'vs_baseline': None,
      'error': error,
      **extra,
      'sha': repo_sha(),
  }


def _arm_watchdog():
  """A cold full-size run (init + calibration + compiles) can take tens
  of minutes; if the DRIVER's timeout kills the process first there is
  no line at all.  Self-bound the wall time instead
  (DET_BENCH_WATCHDOG_S, default 2400 s, 0 disables) so a too-slow run
  still prints a labelled failure line — and exits 1.

  Two layers: SIGALRM raises _Watchdog with a full traceback (XLA's
  compile polls signals), and a daemon-thread backstop 90 s later
  prints the line and hard-exits — Python signal handlers only run
  when the main thread executes bytecode, so a blocking C call that
  never polls would otherwise outlive the alarm and hit the driver's
  kill with nothing printed."""
  import signal
  import threading
  budget = float(os.environ.get('DET_BENCH_WATCHDOG_S', '2400'))
  if budget <= 0:
    return

  def backstop():
    emit(failure_line(
        f'watchdog backstop: wall time exceeded {budget:.0f}s + 90s '
        'grace (main thread stuck in a non-interruptible call)'))
    os._exit(1)

  timer = threading.Timer(budget + 90, backstop)
  timer.daemon = True
  timer.start()
  _WATCHDOG_STATE['timer'] = timer
  if not hasattr(signal, 'SIGALRM'):
    return

  def fire(signum, frame):
    raise _Watchdog(f'wall time exceeded {budget:.0f}s')

  signal.signal(signal.SIGALRM, fire)
  signal.alarm(max(1, int(round(budget))))


_WATCHDOG_STATE = {}


def _disarm_watchdog():
  import signal
  if hasattr(signal, 'SIGALRM'):
    signal.alarm(0)
  timer = _WATCHDOG_STATE.pop('timer', None)
  if timer is not None:
    timer.cancel()


def run(main_fn=main):
  """``main_fn`` under the watchdog; returns the process exit code.  A
  raise (the watchdog's included) prints the failure line and is 1 —
  never carried past as a clean exit."""
  _arm_watchdog()
  try:
    main_fn()
    return 0
  except (Exception, _Watchdog) as e:
    emit(failure_line(f'{type(e).__name__}: {e}',
                      trace_tail=traceback.format_exc()[-1500:]))
    return 1
  finally:
    _disarm_watchdog()  # a late fire must not follow the last line


if __name__ == '__main__':
  sys.exit(run())
