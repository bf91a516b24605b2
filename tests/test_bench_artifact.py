"""Artifact-robustness helpers in bench.py: the driver parses ONE JSON
line per run, so the provenance/watchdog/exit-code machinery around it
needs pinning (sha provenance, self-bounded wall time, and no failure
that leaves exit code 0)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench():
  spec = importlib.util.spec_from_file_location(
      'bench_for_test', os.path.join(_ROOT, 'bench.py'))
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod


def test_repo_sha_prefers_snapshot_file_then_git(bench):
  # the live checkout has no SNAPSHOT_SHA: git answers
  sha = bench.repo_sha()
  assert sha and len(sha) >= 7


def test_bench_command_refuses_a_cpu():
  """`JAX_PLATFORMS=cpu python bench.py` times nothing: exit code 1 and
  a plain reason, no JSON line with a value."""
  proc = subprocess.run(
      [sys.executable, os.path.join(_ROOT, 'bench.py')],
      env={**os.environ, 'JAX_PLATFORMS': 'cpu'}, cwd=_ROOT,
      capture_output=True, text=True, timeout=120)
  assert proc.returncode == 1, (proc.returncode, proc.stderr[-500:])
  assert 'no TPU' in proc.stderr
  assert proc.stdout.strip() == ''


def test_raised_phase_makes_exit_code_nonzero(bench, capsys):
  """A secondary phase that raised still prints its `*_error` key, and
  the run exits non-zero; a clean line exits through `run` as 0."""
  with pytest.raises(SystemExit) as exc:
    bench.run(lambda: bench.finish(
        {'metric': 'm', 'value': 1.5, 'serving_error': 'ValueError: x'}))
  assert exc.value.code not in (0, None)
  assert 'serving_error' in str(exc.value.code)
  line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
  assert line['serving_error'] == 'ValueError: x'  # still on the line
  assert bench.run(lambda: bench.finish({'metric': 'm', 'value': 1.5})) == 0


def test_exception_and_watchdog_exit_one(bench, capsys):
  """An exception in main() — the SIGALRM watchdog's included — prints
  the labelled failure line and is exit code 1, never 0."""
  def boom():
    raise RuntimeError('compile exploded')

  def slow():
    raise bench._Watchdog('wall time exceeded 1s')

  for fn, text in ((boom, 'compile exploded'), (slow, 'wall time')):
    assert bench.run(fn) == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line['value'] is None and text in line['error']


def test_watchdog_arm_disarm_cycle(bench, monkeypatch):
  import signal
  monkeypatch.setenv('DET_BENCH_WATCHDOG_S', '60')
  bench._arm_watchdog()
  try:
    assert signal.getitimer(signal.ITIMER_REAL)[0] > 0  # alarm armed
    assert bench._WATCHDOG_STATE.get('timer') is not None
  finally:
    bench._disarm_watchdog()
  assert signal.getitimer(signal.ITIMER_REAL)[0] == 0
  assert 'timer' not in bench._WATCHDOG_STATE


def test_watchdog_disabled_by_zero(bench, monkeypatch):
  import signal
  monkeypatch.setenv('DET_BENCH_WATCHDOG_S', '0')
  bench._arm_watchdog()
  assert signal.getitimer(signal.ITIMER_REAL)[0] == 0
  assert 'timer' not in bench._WATCHDOG_STATE


def test_hot_cache_counters_present_and_consistent():
  """The ISSUE-5 journaled proof: the exchange/scatter counters bench
  folds into every artifact exist, cross-check (hit + cold fractions
  sum to 1; rows sent never exceed the occurrence count), and show the
  acceptance-bar reductions on the power-law synthetic-tiny workload —
  so a future regression that silently disables the cache (hit rate 0,
  ratios 1x) fails tier-1."""
  import jax
  import numpy as np
  from distributed_embeddings_tpu.models.synthetic import (
      SYNTHETIC_MODELS, InputGenerator, SyntheticModel, expand_tables)
  from distributed_embeddings_tpu.parallel import create_mesh, hotcache

  config = SYNTHETIC_MODELS['tiny']
  tables, _, _ = expand_tables(config)
  gen = InputGenerator(config, 1024, alpha=1.05, num_batches=1, seed=0)
  (_, cats), _ = gen.pool[0]
  # the counters route ids host-side from the plan alone — no params
  # materialise, so the full tiny table SET is fine in a unit test
  model = SyntheticModel(config, mesh=create_mesh(jax.devices()[:1]),
                         dp_input=True)
  hot_sets = hotcache.analytic_power_law_hot_sets(tables, 1.05, 0.85)
  c = hotcache.measure_exchange_counters(model.dist_embedding, cats,
                                         hot_sets=hot_sets)
  for key in ('alltoall_rows_sent', 'alltoall_rows_sent_off',
              'unique_cold_rows', 'hot_hit_rate',
              'cold_occurrence_fraction', 'scatter_rows_per_step',
              'scatter_rows_per_step_off', 'total_id_occurrences'):
    assert key in c, key
  # self-consistency: independently counted fractions close to 1
  assert abs(c['hot_hit_rate'] + c['cold_occurrence_fraction'] - 1.0) \
      < 1e-6, c
  # rows crossing the exchange can never exceed the batch id count
  assert c['alltoall_rows_sent'] <= c['total_id_occurrences'], c
  assert c['unique_cold_rows'] == c['alltoall_rows_sent']
  # the acceptance-bar reductions (measured 7.2x / 2.8x at this batch):
  # a silently disabled cache collapses both to 1x and fails here
  assert c['alltoall_rows_sent_off'] >= 3 * c['alltoall_rows_sent'], c
  assert c['scatter_rows_per_step_off'] >= 2 * c['scatter_rows_per_step'], c
  assert c['hot_hit_rate'] > 0.3, c


def test_schema_version_and_host_pressure_gauges(bench):
  """The ISSUE-15 artifact-schema satellite: the artifact carries a
  schema_version (so tools/perf_sentinel.py can tell an old line from
  a missing key) and BOTH host-pressure gauges — loadavg (since PR 1)
  plus available memory — each registered in the artifact-key
  schema."""
  assert isinstance(bench.SCHEMA_VERSION, int)
  assert bench.SCHEMA_VERSION >= 2
  mem = bench.host_mem()
  assert mem is None or mem > 0
  from distributed_embeddings_tpu.obs import metrics as obs_metrics
  for key in ('schema_version', 'available_mem_mb'):
    assert key in obs_metrics.REGISTERED_ARTIFACT_KEYS, key


@pytest.mark.parametrize('seed', [0, 1, 2])
def test_per_device_counters_reconcile_fuzzed(seed):
  """The ISSUE-15 reconciliation pin, fuzzed over plan/batch/hot-set
  draws on the faked 8-device mesh: the per-device imbalance lists are
  computed on an independent path from the global scalars and must sum
  back to them exactly; the skew gauges derive from the same lists;
  the hottest shard is a real named (group, device) cell."""
  import re
  import jax
  import numpy as np
  from distributed_embeddings_tpu.parallel import (DistributedEmbedding,
                                                   TableConfig,
                                                   create_mesh, hotcache)

  rng = np.random.default_rng(seed)
  n_tables = int(rng.integers(2, 5))
  cfgs = [TableConfig(int(rng.integers(32, 257)),
                      int(rng.choice([8, 16])), 'sum')
          for _ in range(n_tables)]
  mesh = create_mesh(jax.devices()[:8])
  dist = DistributedEmbedding(cfgs, mesh=mesh, dp_input=True)
  batch = 8 * int(rng.integers(4, 17))
  cats = [np.minimum(rng.zipf(1.3, size=(batch,)) - 1,
                     c.input_dim - 1).astype(np.int32) for c in cfgs]
  hot = {}
  for t, c in enumerate(cfgs):
    if rng.random() < 0.7:
      k = int(rng.integers(1, max(2, c.input_dim // 8)))
      hot[t] = hotcache.HotSet(
          t, np.sort(rng.choice(c.input_dim, size=k,
                                replace=False)).astype(np.int64))
  c = hotcache.measure_exchange_counters(dist, cats, hot_sets=hot)
  for key in ('alltoall_rows_sent_per_device',
              'alltoall_rows_sent_off_per_device',
              'hot_hit_rate_per_device',
              'total_id_occurrences_per_device',
              'scatter_rows_per_device', 'exchange_rows_max',
              'exchange_rows_mean', 'hottest_shard'):
    assert key in c, key
  S = 8
  assert len(c['alltoall_rows_sent_per_device']) == S
  # the reconciliation invariant: per-device sums == the global keys
  assert sum(c['alltoall_rows_sent_per_device']) \
      == c['alltoall_rows_sent']
  assert sum(c['alltoall_rows_sent_off_per_device']) \
      == c['alltoall_rows_sent_off']
  assert sum(c['total_id_occurrences_per_device']) \
      == c['total_id_occurrences']
  # occurrence-weighted per-device hit rates reconstruct the global
  weighted = sum(r * n for r, n in
                 zip(c['hot_hit_rate_per_device'],
                     c['total_id_occurrences_per_device']))
  assert abs(weighted / max(1, c['total_id_occurrences'])
             - c['hot_hit_rate']) < 1e-3
  # skew gauges derive from the same per-device list
  assert c['exchange_rows_max'] == max(c['alltoall_rows_sent_per_device'])
  assert c['exchange_rows_mean'] == pytest.approx(
      np.mean(c['alltoall_rows_sent_per_device']), abs=0.01)
  # global scatter = per-group max over devices, summed: it bounds any
  # single device's group-summed scatter from above
  assert c['scatter_rows_per_step'] >= max(c['scatter_rows_per_device'])
  if c['hottest_shard'] is not None:
    assert re.fullmatch(r'g\d+@dev\d+', c['hottest_shard'])


def test_devprof_artifact_keys():
  """The ISSUE-15 device-lane journaled proof, block-level: the
  devprof block bench folds into the artifact carries the pinned keys
  (each registered — test_artifact_keys_registered scans this loop)."""
  from distributed_embeddings_tpu.obs import devprof
  prof = devprof.StepProfile(
      phases={n: 1.0 for n in devprof.STEP_PHASES},
      direct={n: True for n in devprof.STEP_PHASES},
      step_ms=5.0, coverage_pct=100.0,
      cost={'fwd': {'flops': 1.0, 'bytes': 2.0}}, cost_ok=True)
  block = devprof.artifact_block(prof, serve_rung_ms={8: 0.25})
  for key in ('devprof_phase_ms', 'devprof_step_ms',
              'devprof_coverage_pct', 'devprof_cost',
              'devprof_cost_ok', 'devprof_serve_rung_ms'):
    assert key in block, key
  import json
  json.dumps(block)


def test_per_device_artifact_keys_registered():
  """Every per-device imbalance key measure_exchange_counters emits is
  in REGISTERED_ARTIFACT_KEYS (the same scan-pin discipline as the
  scalar counters)."""
  from distributed_embeddings_tpu.obs import metrics as obs_metrics
  for key in ('alltoall_rows_sent_per_device',
              'alltoall_rows_sent_off_per_device',
              'hot_hit_rate_per_device',
              'total_id_occurrences_per_device',
              'scatter_rows_per_device', 'exchange_rows_max',
              'exchange_rows_mean', 'hottest_shard'):
    assert key in obs_metrics.REGISTERED_ARTIFACT_KEYS, key


def test_a2a_overlap_stats_math():
  """The journaled exchange-overlap block (design §11): the derived
  a2a_overlap_pct is (off - on) / exchange clamped to [0, 1], a
  noise-negative delta reads as 0, and a missing exchange wall (one
  device) reads as 0 rather than dividing by zero."""
  from distributed_embeddings_tpu.parallel import overlap
  assert overlap.overlap_pct(100.0, 90.0, 20.0) == 0.5
  assert overlap.overlap_pct(100.0, 70.0, 20.0) == 1.0   # clamp high
  assert overlap.overlap_pct(100.0, 101.0, 20.0) == 0.0  # noise-negative
  assert overlap.overlap_pct(100.0, 90.0, 0.0) == 0.0    # no exchange
  block = overlap.a2a_overlap_stats(100.0, 90.0, 20.0, 4,
                                    group_chunks=[4, 2, 1],
                                    window_ms=[91.0, 90.0, 92.0])
  assert block['a2a_overlap_pct'] == 0.5
  assert block['overlap_chunks'] == 4
  assert block['a2a_group_chunks'] == [4, 2, 1]
  assert 0.0 <= block['a2a_overlap_pct'] <= 1.0
  # chunk geometry: uneven splits tile [0, n) exactly, never exceed the
  # slot count, and chunks=1 is the monolithic single range
  assert overlap.chunk_bounds(10, 4) == [(0, 3), (3, 6), (6, 8), (8, 10)]
  assert overlap.chunk_bounds(3, 8) == [(0, 1), (1, 2), (2, 3)]
  assert overlap.chunk_bounds(7, 1) == [(0, 7)]


def test_a2a_overlap_measured_and_off_arm_counters_unchanged():
  """The ISSUE-6 journaled proof, both halves.

  (1) a2a_overlap_pct derives from a REAL exchange-only measurement
  (measure_exchange_ms on the faked mesh) and lands in [0, 1].

  (2) The off arm is program-identical to pre-PR: its exchange
  counters (measure_exchange_counters on the overlap_chunks=1 plan)
  EXACTLY reproduce the PR 5 journaled values for the same workload
  (power-law tiny, batch 4096, coverage 0.85, seed 0) — the counters
  are exact host-side id-stream accounting, independent of hardware,
  so a silently-changed baseline (different plan, different dedup,
  different hot selection) fails tier-1 here.  The chunked plan must
  produce the SAME counters: chunk boundaries move buffer slices,
  never stream contents."""
  import jax
  import numpy as np
  from distributed_embeddings_tpu.models.synthetic import (
      SYNTHETIC_MODELS, InputGenerator, SyntheticModel, expand_tables)
  from distributed_embeddings_tpu.parallel import (create_mesh, hotcache,
                                                   overlap)

  config = SYNTHETIC_MODELS['tiny']
  tables, _, _ = expand_tables(config)
  gen = InputGenerator(config, 4096, alpha=1.05, num_batches=1, seed=0)
  (_, cats), _ = gen.pool[0]
  # 1-device mesh: the PR 5 journal line was measured on the 1-chip CPU
  # fallback, and the per-(source device, dest slot) dedup counters are
  # mesh-size-dependent — the pin must replay the journal's exact mesh
  mesh = create_mesh(jax.devices()[:1])
  off = SyntheticModel(config, mesh=mesh, dp_input=True)
  on = SyntheticModel(config, mesh=mesh, dp_input=True, overlap_chunks=4)
  hot_sets = hotcache.analytic_power_law_hot_sets(tables, 1.05, 0.85)

  # -- (2) exact off-arm counters, pinned to the PR 5 journal ------------
  pr5 = {'alltoall_rows_sent_off': 348160, 'alltoall_rows_sent': 40766,
         'scatter_rows_per_step_off': 103731, 'scatter_rows_per_step': 40446}
  for name, model in (('off', off), ('chunked', on)):
    c = hotcache.measure_exchange_counters(model.dist_embedding, cats,
                                           hot_sets=hot_sets)
    for k, v in pr5.items():
      assert c[k] == v, (name, k, c[k], v)
    assert round(c['hot_hit_rate'], 3) == 0.591, (name, c['hot_hit_rate'])

  # -- (1) a real exchange measurement and a [0, 1] journaled pct --------
  small = InputGenerator(config, 256, alpha=1.05, num_batches=1, seed=0)
  (_, cats_small), _ = small.pool[0]
  import jax.numpy as jnp
  ex_ms = overlap.measure_exchange_ms(
      off.dist_embedding, [jnp.asarray(x) for x in cats_small],
      chunks=1, repeats=2)
  assert ex_ms > 0.0
  block = overlap.a2a_overlap_stats(10.0, 9.0, ex_ms, 4)
  assert 'a2a_overlap_pct' in block
  assert 0.0 <= block['a2a_overlap_pct'] <= 1.0


def test_serving_artifact_keys():
  """The ISSUE-9/12 journaled proof: the serving three-arm A/B block
  bench folds into the artifact carries the pinned keys (serve_p50_ms /
  serve_p99_ms / serve_qps + the monolithic and no-batch arms, the
  bucket-ladder padding accounting and the pipeline overlap), the
  percentiles are ordered, every arm's QPS is a real measurement, and
  the ladder strictly reduces padding vs the monolithic arm — so a
  future change that silently drops the serving measurement (or
  renames its keys, or disables the ladder) fails tier-1 here."""
  import jax
  import numpy as np
  from distributed_embeddings_tpu import serving
  from distributed_embeddings_tpu.parallel import (TableConfig,
                                                   create_mesh, hotcache)

  cfgs = [TableConfig(64, 8, 'sum'), TableConfig(40, 8, 'sum')]
  rng = np.random.default_rng(0)
  weights = [(rng.normal(size=(c.input_dim, c.output_dim)) * 0.1)
             .astype(np.float32) for c in cfgs]
  hot = {0: hotcache.HotSet(0, np.arange(8))}
  engine = serving.ServingEngine(
      cfgs, weights, batch_size=16,
      mesh=create_mesh(jax.devices()[:1]), hot_sets=hot)
  cats = [rng.integers(0, c.input_dim, size=(32,)).astype(np.int32)
          for c in cfgs]
  requests = serving.split_requests(cats, sizes=(1, 2, 4))
  # concurrency 3 over (1,2,4)-sized requests bounds every merged
  # batch at 7 samples: the monolithic arm MUST launch 16-wide padded
  # batches while the ladder stays on the 2/4/8 rungs — the strict
  # pad-waste reduction below is structural, not timing luck
  st = serving.measure_serving(engine, requests, max_delay_ms=1.0,
                               concurrency=3)
  for key in ('serve_p50_ms', 'serve_p99_ms', 'serve_qps',
              'serve_batches', 'serve_batch_fill', 'serve_requests',
              'serve_batch', 'serve_max_delay_ms', 'serve_concurrency',
              'serve_buckets', 'serve_bucket_launches',
              'serve_rows_launched', 'serve_pad_rows',
              'serve_pad_waste_pct', 'serve_pipeline_overlap_pct',
              'serve_pipeline_merge_demux_ms',
              'serve_pipeline_blocked_ms',
              'serve_mono_p50_ms', 'serve_mono_p99_ms',
              'serve_mono_qps', 'serve_mono_batches',
              'serve_mono_batch_fill', 'serve_mono_pad_waste_pct',
              'serve_nobatch_p50_ms', 'serve_nobatch_p99_ms',
              'serve_nobatch_qps', 'serve_nobatch_pad_waste_pct'):
    assert key in st, key
  assert st['serve_requests'] == len(requests)
  assert 0 < st['serve_p50_ms'] <= st['serve_p99_ms']
  assert 0 < st['serve_mono_p50_ms'] <= st['serve_mono_p99_ms']
  assert st['serve_qps'] > 0 and st['serve_nobatch_qps'] > 0
  assert st['serve_mono_qps'] > 0
  assert 0 < st['serve_batch_fill'] <= 1.0
  # the ISSUE-12 acceptance bar: the ladder strictly reduces padding
  # vs the monolithic full-signature arm over the same stream, the
  # pipeline overlap is a real [0, 1] measurement, and the per-bucket
  # launch counts cover exactly the launched rows
  assert st['serve_pad_waste_pct'] < st['serve_mono_pad_waste_pct']
  assert 0.0 <= st['serve_pipeline_overlap_pct'] <= 1.0
  assert st['serve_buckets'] == list(engine.buckets)
  launched = sum(int(b) * c
                 for b, c in st['serve_bucket_launches'].items())
  assert launched == st['serve_rows_launched'] > 0
  assert all(int(b) in engine.buckets
             for b in st['serve_bucket_launches'])
  # the hit-rate twin bench journals alongside: exact, host-side
  rate = serving.hot_hit_rate(hot, cfgs, [0, 1], requests)
  assert 0.0 <= rate <= 1.0


def test_overload_artifact_keys():
  """The ISSUE-19 journaled proof: the overload A/B block bench folds
  into the artifact carries the pinned serve_over_* keys (per-class
  p50/p99/p99.9, shed counts by class and reason, degraded-mode
  crossings, failover/quarantine counts — design.md §23) plus the
  serve_p999_ms tail the healthy arm gained, so a future change that
  silently drops the overload measurement (or renames its keys) fails
  tier-1 here."""
  import jax
  import numpy as np
  from distributed_embeddings_tpu import serving
  from distributed_embeddings_tpu.parallel import TableConfig, create_mesh

  cfgs = [TableConfig(64, 8, 'sum'), TableConfig(40, 8, 'sum')]
  rng = np.random.default_rng(1)
  weights = [(rng.normal(size=(c.input_dim, c.output_dim)) * 0.1)
             .astype(np.float32) for c in cfgs]
  engine = serving.ServingEngine(
      cfgs, weights, batch_size=16,
      mesh=create_mesh(jax.devices()[:1]))
  cats = [rng.integers(0, c.input_dim, size=(32,)).astype(np.int32)
          for c in cfgs]
  requests = serving.split_requests(cats, sizes=(1, 2, 4), limit=16)
  st = serving.measure_serving(engine, requests, max_delay_ms=1.0,
                               concurrency=3)
  assert st['serve_p999_ms'] >= st['serve_p99_ms'] > 0
  over = serving.measure_overload([engine], requests, max_delay_ms=1.0,
                                  deadline_ms=2000.0, queue_depth=64,
                                  priority_mix=0.5)
  for key in ('serve_over_requests', 'serve_over_served',
              'serve_over_shed', 'serve_over_shed_rate',
              'serve_over_offered_qps', 'serve_over_qps',
              'serve_over_deadline_ms', 'serve_over_priority_mix',
              'serve_over_replicas'):
    assert key in over, key
  for key in ('serve_over_high_p50_ms', 'serve_over_high_p99_ms',
              'serve_over_high_p999_ms', 'serve_over_low_p50_ms',
              'serve_over_low_p99_ms', 'serve_over_low_p999_ms',
              'serve_over_high_shed', 'serve_over_low_shed',
              'serve_over_shed_deadline', 'serve_over_shed_queue_full'):
    assert key in over, key
  for key in ('serve_over_degraded_served', 'serve_over_degraded_enters',
              'serve_over_degraded_exits', 'serve_over_failovers',
              'serve_over_quarantined'):
    assert key in over, key
  assert over['serve_over_requests'] == len(requests)
  assert over['serve_over_served'] + over['serve_over_shed'] \
      == len(requests)
  assert over['serve_over_replicas'] == 1
  assert 0.0 <= over['serve_over_shed_rate'] <= 1.0
  assert over['serve_over_failovers'] == 0


def test_obs_artifact_keys(bench):
  """The ISSUE-11 journaled proof, library-level: the obs block bench
  folds into the artifact carries the pinned keys, the direct-measured
  obs_overhead_pct clears the <= 2 acceptance bar by construction on
  any sane host (one span + one counter per step, microseconds against
  a hundreds-of-ms step), and the metrics digest is a real sha256 —
  so a future change that silently drops the obs measurement (or
  renames its keys) fails tier-1 here."""
  import re
  from distributed_embeddings_tpu import obs
  from distributed_embeddings_tpu.obs import metrics, trace
  obs.reset()
  obs.enable()
  try:
    with trace.span('train/step', step=1):
      metrics.inc('train.steps')
    block = bench.obs_block(500.0, 501.0)
    for key in ('obs_trace', 'obs_trace_path', 'obs_trace_events',
                'obs_off_ms', 'obs_on_ms', 'obs_window_delta_pct',
                'obs_metrics_digest', 'obs_step_call_us',
                'obs_overhead_pct'):
      assert key in block, key
    assert block['obs_trace'] is False     # no trace_path: buffered only
    assert block['obs_trace_events'] >= 1  # the traced step is counted
    assert block['obs_off_ms'] == 500.0
    assert 0.0 <= block['obs_overhead_pct'] <= 2.0, block
    assert block['obs_step_call_us'] > 0
    assert re.fullmatch(r'[0-9a-f]{64}', block['obs_metrics_digest'])
    # window delta keeps its sign (never laundered into the headline)
    assert block['obs_window_delta_pct'] == pytest.approx(0.2)
  finally:
    obs.reset()


def test_lint_artifact_keys(bench):
  """The ISSUE-13 journaled proof: the bench artifact carries the
  static-analysis gate counts (design §17) — lint_findings is 0 on a
  healthy tree (the SAME gate tier-1's test_lint.py enforces) and
  lint_waivers equals the checked-in rationale-bearing baseline, so a
  change that breaks the gate or quietly grows the baseline is visible
  in the artifact record AND fails here."""
  from distributed_embeddings_tpu.analysis import (Baseline, core,
                                                   list_passes)
  block = bench.lint_block()
  for key in ('lint_findings', 'lint_waivers'):
    assert key in block, key
  assert block['lint_findings'] == 0, block
  base = Baseline.load(core.default_baseline_path())
  # equality, not non-emptiness: an emptied baseline is the cleaner
  # tree, never a failure.  The file is shared with graphlint
  # (design §18): only detlint-owned waivers match lint_block's count
  detlint_owned = [w for w in base.waivers
                   if w['id'].split('/', 1)[0] in list_passes()]
  assert block['lint_waivers'] == len(detlint_owned)


def test_graphlint_artifact_keys(bench):
  """The ISSUE-14 journaled proof: the bench artifact carries the
  IR-analysis gate counts (design §18) — graphlint_findings is 0 on a
  healthy tree (the SAME gate tier-1's test_graphlint.py enforces),
  the donation proof holds (every sparse-train-step state leaf
  input-output aliased), the monitored windows saw zero retraces, and
  the peak per-device estimate is a real nonzero figure next to the
  perf_notes fits ladder."""
  block = bench.graphlint_block()
  for key in ('graphlint_findings', 'graphlint_donation_ok',
              'graphlint_retraces', 'graphlint_peak_hbm_bytes'):
    assert key in block, key
  assert block['graphlint_findings'] == 0, block
  assert block['graphlint_donation_ok'] is True, block
  assert block['graphlint_retraces'] == 0, block
  assert block['graphlint_peak_hbm_bytes'] > 0, block
  # fused-exchange counters (ISSUE 17 / design §21), counted from the
  # graphlint schedule of the two-group fused/per-group twins: the
  # fused program must beat its per-group twin by AT LEAST the group
  # count in each direction (two groups -> one collective saved per
  # phase per direction), and the fused on-wire payload is journaled
  for key in ('exchange_collectives_fwd', 'exchange_collectives_bwd',
              'exchange_collectives_fwd_pergroup',
              'exchange_collectives_bwd_pergroup',
              'fused_exchange_bytes'):
    assert key in block, key
  groups = 2  # the twin programs' table count (distinct widths)
  fused = (block['exchange_collectives_fwd']
           + block['exchange_collectives_bwd'])
  pergroup = (block['exchange_collectives_fwd_pergroup']
              + block['exchange_collectives_bwd_pergroup'])
  assert fused + groups <= pergroup, block
  assert (block['exchange_collectives_fwd']
          < block['exchange_collectives_fwd_pergroup']), block
  assert (block['exchange_collectives_bwd']
          < block['exchange_collectives_bwd_pergroup']), block
  assert block['exchange_collectives_fwd'] == 2, block   # ids out, rows back
  assert block['exchange_collectives_bwd'] == 1, block   # one cotangent leg
  assert block['fused_exchange_bytes'] > 0, block


def test_commlint_artifact_keys(bench):
  """The ISSUE-18 journaled proof: the bench artifact carries the
  cross-rank protocol gate counts (design §22) — commlint_findings is
  0 on a healthy tree (the SAME gate tier-1's test_commlint.py
  enforces), commlint_waivers equals the checked-in commlint-owned
  waiver count (the rank-variant recovery paths commsan guards at
  runtime), and commlint_schedules_predicted counts the flagship
  programs whose collective schedule was re-derived from the lookup
  plans and matched against the ledger — the full-catalog 15/15 pin
  lives in test_commlint.py; here the journaled count must be live."""
  from distributed_embeddings_tpu.analysis import Baseline, core
  from distributed_embeddings_tpu.analysis import commlint
  block = bench.commlint_block()
  for key in ('commlint_findings', 'commlint_waivers',
              'commlint_schedules_predicted'):
    assert key in block, key
  assert block['commlint_findings'] == 0, block
  base = Baseline.load(core.default_baseline_path())
  commlint_owned = [w for w in base.waivers
                    if w['id'].split('/', 1)[0]
                    in commlint.COMM_PASS_NAMES]
  assert block['commlint_waivers'] == len(commlint_owned), block
  assert block['commlint_schedules_predicted'] > 0, block


def test_artifact_keys_registered():
  """Every artifact key THIS test file pins is in
  obs.metrics.REGISTERED_ARTIFACT_KEYS — the registry the detlint
  registry-schema pass checks producers against — so the test pins and
  the registry can never drift apart."""
  import ast
  import pathlib
  from distributed_embeddings_tpu.obs import metrics as obs_metrics
  tree = ast.parse(pathlib.Path(__file__).read_text())
  pinned = set()
  # the `for key in (...)` loops over artifact keys, by shape
  for node in ast.walk(tree):
    if isinstance(node, ast.For) and isinstance(node.target, ast.Name) \
        and node.target.id == 'key' and isinstance(node.iter, ast.Tuple):
      for elt in node.iter.elts:
        if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
          pinned.add(elt.value)
  assert len(pinned) > 30, 'key-loop scan broken?'
  missing = pinned - obs_metrics.REGISTERED_ARTIFACT_KEYS
  assert not missing, (
      f'artifact keys pinned here but not registered: {missing} — add '
      'them to obs.metrics.REGISTERED_ARTIFACT_KEYS in the same change')


def test_split_windows(bench):
  assert bench.split_windows(20, 3) == [7, 7, 6]
  assert bench.split_windows(2, 5) == [1, 1]   # never more windows than steps
  assert bench.split_windows(5, 1) == [5]
  assert sum(bench.split_windows(17, 4)) == 17


def test_host_load_shape(bench):
  load = bench.host_load()
  assert load is None or (len(load) == 3
                          and all(isinstance(x, float) for x in load))


def test_quantized_and_cold_tier_counters():
  """The ISSUE-7 journaled proof, library-level (the same calls bench
  folds into the artifact): the int8 off/on byte accounting shows the
  >= 3.5x table_bytes_per_row reduction on power-law synthetic-tiny,
  and the cold-tier fetch counters cross-check EXACTLY (fetched bytes
  == rows x quantized row bytes per group, scale bytes by name) with
  the overlap pct a direct measurement in [0, 1]."""
  import jax
  import numpy as np
  from distributed_embeddings_tpu.models.synthetic import (
      SYNTHETIC_MODELS, InputGenerator, SyntheticModel, expand_tables)
  from distributed_embeddings_tpu.parallel import (coldtier, create_mesh,
                                                   hotcache, quantization)

  config = SYNTHETIC_MODELS['tiny']
  tables, _, _ = expand_tables(config)
  mesh = create_mesh(jax.devices()[:1])

  # -- int8 off/on byte accounting: the >= 3.5x acceptance bar ----------
  off_m = SyntheticModel(config, mesh=mesh, dp_input=True)
  on_m = SyntheticModel(config, mesh=mesh, dp_input=True,
                        table_dtype='int8')
  off_b = quantization.table_bytes_stats(off_m.dist_embedding.plan, 4)
  on_b = quantization.table_bytes_stats(on_m.dist_embedding.plan, 4)
  for key in ('table_bytes_per_row', 'table_scale_bytes_per_row',
              'table_total_bytes_per_row', 'table_payload_bytes',
              'table_scale_bytes', 'table_rows'):
    assert key in off_b and key in on_b, key
  reduction = off_b['table_bytes_per_row'] / on_b['table_bytes_per_row']
  assert reduction >= 3.5, (reduction, off_b, on_b)
  # the scale overhead is journaled by name, never folded silently
  assert on_b['table_scale_bytes'] == \
      on_b['table_rows'] * quantization.SCALE_BYTES

  # -- cold-tier counters: exact cross-check + measured overlap ---------
  hot_sets = hotcache.analytic_power_law_hot_sets(tables, 1.05, 0.85)
  probe = SyntheticModel(config, mesh=mesh, dp_input=True,
                         hot_cache=hot_sets, table_dtype='int8')
  budget = max(
      int(probe.dist_embedding.plan.resident_table_bytes() * 0.6),
      probe.dist_embedding.plan.hot_buffer_bytes() + 4096)
  tier_m = SyntheticModel(config, mesh=mesh, dp_input=True,
                          hot_cache=hot_sets, table_dtype='int8',
                          cold_tier=True, device_hbm_budget=budget)
  dist = tier_m.dist_embedding
  assert dist.plan.cold_tier_groups, 'budget did not trigger the tier'
  gen = InputGenerator(config, 1024, alpha=1.05, num_batches=2, seed=0)
  batches = [[np.asarray(c) for c in gen.pool[i][0][1]] for i in range(2)]
  pipe = coldtier.ColdFetchPipeline(dist, iter(batches))
  total_rows = 0
  total_bytes = 0
  for _, fetch in pipe:
    fs = coldtier.fetch_stats(dist, fetch)
    # the pinned cross-check: bytes == sum(rows x per-group row bytes)
    assert fs['cold_tier_fetch_bytes'] == sum(
        n * rb for n, rb in zip(fs['cold_tier_fetch_rows_per_group'],
                                fs['cold_tier_row_bytes_per_group']))
    assert fs['cold_tier_fetch_scale_bytes'] == \
        fs['cold_tier_fetch_rows'] * quantization.SCALE_BYTES
    for gi, rb in zip(dist.plan.cold_tier_groups,
                      fs['cold_tier_row_bytes_per_group']):
      assert rb == quantization.payload_bytes_per_row(
          dist.plan.groups[gi].width, dist.plan.table_spec, 4)
    total_rows += fs['cold_tier_fetch_rows']
    total_bytes += fs['cold_tier_fetch_bytes']
  assert total_rows > 0 and total_bytes > 0
  pstats = pipe.stats()
  assert pstats['batches'] == 2
  assert 0.0 <= pstats['overlap_pct'] <= 1.0   # measured, never inferred
  ts = coldtier.tier_stats(dist)
  assert ts['cold_tier_resident_bytes'] <= budget
  assert ts['cold_tier_host_bytes'] == dist.cold_tier.host_bytes() > 0
