"""Observability layer (obs/, design §15): tracer round-trip + schema,
histogram percentile resolution, disabled-path no-ops, concurrent
serving-batcher span nesting, the trace_report CI gate, and the
span/metric name source scans (the REGISTERED_EVENTS discipline
extended to the new surface).
"""

import importlib.util
import json
import os
import pathlib
import re
import threading

import numpy as np
import pytest

import jax

from distributed_embeddings_tpu import obs
from distributed_embeddings_tpu.obs import metrics as obs_metrics
from distributed_embeddings_tpu.obs import trace as obs_trace
from distributed_embeddings_tpu.obs.metrics import (Histogram,
                                                    LatencyWindow,
                                                    OverlapStat)
from distributed_embeddings_tpu.utils import resilience

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load_trace_report():
  spec = importlib.util.spec_from_file_location(
      'trace_report_for_test', ROOT / 'tools' / 'trace_report.py')
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod


@pytest.fixture(autouse=True)
def _obs_isolated():
  """Every test starts and ends with the layer disarmed and empty —
  obs state is process-global by design."""
  obs.reset()
  yield
  obs.reset()


# --------------------------------------------------------------------------
# tracer: round trip + schema
# --------------------------------------------------------------------------


def test_trace_round_trip_is_valid_chrome_trace(tmp_path):
  """Spans emitted across threads save as ONE Perfetto-loadable
  Chrome-trace JSON object whose events satisfy the schema the report
  tool validates (names/ph/ts, X durations, b/e async pairing)."""
  obs.enable()
  with obs_trace.span('train/step', step=1):
    tok = obs_trace.begin('feed/build', seq=0)
    assert obs_trace.end(tok) >= 0.0   # the seconds it measured
    with obs_trace.span('audit/check'):
      pass
  obs_trace.complete('dev/fwd/exchange', obs_trace.now() - 0.003, 0.003)
  obs_trace.async_span('serve/enqueue', 42, obs_trace.now() - 0.001,
                       obs_trace.now(), samples=2)
  obs_trace.instant('train/step', note='marker')

  def other_thread():
    with obs_trace.span('feed/build', seq=1):
      pass

  t = threading.Thread(target=other_thread, name='producer')
  t.start()
  t.join()
  path = str(tmp_path / 'trace.json')
  obs_trace.save(path)

  with open(path, encoding='utf-8') as f:
    payload = json.load(f)
  assert isinstance(payload, dict)
  assert isinstance(payload['traceEvents'], list)
  assert payload['displayTimeUnit'] == 'ms'
  names = set()
  for ev in payload['traceEvents']:
    assert isinstance(ev['name'], str) and ev['name']
    assert ev['ph'] in ('X', 'b', 'e', 'i', 'M')
    if ev['ph'] == 'M':
      continue
    names.add(ev['name'])
    assert isinstance(ev['ts'], (int, float))
    assert 'pid' in ev and 'tid' in ev
    if ev['ph'] == 'X':
      assert ev['dur'] >= 0
  assert names <= obs.REGISTERED_SPANS
  assert {'train/step', 'dev/fwd/exchange', 'audit/check',
          'serve/enqueue', 'feed/build'} <= names
  # the report tool's validator accepts the same file (one schema)
  tr = _load_trace_report()
  events = tr.load_trace(path)
  assert len(events) == len(payload['traceEvents'])
  # thread metadata: the producer thread got its own labelled track
  meta = [e for e in payload['traceEvents'] if e['ph'] == 'M']
  assert any(e['args']['name'] == 'producer' for e in meta)


def test_trace_buffer_bound_counts_drops(tmp_path):
  obs_trace.enable(max_events=4)
  obs_trace.enable()  # a re-arm WITHOUT max_events keeps the bound
  for k in range(10):
    with obs_trace.span('train/step', step=k):
      pass
  assert obs_trace.event_count() <= 4
  assert obs_trace.dropped() > 0
  path = str(tmp_path / 't.json')
  obs_trace.save(path)
  with open(path, encoding='utf-8') as f:
    assert json.load(f)['otherData']['dropped_events'] > 0


# --------------------------------------------------------------------------
# metrics: histogram resolution, registry, exporter
# --------------------------------------------------------------------------


@pytest.mark.parametrize('seed', [0, 1, 2])
def test_histogram_percentiles_within_bucket_resolution(seed):
  """The fixed-bucket estimate brackets the EXACT sample percentile:
  the inverted-CDF percentile always lies inside percentile_bounds, and
  the point estimate is its (conservative) upper edge."""
  rng = np.random.default_rng(seed)
  data = np.abs(rng.lognormal(mean=seed, sigma=1.5, size=4000))
  h = Histogram()
  for v in data:
    h.observe(v)
  assert h.count == data.size
  for p in (50, 90, 99):
    exact = float(np.percentile(data, p, method='inverted_cdf'))
    lo, hi = h.percentile_bounds(p)
    assert lo <= exact <= hi, (p, lo, exact, hi)
    assert h.percentile(p) == hi


def test_histogram_empty_and_reset():
  h = Histogram()
  assert h.percentile(50) is None and h.percentile_bounds(99) is None
  h.observe(3.0)
  assert h.percentile(50) == 3.0  # clamped to the observed max
  h.reset()
  assert h.count == 0 and h.percentile(50) is None


def test_registry_snapshot_prometheus_and_journal(tmp_path, monkeypatch):
  monkeypatch.setenv('DET_FT_JOURNAL', str(tmp_path / 'journal.jsonl'))
  obs.enable()
  obs_metrics.inc('train.steps', 5)
  obs_metrics.set_gauge('train.loss', 0.25)
  obs_metrics.observe('audit.call_ms', 12.0)
  snap = obs_metrics.snapshot()
  assert snap['train.steps'] == 5.0
  assert snap['train.loss'] == 0.25
  assert snap['audit.call_ms']['count'] == 1
  d1 = obs_metrics.snapshot_digest()
  # identical recordings digest identically (the artifact fingerprint)
  obs_metrics.reset()
  obs_metrics.inc('train.steps', 5)
  obs_metrics.set_gauge('train.loss', 0.25)
  obs_metrics.observe('audit.call_ms', 12.0)
  assert obs_metrics.snapshot_digest() == d1
  text = obs_metrics.prometheus_text()
  assert '# TYPE det_train_steps counter' in text
  assert 'det_train_steps 5' in text
  assert 'det_audit_call_ms_bucket{le="+Inf"} 1' in text
  assert 'det_audit_call_ms_count 1' in text
  resilience.clear_recent()
  ev = obs_metrics.journal_snapshot(step=7)
  assert ev['kind'] == 'metrics_snapshot' and ev['step'] == 7
  assert resilience.recent('metrics_snapshot')
  with open(tmp_path / 'journal.jsonl', encoding='utf-8') as f:
    line = json.loads(f.readlines()[-1])
  assert line['metrics']['train.steps'] == 5.0


def test_registry_refuses_unregistered_and_mistyped_names():
  obs.enable()
  with pytest.raises(KeyError, match='unregistered metric'):
    obs_metrics.inc('train.stpes')  # the typo the schema exists for
  with pytest.raises(TypeError, match='is a counter'):
    obs_metrics.observe('train.steps', 1.0)


# --------------------------------------------------------------------------
# disabled path: no-ops, zero journal writes
# --------------------------------------------------------------------------


def test_disabled_spans_and_counters_are_noops(tmp_path, monkeypatch):
  journal = tmp_path / 'journal.jsonl'
  monkeypatch.setenv('DET_FT_JOURNAL', str(journal))
  resilience.clear_recent()
  # every disabled span is ONE shared object: nothing allocated
  assert obs_trace.span('train/step', step=1) is obs_trace.span('feed/wait')
  # a disabled begin is the bare start time, so end() still hands the
  # caller's stats counter its seconds: no span, no annotation
  tok = obs_trace.begin('feed/wait')
  assert isinstance(tok, float)
  assert 0.0 <= obs_trace.end(tok) < 1.0
  assert obs_trace.end(None) == 0.0
  obs_trace.complete('dev/fwd/exchange', 0.0, 1.0)
  obs_trace.async_span('serve/enqueue', 1, 0.0, 1.0)
  obs_trace.instant('train/step')
  assert obs_trace.event_count() == 0
  obs_metrics.inc('train.steps')
  obs_metrics.set_gauge('train.loss', 1.0)
  obs_metrics.observe('audit.call_ms', 1.0)
  assert obs_metrics.snapshot() == {}
  assert obs_metrics.journal_snapshot(step=1) is None
  assert not journal.exists(), 'disabled obs must write ZERO journal lines'
  assert resilience.recent('metrics_snapshot') == []


def test_measure_overhead_leaves_no_residue():
  out = obs.measure_overhead(100.0, reps=200)
  assert out['obs_step_call_us'] > 0
  assert 0 <= out['obs_overhead_pct'] < 2.0
  # the microbench armed, measured, truncated, and disarmed — keeping
  # only the thread_name metadata its scaffolding registered (the tid
  # stays cached, so deleting the label would orphan later spans) and
  # restoring the dropped counter
  assert not obs_trace.enabled() and not obs_metrics.enabled()
  assert all(e['ph'] == 'M' for e in obs_trace.events())
  assert obs_trace.dropped() == 0
  assert obs_metrics.snapshot().get('train.steps', 0.0) == 0.0
  # later spans on this thread still land on a LABELLED track
  obs.enable()
  with obs_trace.span('train/step', step=1):
    pass
  evs = obs_trace.events()
  tids = {e['tid'] for e in evs if e['ph'] == 'X'}
  named = {e['tid'] for e in evs if e['ph'] == 'M'}
  assert tids <= named


# --------------------------------------------------------------------------
# shared stats primitives (the three-way unification)
# --------------------------------------------------------------------------


def test_overlap_stat_matches_both_legacy_conventions():
  ov = OverlapStat()
  assert ov.overlap_pct() is None      # CsrFeed: None before any build
  assert ov.overlap_frac() == 0.0      # ColdFetchPipeline: 0.0
  ov.add_build(10.0)
  ov.add_blocked(2.5)
  ov.count_batch()
  assert ov.overlap_pct() == pytest.approx(75.0)
  assert ov.overlap_frac() == pytest.approx(0.75)
  ov.add_blocked(100.0)                # blocked > build clamps at 0
  assert ov.overlap_pct() == 0.0 and ov.overlap_frac() == 0.0
  assert ov.batches == 1


def test_latency_window_trims_and_matches_numpy():
  w = LatencyWindow(cap=100, keep=50)
  vals = list(np.random.default_rng(0).uniform(1, 50, size=80))
  w.extend(vals)
  assert w.percentile(50) == pytest.approx(float(np.percentile(vals, 50)))
  w.extend(list(range(30)))            # 110 > cap: trimmed to last 50
  assert len(w) == 50
  assert w.percentile(99) is not None


# --------------------------------------------------------------------------
# concurrent serving-batcher spans (fuzzed submission)
# --------------------------------------------------------------------------


def _nesting_ok(events, eps_us=2.0):
  """X events per (pid, tid) must follow with-statement stack
  discipline: any two intervals are disjoint or properly nested."""
  tracks = {}
  for ev in events:
    if ev.get('ph') == 'X':
      tracks.setdefault((ev['pid'], ev['tid']), []).append(
          (float(ev['ts']), float(ev['ts']) + float(ev['dur']),
           ev['name']))
  for track in tracks.values():
    track.sort()
    stack = []
    for ts, te, name in track:
      while stack and ts >= stack[-1][1] - eps_us:
        stack.pop()
      if stack and te > stack[-1][1] + eps_us:
        return False, (name, ts, te, stack[-1])
      stack.append((ts, te, name))
  return True, None


def test_concurrent_batcher_spans_nest_under_fuzzed_submission(tmp_path):
  """8 threads x fuzzed request sizes through a live DynamicBatcher
  with the tracer armed: the saved trace stays schema-valid, every
  per-thread X track keeps stack discipline (the Perfetto rendering
  contract), every async enqueue b has its e, and the span counts
  reconcile with the batcher's own stats."""
  from distributed_embeddings_tpu import serving
  from distributed_embeddings_tpu.parallel import TableConfig, create_mesh
  cfgs = [TableConfig(48, 8, 'sum'), TableConfig(32, 8, 'sum')]
  rng = np.random.default_rng(0)
  weights = [(rng.normal(size=(c.input_dim, c.output_dim)) * 0.1)
             .astype(np.float32) for c in cfgs]
  engine = serving.ServingEngine(
      cfgs, weights, batch_size=16,
      mesh=create_mesh(jax.devices()[:1]))
  engine.warmup()  # compile OUTSIDE the traced window
  obs.enable()
  n_threads, per_thread = 8, 5
  errors = []

  def client(seed):
    r = np.random.default_rng(seed)
    try:
      with_sizes = [int(r.integers(1, 5)) for _ in range(per_thread)]
      for n in with_sizes:
        cats = [r.integers(0, c.input_dim, size=(n,)).astype(np.int32)
                for c in cfgs]
        out = bat.submit(cats).result(timeout=60.0)
        assert out[0].shape == (n, 8)
    except BaseException as e:  # surfaced after join
      errors.append(e)

  with serving.DynamicBatcher(engine, max_delay_ms=1.0) as bat:
    threads = [threading.Thread(target=client, args=(s,), name=f'c{s}')
               for s in range(n_threads)]
    for t in threads:
      t.start()
    for t in threads:
      t.join()
    stats = bat.stats()
  assert not errors, errors
  path = str(tmp_path / 'serve_trace.json')
  obs_trace.save(path)
  tr = _load_trace_report()
  events = tr.load_trace(path)  # schema + async b/e pairing validated
  ok, bad = _nesting_ok(events)
  assert ok, f'partial-overlap X spans on one track: {bad}'
  counts = {}
  for ev in events:
    if ev.get('ph') in ('X', 'b'):
      counts[ev['name']] = counts.get(ev['name'], 0) + 1
  total = n_threads * per_thread
  assert counts.get('serve/submit') == total
  assert counts.get('serve/enqueue') == total      # one async pair each
  assert counts.get('serve/demux') == stats['batches']
  assert counts.get('serve/execute') == stats['batches']
  assert counts.get('serve/lookup') == stats['batches']
  assert stats['completed'] == total


# --------------------------------------------------------------------------
# trace_report: analysis + CI gate
# --------------------------------------------------------------------------


def test_trace_report_attribution_and_gates(tmp_path):
  obs.enable()
  base = obs_trace.now() - 0.1
  for k in range(3):
    with obs_trace.span('train/step', step=k + 1):
      tok = obs_trace.begin('feed/build')
      obs_trace.end(tok)
    # three DISJOINT 2 ms syncs (3 ms apart): blocked union must be 6
    obs_trace.complete('train/sync', base + k * 0.003, 0.002,
                       step=k + 1)
  # overlapping waits must NOT double-count: two 2 ms spans over the
  # same window add ~0 to the union
  obs_trace.complete('train/sync', base, 0.002)
  obs_trace.complete('train/sync', base + 0.001, 0.0015)
  path = str(tmp_path / 'trace.json')
  obs_trace.save(path)
  tr = _load_trace_report()
  rep = tr.report(tr.load_trace(path))
  assert rep['phases']['train/step']['count'] == 3
  assert len(rep['steps']) == 3
  assert [s['step'] for s in rep['steps']] == [1, 2, 3]
  assert all('feed/build' in s['phases'] for s in rep['steps'])
  # union semantics: 3 disjoint 2 ms + 2 fully-overlapped extras = ~6.5
  assert rep['critical_path']['blocked_ms'] == pytest.approx(6.5,
                                                             abs=0.5)
  assert rep['phases']['train/sync']['count'] == 5  # raw per-span sums
  assert rep['unregistered'] == []
  text = tr.format_report(rep)
  assert 'per-step breakdown' in text and 'train/step' in text
  assert tr.main([path]) == 0
  assert tr.main([path, '--require', 'train/step,feed/build']) == 0
  # a device phase is no host span: the obs file never holds one
  assert tr.main([path, '--require', 'fwd/exchange']) == 4
  assert 'trace_time_ms' not in rep['critical_path']
  assert tr.main([path, '--require', 'coldtier/fetch']) == 4


def test_trace_report_rejects_malformed_truncated_and_unregistered(
    tmp_path, capsys):
  tr = _load_trace_report()
  # not JSON at all
  p1 = tmp_path / 'garbage.json'
  p1.write_text('this is not json')
  assert tr.main([str(p1)]) == 2
  # valid JSON, wrong shape
  p2 = tmp_path / 'wrong.json'
  p2.write_text(json.dumps({'events': []}))
  assert tr.main([str(p2)]) == 2
  # truncated mid-file
  obs.enable()
  with obs_trace.span('train/step', step=1):
    pass
  full = tmp_path / 'full.json'
  obs_trace.save(str(full))
  trunc = tmp_path / 'trunc.json'
  trunc.write_bytes(full.read_bytes()[:120])
  assert tr.main([str(trunc)]) == 2
  # X event with a negative duration
  p3 = tmp_path / 'negdur.json'
  p3.write_text(json.dumps({'traceEvents': [
      {'name': 'train/step', 'ph': 'X', 'ts': 0, 'dur': -5,
       'pid': 1, 'tid': 1}]}))
  assert tr.main([str(p3)]) == 2
  # async begin without end (a crashed producer's torn trace)
  p4 = tmp_path / 'dangling.json'
  p4.write_text(json.dumps({'traceEvents': [
      {'name': 'serve/enqueue', 'ph': 'b', 'id': '1', 'ts': 0,
       'pid': 1, 'tid': 1}]}))
  assert tr.main([str(p4)]) == 2
  # unregistered span name passes by default, fails --strict
  p5 = tmp_path / 'unreg.json'
  p5.write_text(json.dumps({'traceEvents': [
      {'name': 'my/custom', 'ph': 'X', 'ts': 0, 'dur': 1,
       'pid': 1, 'tid': 1}]}))
  assert tr.main([str(p5)]) == 0
  out = capsys.readouterr().out
  assert 'WARNING: unregistered span name(s): my/custom' in out
  assert tr.main([str(p5), '--strict']) == 3


# --------------------------------------------------------------------------
# device lane (design §19): round trip + critical-path split
# --------------------------------------------------------------------------


def test_device_lane_round_trip_and_report_split(tmp_path):
  """Device-lane X events (the obs.devprof emission shape) land on ONE
  dedicated track labelled 'device', validate under ``trace_report
  --strict``, and split the critical path's unattributed remainder
  into device-attributed vs residue."""
  obs.enable()
  tid = obs_trace.device_tid()
  assert tid > 0
  base = obs_trace.now() - 0.020
  obs_trace.complete('dev/fwd/exchange', base, 0.004, tid=tid,
                     direct=True)
  obs_trace.complete('dev/fwd/lookup_combine', base + 0.004, 0.006,
                     tid=tid, direct=False)
  obs_trace.complete('dev/apply/update', base + 0.010, 0.002, tid=tid,
                     direct=True)
  with obs_trace.span('train/step', step=1):
    pass
  path = str(tmp_path / 'dev.json')
  obs_trace.save(path)
  tr = _load_trace_report()
  events = tr.load_trace(path)
  dev = [e for e in events
         if e.get('ph') == 'X' and e.get('cat') == 'device']
  assert len(dev) == 3
  assert len({e['tid'] for e in dev}) == 1, 'one device track'
  meta = [e for e in events if e.get('ph') == 'M']
  assert any(e['args']['name'] == 'device' and e['tid'] == dev[0]['tid']
             for e in meta), 'device track must be labelled'
  rep = tr.report(events)
  cp = rep['critical_path']
  assert cp['device_ms'] == pytest.approx(12.0, abs=0.5)
  assert 'residue_ms' in cp
  assert cp['residue_ms'] <= cp['unattributed_ms'] + 1e-6
  assert rep['phases']['dev/fwd/exchange']['cat'] == 'device'
  assert rep['unregistered'] == []
  assert tr.main([path, '--strict', '--require',
                  'dev/fwd/exchange,dev/apply/update']) == 0


def test_device_tid_disabled_allocates_nothing():
  assert obs_trace.device_tid() == 0
  assert obs_trace.event_count() == 0


# --------------------------------------------------------------------------
# long-run capture: enable() re-entrancy pin + rotating segments (§19)
# --------------------------------------------------------------------------


def test_enable_pin_survives_nested_disable():
  """A long-run owner pins the tracer; a nested component's teardown
  ``disable()`` must not disarm it (re-entrancy pin contract)."""
  obs_trace.enable(pin=True)
  assert obs_trace.enabled()
  assert obs_trace.disable() is False   # nested teardown: no-op
  assert obs_trace.enabled()
  obs_trace.unpin()
  assert obs_trace.disable() is True
  assert not obs_trace.enabled()
  obs_trace.enable(pin=True)
  assert obs_trace.disable(force=True) is True  # the hard teardown
  assert not obs_trace.enabled()


def test_save_rotating_segments_keep_head_and_labels(tmp_path):
  """save_rotating flushes numbered segment files instead of dropping:
  the HEAD of a long run survives in segment 0, the buffer empties
  (keeping thread labels so later spans stay on named tracks), and
  each segment is independently report-loadable."""
  obs_trace.enable()
  path = str(tmp_path / 'rot.json')
  assert obs_trace.save_rotating(path, max_events=5) is None  # below
  for k in range(5):
    with obs_trace.span('train/step', step=k):
      pass
  seg0 = obs_trace.save_rotating(path, max_events=5)
  assert seg0 is not None and seg0.endswith('.seg0000.json')
  tr = _load_trace_report()
  ev0 = tr.load_trace(seg0)
  assert [e['args']['step'] for e in ev0 if e.get('ph') == 'X'] \
      == [0, 1, 2, 3, 4], 'the head of the run must survive rotation'
  assert obs_trace.dropped() == 0
  assert all(e['ph'] == 'M' for e in obs_trace.events())
  for k in range(5, 10):
    with obs_trace.span('train/step', step=k):
      pass
  seg1 = obs_trace.save_rotating(path, max_events=5)
  assert seg1 is not None and seg1.endswith('.seg0001.json')
  ev1 = tr.load_trace(seg1)
  assert [e['args']['step'] for e in ev1 if e.get('ph') == 'X'] \
      == [5, 6, 7, 8, 9]
  x_tids = {e['tid'] for e in ev1 if e['ph'] == 'X'}
  m_tids = {e['tid'] for e in ev1 if e['ph'] == 'M'}
  assert x_tids <= m_tids, 'rotated segments must keep track labels'
  assert obs_trace.segment_count() == 2
  with open(seg1, encoding='utf-8') as f:
    assert json.load(f)['otherData']['segment'] == 1


def test_save_rotating_flushes_a_bound_limited_buffer(tmp_path):
  """A buffer whose own enable(max_events=) bound sits below the
  rotation threshold stops growing before the threshold is reached —
  once NEW drops happen, save_rotating must flush instead of waiting
  forever (the data loss it exists to prevent), and must not re-flush
  on every later call without new drops."""
  obs_trace.enable(max_events=6)
  path = str(tmp_path / 'bound.json')
  for k in range(10):           # > bound: drops accrue
    with obs_trace.span('train/step', step=k):
      pass
  assert obs_trace.dropped() > 0
  seg0 = obs_trace.save_rotating(path, max_events=100)  # threshold unmet
  assert seg0 is not None, 'full buffer with drops must flush'
  # buffer emptied, no new drops: the next call is a no-op again
  assert obs_trace.save_rotating(path, max_events=100) is None


# --------------------------------------------------------------------------
# registry discipline (§15), enforced by the detlint registry-schema
# pass (docs/design.md §17) — the AST-resolving successor of the regex
# source scans that used to live here
# --------------------------------------------------------------------------


def test_span_and_metric_names_registered_detlint():
  """Every trace/metric call site in the runtime uses a registered
  name — a typo'd phase silently vanishes from every report otherwise.
  The detlint registry-schema pass resolves call sites alias-aware
  (strictly stronger than the old regex scan: renamed direct imports
  are covered, and a derived name raises an explicit unverifiable
  finding instead of a silent miss)."""
  from distributed_embeddings_tpu.analysis import run_passes
  res = run_passes(str(ROOT), passes=['registry'])
  bad = [f for f in (res.findings + res.unverifiable + res.waived)
         if f.rule.startswith(('registry/span', 'registry/phase',
                               'registry/metric'))
         or f.rule == 'registry/unverifiable-name']
  assert not bad, '\n'.join(f.brief() for f in bad)
  # the scan-not-broken guard the regex tests carried: real sites seen
  assert res.meta['registry_sites']['span'] > 10
  assert res.meta['registry_sites']['phase'] > 40
  assert res.meta['registry_sites']['metric'] > 10


def test_span_and_metric_enforcement_no_weaker(tmp_path):
  """Seeded-violation pin: everything the deleted regex scans caught,
  the pass still catches — the exact surface shapes the regexes
  matched (`obs_trace.span('x')`, `metrics.inc('y')`) seed a fixture
  tree and must each produce a finding."""
  from distributed_embeddings_tpu.analysis import run_passes
  pkg = tmp_path / 'distributed_embeddings_tpu'
  pkg.mkdir()
  (pkg / 'seeded.py').write_text(
      'from distributed_embeddings_tpu.obs import trace as obs_trace\n'
      'from distributed_embeddings_tpu.obs import metrics\n'
      'def f():\n'
      "  tok = obs_trace.begin('typo/phase')\n"
      "  obs_trace.end(tok)\n"
      "  with obs_trace.span('another/typo'):\n"
      "    metrics.inc('typo.metric')\n"
      "  with obs_trace.phase('fwd/gather'):\n"
      "    pass\n")
  res = run_passes(str(tmp_path), passes=['registry'])
  caught = {(f.rule, f.symbol) for f in res.findings}
  assert ('registry/span-unregistered', 'typo/phase') in caught
  assert ('registry/span-unregistered', 'another/typo') in caught
  assert ('registry/metric-unregistered', 'typo.metric') in caught
  assert ('registry/phase-unregistered', 'fwd/gather') in caught


def test_parallel_binds_cumulative_ops_through_the_scoped_helpers():
  """An op reaches the trace under the scope of the place that BINDS
  it, and JAX binds ``cumsum``/``cummax``/``cumprod``/``cumlogsumexp``
  inside one function a module's call sites share (design §15): in
  ``parallel/`` they go through ``routing.cumsum0``/``cummax0`` or a
  device trace books them to no phase.  The two new phases come through
  the registry like every other (the scan above)."""
  assert {'residual', 'attention/core'} <= set(obs_trace.REGISTERED_PHASES)
  bare = re.compile(r'\b(?:jnp|lax)\.(?:cumsum|cummax|cummin|cumprod'
                    r'|cumlogsumexp)\(')
  found = []
  for path in sorted((ROOT / 'distributed_embeddings_tpu' / 'parallel')
                     .glob('*.py')):
    for number, line in enumerate(path.read_text().splitlines(), 1):
      code = line.split('#', 1)[0]
      if bare.search(code) and '``' not in code:   # docstrings quote them
        found.append(f'{path.name}:{number}: {line.strip()}')
  assert not found, '\n'.join(found)


# --------------------------------------------------------------------------
# the acceptance pin: one trace covering training AND serving
# --------------------------------------------------------------------------


def test_traced_training_plus_serving_single_file(tmp_path):
  """A traced 3-step training run (host CSR build through a CsrFeed)
  plus one batched serving request produce ONE Perfetto-loadable trace
  whose span set covers the host side of the whole step and stays
  inside the registered taxonomy.  The compiled step's own sections are
  device phases (tests/test_phases.py): tracing the step emits no host
  span for them."""
  import optax
  from distributed_embeddings_tpu import serving
  from distributed_embeddings_tpu.parallel import (
      CsrFeed, DistributedEmbedding, SparseSGD, TableConfig, create_mesh,
      fit, init_hybrid_train_state, make_hybrid_train_step, set_weights)
  obs.enable()
  mesh = create_mesh(jax.devices()[:4])
  cfgs = [TableConfig(48, 8, 'sum'), TableConfig(32, 8, 'sum')]
  rng = np.random.default_rng(0)
  weights = [(rng.normal(size=(c.input_dim, c.output_dim)) * 0.1)
             .astype(np.float32) for c in cfgs]
  dist = DistributedEmbedding(cfgs, mesh=mesh, dp_input=True)
  kernel = np.asarray(rng.standard_normal((16, 1)).astype(np.float32))

  def head_loss(dense, emb_outs, labels):
    import jax.numpy as jnp
    h = jnp.concatenate(list(emb_outs), axis=-1)
    return jnp.mean((h @ dense['kernel'] - labels) ** 2)

  opt = SparseSGD(learning_rate=0.05)
  state = init_hybrid_train_state(
      dist, {'embedding': set_weights(dist, weights), 'kernel': kernel},
      optax.sgd(0.05), opt)
  step = make_hybrid_train_step(dist, head_loss, optax.sgd(0.05), opt,
                                donate=False)
  data = []
  for _ in range(3):
    cats = [rng.integers(0, c.input_dim, size=(8,)).astype(np.int32)
            for c in cfgs]
    y = rng.normal(size=(8, 1)).astype(np.float32)
    data.append((cats, y))
  state, history = fit(step, state, iter(data), steps=3, log_every=1,
                       verbose=False)
  assert len(history['loss']) == 3
  # host CSR build spans via the same feed machinery training uses
  feed_dist = DistributedEmbedding([TableConfig(64, 8, 'sum')],
                                   mesh=mesh, lookup_impl='sparsecore')
  src = [[rng.integers(0, 64, size=(8, 2)).astype(np.int32)]
         for _ in range(2)]
  for _fed in CsrFeed(feed_dist, iter(src)):
    pass
  # one batched serving request through the same trace
  engine = serving.ServingEngine(
      cfgs, weights, batch_size=4,
      mesh=create_mesh(jax.devices()[:1]))
  with serving.DynamicBatcher(engine, max_delay_ms=2.0) as bat:
    out = bat.submit([np.asarray(x[:2])
                      for x in data[0][0]]).result(timeout=60.0)
  assert out[0].shape == (2, 8)
  path = str(tmp_path / 'full_trace.json')
  obs_trace.save(path)
  tr = _load_trace_report()
  rep = tr.report(tr.load_trace(path))
  required = {'train/step', 'train/sync', 'feed/build', 'feed/wait',
              'serve/submit', 'serve/enqueue',
              'serve/dispatch', 'serve/lookup', 'serve/execute',
              'serve/demux'}
  have = set(rep['phases'])
  assert required <= have, f'missing spans: {required - have}'
  assert have <= obs.REGISTERED_SPANS, have - obs.REGISTERED_SPANS
  assert not have & set(obs_trace.REGISTERED_PHASES), \
      'a device phase leaked into the host timeline'
  assert rep['unregistered'] == []
  assert tr.main([path, '--strict',
                  '--require', ','.join(sorted(required))]) == 0
