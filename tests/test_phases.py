"""Device phases (obs.trace.phase, design §15): every section of the
compiled step carries a registered phase, in all five builders and both
apply paths; the scopes add no operation; the program's host
spans reach the profiler's own trace; and ``tools/trace_report.py
--profile`` reads a recorded v5e trace of a scoped program.
"""

import contextlib
import functools
import glob
import gzip
import importlib
import importlib.util
import json
import pathlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from distributed_embeddings_tpu import obs
from distributed_embeddings_tpu.obs import trace as obs_trace
from distributed_embeddings_tpu.ops import pallas_segwalk
from distributed_embeddings_tpu.parallel import (
    DistributedEmbedding, SparseAdagrad, SparseAdam, SparseSGD, TableConfig,
    create_mesh, init_hybrid_train_state, make_hybrid_train_step)
from distributed_embeddings_tpu.parallel.hotcache import HotSet

ROOT = pathlib.Path(__file__).resolve().parents[1]
RECORDED = ROOT / 'tests' / 'data' / 'v5e_scoped_step.trace.json.gz'
GB = 16

FWD = {'fwd/route', 'fwd/exchange', 'fwd/lookup_combine'}
BWD = {'bwd/route', 'bwd/exchange'}
APPLY = {'apply/stream', 'apply/dedup', 'apply/update', 'apply/write_rows'}
DENSE = {'head', 'dense_update'}

# builder or apply path -> (layer kwargs, mesh shape, optimizer, the
# phases that path must show).  Two table groups (widths 8 and 16).
CASES = {
    'dp': ({}, 4, SparseAdagrad(0.05),
           FWD | BWD | APPLY | DENSE | {'apply/read_rows'}),
    'mp': ({'dp_input': False}, 4, SparseSGD(0.05),
           FWD | BWD | APPLY | DENSE),
    'hot': ({'hot_cache': {0: HotSet(0, np.array([1, 3, 5]))}}, 4,
            SparseAdagrad(0.05),
            FWD | BWD | APPLY | DENSE | {'apply/read_rows'}),
    'hierarchical': ({'dcn_sharding': True}, (2, 4), SparseSGD(0.05),
                     FWD | BWD | APPLY | DENSE),
    'chunked_adam': ({'overlap_chunks': 2}, 4, SparseAdam(0.01),
                     FWD | BWD | APPLY | DENSE | {'apply/read_rows'}),
    'segwalk': ({}, 4, SparseAdagrad(0.05, use_segwalk_apply=True),
                FWD | BWD | DENSE
                | {'apply/stream', 'apply/dedup', 'apply/update'}),
}


def _lower(case):
  kw, mesh_shape, opt, _ = CASES[case]
  mesh = (create_mesh(mesh_shape) if isinstance(mesh_shape, tuple)
          else create_mesh(jax.devices()[:mesh_shape]))
  cfgs = [TableConfig(40, 8, 'sum'), TableConfig(30, 8, 'sum'),
          TableConfig(50, 16, 'mean'), TableConfig(24, 16, 'sum')]
  dist = DistributedEmbedding(cfgs, mesh=mesh, **kw)
  rng = np.random.default_rng(0)
  kernel = jnp.asarray(rng.normal(size=(48, 1)).astype(np.float32))

  def head(dense, emb_outs, y):
    x = jnp.concatenate(list(emb_outs), axis=1)
    return jnp.mean((x @ dense['kernel'] - y) ** 2)

  step = make_hybrid_train_step(dist, head, optax.sgd(0.1), opt,
                                donate=False)
  state = init_hybrid_train_state(
      dist, {'embedding': dist.init(0), 'kernel': kernel},
      optax.sgd(0.1), opt)
  cats = [jnp.asarray(rng.integers(0, c.input_dim, (GB, 2)), jnp.int32)
          for c in cfgs]
  if not dist.dp_input:
    cats = [cats[i] for dev in dist.plan.input_ids_list for i in dev]
  y = jnp.asarray(rng.normal(size=(GB, 1)).astype(np.float32))
  return step.jitted.lower(state, cats, y)


_HEAVY = re.compile(r' (gather|scatter|sort|dot|convolution|all-to-all'
                    r'|reduce-window)\(.*op_name="([^"]*)"')


@pytest.mark.parametrize('case', list(CASES))
def test_every_section_of_the_step_carries_a_phase(case, monkeypatch):
  """Lower and compile the hybrid step once per builder and apply path:
  the path's phases all occur, and EVERY gather, scatter, sort, dot,
  convolution, all-to-all and running sum (a ``reduce-window``:
  ``routing.cumsum0``) that came from the program (``op_name``
  starts with ``jit(``; XLA's own helpers carry a bare primitive) sits
  in a registered phase — through ``jvp``/``transpose(jvp)`` too — so a
  sixth builder cannot forget."""
  if case == 'segwalk':
    monkeypatch.setattr(pallas_segwalk, 'FORCE_INTERPRET', True)
  lowered = _lower(case)
  text = lowered.as_text(debug_info=True)
  for name in CASES[case][3]:
    assert re.search(rf'["/(]{re.escape(name)}[/")]', text), \
        f'{case}: phase {name} not in the lowered step'
  # both table groups show as child scopes of the lookup and the apply
  for child in ('fwd/lookup_combine/g0', 'fwd/lookup_combine/g1',
                'apply/dedup/g0', 'apply/dedup/g1'):
    assert f'{child}/' in text, f'{case}: no child scope {child}'
  assert 'transpose(jvp(head))' in text
  if case == 'segwalk':
    # the kernel sums, reads, updates and writes in one pass
    assert 'apply/write_rows' not in text and 'apply/update/g1' in text
  seen, missing = 0, []
  for line in lowered.compile().as_text().split('\n'):
    m = _HEAVY.search(line)
    if m and m.group(2).startswith('jit('):
      seen += 1
      if obs_trace.phase_of(m.group(2)) is None:
        missing.append(m.group(2))
  assert seen > 20, 'the scan found no ops: the HLO text changed shape'
  assert not missing, f'{case}: ops outside any phase: {sorted(set(missing))}'


@pytest.mark.parametrize('dtype', [np.int32, np.float32])
@pytest.mark.parametrize('trailing', [(), (3,)])
@pytest.mark.parametrize('n', [1, 2, 4097])
def test_cumulative_helpers_equal_jax_and_carry_the_callers_phase(
    n, trailing, dtype):
  """``routing.cumsum0`` / ``cummax0`` are ``jnp.cumsum(axis=0)`` /
  ``lax.cummax`` bit for bit, and lowered for a TPU (no chip needed)
  inside a phase the op's location ends in ``<phase>/reduce_window_sum``
  (``_max``): the leaf trace reductions class as ``cumsum``, under the
  scope of the place that called it.  ``jnp.cumsum`` itself lowers
  through a function the module's call sites share, whose ops keep no
  scope."""
  from distributed_embeddings_tpu.parallel.routing import cummax0, cumsum0
  rng = np.random.default_rng(n)
  x = (rng.integers(-9, 9, (n,) + trailing) if dtype == np.int32
       else rng.normal(size=(n,) + trailing)).astype(dtype)
  for ours, theirs in ((cumsum0, functools.partial(jnp.cumsum, axis=0)),
                       (cummax0, jax.lax.cummax)):
    got, want = jax.jit(ours)(x), jax.jit(theirs)(x)
    assert got.dtype == want.dtype == dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

  def scoped(x):
    with obs_trace.phase('apply/dedup'):
      a = cumsum0(x)
    with obs_trace.phase('fwd/route'):
      return a, cummax0(x)

  text = jax.jit(scoped).trace(x).lower(
      lowering_platforms=('tpu',)).as_text(debug_info=True)
  assert 'call @cum' not in text          # bound here, no shared function
  windows = re.findall(r'"stablehlo\.reduce_window".*?\}\) :.*?loc\((#loc\d+)\)',
                       text, re.S)
  named = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
  assert [named[w] for w in windows] == [
      'jit(scoped)/apply/dedup/reduce_window_sum',
      'jit(scoped)/fwd/route/reduce_window_max']
  assert (obs_trace.phase_of(named[windows[0]]), obs_trace.phase_of(
      named[windows[1]])) == (('apply/dedup', None), ('fwd/route', None))


def test_phases_add_no_operation(monkeypatch):
  """A phase is metadata: with every scope turned into a no-op the
  lowered step is the same text, operation for operation."""
  scoped = _lower('dp').as_text()
  monkeypatch.setattr(jax, 'named_scope',
                      lambda name: contextlib.nullcontext())
  assert _lower('dp').as_text() == scoped
  assert 'apply/dedup/' not in _lower('dp').as_text(debug_info=True)


def test_phase_leaves_are_no_primitive_names():
  """A phase's leaf may not be, or start with, a primitive that trace
  reductions class ops by: an op whose ``tf_op`` ends at the scope
  would be booked to that class under an unchanged program."""
  assert obs_trace.REGISTERED_PHASES, 'no phases registered'
  for name in obs_trace.REGISTERED_PHASES:
    leaf = name.rsplit('/', 1)[-1]
    assert not leaf.startswith(obs_trace.PRIMITIVE_LEAVES), name
    assert name not in obs_trace.REGISTERED_SPANS, \
        f'{name}: a device phase and a host span of one name mislead'


@pytest.mark.parametrize('op_name, expected', [
    ('jit(step)/jit(local_fn)/shard_map/fwd/exchange/all_to_all',
     ('fwd/exchange', None)),
    ('jit(step)/fwd/lookup_combine/g0/jit(_take)/gather:',
     ('fwd/lookup_combine', 'g0')),
    ('jit(step)/transpose(jvp(head))/dot_general', ('head', None)),
    ('jit(step)/jvp(fwd/lookup_combine)/jit(_take)/gather',
     ('fwd/lookup_combine', None)),
    # the innermost phase wins, and a tf_op may end at the scope
    ('jit(step)/fwd/lookup_combine/g1/fwd/exchange/g1/psum_scatter',
     ('fwd/exchange', 'g1')),
    ('jit(step)/apply/write_rows/g12', ('apply/write_rows', 'g12')),
    ('jit(step)/jit(head)/add', None),   # a function's name is no scope
    ('jit(step)/jit(local_fn)/gather:', None),
    ('', None),
])
def test_phase_of_reads_the_name_stack(op_name, expected):
  assert obs_trace.phase_of(op_name) == expected


@pytest.fixture
def _obs_isolated():
  obs.reset()
  yield
  obs.reset()


def test_disabled_tracer_still_scopes_and_builds_no_annotation(
    _obs_isolated, monkeypatch):
  """Tracer off: ``span`` is the one shared no-op object and no
  ``TraceAnnotation`` is built; ``phase`` scopes all the same, with the
  group of the enclosing ``phase_group``."""
  def boom(*a, **k):
    raise AssertionError('a disabled span built a TraceAnnotation')
  monkeypatch.setattr(jax.profiler, 'TraceAnnotation', boom)
  monkeypatch.setattr(jax.profiler, 'StepTraceAnnotation', boom)
  assert not obs_trace.enabled()
  assert obs_trace.span('train/step', step=1) is obs_trace.span('feed/wait')
  obs_trace.end(obs_trace.begin('serve/merge', requests=1))

  @obs_trace.phase('apply/dedup')
  def dedup(x):
    return jnp.sort(x)

  def f(x):
    with obs_trace.phase('fwd/route'):
      x = x + 1
    with obs_trace.phase_group('g3'):
      return dedup(x)

  text = jax.jit(f).lower(jnp.arange(4.0)).as_text(debug_info=True)
  assert 'fwd/route/add' in text
  assert 'apply/dedup/g3/' in text


def _load_trace_report():
  spec = importlib.util.spec_from_file_location(
      'trace_report_for_phases', ROOT / 'tools' / 'trace_report.py')
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod


def test_host_spans_reach_the_profilers_trace(_obs_isolated, tmp_path):
  """Under ``obs.trace.profile`` a ``train/step`` span and a served
  request's ``serve/dispatch`` span are events of the profiler's own
  ``*.trace.json.gz``, on the host plane: the clock of the device
  planes, with no call site of their own."""
  from distributed_embeddings_tpu import serving
  cfgs = [TableConfig(48, 8, 'sum'), TableConfig(32, 8, 'sum')]
  rng = np.random.default_rng(0)
  weights = [(rng.normal(size=(c.input_dim, c.output_dim)) * 0.1)
             .astype(np.float32) for c in cfgs]
  engine = serving.ServingEngine(cfgs, weights, batch_size=4,
                                 mesh=create_mesh(jax.devices()[:1]))
  engine.warmup()
  directory = str(tmp_path / 'prof')
  with obs_trace.profile(directory):
    assert obs_trace.enabled()
    with obs_trace.span('train/step', step=7):
      jnp.ones((8,)).sum().block_until_ready()
    with serving.DynamicBatcher(engine, max_delay_ms=1.0) as bat:
      cats = [rng.integers(0, c.input_dim, size=(2,)).astype(np.int32)
              for c in cfgs]
      bat.submit(cats).result(timeout=60.0)
      stats = bat.stats()
  assert not obs_trace.enabled(), 'profile() must disarm what it armed'
  assert stats['queue_wait_p50_ms'] is not None
  paths = glob.glob(directory + '/**/*.trace.json.gz', recursive=True)
  assert len(paths) == 1
  with gzip.open(paths[0], 'rt') as f:
    events = json.load(f)['traceEvents']
  procs = {e['pid']: e['args']['name'] for e in events
           if e.get('ph') == 'M' and e.get('name') == 'process_name'}
  on_host = {e['name'] for e in events if e.get('ph') == 'X'
             and procs.get(e['pid'], '').startswith('/host:')}
  assert {'train/step', 'serve/dispatch', 'serve/submit',
          'serve/lookup'} <= on_host
  # the tracer's own buffer holds the same spans (one measurement)
  own = {e['name'] for e in obs_trace.events() if e.get('ph') == 'X'}
  assert {'train/step', 'serve/dispatch'} <= own
  # the report reads it: no device plane on a CPU, the spans are there
  tr = _load_trace_report()
  rep = tr.profile_report(tr.load_profile(tr.find_profile(directory)))
  assert rep['chip'] is None and rep['phases'] == {}
  assert rep['host_spans']['train/step']['count'] == 1
  assert rep['unregistered'] == []
  assert tr.main(['--profile', directory, '--require', 'train/step']) == 0
  assert tr.main(['--profile', directory, '--require', 'fwd/route']) == 4


# the ``tf_op`` of device ops as a language-model step's trace holds them
# (``jit(step)`` outside, ``head`` under the step's vjp, the blocks'
# halves under ``jax.checkpoint``): (tf_op, microseconds, the phase
# ``trace_report`` books it to = the innermost registered one)
_LM_OPS = [
    ('jit(step)/jvp(head)/residual/mul:', 30.0, 'residual'),
    ('jit(step)/transpose(jvp(head))/checkpoint/rematted_computation/'
     'residual/reduce_sum:', 10.0, 'residual'),
    ('jit(step)/jvp(head)/attention/window/dot_general:', 50.0,
     'attention/window'),
    ('jit(step)/jvp(head)/attention/window/attention/core/attention_fwd/'
     'pallas_call:', 20.0, 'attention/core'),
    ('jit(step)/transpose(jvp(head))/checkpoint/rematted_computation/'
     'attention/attention/core/transpose:', 5.0, 'attention/core'),
    ('jit(step)/apply/dedup/g0/reduce_window_sum:', 7.0, 'apply/dedup'),
    ('reduce_window_sum:', 3.0, None),
]


def test_profile_report_lists_the_new_phases_under_their_layer():
  """``tools/trace_report.py --profile`` takes ``residual`` and
  ``attention/core`` from ``REGISTERED_PHASES`` alone: each op goes to
  its innermost registered phase under the layer the table names, a
  running sum bound under ``apply/dedup`` is the phase's and a bare one
  ``unscoped``; and the benchmark's reduction (``lib/xtrace``, whose
  scope rule is the copy of this one) reads the same ops by prefix:
  the core counts for ``attention`` and ``attention/window`` too."""
  tr = _load_trace_report()
  events = [
      {'ph': 'M', 'name': 'process_name', 'pid': 1,
       'args': {'name': '/device:TPU:0'}},
      {'ph': 'M', 'name': 'thread_name', 'pid': 1, 'tid': 1,
       'args': {'name': 'XLA Ops'}},
      {'ph': 'M', 'name': 'thread_name', 'pid': 1, 'tid': 2,
       'args': {'name': 'XLA Modules'}},
      {'ph': 'X', 'pid': 1, 'tid': 2, 'name': 'jit_step(1)', 'ts': 0.0,
       'dur': 200.0}]
  ts = 0.0
  for i, (tf_op, us, _) in enumerate(_LM_OPS):
    events.append({'ph': 'X', 'pid': 1, 'tid': 1, 'name': f'fusion.{i}',
                   'ts': ts, 'dur': us,
                   'args': {'tf_op': tf_op, 'long_name': f'%fusion.{i} = '}})
    ts += us
  rep = tr.profile_report(events, program='jit_step')
  want = {}
  for _, us, name in _LM_OPS:
    if name:
      want[name] = want.get(name, 0.0) + us / 1000.0
  assert {n: p['ms'] for n, p in rep['phases'].items()} == pytest.approx(want)
  assert {rep['phases'][n]['layer'] for n in (
      'residual', 'attention/core', 'attention/window')} == {'dense head'}
  assert rep['unscoped']['ms'] == pytest.approx(0.003)
  assert [r['tf_op'] for r in rep['unscoped']['ops']] == ['reduce_window_sum:']
  text = tr.format_profile(rep)
  assert re.search(r'residual\s+dense head', text)
  assert re.search(r'attention/core\s+dense head', text)
  # the benchmark's own copy of the scope rule, on the same ops
  xtrace = importlib.import_module('benchmarks.lib.xtrace')
  layer = importlib.import_module('benchmarks.lib.layer')
  paths = {}
  for tf_op, us, name in _LM_OPS:
    path = xtrace.scope_path(tf_op)
    assert (path == xtrace.UNSCOPED) == (name is None), tf_op
    assert name is None or layer.under(path, name), (path, name)
    paths[path] = paths.get(path, 0.0) + us
  under = lambda prefix: sum(us for path, us in paths.items()
                             if layer.under(path, prefix))
  assert (under('attention/core'), under('attention/window'),
          under('attention'), under('residual')) == (25.0, 70.0, 75.0, 40.0)


def test_profile_report_on_the_recorded_hybrid_step():
  """Three steps of the small hybrid step recorded on one v5e from PR
  35's tree (``benchmarks/dev/record_hybrid_trace.py``): the report
  lists ``residual`` and ``attention/core`` under ``dense head`` with no
  rule of its own for them, the core beside what is left of
  ``attention``, and phases and remainders are the busy time."""
  tr = _load_trace_report()
  rep = tr.profile_report(tr.load_profile(str(
      ROOT / 'benchmarks' / 'tests' / 'data'
      / 'v5e_hybrid_step_scoped.trace.json.gz')), program='jit_step')
  assert (rep['program'], rep['steps']) == ('jit_step', 3)
  phases = rep['phases']
  assert phases['residual']['layer'] == 'dense head'
  assert phases['attention/core']['layer'] == 'dense head'
  assert phases['residual']['ms'] == pytest.approx(0.0082, abs=1e-4)
  assert phases['attention/core']['ms'] == pytest.approx(0.0532, abs=1e-4)
  # the innermost phase takes an op: ``attention`` keeps what stands
  # around the core, and the two are the benchmark's ``attention_ms``
  assert phases['attention']['ms'] + phases['attention/core']['ms'] == (
      pytest.approx(0.0681, abs=1e-4))
  total = (sum(p['ms'] for p in phases.values()) + rep['unscoped']['ms']
           + rep['no_source']['ms'])
  assert total == pytest.approx(rep['busy_ms'], rel=1e-3)


@pytest.mark.parametrize('second,same', [
    ('%fused.9 (p.4: f32[2]) -> f32[2] {\n  %p.4 = f32[2] parameter(0)\n'
     '  ROOT %add.7 = f32[2] add(%p.4, %p.4), metadata={op_name="a/b/add"}\n'
     '}\n', True),
    ('%fused.9 (p.4: f32[2]) -> f32[2] {\n  %p.4 = f32[2] parameter(0)\n'
     '  ROOT %add.7 = f32[2] multiply(%p.4, %p.4)\n}\n', False)])
def test_hlo_same_sees_through_names_and_metadata_only(tmp_path, second,
                                                       same):
  """``tools/hlo_same.py``: two compiled texts are the same program
  where they differ in ``metadata={...}`` and in the numbers XLA gives
  its instructions, and not where an operation differs."""
  spec = importlib.util.spec_from_file_location(
      'hlo_same_for_phases', ROOT / 'tools' / 'hlo_same.py')
  hlo_same = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(hlo_same)
  first = ('%fused.3 (p.1: f32[2]) -> f32[2] {\n  %p.1 = f32[2] parameter(0)\n'
           '  ROOT %add.2 = f32[2] add(%p.1, %p.1), metadata={op_name="add"}\n'
           '}\n')
  (tmp_path / 'a.txt').write_text(first)
  (tmp_path / 'b.txt').write_text(second)
  assert hlo_same.main(['', str(tmp_path / 'a.txt'),
                        str(tmp_path / 'b.txt')]) == (0 if same else 1)


# --------------------------------------------------------------------------
# the report on a trace recorded on a v5e from this program
# (tests/data/record_v5e_scoped_step.py; my chip run, PR 24)
# --------------------------------------------------------------------------


def test_profile_report_on_the_recorded_v5e_trace(tmp_path, capsys):
  """Three steps of a three-group step on one v5e: per-phase
  milliseconds, the two remainders and the named idle gaps are pinned;
  phases + remainders are the busy time; the gates gate."""
  tr = _load_trace_report()
  rep = tr.profile_report(tr.load_profile(str(RECORDED)),
                          program='jit_step')
  assert (rep['chip'], rep['program'], rep['steps']) == (
      '/device:TPU:0', 'jit_step', 3)
  assert rep['step_period_ms'] == pytest.approx(18.4248, abs=1e-3)
  assert rep['busy_ms'] == pytest.approx(13.6285, abs=1e-3)
  ms = {n: p['ms'] for n, p in rep['phases'].items()}
  assert ms == pytest.approx({
      'apply/dedup': 8.2408, 'apply/write_rows': 1.9954,
      'fwd/lookup_combine': 0.4138, 'apply/read_rows': 0.1493,
      'fwd/route': 0.0473, 'head': 0.0131, 'apply/stream': 0.0073},
                             abs=1e-3)
  assert list(ms) == sorted(ms, key=ms.get, reverse=True)
  dedup = rep['phases']['apply/dedup']
  assert dedup['layer'] == 'sparse apply'
  assert dedup['children'] == pytest.approx(
      {'g1': 3.2199, 'g0': 2.9201, 'g2': 2.1007}, abs=1e-3)
  # each op keeps its primitive: a class splits by phase
  assert dedup['by_primitive']['gather'] == pytest.approx(7.7433, abs=1e-3)
  assert rep['phases']['fwd/lookup_combine']['by_primitive'][
      'gather'] == pytest.approx(0.1803, abs=1e-3)
  # the two remainders, kept apart
  assert rep['unscoped']['ms'] == pytest.approx(0.2856, abs=1e-3)
  unscoped = {r['name']: r['tf_op'] for r in rep['unscoped']['ops']}
  assert unscoped['cosine_reduce_fusion'] == 'jit(step)/reduce_sum:', \
      'the reduction the recorder left outside every phase'
  assert rep['no_source']['ms'] == pytest.approx(2.4759, abs=1e-3)
  assert all(r['tf_op'] == '' and r['hlo'].startswith('%')
             for r in rep['no_source']['ops'])
  reads = {r['name']: r['reads'] for r in rep['no_source']['ops']}
  assert reads['reduce-window.22'] == {'copy.455': 'apply/dedup/g1'}
  total = (sum(ms.values()) + rep['unscoped']['ms']
           + rep['no_source']['ms'])
  assert total == pytest.approx(rep['busy_ms'], rel=1e-3)
  # the recorder sleeps 3 ms under feed/wait between steps
  assert [(g['span'], g['before']) for g in rep['idle_gaps']] == [
      ('feed/wait', 'and_select_fusion.8')] * 2
  assert rep['idle_gaps'][0]['ms'] == pytest.approx(4.8812, abs=1e-3)
  assert rep['host_spans']['train/step']['count'] == 3
  text = tr.format_profile(rep, children=True)
  assert 'apply/dedup' in text and 'under feed/wait' in text
  # the CLI: --require on phases and spans, --strict on the coverage
  # (the recorder's unscoped op puts it at 2.1% of busy), --json
  path = str(RECORDED)
  assert tr.main(['--profile', path, '--children', '--ops', '3']) == 0
  assert tr.main(['--profile', path, '--require',
                  'apply/dedup,fwd/lookup_combine,train/step']) == 0
  assert tr.main(['--profile', path, '--require', 'bwd/exchange']) == 4
  assert tr.main(['--profile', path, '--strict']) == 3
  capsys.readouterr()
  assert tr.main(['--profile', path, '--json']) == 0
  assert json.loads(capsys.readouterr().out)['steps'] == 3
  # a truncated file is malformed, not an empty report
  cut = tmp_path / 'cut.trace.json.gz'
  cut.write_bytes(RECORDED.read_bytes()[:20000])
  assert tr.main(['--profile', str(cut)]) == 2
  assert tr.main(['--profile', str(tmp_path / 'nothing_here')]) == 2
