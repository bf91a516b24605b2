"""The trace reduction on two small recorded traces: four steps of the
program's train step at a toy size on one v5e (my chip run, PR 23), cut
down to the device's op and module events and the host's bench spans;
and three steps of the step with its phases named (PR 24's recording,
``tests/data/record_v5e_scoped_step.py``), on which the phase times are
held to what ``tools/trace_report.py --profile <file> --json`` reads.
"""
import os

import pytest

from benchmarks.lib import layer, xtrace

DATA = os.path.join(os.path.dirname(__file__), 'data')
TRACE = os.path.join(DATA, 'v5e_probe_step.trace.json.gz')
SCOPED = os.path.join(DATA, 'v5e_scoped_step.trace.json.gz')


@pytest.fixture(scope='module')
def reduced():
  return xtrace.reduce_trace(TRACE, program='jit_step')


def test_planes_steps_and_window(reduced):
  assert reduced['devices'] == ['/device:TPU:0']
  assert reduced['fullest'] == '/device:TPU:0'
  assert reduced['steps'] == 4 and reduced['module'] == 'jit_step'
  # no bench/window span in this recording: the window is the trace's own
  assert reduced['window_s'] == pytest.approx(0.0983335, rel=1e-4)
  assert reduced['busy_mean_s'] == pytest.approx(0.0832816, rel=1e-4)
  assert reduced['module_s'] == pytest.approx(0.0833088, rel=1e-4)
  # four steps of 20.8 ms with 5 ms slept between them: start to start
  assert reduced['module_s'] / 4 < reduced['step_period_s'] < (
      reduced['window_s'] / 3)


@pytest.mark.parametrize('name,seconds', [
    ('gather', 0.0360160), ('scatter', 0.0141807), ('sort', 0.00184110),
    ('cumsum', 0.000639088), ('matmul', 0.000246705), ('copy', 0.0225086),
    ('other', 0.00784947), ('a2a', 0.0), ('collective', 0.0)])
def test_class_seconds(reduced, name, seconds):
  got = reduced['class_s']['/device:TPU:0'][name]
  assert got == pytest.approx(seconds, rel=1e-4, abs=1e-12)


def test_classes_add_up_to_busy(reduced):
  # ops on a TensorCore run one after another: class times sum to busy
  total = sum(reduced['class_s']['/device:TPU:0'].values())
  assert total == pytest.approx(reduced['busy_mean_s'], rel=1e-3)


def test_top_ops_and_gaps(reduced):
  top = max(reduced['ops'], key=reduced['ops'].get)
  assert top == 'fusion.83 (scatter)'
  assert reduced['ops'][top] == pytest.approx(0.00563487, rel=1e-4)
  # the recording slept 3 ms between steps outside any span
  name, seconds = reduced['idle_gaps'][0]
  assert name == 'no bench span' and 0.004 < seconds < 0.007
  assert sum(s for _, s in reduced['idle_gaps']) == pytest.approx(
      reduced['window_s'] - reduced['busy_mean_s'], rel=1e-6)


@pytest.mark.parametrize('args,want', [
    ({'long_name': '%all-to-all.3 = f32[8]{0} all-to-all(f32[8]{0} %x)'},
     'a2a'),
    ({'long_name': '%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %x)'},
     'collective'),
    ({'long_name': '%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kCustom',
      'tf_op': 'jit(step)/scatter-add:'}, 'scatter'),
    ({'long_name': '%copy.3 = f32[8]{0} copy(f32[8]{0} %x)',
      'tf_op': 'jit(step)/gather:'}, 'gather'),
    ({'long_name': '%copy.3 = f32[8]{0} copy(f32[8]{0} %x)'}, 'copy'),
    ({'long_name': '%f = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %x)',
      'hlo_category': 'convolution fusion'}, 'matmul'),
    ({'long_name': '%iota.1 = s32[4]{0} iota()'}, 'other'),
])
def test_classify(args, want):
  assert xtrace.classify(args) == want


@pytest.fixture(scope='module')
def scoped():
  return xtrace.reduce_trace(SCOPED, program='jit_step')


def test_phases_and_the_two_remainders_add_up_to_busy(scoped, reduced):
  for trace in (scoped, reduced):
    for device, busy in trace['busy_s'].items():
      assert sum(trace['phase_s'][device].values()) == pytest.approx(
          busy, abs=1e-9)
      assert sum(s for by in trace['phase_class_s'][device].values()
                 for s in by.values()) == pytest.approx(busy, abs=1e-9)


# ms per step as ``tools/trace_report.py --profile --json`` prints them
# for the same file (PR 26): its phases, then its two remainders
@pytest.mark.parametrize('prefix,ms', [
    ('apply/dedup', 8.2408), ('fwd/lookup_combine', 0.4138),
    ('apply/write_rows', 1.9954), ('apply/read_rows', 0.1493),
    ('apply/dedup/g1', 3.2199), ('apply/write_rows/g2', 1.4493),
    ('fwd/route', 0.0473), ('head', 0.0131), ('apply/stream', 0.0073),
    (xtrace.UNSCOPED, 0.2856), (xtrace.NO_SOURCE, 2.4759)])
def test_phase_ms_reads_what_the_trace_report_reads(scoped, prefix, ms):
  assert scoped['steps'] == 3
  assert layer.phase_ms({'trace': scoped}, prefix) == pytest.approx(
      ms, abs=6e-5)


def test_a_class_splits_by_phase(scoped):
  context = {'trace': scoped}
  parts = [layer.phase_ms(context, p, ('gather',)) for p in
           ('apply/dedup', 'fwd/lookup_combine', 'apply/read_rows')]
  # every gather of this step lies in one of the three phases
  assert sum(parts) == pytest.approx(layer.class_ms(context, ('gather',)),
                                     rel=1e-9)
  assert layer.phase_ms(context, 'apply/write_rows', ('gather',)) is None
  assert layer.phase_ms(context, 'no/such/phase') is None


def test_a_step_without_scopes_reads_nothing_and_is_all_unbooked(reduced):
  # the PR 23 recording was compiled before the phases existed
  context = {'trace': reduced}
  assert layer.phase_ms(context, 'apply/dedup') is None
  phases = reduced['phase_s']['/device:TPU:0']
  assert set(phases) == {xtrace.UNSCOPED, xtrace.NO_SOURCE}
  assert sum(phases.values()) == pytest.approx(reduced['busy_mean_s'])


@pytest.mark.parametrize('tf_op,path', [
    ('', 'no_source'),
    ('reduce_window_sum:', 'unscoped'),
    ('jit(step)/broadcast_in_dim:', 'unscoped'),
    ('jit(step)/while/body/mul:', 'unscoped'),
    ('jit(step)/apply/dedup/g1/jit(_take)/gather:', 'apply/dedup/g1'),
    ('jit(step)/cond/branch_1_fun/apply/read_rows/g0/select_n:',
     'apply/read_rows/g0'),
    ('jit(step)/while/body/apply/dedup/g0/apply/dedup/g0/sub:',
     'apply/dedup/g0/apply/dedup/g0'),
    ('jit(step)/jit(local_fn)/fwd/lookup_combine/g2/reduce_sum:',
     'fwd/lookup_combine/g2'),
    ('jit(step)/transpose(jvp(head))/dot_general:', 'head'),
    ('jit(step)/jvp(head)/jit(relu)/max:', 'head'),
])
def test_scope_path(tf_op, path):
  assert xtrace.scope_path(tf_op) == path


def test_under_matches_whole_components_wherever_jax_nested_them():
  assert layer.under('apply/dedup/g1', 'apply/dedup')
  assert layer.under('outer/apply/dedup/g1', 'apply/dedup')
  assert layer.under('apply/dedup/g1', 'apply/dedup/g1')
  assert not layer.under('apply/dedup/g10', 'apply/dedup/g1')
  assert not layer.under('apply/dedupe', 'apply/dedup')
  assert not layer.under('apply', 'apply/dedup')
