"""The trace reduction on a small recorded trace: four steps of the
program's train step at a toy size on one v5e (my chip run, PR 23),
cut down to the device's op and module events and the host's bench spans.
"""
import os

import pytest

from benchmarks.lib import xtrace

TRACE = os.path.join(os.path.dirname(__file__), 'data',
                     'v5e_probe_step.trace.json.gz')


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
