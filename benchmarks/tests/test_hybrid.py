"""The hybrid state-space class at toy size (``toy_hybrid/``: a
directory and manifest of its own): the cell comes out ``correct``
through ``cell.run_cell`` on the CPU, the control and the faults of
``test_correct.py`` fail it, ``work`` counts what a count by hand counts,
and the readers of the head's phases read a trace recorded on the chip.
"""
import argparse
import json
import os
import time

import pytest

from benchmarks.classes import hybrid_ssm
from benchmarks.dev import record_hybrid_trace
from benchmarks.lib import cell as cell_lib
from benchmarks.lib import layer, names, reference, xtrace
from benchmarks.tests import test_correct

TOY = os.path.join(cell_lib.BENCH_DIR, 'tests', 'toy_hybrid')
CELL = 'toy-hybrid-1'
CONFIG = names.load_json(TOY, 'configs', 'toy-hybrid')
MIX = names.load_json(TOY, 'traffic', 'toy-packed')
LIMITS = names.load_json(TOY, 'limits', CELL)
SEEDS = (7, 2**31 + 5, 123456789)
RECORDED = os.path.join(os.path.dirname(__file__), 'data',
                        'v5e_hybrid_step.trace.json.gz')


def _run(seed, tmp_path):
  import jax
  with open(os.path.join(TOY, 'manifest.json')) as f:
    manifest = json.load(f)
  args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.2, trace=0)
  return cell_lib.run_cell(manifest, TOY, CELL, args, jax.devices()[:1],
                           time.perf_counter(), str(tmp_path))


def _fails(numbers):
  return [n for n, limit in LIMITS.items() if numbers[n] > limit]


@pytest.fixture(scope='module')
def stated():
  return {seed: reference.run_reference(CONFIG, MIX, seed)
          for seed in SEEDS}


@pytest.mark.parametrize('seed', SEEDS)
def test_control_is_not_correct(stated, seed):
  control = reference.run_reference(CONFIG, MIX, seed, precision='control')
  numbers, _ = reference.compare(control, stated[seed])
  assert _fails(numbers), numbers


@pytest.mark.parametrize('seed', SEEDS[:2])
@pytest.mark.parametrize('fault', ['state_unchanged', 'half_batch'])
def test_fault_in_the_reference_is_not_correct(stated, fault, seed):
  broken = reference.run_reference(CONFIG, MIX, seed, fault=fault)
  numbers, _ = reference.compare(broken, stated[seed])
  assert _fails(numbers), numbers


@pytest.mark.parametrize('fault,correct', [
    (None, True), ('state_unchanged', False), ('half_batch', False)])
def test_run_with_the_timed_path_broken(monkeypatch, tmp_path, fault,
                                        correct):
  if fault == 'half_batch':
    # a tied head takes the tables too: the toy manifest's planter wraps
    # a three-argument head
    import jax
    from distributed_embeddings_tpu import parallel
    real = parallel.make_hybrid_train_step
    half = lambda a: a[:a.shape[0] // 2]

    def broken(dist, head_loss_fn, *args, **kw):
      def halved(dense, emb_outs, batch, tables):
        return head_loss_fn(dense, tuple(half(e) for e in emb_outs),
                            jax.tree.map(half, batch), tables)
      return real(dist, halved, *args, **kw)

    monkeypatch.setattr(parallel, 'make_hybrid_train_step', broken)
  else:
    test_correct._plant(monkeypatch, fault)
  result = _run(2**31 + 77, tmp_path)
  assert result['correct'] is correct, result['compared']
  assert result['attempted'] > 0 and result['failed'] == 0


def test_every_new_metric_has_its_reader():
  with open(os.path.join(TOY, 'manifest.json')) as f:
    per_layer = json.load(f)['per_layer']
  assert {'ssm_scan_ms', 'ssm_scan_roofline', 'attention_ms',
          'vocab_head_ms', 'tied_apply_ms'} <= {m['name'] for m in per_layer}
  for metric in per_layer:
    assert callable(cell_lib._function('metrics', metric['name'], 'read'))


def test_work_counts_the_flops_a_count_by_hand_counts():
  # hidden 32, ffn 64, 2 scan heads of 8 x state 16, 4 query heads of 8
  # over 2 key-value heads, 64 rows; mamba-mamba-attention-mamba; two
  # sequences of 48
  in_proj = 32 * (16 + (16 + 2 * 16) + 2)          # z | xBC | dt
  mamba = in_proj + 16 * 32
  attention = 2 * 32 * 32 + 2 * 32 * 16
  mlp = 32 * 128 + 64 * 32
  parameters = 3 * (mamba + mlp) + (attention + mlp) + 64 * 32
  assert hybrid_ssm.matrix_parameters(CONFIG) == parameters
  tokens = 2 * 48
  scan = 3 * 5 * (2 * 8 * 16) * tokens * 3
  attend = 2 * 3 * 2 * 2 * (48 * 48 // 2) * 8 * 4
  got = hybrid_ssm.work(CONFIG, None, 2, 1, MIX)
  assert got['flops'] == 6 * tokens * parameters + scan + attend
  assert got['bytes'] == 28 * (parameters - 64 * 32)
  assert hybrid_ssm.scan_work(CONFIG, tokens)['flops'] == scan
  per_position = 2 * (48 + 2) + 16 + 16 + 48 + 2
  states = 4 * (2 * 8 * 16) * tokens / 8
  assert hybrid_ssm.scan_work(CONFIG, tokens)['bytes'] == (
      4 * (per_position * tokens + states) * 3)


def test_published_sizes_count_what_the_issue_counted():
  config = names.load_json(cell_lib.BENCH_DIR, 'configs',
                           'granite-4.0-h-micro')
  # 9 x (17,432,576 + 8,388,608 + 50,331,648) + (10,485,760 + 50,331,648)
  # + 12,544 x 2048
  assert hybrid_ssm.matrix_parameters(config) == 771_883_008
  dense = hybrid_ssm.dense_params({**config, 'layer_types': []}, 1)
  assert dense['final_norm'].shape == (2048,)
  mix = names.load_json(cell_lib.BENCH_DIR, 'traffic', 'train-packed')
  flops = hybrid_ssm.work(config, None, 2, 1, mix)['flops']
  assert 3.79e13 < 6 * 8192 * 771_883_008 < flops < 1.05 * 3.8e13


# ---- the phase readers, on a trace recorded on the chip -----------------


@pytest.fixture(scope='module')
def recorded():
  return xtrace.reduce_trace(RECORDED, program='jit_step')


def _read(name, recorded, **config):
  context = {'trace': recorded, 'mix': {'seq_len': 512}, 'global_batch': 2,
             'devices': [None], 'device_kind': 'TPU v5 lite',
             'config': {**record_hybrid_trace.CONFIG, **config}}
  return cell_lib._function('metrics', name, 'read')(context)


@pytest.mark.parametrize('name,value', [
    ('ssm_scan_ms', 0.339780), ('attention_ms', 0.0680395),
    ('vocab_head_ms', 0.0593688), ('tied_apply_ms', 0.0284391),
    ('apply_dedup_ms', 0.0353042), ('fwd_lookup_ms', 0.00712958)])
def test_phase_readers_on_the_recorded_step(recorded, name, value):
  """Three steps of a small hybrid step on one v5e (my chip run, PR 27;
  ``benchmarks/dev/record_hybrid_trace.py``): the head's phases are read
  wherever ``jax.checkpoint`` nested them (``head/head/
  rematted_computation/mixer/selective_scan``), forward and backward."""
  assert recorded['steps'] == 3 and recorded['module'] == 'jit_step'
  assert _read(name, recorded) == pytest.approx(value, rel=1e-4)


def test_scan_roofline_divides_the_counted_floor_by_the_scan_time(recorded):
  work = hybrid_ssm.scan_work(record_hybrid_trace.CONFIG, 2 * 512)
  floor = max(work['flops'] / 197e12, work['bytes'] / 819e9)
  got = _read('ssm_scan_roofline', recorded,
              scan_work='benchmarks.classes.hybrid_ssm:scan_work')
  assert got == pytest.approx(100 * floor / 0.339780e-3, rel=1e-4)
  assert 0 < got < 100
  # a configuration that names no scan_work reads nothing, and raises not
  assert _read('ssm_scan_roofline', recorded) is None


def test_the_heads_phases_and_the_remainders_sum_to_busy(recorded):
  phases = recorded['phase_s'][recorded['fullest']]
  assert sum(phases.values()) == pytest.approx(recorded['busy_mean_s'])
  inside = sum(s for path, s in phases.items()
               if any(layer.under(path, p) for p in (
                   'mixer/proj', 'mixer/conv', 'mixer/selective_scan',
                   'attention', 'mlp', 'vocab')))
  head = sum(s for path, s in phases.items() if layer.under(path, 'head'))
  # the residual adds and norms between the parts are the head's own
  assert 0.85 * head < inside < head


def test_a_step_without_the_phases_reads_nothing():
  probe = xtrace.reduce_trace(os.path.join(
      os.path.dirname(RECORDED), 'v5e_scoped_step.trace.json.gz'))
  for name in ('ssm_scan_ms', 'ssm_scan_roofline', 'attention_ms',
               'vocab_head_ms', 'tied_apply_ms'):
    assert _read(name, probe,
                 scan_work='benchmarks.classes.hybrid_ssm:scan_work') is None
