"""The short-convolution mixture-of-experts class at toy size
(``toy_lfm2/``: a directory and manifest of its own): the cell comes out
``correct`` through ``cell.run_cell`` on the CPU, the control and the
faults of ``test_correct.py`` fail it, ``work`` counts what a count by
hand counts, the published sizes count what ISSUE 33 counted, and the
readers of the operator's phase read a reduced trace.
"""
import argparse
import json
import os
import time

import pytest

from benchmarks.classes import lfm2_moe
from benchmarks.lib import cell as cell_lib
from benchmarks.lib import names, reference
from benchmarks.tests import test_correct

TOY = os.path.join(cell_lib.BENCH_DIR, 'tests', 'toy_lfm2')
CELL = 'toy-lfm2-1'
CONFIG = names.load_json(TOY, 'configs', 'toy-lfm2')
MIX = names.load_json(TOY, 'traffic', 'toy-packed-lfm2')
LIMITS = names.load_json(TOY, 'limits', CELL)
SEEDS = (7, 2**31 + 5, 123456789)
NEW = ('short_conv_ms', 'short_conv_roofline')


def _run(seed, tmp_path):
  import jax
  with open(os.path.join(TOY, 'manifest.json')) as f:
    manifest = json.load(f)
  args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.2, trace=0)
  return cell_lib.run_cell(manifest, TOY, CELL, args, jax.devices()[:1],
                           time.perf_counter(), str(tmp_path))


def _fails(numbers):
  return [n for n, limit in LIMITS.items()
          if not n.startswith('_') and numbers[n] > limit]


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
    # a tied head takes the tables too (as ``test_hybrid.py``)
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
  for path, cell in ((os.path.join(TOY, 'manifest.json'), CELL),
                     (os.path.join(os.path.dirname(cell_lib.BENCH_DIR),
                                   'BENCHMARK.json'),
                      'lfm2-train-packed-8k')):
    with open(path) as f:
      per_layer = [m for m in json.load(f)['per_layer']
                   if cell in m.get('workloads', [cell])]
    assert set(NEW) | {'tied_apply_ms', 'moe_expert_roofline',
                       'attention_ms'} <= {m['name'] for m in per_layer}, path
    assert 'apply_write_rows_ms' not in {m['name'] for m in per_layer}
    for metric in per_layer:
      assert callable(cell_lib._function('metrics', metric['name'], 'read'))


def test_work_counts_the_flops_a_count_by_hand_counts():
  # hidden 64, 4 query heads of 16 over 2 key-value heads, dense SwiGLU
  # of 96, experts of 32: 4 held of 16, 2 a token; conv-attention-conv
  # with one dense layer; 96 rows; two sequences of 48
  conv = 64 * 192 + 64 * 64
  attention = 2 * 64 * 64 + 2 * 64 * 32           # q, o; k, v
  expert = 3 * 64 * 32
  router = 64 * 16
  every_token = 2 * conv + attention + 3 * 64 * 96 + 2 * router + 96 * 64
  assert lfm2_moe.matrix_parameters(CONFIG) == every_token
  assert lfm2_moe.held_parameters(CONFIG) == (
      every_token + 2 * 3 * 64 + 2 * 16 + 2 * 16 + 2 * 4 * expert)
  tokens = 2 * 48
  assignments = tokens * 2 * 4 / 16                # half a token's
  assert lfm2_moe.expected_assignments(CONFIG, tokens) == assignments
  experts = 3 * 2 * expert * assignments * 2
  assert lfm2_moe.expert_work(CONFIG, tokens) == {
      'flops': experts,
      'bytes': 4 * (3 * 4 * expert + 4 * 64 * assignments) * 2}
  attend = 2 * 3 * 2 * 2 * 16 * 4 * (48 * 48 // 2)
  assert lfm2_moe.attention_work(CONFIG, 2, 48) == {'flops': attend}
  assert lfm2_moe.short_conv_work(CONFIG, tokens) == {
      'flops': 3 * 2 * conv * tokens * 2,
      'bytes': 4 * (3 * conv + 5 * 64 * tokens) * 2}
  got = lfm2_moe.work(CONFIG, None, 2, 1, MIX)
  assert got['flops'] == 6 * tokens * every_token + experts + attend
  assert got['bytes'] == 28 * (lfm2_moe.held_parameters(CONFIG) - 96 * 64)


def test_published_sizes_count_what_the_issue_counted():
  config = names.load_json(cell_lib.BENCH_DIR, 'configs', 'lfm2-24b-a2b')
  n = lfm2_moe.parameters(config)
  assert n['short_conv'] == 16_783_360 and n['attention'] == 10_485_888
  assert n['dense_ffn'] == 72_351_744 and n['expert'] == 9_437_184
  assert n['router'] == 131_072 + 64 and n['table'] == 16_777_216
  # what this chip holds: the dense layer, four routed layers under an
  # operator and two under attention, 8 experts each, an eighth of the
  # table: 647.8 M
  routed = n['router'] + 8 * n['expert']
  held = lfm2_moe.held_parameters(config)
  assert held == (n['short_conv'] + n['dense_ffn']
                  + 4 * (n['short_conv'] + routed)
                  + 2 * (n['attention'] + routed) + n['table'])
  assert round(held / 1e6, 1) == 647.8
  # the published model from the same counts: 30 operators, 10 attention
  # layers, 2 dense and 38 routed layers of 64 experts, the whole tied
  # vocabulary once: 23.8 B, of which a token meets 2.3 B
  whole = (30 * n['short_conv'] + 10 * n['attention'] + 2 * n['dense_ffn']
           + 38 * (n['router'] + 64 * n['expert']) + 65_536 * 2048)
  assert round(whole / 1e9, 1) == 23.8
  active = whole - 38 * 60 * n['expert']
  assert round(active / 1e9, 1) == 2.3
  assert config['reduced'] == ['num_hidden_layers', 'num_dense_layers',
                               'layer_types', 'num_experts', 'vocab_size']
  assert config['published']['num_experts'] == 64
  assert (config['num_experts'], config['num_experts_per_tok']) == (8, 4)
  assert config['layer_types'].count('conv') == 5
  mix = names.load_json(cell_lib.BENCH_DIR, 'traffic', 'train-packed-8k')
  work = lfm2_moe.work(config, None, 2, 1, mix)
  # ISSUE 33: about 25 TFLOP a step, 21.9 products of weights, 3.3
  # attention's own
  assert 25.1e12 < work['flops'] < 25.3e12
  assert 3.29e12 < lfm2_moe.attention_work(config, 2, 8192)['flops'] < 3.31e12
  assert lfm2_moe.expected_assignments(config, 16384) == 8192
  conv = lfm2_moe.short_conv_work(config, 16384)
  # 8.4 ms a layer at the chip's peak, and FLOPs bind
  assert conv['flops'] == 5 * 6 * 16384 * 4 * 2048 * 2048
  assert 8.3e-3 < conv['flops'] / 5 / 197e12 < 8.4e-3
  assert conv['flops'] / 197e12 > conv['bytes'] / 819e9
  experts = lfm2_moe.expert_work(config, 16384)
  assert experts['flops'] / 197e12 > experts['bytes'] / 819e9


# ---- the phase readers, on a reduced trace --------------------------------


def _context(phases, steps=4, **config):
  """A reduced trace of one device with ``phases`` (scope path -> self
  seconds over ``steps`` steps), as ``xtrace.reduce_trace`` returns it."""
  real = names.load_json(cell_lib.BENCH_DIR, 'configs', 'lfm2-24b-a2b')
  return {'trace': {'steps': steps, 'phase_s': {'/device:TPU:0': phases},
                    'fullest': '/device:TPU:0', 'ops': {}},
          'config': {**real, **config}, 'mix': {'seq_len': 8192},
          'global_batch': 2, 'devices': [None],
          'device_kind': 'TPU v5 lite'}


def _read(name, context):
  return cell_lib._function('metrics', name, 'read')(context)


PHASES = {
    'head/head/rematted_computation/mixer/short_conv': 0.5,
    'head/mixer/short_conv': 0.3,
    'head/head/rematted_computation/attention/full': 0.4,
    'head/head/rematted_computation/moe/experts': 0.2,
    'head/mixer/conv': 0.7,          # another stack's phase: not the operator's
    'head/vocab': 0.16,
    'apply/tied/g0': 0.008,
}


@pytest.mark.parametrize('name,value', [
    ('short_conv_ms', 200.0), ('attention_ms', 100.0),
    ('moe_expert_ms', 50.0), ('tied_apply_ms', 2.0),
    ('vocab_head_ms', 40.0)])
def test_phase_readers_sum_the_paths_under_their_phases(name, value):
  """Milliseconds a step: ``short_conv_ms`` is the operator's phase
  wherever ``jax.checkpoint`` nested it, and no other stack's
  ``mixer/conv``."""
  assert _read(name, _context(PHASES)) == pytest.approx(value)


def test_short_conv_roofline_divides_the_counted_floor_by_the_phases_time():
  config = names.load_json(cell_lib.BENCH_DIR, 'configs', 'lfm2-24b-a2b')
  floor = lfm2_moe.short_conv_work(config, 16384)['flops'] / 197e12
  got = _read('short_conv_roofline', _context(PHASES))
  assert got == pytest.approx(100 * floor / 200e-3)
  assert 0 < got < 100
  # a configuration that names no short_conv_work reads nothing, and
  # raises not
  bare = _context(PHASES)
  del bare['config']['short_conv_work']
  assert _read('short_conv_roofline', bare) is None


def test_a_step_without_the_phase_reads_nothing():
  """The parent's program has no such operator: both new readers return
  ``None`` there and raise nothing."""
  for phases in ({}, {'head/mlp': 0.3, 'head/mixer/conv': 0.1}):
    for name in NEW:
      assert _read(name, _context(phases)) is None, name
