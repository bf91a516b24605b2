"""The mixture-of-experts class at toy size (``toy_moe/``: a directory and
manifest of its own): the cell comes out ``correct`` through
``cell.run_cell`` on the CPU, the control and the faults of
``test_correct.py`` fail it, ``work`` counts what a count by hand counts,
the published sizes count what ISSUE 31 counted, and the readers of the
routed layer's phases read a reduced trace.
"""
import argparse
import json
import os
import time

import pytest

from benchmarks.classes import moe_lm
from benchmarks.lib import cell as cell_lib
from benchmarks.lib import names, reference
from benchmarks.tests import test_correct

TOY = os.path.join(cell_lib.BENCH_DIR, 'tests', 'toy_moe')
CELL = 'toy-moe-1'
CONFIG = names.load_json(TOY, 'configs', 'toy-moe')
MIX = names.load_json(TOY, 'traffic', 'toy-packed-moe')
LIMITS = names.load_json(TOY, 'limits', CELL)
SEEDS = (7, 2**31 + 5, 123456789)
NEW = ('moe_route_ms', 'moe_dispatch_ms', 'moe_expert_ms',
       'moe_expert_roofline', 'window_attention_ms')


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
  if fault == 'half_batch':
    # untied: which rows a step touches follows the batch, so the count
    # of moved elements is the number half a batch cannot pass
    assert 'moved_gap' in _fails(numbers)


@pytest.mark.parametrize('fault,correct', [
    (None, True), ('state_unchanged', False), ('half_batch', False)])
def test_run_with_the_timed_path_broken(monkeypatch, tmp_path, fault,
                                        correct):
  test_correct._plant(monkeypatch, fault)
  result = _run(2**31 + 77, tmp_path)
  assert result['correct'] is correct, result['compared']
  assert result['attempted'] > 0 and result['failed'] == 0


def test_every_new_metric_has_its_reader():
  for path in (os.path.join(TOY, 'manifest.json'),
               os.path.join(os.path.dirname(cell_lib.BENCH_DIR),
                            'BENCHMARK.json')):
    with open(path) as f:
      per_layer = json.load(f)['per_layer']
    assert set(NEW) <= {m['name'] for m in per_layer}, path
    for metric in per_layer:
      assert callable(cell_lib._function('metrics', metric['name'], 'read'))


def test_work_counts_the_flops_a_count_by_hand_counts():
  # hidden 64, 4 query heads of 16 over 2 key-value heads, dense SwiGLU
  # of 96, experts of 32: 4 held of 16, 4 a token; sliding-sliding-full
  # with one dense layer, window 16; 96 rows; two sequences of 48
  attention = 3 * 64 * 64 + 2 * 64 * 32           # q, gate, o; k, v
  shared = expert = 3 * 64 * 32
  router = 64 * 16
  every_token = (3 * attention + 3 * 64 * 96 + 2 * (router + shared)
                 + 64 * 96)
  assert moe_lm.matrix_parameters(CONFIG) == every_token
  assert moe_lm.held_parameters(CONFIG) == (
      every_token + 2 * 4 * expert + 96 * 64)
  tokens = 2 * 48
  assignments = tokens * 4 * 4 / 16                # one a token
  assert moe_lm.expected_assignments(CONFIG, tokens) == assignments
  experts = 3 * 2 * expert * assignments * 2
  assert moe_lm.expert_work(CONFIG, tokens) == {
      'flops': experts,
      'bytes': 4 * (3 * 4 * expert + 4 * 64 * assignments) * 2}
  windowed = 16 * 17 // 2 + (48 - 16) * 16
  attend = 2 * 3 * 2 * 2 * 16 * 4 * (2 * windowed + 48 * 48 // 2)
  assert moe_lm.attention_work(CONFIG, 2, 48) == {'flops': attend}
  got = moe_lm.work(CONFIG, None, 2, 1, MIX)
  assert got['flops'] == 6 * tokens * every_token + experts + attend
  assert got['bytes'] == 28 * (every_token + 2 * 4 * expert)


def test_published_sizes_count_what_the_issue_counted():
  config = names.load_json(cell_lib.BENCH_DIR, 'configs', 'trinity-mini')
  n = moe_lm.parameters(config)
  assert n['attention'] == 27_262_976 and n['expert'] == 6_291_456
  assert n['dense_ffn'] == 37_748_736 and n['router'] == 262_144
  assert n['embedding'] == n['head'] == 25_024 * 2048 == 51_249_152
  # what this chip holds: one dense layer, four routed layers of 16
  # experts, an eighth of table and head: 705.5 M (norm gains left out)
  held = moe_lm.held_parameters(config)
  assert held == (65_011_712 + 4 * 134_479_872 + 2 * 51_249_152)
  assert round(held / 1e6, 1) == 705.4 or round(held / 1e6, 1) == 705.5
  # the published model from the same counts: 2 dense + 30 routed layers
  # of 128 experts, the whole vocabulary twice: 26.1 B
  whole = (32 * n['attention'] + 2 * n['dense_ffn']
           + 30 * (n['router'] + n['shared'] + 128 * n['expert'])
           + 2 * 200_192 * 2048)
  assert round(whole / 1e9, 1) == 26.1
  assert config['reduced'] == ['num_hidden_layers', 'num_dense_layers',
                               'layer_types', 'num_experts', 'vocab_size']
  assert config['published']['num_experts'] == 128
  assert (config['num_experts'], config['num_experts_per_tok']) == (16, 8)
  mix = names.load_json(cell_lib.BENCH_DIR, 'traffic', 'train-packed-8k')
  work = moe_lm.work(config, None, 2, 1, mix)
  # ISSUE 31: 36.3 TFLOP a step, 27.2 products of weights, 9.1 attention's
  assert 36.2e12 < work['flops'] < 36.4e12
  assert 9.0e12 < moe_lm.attention_work(config, 2, 8192)['flops'] < 9.1e12
  assert moe_lm.expected_assignments(config, 16384) == 16384
  experts = moe_lm.expert_work(config, 16384)
  assert experts['flops'] == 6 * 16384 * 6_291_456 * 4
  # FLOPs bind the experts' roofline at these sizes
  assert experts['flops'] / 197e12 > experts['bytes'] / 819e9


# ---- the phase readers, on a reduced trace --------------------------------


def _context(phases, steps=4, **config):
  """A reduced trace of one device with ``phases`` (scope path -> self
  seconds over ``steps`` steps), as ``xtrace.reduce_trace`` returns it."""
  real = names.load_json(cell_lib.BENCH_DIR, 'configs', 'trinity-mini')
  return {'trace': {'steps': steps, 'phase_s': {'/device:TPU:0': phases},
                    'fullest': '/device:TPU:0',
                    'ops': {'ragged-dot-none.7 (other)': 0.1,
                            'ragged-dot-none.8 (other)': 0.06,
                            'fusion.9 (matmul)': 0.5} if phases else {}},
          'config': {**real, **config}, 'mix': {'seq_len': 8192},
          'global_batch': 2, 'devices': [None],
          'device_kind': 'TPU v5 lite'}


def _read(name, context):
  return cell_lib._function('metrics', name, 'read')(context)


PHASES = {
    'head/head/rematted_computation/moe/route': 0.004,
    'head/moe/route': 0.002,
    'head/head/rematted_computation/moe/dispatch': 0.008,
    'head/moe/combine': 0.012,
    'head/head/rematted_computation/moe/experts': 0.2,
    'head/moe/shared/mlp': 0.1,
    'head/head/rematted_computation/attention/window': 1.0,
    'head/head/rematted_computation/attention/full': 0.6,
    'head/vocab': 0.16,
    'no_source': 0.3,        # XLA's own ops, the ragged-dot kernels among them
}


@pytest.mark.parametrize('name,value', [
    ('moe_route_ms', 1.5), ('moe_dispatch_ms', 5.0), ('moe_expert_ms', 90.0),
    ('window_attention_ms', 250.0), ('attention_ms', 400.0),
    ('vocab_head_ms', 40.0)])
def test_phase_readers_sum_the_paths_under_their_phases(name, value):
  """Milliseconds a step: ``moe_dispatch_ms`` is dispatch and combine,
  ``moe_expert_ms`` the phase and the unscoped ``ragged-dot`` kernels,
  ``attention_ms`` both kinds of attention, wherever ``jax.checkpoint``
  nested them."""
  assert _read(name, _context(PHASES)) == pytest.approx(value)


def test_kernels_that_carry_a_scope_are_not_counted_twice():
  """The ``ragged-dot`` kernels are found by name only as far as the chip
  has time that no phase books: were they scoped (under ``moe/experts``
  or anywhere else), that time would be gone from ``no_source`` and the
  phase alone would be read."""
  scoped = {**PHASES, 'no_source': 0.02}
  assert _read('moe_expert_ms', _context(scoped)) == pytest.approx(55.0)
  del scoped['no_source']
  assert _read('moe_expert_ms', _context(scoped)) == pytest.approx(50.0)


def test_expert_roofline_divides_the_counted_floor_by_the_experts_time():
  config = names.load_json(cell_lib.BENCH_DIR, 'configs', 'trinity-mini')
  floor = moe_lm.expert_work(config, 16384)['flops'] / 197e12
  got = _read('moe_expert_roofline', _context(PHASES))
  assert got == pytest.approx(100 * floor / 90e-3)
  assert 0 < got < 100
  # a configuration that names no expert_work reads nothing, and raises not
  bare = _context(PHASES)
  del bare['config']['expert_work']
  assert _read('moe_expert_roofline', bare) is None


def test_a_step_without_the_phases_reads_nothing():
  """The parent's program has no routed layer: every new reader returns
  ``None`` there and raises nothing."""
  for phases in ({}, {'head/mlp': 0.3, 'apply/dedup/g0': 0.1}):
    for name in NEW:
      assert _read(name, _context(phases)) is None, name
