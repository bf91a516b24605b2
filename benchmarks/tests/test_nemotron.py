"""The hybrid Mamba-2 mixture-of-experts class at toy size
(``toy_nemotron/``: a directory and manifest of its own): the cell comes
out ``correct`` through ``cell.run_cell`` on the CPU, the control and
the faults of ``test_correct.py`` fail it, ``work`` counts what a count
by hand counts, the published sizes count what the cut was sized by, and
the shared expert's reader reads a reduced trace.
"""
import argparse
import json
import os
import time

import pytest

from benchmarks.classes import nemotron_h
from benchmarks.lib import cell as cell_lib
from benchmarks.lib import names, reference
from benchmarks.tests import test_correct

TOY = os.path.join(cell_lib.BENCH_DIR, 'tests', 'toy_nemotron')
CELL = 'toy-nemotron-1'
CONFIG = names.load_json(TOY, 'configs', 'toy-nemotron')
MIX = names.load_json(TOY, 'traffic', 'toy-packed-nemotron')
LIMITS = names.load_json(TOY, 'limits', CELL)
SEEDS = (7, 2**31 + 5, 123456789)
REAL = 'nemotron-train-packed-8k'


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
  if fault == 'half_batch':
    # untied: which rows a step touches follows the batch
    assert 'moved_gap' in _fails(numbers)


@pytest.mark.parametrize('fault,correct', [
    (None, True), ('state_unchanged', False), ('half_batch', False)])
def test_run_with_the_timed_path_broken(monkeypatch, tmp_path, fault,
                                        correct):
  test_correct._plant(monkeypatch, fault)
  result = _run(2**31 + 77, tmp_path)
  assert result['correct'] is correct, result['compared']
  assert result['attempted'] > 0 and result['failed'] == 0


def test_every_metric_of_the_cell_has_its_reader():
  for path, cell in ((os.path.join(TOY, 'manifest.json'), CELL),
                     (os.path.join(os.path.dirname(cell_lib.BENCH_DIR),
                                   'BENCHMARK.json'), REAL)):
    with open(path) as f:
      per_layer = [m for m in json.load(f)['per_layer']
                   if cell in m.get('workloads', [cell])]
    got = {m['name'] for m in per_layer}
    assert {'moe_shared_ms', 'ssm_scan_roofline', 'moe_expert_roofline',
            'apply_write_rows_ms', 'attention_core_ms'} <= got, path
    # untied, no short convolution, no window
    assert not got & {'tied_apply_ms', 'short_conv_ms', 'window_attention_ms'}
    for metric in per_layer:
      assert callable(cell_lib._function('metrics', metric['name'], 'read'))


def test_work_counts_the_flops_a_count_by_hand_counts():
  # hidden 64; MEM*E: two mixers of 8 heads of 16 (inner 128) with 2
  # groups of 16 in B and C, 4 taps; attention 4 x 16 over 2 x 16; two
  # routed layers of 4 held of 16 experts of 32, 3 a token, a shared
  # expert of 48; 96 rows; two sequences of 48
  conv_dim = 128 + 2 * 2 * 16
  mixer = 64 * (128 + conv_dim + 8) + 128 * 64
  attention = 2 * 64 * 64 + 2 * 64 * 32
  expert, shared, router = 2 * 64 * 32, 2 * 64 * 48, 64 * 16
  every_token = 2 * mixer + attention + 2 * (router + shared) + 64 * 96
  assert nemotron_h.matrix_parameters(CONFIG) == every_token
  vectors = 5 * conv_dim + 3 * 8 + 128
  assert nemotron_h.held_parameters(CONFIG) == (
      every_token + 2 * vectors + 2 * 16 + 2 * 4 * expert + 96 * 64)
  tokens = 2 * 48
  assignments = tokens * 3 * 4 / 16
  assert nemotron_h.expected_assignments(CONFIG, tokens) == assignments
  experts = 3 * 2 * expert * assignments * 2
  assert nemotron_h.expert_work(CONFIG, tokens) == {
      'flops': experts,
      'bytes': 4 * (3 * 4 * expert + 4 * 64 * assignments) * 2}
  scan = 3 * 5 * 8 * 16 * 16 * tokens * 2
  per_position = 2 * (conv_dim + 8) + 128 + 128 + conv_dim + 8
  states = 4 * 8 * 16 * 16 * tokens / 16
  assert nemotron_h.scan_work(CONFIG, tokens) == {
      'flops': scan, 'bytes': 4 * (per_position * tokens + states) * 2}
  attend = 2 * 3 * 2 * 2 * 16 * 4 * (48 * 48 // 2)
  assert nemotron_h.attention_work(CONFIG, 2, 48) == {'flops': attend}
  got = nemotron_h.work(CONFIG, None, 2, 1, MIX)
  assert got['flops'] == 6 * tokens * every_token + experts + scan + attend
  assert got['bytes'] == 28 * (nemotron_h.held_parameters(CONFIG) - 96 * 64)


def test_published_sizes_count_what_the_cut_was_sized_by():
  config = names.load_json(cell_lib.BENCH_DIR, 'configs',
                           'nemotron-3-nano-30b-a3b')
  n = nemotron_h.parameters(config)
  # a mixer 38.74 M: in-projection 2688 x 10304, out 4096 x 2688, the
  # convolution's 4 taps and bias over 6144 channels, the vectors
  assert n['mamba'] == (2688 * 10304 + 4096 * 2688 + 5 * 6144 + 3 * 64
                        + 4096)
  assert round(n['mamba'] / 1e6, 2) == 38.74
  assert round(n['attention'] / 1e6, 2) == 23.40
  routed = n['router'] + n['shared'] + 8 * n['expert']
  assert round(routed / 1e6, 2) == 100.12
  assert n['embedding'] == n['head'] == 16384 * 2688
  held = nemotron_h.held_parameters(config)
  assert held == 4 * n['mamba'] + n['attention'] + 4 * routed + 2 * n['head']
  assert round(held / 1e6, 1) == 666.9
  assert config['reduced'] == ['num_hidden_layers', 'hybrid_override_pattern',
                               'n_routed_experts', 'vocab_size']
  assert config['hybrid_override_pattern'] == 'MEMEM*EME'
  assert config['published']['hybrid_override_pattern'].startswith(
      config['hybrid_override_pattern'])
  assert config['published']['n_routed_experts'] == 128
  mix = names.load_json(cell_lib.BENCH_DIR, 'traffic', 'train-packed-8k')
  assert nemotron_h.expected_assignments(config, 16384) == 6144
  work = nemotron_h.work(config, None, 2, 1, mix)
  assert 35.0e12 < work['flops'] < 35.2e12
  # the scans' bytes bind; the experts' FLOPs do
  scan = nemotron_h.scan_work(config, 16384)
  assert scan['bytes'] / 819e9 > scan['flops'] / 197e12
  experts = nemotron_h.expert_work(config, 16384)
  assert experts['flops'] / 197e12 > experts['bytes'] / 819e9


def _context(phases, steps=4):
  real = names.load_json(cell_lib.BENCH_DIR, 'configs',
                         'nemotron-3-nano-30b-a3b')
  return {'trace': {'steps': steps, 'phase_s': {'/device:TPU:0': phases},
                    'fullest': '/device:TPU:0', 'ops': {}},
          'config': real, 'mix': {'seq_len': 8192}, 'global_batch': 2,
          'devices': [None], 'device_kind': 'TPU v5 lite'}


def test_the_shared_experts_reader_sums_its_phase():
  phases = {'head/head/rematted_computation/moe/shared': 0.3,
            'head/moe/shared': 0.1, 'head/moe/experts': 0.5,
            'head/mixer/selective_scan': 0.2}
  read = cell_lib._function('metrics', 'moe_shared_ms', 'read')
  assert read(_context(phases)) == pytest.approx(100.0)
  # the parent's program: no such phase, nothing read and nothing raised
  assert read(_context({'head/moe/experts': 0.5})) is None
  ssm = cell_lib._function('metrics', 'ssm_scan_roofline', 'read')
  floor = nemotron_h.scan_work(_context({})['config'], 16384)['bytes'] / 819e9
  got = ssm(_context({**phases, 'head/mixer/conv': 0.2}))
  assert got == pytest.approx(100 * floor / 0.1)
