"""What keeps a later cell, class, traffic shape or metric an addition of
files: nothing in ``benchmarks/lib`` branches on a key that only one
class's configuration has, every name a data file gives resolves, and the
reference's side of a class imports nothing of the program."""
import glob
import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks.lib import cell, names

BENCH = cell.BENCH_DIR
ROOT = os.path.dirname(BENCH)
TOY = os.path.join(BENCH, 'tests', 'toy')
CLASS_NAMES = ('tables', 'builder', 'reference_head', 'dense_params', 'work')


def _files(root, kind):
  return sorted(glob.glob(os.path.join(root, kind, '*.json')))


CONFIGS = _files(BENCH, 'configs') + _files(TOY, 'configs')
MIXES = _files(BENCH, 'traffic') + _files(TOY, 'traffic')


def test_lib_holds_no_branch_on_a_configuration_key():
  branch = re.compile(r"""\bif\b.*['"]\s+(?:not\s+)?in\s+"""
                      r"""(?:config\b|context\[['"]config['"]\]|mix\b)""")
  found = []
  for path in sorted(glob.glob(os.path.join(BENCH, 'lib', '*.py'))):
    with open(path) as f:
      found += [f'{os.path.basename(path)}:{n}: {line.strip()}'
                for n, line in enumerate(f, 1) if branch.search(line)]
  assert not found, found
  with open(os.path.join(BENCH, 'lib', 'cell.py')) as f:
    assert 'RUNNERS' not in f.read()      # a runner is found by its file


@pytest.mark.parametrize('path', CONFIGS, ids=os.path.basename)
def test_a_configuration_names_its_class(path):
  with open(path) as f:
    config = json.load(f)
  for name in CLASS_NAMES:
    assert callable(names.resolve(config[name])), (path, name)
  specs, table_map, hotness = names.resolve(config['tables'])(config)
  assert len(table_map) == len(hotness) and max(table_map) < len(specs)
  assert config['optimizer']['kind'] in ('sgd', 'adagrad', 'adam')


@pytest.mark.parametrize('path', MIXES, ids=os.path.basename)
def test_a_mix_names_its_runner_and_its_generator(path):
  with open(path) as f:
    mix = json.load(f)
  assert callable(cell._function('runners', mix['kind'], 'run'))
  assert callable(names.resolve(mix['generator']))
  assert cell._function('runners', 'no-such-kind', 'run') is None
  # what it asks of the runtime yields to what the caller set
  environ = {'TPU_PREMAPPED_BUFFER_SIZE': 'the caller\'s'}
  names.set_runtime_env(mix, environ)
  assert environ.pop('TPU_PREMAPPED_BUFFER_SIZE') == 'the caller\'s'
  wanted = dict(mix.get('runtime_env', {}))
  wanted.pop('TPU_PREMAPPED_BUFFER_SIZE', None)
  assert environ == wanted and all(isinstance(v, str) for v in wanted.values())


@pytest.mark.parametrize('manifest', [
    os.path.join(ROOT, 'BENCHMARK.json'), os.path.join(TOY, 'manifest.json')],
    ids=os.path.basename)
def test_every_per_layer_metric_has_its_reader(manifest):
  with open(manifest) as f:
    per_layer = json.load(f)['per_layer']
  for metric in per_layer:
    assert callable(cell._function('metrics', metric['name'], 'read'))


def test_work_counts_the_flops_the_two_heads_had_before_it_moved():
  # PR 23's arithmetic, by hand: three products of 2 x B x fan_in x
  # fan_out a layer; the dot interaction 3 x 2 x B x n x n x d
  tiny = names.load_json(BENCH, 'configs', 'synthetic-tiny')
  # the 58 inputs' widths, block by block, and the 10 dense features
  fan_in = (2 * 8 + 2 * 16 + 2 * 16 + 16 + 16 * 8 + 10 * 8 + 4 * 8 + 2 * 16
            + 19 * 16) + 10
  want = 6 * 65536 * (fan_in * 256 + 256 * 128 + 128)
  got = names.resolve(tiny['work'])(tiny, None, 65536, 1, {})
  assert got == {'flops': want, 'bytes': 0}
  dlrm = names.load_json(BENCH, 'configs', 'dlrm-mlperf')
  mlps = (13 * 512 + 512 * 256 + 256 * 128 + (27 * 26 // 2 + 128) * 1024
          + 1024 * 1024 + 1024 * 512 + 512 * 256 + 256)
  want = (6 * 65536 * mlps + 6 * 65536 * 27 * 27 * 128) / 4
  got = names.resolve(dlrm['work'])(dlrm, None, 65536, 4, {})
  assert got == {'flops': want, 'bytes': 0}


@pytest.mark.parametrize('path', CONFIGS, ids=os.path.basename)
def test_the_reference_side_of_a_class_imports_nothing_of_the_program(path):
  code = (
      'import json, sys\n'
      'from benchmarks.lib import names, reference, ref_heads\n'
      f'config = json.load(open({path!r}))\n'
      "for name in ('tables', 'reference_head', 'dense_params'):\n"
      '  fn = names.resolve(config[name])\n'
      "  fn(config, 1) if name == 'dense_params' else fn(config)\n"
      "bad = [m for m in sys.modules if m.startswith('distributed_embed')]\n"
      'assert not bad, bad\n')
  subprocess.run([sys.executable, '-c', code], check=True, cwd=ROOT,
                 timeout=120, env={**os.environ, 'JAX_PLATFORMS': 'cpu'})
