"""chip_smoke.py off the chip: its body at a toy size on the 8-device CPU
mesh, its NumPy oracles against hand-made cases, the command's refusal
of a CPU, and the compile-cache rule it shares with every entry point.
What only the chip can say (times, peak bytes, the scatter hints on a
backend that believes them) is the command's own job, on the chip."""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope='module')
def smoke():
  spec = importlib.util.spec_from_file_location(
      'chip_smoke_for_test', os.path.join(_ROOT, 'chip_smoke.py'))
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod


def test_smoke_body_at_toy_size_on_the_cpu_mesh(smoke):
  """The whole body — train, NumPy forward and Adagrad oracles, serving
  — on a config with synthetic-tiny's structure (shared multi-hot
  tables, many one-hot tables, widths 8 and 16) and toy rows."""
  from distributed_embeddings_tpu.models.synthetic import (EmbeddingConfig,
                                                           ModelConfig)
  E = EmbeddingConfig
  toy = ModelConfig('Toy V3', (
      E(1, (1, 10), 1000, 8, True), E(1, (1, 10), 5000, 16, True),
      E(1, (1,), 5000, 16, False), E(4, (1,), 10, 8, False),
      E(3, (1,), 100, 8, False), E(2, (1,), 1000, 16, False)),
                    (32, 16), 10, None)
  lines = []
  obs = smoke.run_smoke(jax.devices()[:8], toy, 256, parity_batch=64,
                        serve_batch=32, serve_requests=12,
                        log=lines.append)
  assert np.isfinite(obs['losses']).all() and len(obs['losses']) == 9
  assert len(obs['step_ms']) == 5 and obs['scalar_pull_ms'] > 0
  assert obs['adagrad']['tables'] == 12
  assert obs['adagrad']['rows_fed_by_duplicates'] > 0
  assert obs['adagrad']['worst_error_over_allowance'] <= 1.0
  assert obs['serve']['completed'] == 12
  assert obs['compiles'] > 0
  text = '\n'.join(lines)
  # the multi-device assertions ran: 8 devices is a multi-chip mesh
  assert 'all-to-all in the compiled step' in text
  assert 'forward parity: 14 inputs' in text
  json.dumps(obs)  # what the command prints must serialise


def test_lookup_oracle_is_exact_for_one_hot_and_bounded_for_bags(smoke):
  rng = np.random.default_rng(0)
  table = rng.normal(size=(50, 8)).astype(np.float32)
  rows_of = lambda ids: table[ids]
  one = rng.integers(0, 50, size=(16,))
  bag = rng.integers(0, 50, size=(16, 10))
  smoke.check_lookup('one-hot', table[one], rows_of, one)
  got = table[bag].astype(np.float64).sum(axis=1).astype(np.float32)
  smoke.check_lookup('bag', got, rows_of, bag)  # other order: round-off
  wrong = table[one].copy()
  wrong[3, 2] = np.nextafter(wrong[3, 2], np.float32(np.inf))
  with pytest.raises(smoke.SmokeFailure, match='not bit-exact'):
    smoke.check_lookup('one-hot', wrong, rows_of, one)
  with pytest.raises(smoke.SmokeFailure, match='beyond f32'):
    smoke.check_lookup('bag', got + np.float32(1e-3), rows_of, bag)


def test_adagrad_oracle_on_duplicate_ids(smoke):
  """row_sums adds duplicates; check_adagrad accepts the exact update,
  and refuses a dropped duplicate, an update landed on an untouched
  row, and a touched accumulator left behind."""
  rng = np.random.default_rng(1)
  before = rng.normal(size=(40, 8)).astype(np.float32)
  ids = np.array([7, 3, 7, 7, 12, 3], np.int64)
  grads = rng.normal(size=(6, 8)).astype(np.float32) * 1e-2
  rows, gsum, counts = smoke.row_sums(ids, grads)
  assert rows.tolist() == [3, 7, 12] and counts.tolist() == [2, 3, 1]
  np.testing.assert_allclose(gsum[1], grads[[0, 2, 3]].sum(0), rtol=1e-6)

  acc0 = np.float32(smoke.ACC0)
  acc = np.full_like(before, acc0)
  acc[rows] = (acc0 + gsum**2).astype(np.float32)
  after = before.copy()
  after[rows] = (before[rows] - smoke.LR * gsum
                 / np.sqrt(acc0 + gsum**2 + smoke.EPS)).astype(np.float32)
  tol = 64 * smoke.EPS32 * float(np.abs(np.cumsum(gsum, axis=0)).max())
  err, leaked = smoke.check_adagrad('t', before, after, acc, rows, gsum,
                                    tol)
  assert err <= 1.0 and leaked == 0

  dropped = after.copy()  # the update of ONE of row 7's three duplicates
  dropped[7] = (before[7] - smoke.LR * (gsum[1] - grads[3])
                / np.sqrt(acc0 + smoke.EPS)).astype(np.float32)
  with pytest.raises(smoke.SmokeFailure, match='touched rows off'):
    smoke.check_adagrad('t', before, dropped, acc, rows, gsum, tol)
  misplaced = after.copy()
  misplaced[20] += np.float32(1e-4)
  with pytest.raises(smoke.SmokeFailure, match='untouched table rows'):
    smoke.check_adagrad('t', before, misplaced, acc, rows, gsum, tol)
  stale_acc = acc.copy()
  stale_acc[21, 0] = np.nextafter(acc0, np.float32(1))
  with pytest.raises(smoke.SmokeFailure, match='untouched accumulator'):
    smoke.check_adagrad('t', before, after, stale_acc, rows, gsum, tol)


def test_chip_smoke_command_refuses_a_cpu():
  """`JAX_PLATFORMS=cpu python chip_smoke.py`: non-zero, says why, names
  the device it found, prints no result line."""
  proc = subprocess.run(
      [sys.executable, os.path.join(_ROOT, 'chip_smoke.py')],
      env={**os.environ, 'JAX_PLATFORMS': 'cpu'}, cwd=_ROOT,
      capture_output=True, text=True, timeout=120)
  assert proc.returncode not in (0, 2, 3), proc.returncode
  assert 'no TPU' in proc.stderr
  assert "'platform': 'cpu'" in proc.stdout
  assert '"ok"' not in proc.stdout


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
  """JAX_COMPILATION_CACHE_DIR set: the helper sets nothing and reports
  it.  Unset: the fixed <checkout>/.jax_cache, nothing temporary in it."""
  from distributed_embeddings_tpu.utils import compile_cache
  saved = jax.config.jax_compilation_cache_dir
  try:
    jax.config.update('jax_compilation_cache_dir', 'untouched-marker')
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == 'untouched-marker'
    monkeypatch.delenv(compile_cache.ENV_VAR)
    fixed = os.path.join(_ROOT, '.jax_cache')
    assert compile_cache.configure() == fixed
    assert jax.config.jax_compilation_cache_dir == fixed
    assert compile_cache.configure() == fixed  # same path every time
  finally:
    jax.config.update('jax_compilation_cache_dir', saved)


@pytest.mark.parametrize('from_env', [True, False])
def test_compile_cache_keys_on_the_scopes(monkeypatch, tmp_path, from_env):
  """The device phases are metadata of the executable: the cache's key
  takes metadata in, wherever the directory came from, so a step whose
  phases changed is compiled again and not loaded with the old ones."""
  from distributed_embeddings_tpu.utils import compile_cache
  flag = 'jax_compilation_cache_include_metadata_in_key'
  saved = (jax.config.jax_compilation_cache_dir, getattr(jax.config, flag))
  try:
    jax.config.update(flag, False)
    if from_env:
      monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    else:
      monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    compile_cache.configure()
    assert getattr(jax.config, flag) is True
  finally:
    jax.config.update('jax_compilation_cache_dir', saved[0])
    jax.config.update(flag, saved[1])


def test_no_other_compile_cache_setting_in_the_tree():
  """One helper owns the setting: no entry point overrides it in code."""
  hits = []
  for base, _, files in os.walk(_ROOT):
    if any(part.startswith('.') or part == 'chiprun_out'
           for part in os.path.relpath(base, _ROOT).split(os.sep)
           if part != '.'):
      continue
    for name in files:
      path = os.path.join(base, name)
      if not name.endswith(('.py', '.sh')) or path == __file__:
        continue
      with open(path, encoding='utf-8') as f:
        if 'jax_compilation_cache_dir' in f.read():
          hits.append(os.path.relpath(path, _ROOT))
  assert hits == ['distributed_embeddings_tpu/utils/compile_cache.py']
