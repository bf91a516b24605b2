"""Find a cell's files by name and run it.  Everything that belongs to
one configuration, one traffic mix, one cell's limits or one per-layer
metric is a file of its own under the benchmark's directory:

  configs/<config>.json   traffic/<mix>.json   limits/<cell>.json
  metrics/<metric>.py     (one function: ``read(context) -> number|None``)

so a later cell or metric is new files and a manifest entry, no edit.
"""

import importlib
import importlib.util
import os
import shutil
import sys
import time

from benchmarks.lib import names, xtrace

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUNNERS = {'train_pool': 'benchmarks.lib.train'}


class CompileCount:
  """Backend compiles JAX reports while it is active (a persistent-cache
  hit passes through the same event), as ``chip_smoke.CompileLog`` counts
  them."""

  def __init__(self):
    self.count = 0

  def _on(self, event, duration, **_):
    if event == '/jax/core/compile/backend_compile_duration':
      self.count += 1

  def __enter__(self):
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(self._on)
    return self

  def __exit__(self, *exc):
    from jax import monitoring
    monitoring.unregister_event_duration_listener(self._on)


def stamper(started):
  """``stamp(what)``: a line on standard error with the seconds since the
  process started."""
  def stamp(what):
    print(f'[{time.perf_counter() - started:6.1f}s] {what}', file=sys.stderr,
          flush=True)
  return stamp


def memory_peak_bytes(devices):
  """The peak on the fullest chip so far; a runner reads it when its
  window has closed, before the reference may add to it."""
  return int(max((d.memory_stats() or {}).get('peak_bytes_in_use', 0)
                 for d in devices))


def _reader(name):
  path = os.path.join(BENCH_DIR, 'metrics', f'{name}.py')
  spec = importlib.util.spec_from_file_location(
      'benchmarks_metric_' + name.replace('.', '_').replace('-', '_'), path)
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  return module.read


def run_cell(manifest, root, cell_name, args, devices, started, cache_dir):
  """Run cell ``cell_name`` of ``manifest`` with its files under ``root``
  on ``devices``; returns the result line as a dict."""
  cells = {w['name']: w for w in manifest['workloads']}
  if cell_name not in cells:
    raise SystemExit(f'unknown workload {cell_name!r}; the manifest has '
                     f'{sorted(cells)}')
  cell = cells[cell_name]
  config = names.load_json(root, 'configs', cell['config'])
  mix = names.load_json(root, 'traffic', cell['traffic'])
  limits = names.load_json(root, 'limits', cell_name)
  per_layer = [m for m in manifest['per_layer']
               if cell_name in m.get('workloads', [cell_name])]

  if mix.get('kind') not in RUNNERS:
    raise SystemExit(f'traffic/{cell["traffic"]}.json: unknown kind '
                     f'{mix.get("kind")!r}; the harness drives {sorted(RUNNERS)}')
  runner = importlib.import_module(RUNNERS[mix['kind']])
  result, end_to_end, context = runner.run(cell, config, mix, limits, args,
                                           devices, started, cache_dir)
  result['device'] = {
      'platform': devices[0].platform, 'kind': devices[0].device_kind,
      'count': len(devices),
      'memory_peak_bytes': result.pop('memory_peak_bytes')}
  if args.trace:
    reduced = xtrace.reduce_trace(xtrace.find_trace(context['trace_dir']),
                                  program=context['program'])
    shutil.rmtree(context['trace_dir'], ignore_errors=True)
    context['trace'] = reduced
    values = {m['name']: (_reader(m['name'])(context), m['unit'])
              for m in per_layer}
    values = {n: v for n, v in values.items() if v[0] is not None}
    result['device'].update(busy_s=reduced['busy_mean_s'],
                            window_s=reduced['window_s'])
    top = sorted(reduced['ops'].items(), key=lambda kv: -kv[1])[:10]
    result['breakdown'] = {
        'device_ops': [[n, s] for n, s in top],
        'idle_gaps': [[n, s] for n, s in reduced['idle_gaps'][:10]]}
  else:
    # a runner reports every end-to-end number it can take; the manifest
    # says which of them this cell is judged on
    values = {m['name']: (end_to_end[m['name']], m['unit'])
              for m in manifest['end_to_end']
              if cell_name in m.get('workloads', [cell_name])}
  result['metrics'] = {name: {'value': float(value), 'unit': unit}
                       for name, (value, unit) in values.items()}
  result['compared'] = result.pop('compared')    # comes last in the line
  return result
