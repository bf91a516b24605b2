"""Find a cell's files by name and run it.  Everything that belongs to
one configuration, one traffic mix, one cell's limits, one kind of
runner or one per-layer metric is a file of its own under the
benchmark's directory, found by the name the manifest or a data file
gives:

  configs/<config>.json   traffic/<mix>.json   limits/<cell>.json
  runners/<kind>.py       (the mix's ``kind``; one function: ``run``)
  metrics/<metric>.py     (one function: ``read(context) -> number|None``)

and what belongs to a model class or a traffic shape is a function the
configuration or the mix names as ``module:function`` (``lib/builders``,
``lib/traffic``).  So a later cell, class, shape, kind or metric is new
files and a manifest entry, no edit.  A reader sees the reduced trace
(``context['trace']``: ``class_s``, ``phase_s``, ``phase_class_s``, ...)
and the profiler's own files under ``context['trace_dir']``, which are
deleted only after every reader has run.
"""

import importlib.util
import os
import shutil
import sys
import time

from benchmarks.lib import names, xtrace

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def _function(directory, name, function):
  """``function`` of ``<directory>/<name>.py`` under the benchmark's
  directory; ``None`` where there is no such file."""
  path = os.path.join(BENCH_DIR, directory, f'{name}.py')
  if not os.path.exists(path):
    return None
  spec = importlib.util.spec_from_file_location(
      f'benchmarks_{directory}_' + name.replace('.', '_').replace('-', '_'),
      path)
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  return getattr(module, function)


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

  run = _function('runners', str(mix.get('kind')), 'run')
  if run is None:
    raise SystemExit(f'traffic/{cell["traffic"]}.json: kind '
                     f'{mix.get("kind")!r} has no benchmarks/runners/'
                     f'{mix.get("kind")}.py')
  result, end_to_end, context = run(cell, config, mix, limits, args, devices,
                                    started, cache_dir)
  result['device'] = {
      'platform': devices[0].platform, 'kind': devices[0].device_kind,
      'count': len(devices),
      'memory_peak_bytes': result.pop('memory_peak_bytes')}
  if args.trace:
    try:
      reduced = xtrace.reduce_trace(xtrace.find_trace(context['trace_dir']),
                                    program=context['program'])
      context['trace'] = reduced
      values = {m['name']: (_function('metrics', m['name'], 'read')(context),
                            m['unit']) for m in per_layer}
    finally:
      shutil.rmtree(context['trace_dir'], ignore_errors=True)
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
