"""The one reduction from a profiler trace to numbers.

``jax.profiler.stop_trace`` leaves ``<dir>/plugins/profile/<time>/
*.trace.json.gz`` beside the xplane file: the same events as Chrome-trace
JSON, with each device op's HLO text (``long_name``), XLA's category
(``hlo_category``) and the JAX primitive it was lowered from (``tf_op``).
This module reads that file with ``gzip`` and ``json`` alone.

Device planes are the processes named ``/device:TPU:<n>``; their
``XLA Ops`` thread holds one event per executed op (sequential on a
TensorCore), ``XLA Modules`` one per program run.  Host spans written by
``jax.profiler.TraceAnnotation`` sit on the ``python`` thread of
``/host:CPU`` on the same clock.

An op is classed by what it was lowered from, in this order (first hit):
collective opcode -> ``a2a`` / ``collective``; ``tf_op`` primitive
``gather`` -> ``gather``; ``scatter*`` -> ``scatter``; ``sort`` ->
``sort``; ``cumsum``/``reduce_window_sum`` -> ``cumsum``;
``dot_general``/``conv*`` or category ``convolution fusion`` ->
``matmul``; opcode ``copy`` (no primitive of the above) -> ``copy``;
else ``other``.  Checked on a recorded v5e trace in
``tests/test_xtrace.py``.

An op is also booked to its SCOPE PATH: the ``jax.named_scope``s it was
traced under (the program's phases, ``apply/dedup/g1``), read off
``tf_op`` as ``tools/trace_report.py`` reads them (this is the
benchmark's own copy of that rule): the path split at its top-level
``/``, the primitive at its end dropped, ``transpose(jvp(x))`` unwrapped
to ``x`` (a phase's backward belongs to the phase), and every part left
out that is JAX's and no scope: a function's name (``jit(step)``) and the
control-flow wrappers (``while/body``, ``cond/branch_1_fun``).  What has
a ``tf_op`` and no scope in it is ``unscoped``; what XLA made itself, with
no ``tf_op``, is ``no_source``.  Phase times are SELF times (a ``while``
does not count its body twice), so all paths with the two remainders sum
to the device's busy time.
"""

import functools
import glob
import gzip
import json
import os
import re

CLASSES = ('a2a', 'collective', 'gather', 'scatter', 'sort', 'cumsum',
           'matmul', 'copy', 'other')
_OPCODE = re.compile(r'^%\S+ = .*? ([\w\-]+)\(')
_COLLECTIVES = ('all-reduce', 'all-gather', 'reduce-scatter',
                'collective-permute', 'collective-broadcast')
_TRANSFORM = re.compile(r'^(?:transpose|jvp|vmap)\((.*)\)$')
# parts of a ``tf_op`` path that JAX's control flow puts there
_CONTROL = re.compile(r'^(?:while|body|cond|branch_\d+_fun|scan|checkpoint'
                      r'|remat\d*|closed_call|core_call|custom_jvp_call'
                      r'|custom_vjp_call(?:_jaxpr)?|pjit|shard_map)$')
UNSCOPED, NO_SOURCE = 'unscoped', 'no_source'


def start_trace(directory):
  """Start the profiler writing under ``directory`` (emptied first), with
  Python's own call tracer off: it slows every host call severalfold,
  which in a cell that the host bounds changes what is traced.  The
  benchmark's ``TraceAnnotation`` spans are host events and stay."""
  import shutil
  import jax
  shutil.rmtree(directory, ignore_errors=True)
  options = jax.profiler.ProfileOptions()
  options.python_tracer_level = 0
  jax.profiler.start_trace(directory, profiler_options=options)


def find_trace(directory):
  paths = sorted(glob.glob(os.path.join(directory, '**', '*.trace.json.gz'),
                           recursive=True))
  if not paths:
    raise FileNotFoundError(f'no *.trace.json.gz under {directory}')
  return paths[-1]


def classify(args):
  """The class of one device op from its trace ``args``."""
  m = _OPCODE.match(args.get('long_name', ''))
  opcode = m.group(1) if m else ''
  prim = args.get('tf_op', '').rstrip(':').rsplit('/', 1)[-1]
  if opcode.startswith('all-to-all') or prim.startswith('all_to_all'):
    return 'a2a'
  if opcode.startswith(_COLLECTIVES) or prim in ('psum', 'all_gather'):
    return 'collective'
  if prim == 'gather':
    return 'gather'
  if prim.startswith('scatter'):
    return 'scatter'
  if prim == 'sort' or opcode == 'sort':
    return 'sort'
  if prim.startswith(('cumsum', 'reduce_window_sum', 'cumlogsumexp')):
    return 'cumsum'
  if (prim.startswith(('dot_general', 'conv_general'))
      or args.get('hlo_category') == 'convolution fusion'):
    return 'matmul'
  if opcode == 'copy':
    return 'copy'
  return 'other'


@functools.lru_cache(maxsize=None)   # a trace repeats each op every step
def scope_path(tf_op):
  """The scope path of one device op from its ``tf_op`` (module docstring):
  ``apply/dedup/g1``, or ``unscoped``, or ``no_source`` for an empty one."""
  if not tf_op:
    return NO_SOURCE
  parts, depth, cur = [], 0, ''
  for ch in tf_op.rstrip(':'):
    depth += (ch == '(') - (ch == ')')
    if ch == '/' and depth == 0:
      parts.append(cur)
      cur = ''
    else:
      cur += ch
  path = []
  for part in parts:          # the last part, the primitive, never joined
    m = _TRANSFORM.match(part)
    while m:
      part = m.group(1)
      m = _TRANSFORM.match(part)
    if '(' not in part:
      path += [p for p in part.split('/') if not _CONTROL.match(p)]
  return '/'.join(path) or UNSCOPED


def _self_times(ops):
  """``[(event, self microseconds)]`` for one thread's events: an op that
  encloses others (``while``, ``conditional``) keeps only the time its
  children do not cover, so the self times sum to the union."""
  out, stack = [], []   # stack of [event, end, child microseconds]

  def pop():
    ev, _, child = stack.pop()
    out.append((ev, max(0.0, ev['dur'] - child)))

  for ev in sorted(ops, key=lambda e: (e['ts'], -e['dur'])):
    while stack and ev['ts'] >= stack[-1][1]:
      pop()
    if stack:
      stack[-1][2] += ev['dur']
    stack.append([ev, ev['ts'] + ev['dur'], 0.0])
  while stack:
    pop()
  return out


def _union(intervals):
  """Total length and merged list of ``[(start, end)]``."""
  merged = []
  for s, e in sorted(intervals):
    if merged and s <= merged[-1][1]:
      merged[-1][1] = max(merged[-1][1], e)
    else:
      merged.append([s, e])
  return sum(e - s for s, e in merged), merged


def reduce_trace(path, window_span='bench/window', program=None):
  """Reduce one trace file.  Times in seconds.

  Only what lies inside the host span ``window_span`` counts (the whole
  trace where there is none).  ``program`` (a substring of the module
  name, e.g. ``jit_step``) picks the runs that count as steps; default:
  the module with most device time.

  Returns a dict: ``window_s``; ``devices`` (plane names); ``steps``
  (program runs inside the window, fullest device); ``busy_s`` per
  device and ``busy_mean_s``; ``class_s`` per device (class -> seconds);
  ``phase_s`` per device (scope path, ``unscoped`` or ``no_source`` ->
  self seconds) and ``phase_class_s`` per device (the same paths -> class
  -> self seconds), which sum to ``busy_s``;
  ``fullest`` (device with most busy time); ``ops`` (``name (class)`` -> seconds
  on the fullest device); ``idle_gaps`` (``[(host span, seconds)]``,
  longest first, fullest device); ``module_s`` (device seconds of the
  step program on the fullest device) and ``step_period_s`` (the mean
  time from one run's start to the next one's there, on the device's
  clock: a step with the gap that follows it; one run alone gives its
  length); ``modules`` (program name ->
  ``(runs, device seconds)`` on the fullest device).
  """
  with gzip.open(path) as f:
    events = json.load(f)['traceEvents']
  proc, thread = {}, {}
  for e in events:
    if e.get('ph') == 'M' and e.get('name') == 'process_name':
      proc[e['pid']] = e['args']['name']
    elif e.get('ph') == 'M' and e.get('name') == 'thread_name':
      thread[(e['pid'], e['tid'])] = e['args']['name']
  xs = [e for e in events if e.get('ph') == 'X']
  spans = [(e['name'], e['ts'], e['ts'] + e['dur']) for e in xs
           if proc.get(e['pid'], '').startswith('/host:')
           and str(e['name']).startswith('bench/')]
  window = [s for s in spans if s[0] == window_span]
  device_pids = sorted(p for p, n in proc.items()
                       if n.startswith('/device:TPU:'))
  dev_events = [e for e in xs if e['pid'] in device_pids]
  if window:
    lo, hi = min(s[1] for s in window), max(s[2] for s in window)
  elif dev_events:
    lo = min(e['ts'] for e in dev_events)
    hi = max(e['ts'] + e['dur'] for e in dev_events)
  else:
    lo = hi = 0.0

  def inside(e):
    # by its middle: the device's clock and the host's agree to some
    # microseconds only, so an op at the window's edge may poke out
    return lo <= e['ts'] + e['dur'] / 2 <= hi

  out = {'window_s': (hi - lo) * 1e-6,
         'devices': [proc[p] for p in device_pids],
         'busy_s': {}, 'class_s': {}, 'phase_s': {}, 'phase_class_s': {},
         'steps': 0, 'ops': {},
         'idle_gaps': [], 'module_s': 0.0, 'step_period_s': 0.0, 'modules': {},
         'fullest': None}
  per_dev = {}
  for pid in device_pids:
    ops = [e for e in dev_events if e['pid'] == pid and inside(e)
           and thread.get((pid, e['tid'])) == 'XLA Ops']
    mods = [e for e in dev_events if e['pid'] == pid and inside(e)
            and thread.get((pid, e['tid'])) == 'XLA Modules']
    busy, merged = _union([(e['ts'], e['ts'] + e['dur']) for e in ops])
    classes = dict.fromkeys(CLASSES, 0.0)
    for e in ops:
      e['class'] = classify(e.get('args', {}))
      classes[e['class']] += e['dur'] * 1e-6
    phases, phase_classes = {}, {}
    for e, self_us in _self_times(ops):
      path = scope_path(e.get('args', {}).get('tf_op', ''))
      phases[path] = phases.get(path, 0.0) + self_us * 1e-6
      by = phase_classes.setdefault(path, {})
      by[e['class']] = by.get(e['class'], 0.0) + self_us * 1e-6
    out['busy_s'][proc[pid]] = busy * 1e-6
    out['class_s'][proc[pid]] = classes
    out['phase_s'][proc[pid]] = phases
    out['phase_class_s'][proc[pid]] = phase_classes
    per_dev[pid] = (ops, mods, merged)
  if not per_dev:
    out['busy_mean_s'] = 0.0
    return out
  out['busy_mean_s'] = sum(out['busy_s'].values()) / len(device_pids)
  full = max(device_pids, key=lambda p: out['busy_s'][proc[p]])
  out['fullest'] = proc[full]
  ops, mods, merged = per_dev[full]
  by_module = {}
  for e in mods:
    name = re.sub(r'\(\d+\)$', '', e['name'])
    by_module.setdefault(name, []).append(e)
  device_s = {n: sum(e['dur'] for e in runs) * 1e-6
              for n, runs in by_module.items()}
  out['modules'] = {n: (len(runs), device_s[n])
                    for n, runs in by_module.items()}
  if by_module:
    pick = (max((n for n in by_module if program in n), key=device_s.get,
                default=None) if program else None)
    pick = pick or max(by_module, key=device_s.get)
    runs = by_module[pick]
    out['steps'] = len(runs)
    out['module_s'] = device_s[pick]
    starts = sorted(e['ts'] for e in runs)
    out['step_period_s'] = ((starts[-1] - starts[0]) / (len(runs) - 1) * 1e-6
                            if len(runs) > 1 else device_s[pick])
    out['module'] = pick
  for e in ops:
    name = f"{e['name']} ({e['class']})"
    out['ops'][name] = out['ops'].get(name, 0.0) + e['dur'] * 1e-6
  # idle gaps on the fullest device, each named by the host span that
  # covers its middle
  edges = [lo] + [t for s, e in merged for t in (s, e)] + [hi]
  gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
          if edges[i + 1] > edges[i]]
  named = []
  for s, e in gaps:
    mid = (s + e) / 2
    cover = [n for n, a, b in spans if a <= mid <= b and n != window_span]
    named.append((cover[-1] if cover else 'no bench span', (e - s) * 1e-6))
  out['idle_gaps'] = sorted(named, key=lambda g: -g[1])
  return out
