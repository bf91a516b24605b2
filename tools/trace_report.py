#!/usr/bin/env python3
"""Where a step's time goes: the report an operator reads (design §15).

Two inputs, one vocabulary (``obs.trace.REGISTERED_SPANS`` for host
code, ``REGISTERED_PHASES`` for sections of the compiled step).

``--profile <dir>`` reads the JAX profiler's ``*.trace.json.gz`` that
``obs.trace.profile(dir)`` (or any ``jax.profiler`` session) left under
``dir`` and prints, per step of the named program on the busiest chip:

- device ms per phase.  An op belongs to the innermost registered phase
  in its ``tf_op`` path (``jvp(x)`` / ``transpose(jvp(x))`` unwrapped
  to ``x``); ``--children`` splits each phase by table group, ``--ops
  N`` lists each phase's N longest ops;
- two remainders, kept apart: ``unscoped`` (the op has a ``tf_op`` and
  no registered phase in it: a hole in the program's coverage) and
  ``no_source`` (XLA made the op, ``tf_op`` empty: listed by instruction
  name with its HLO text, so a whole-shard copy's operand can be read);
- the step period on the device's clock, and every idle gap over 100 us
  on that chip named by the innermost program or caller host span over
  its middle.

Phases and the two remainders are SELF times (a ``while`` or
``conditional`` does not count its body twice), so they sum to the
chip's busy time.  The phases are metadata of the executable; the
compile cache's key takes them in (``utils/compile_cache.configure``),
so a trace shows the scopes of the source it was captured from.

Without ``--profile`` the argument is a Chrome-trace-event JSON written
by ``distributed_embeddings_tpu.obs.trace.save()``, and the report is:

- the per-phase totals table (count / total / mean ms, grouped by the
  span taxonomy's category: host work, wait = blocked time);
- the per-step breakdown: for every ``train/step`` span, the host
  phases and blocked time that landed inside its window plus the step's
  own wall — generalizing the consumer-blocked-time accounting
  ``csr_feed.py``/``coldtier.py`` proved, to EVERY instrumented phase;
- the critical-path summary: how much of the observed wall is
  attributed host work, how much is blocked/wait, and how much is
  unattributed (device execution and untraced host code).

Usable as a CI gate: exits nonzero on a malformed or truncated trace
(rc 2), on unregistered span names under ``--strict`` (rc 3), and on
missing required spans (``--profile``: phases or host spans) under
``--require`` (rc 4) — a pipeline step that produces a trace can assert
its phase coverage instead of trusting it.  ``--profile --strict``
exits 3 when ``unscoped`` passes 2% of the busy time or a host span of
the program's families is not registered.

    python tools/trace_report.py /tmp/trace.json
    python tools/trace_report.py trace.json --strict \
        --require train/step,feed/wait --json
    python tools/trace_report.py --profile /tmp/prof --children \
        --require fwd/lookup_combine,apply/write_rows
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import sys

from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _cli  # noqa: E402

from distributed_embeddings_tpu.obs.trace import (  # noqa: E402
    REGISTERED_PHASES, REGISTERED_SPANS, phase_of, span_category)

_KNOWN_PH = {'X', 'B', 'E', 'b', 'e', 'i', 'M'}


class TraceFormatError(ValueError):
  """The file is not a well-formed obs trace (malformed JSON, missing
  traceEvents, or an event violating the schema)."""


def load_trace(path: str) -> List[Dict[str, Any]]:
  """Parse + schema-validate one trace file; returns the event list.
  Raises ``TraceFormatError`` on anything a truncated write, a partial
  copy, or a hand-edited file can produce."""
  try:
    with open(path, 'r', encoding='utf-8') as f:
      payload = json.load(f)
  except OSError as e:
    raise TraceFormatError(f'{path}: unreadable: {e}') from e
  except json.JSONDecodeError as e:
    raise TraceFormatError(
        f'{path}: malformed/truncated JSON: {e}') from e
  if isinstance(payload, list):  # bare-array form is legal Chrome trace
    events = payload
  elif isinstance(payload, dict):
    events = payload.get('traceEvents')
    if not isinstance(events, list):
      raise TraceFormatError(
          f'{path}: no traceEvents list (not a trace file)')
  else:
    raise TraceFormatError(f'{path}: not a trace object or array')
  open_async: Dict[Any, int] = {}
  for k, ev in enumerate(events):
    if not isinstance(ev, dict):
      raise TraceFormatError(f'{path}: event {k} is not an object')
    name = ev.get('name')
    ph = ev.get('ph')
    if not isinstance(name, str) or not name:
      raise TraceFormatError(f'{path}: event {k} has no name')
    if ph not in _KNOWN_PH:
      raise TraceFormatError(
          f'{path}: event {k} ({name!r}) has unknown ph {ph!r}')
    if ph == 'M':
      continue
    if not isinstance(ev.get('ts'), (int, float)):
      raise TraceFormatError(
          f'{path}: event {k} ({name!r}) has no numeric ts')
    if ph == 'X':
      dur = ev.get('dur')
      if not isinstance(dur, (int, float)) or dur < 0:
        raise TraceFormatError(
            f'{path}: X event {k} ({name!r}) needs dur >= 0, got {dur!r}')
    if ph in ('b', 'e'):
      key = (ev.get('cat'), name, ev.get('id'))
      if ev.get('id') is None:
        raise TraceFormatError(
            f'{path}: async event {k} ({name!r}) has no id')
      if ph == 'b':
        open_async[key] = open_async.get(key, 0) + 1
      else:
        if open_async.get(key, 0) <= 0:
          raise TraceFormatError(
              f"{path}: async end without begin for {name!r} "
              f"id={ev.get('id')!r}")
        open_async[key] -= 1
  dangling = {k for k, v in open_async.items() if v}
  if dangling:
    raise TraceFormatError(
        f'{path}: {len(dangling)} async span(s) never closed '
        f'(truncated trace?): {sorted(dangling)[:3]}')
  return events


def _durations(events) -> List[Dict[str, Any]]:
  """X events plus b/e pairs folded into {name, cat, ts, dur} rows
  (microseconds)."""
  rows = []
  open_async: Dict[Any, List[float]] = {}
  for ev in events:
    ph = ev.get('ph')
    if ph == 'X':
      rows.append({'name': ev['name'],
                   'cat': ev.get('cat') or span_category(ev['name']),
                   'ts': float(ev['ts']), 'dur': float(ev['dur']),
                   'args': ev.get('args') or {}})
    elif ph == 'b':
      open_async.setdefault(
          (ev.get('cat'), ev['name'], ev.get('id')), []).append(
              float(ev['ts']))
    elif ph == 'e':
      starts = open_async.get((ev.get('cat'), ev['name'], ev.get('id')))
      if starts:
        t0 = starts.pop()
        rows.append({'name': ev['name'],
                     'cat': ev.get('cat') or span_category(ev['name']),
                     'ts': t0, 'dur': float(ev['ts']) - t0, 'args': {}})
  return rows


def report(events) -> Dict[str, Any]:
  """The analysis dict ``format_report`` renders (and ``--json``
  emits)."""
  rows = _durations(events)
  phases: Dict[str, Dict[str, Any]] = {}
  for r in rows:
    p = phases.setdefault(r['name'], {'count': 0, 'total_ms': 0.0,
                                      'cat': r['cat']})
    p['count'] += 1
    p['total_ms'] += r['dur'] / 1000.0
  for p in phases.values():
    p['total_ms'] = round(p['total_ms'], 3)
    p['mean_ms'] = round(p['total_ms'] / p['count'], 3)

  # per-step attribution: host phases and blocked time inside each
  # train/step window (event midpoint decides membership — phases on
  # other threads legitimately straddle the boundaries)
  steps = []
  step_rows = sorted((r for r in rows if r['name'] == 'train/step'),
                     key=lambda r: r['ts'])
  others = [r for r in rows if r['name'] != 'train/step']
  for sr in step_rows:
    lo, hi = sr['ts'], sr['ts'] + sr['dur']
    inside = [r for r in others
              if lo <= r['ts'] + r['dur'] / 2.0 < hi]
    entry = {
        'step': sr['args'].get('step'),
        'wall_ms': round(sr['dur'] / 1000.0, 3),
        'phases': {},
    }
    for r in inside:
      d = entry['phases'].setdefault(r['name'], 0.0)
      entry['phases'][r['name']] = d + r['dur'] / 1000.0
    entry['phases'] = {k: round(v, 3)
                       for k, v in sorted(entry['phases'].items())}
    entry['blocked_ms'] = round(
        sum(v for k, v in entry['phases'].items()
            if span_category(k) == 'wait'), 3)
    steps.append(entry)

  # critical path over interval UNIONS, not duration sums: spans nest
  # (serve/dispatch ⊇ serve/execute ⊇ serve/lookup) and concurrent
  # requests' waits overlap, so summing durations double-counts and
  # clamps the unattributed remainder to a misleading 0 — union time
  # answers "how much wall had host work / a wait in flight"
  def union_ms(cat_rows):
    ivs = sorted((r['ts'], r['ts'] + r['dur']) for r in cat_rows)
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in ivs:
      if cur_hi is None or lo > cur_hi:
        if cur_hi is not None:
          total += cur_hi - cur_lo
        cur_lo, cur_hi = lo, hi
      else:
        cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
      total += cur_hi - cur_lo
    return total / 1000.0

  span0 = min((r['ts'] for r in rows), default=0.0)
  span1 = max((r['ts'] + r['dur'] for r in rows), default=0.0)
  wall_ms = (span1 - span0) / 1000.0
  attributed = union_ms([r for r in rows if r['cat'] in ('host', 'wait')])
  # the devprof device lane (design §19): measured per-phase device
  # time splits the old unattributed remainder into device-attributed
  # wall vs the residue no span covers
  device_ms = union_ms([r for r in rows if r['cat'] == 'device'])
  covered = union_ms([r for r in rows
                      if r['cat'] in ('host', 'wait', 'device')])
  return {
      'events': len(rows),
      'wall_ms': round(wall_ms, 3),
      'phases': {k: phases[k] for k in sorted(phases)},
      'unregistered': sorted(
          n for n in phases if n not in REGISTERED_SPANS),
      'steps': steps,
      'critical_path': {
          'host_ms': round(
              union_ms([r for r in rows if r['cat'] == 'host']), 3),
          'blocked_ms': round(
              union_ms([r for r in rows if r['cat'] == 'wait']), 3),
          # wall not covered by any host/wait span: device execution
          # and untraced host code — the honest remainder, never
          # claimed as attributed
          'unattributed_ms': round(max(0.0, wall_ms - attributed), 3),
          # the remainder's split (design §19): wall the device lane
          # attributes, and the residue no span of any category covers
          'device_ms': round(device_ms, 3),
          'residue_ms': round(max(0.0, wall_ms - covered), 3),
      },
  }


def format_report(rep: Dict[str, Any]) -> str:
  out = []
  out.append(f"trace: {rep['events']} span(s) over "
             f"{rep['wall_ms']:.1f} ms wall")
  out.append('')
  out.append(f"{'phase':<22} {'cat':<6} {'count':>6} "
             f"{'total_ms':>10} {'mean_ms':>9}")
  for name, p in rep['phases'].items():
    out.append(f"{name:<22} {p['cat']:<6} {p['count']:>6} "
               f"{p['total_ms']:>10.3f} {p['mean_ms']:>9.3f}")
  cp = rep['critical_path']
  out.append('')
  out.append('critical path: '
             f"host {cp['host_ms']:.1f} ms, "
             f"blocked {cp['blocked_ms']:.1f} ms, "
             f"unattributed (device + untraced host) "
             f"{cp['unattributed_ms']:.1f} ms")
  if cp.get('device_ms'):
    out.append('device lane: '
               f"{cp['device_ms']:.1f} ms device-attributed "
               '(obs.devprof segmented dispatch), residue '
               f"{cp['residue_ms']:.1f} ms uncovered by any span")
  if rep['steps']:
    out.append('')
    out.append('per-step breakdown:')
    for s in rep['steps']:
      parts = ' '.join(f'{k}={v:.2f}' for k, v in s['phases'].items())
      out.append(f"  step {s['step']}: wall {s['wall_ms']:.2f} ms, "
                 f"blocked {s['blocked_ms']:.2f} ms"
                 + (f' | {parts}' if parts else ''))
  if rep['unregistered']:
    out.append('')
    out.append('WARNING: unregistered span name(s): '
               + ', '.join(rep['unregistered'])
               + ' (not in obs.REGISTERED_SPANS - typo, or a span '
               'added without registering it)')
  return '\n'.join(out)


# --------------------------------------------------------------------------
# --profile: the JAX profiler's trace, device ops by phase
# --------------------------------------------------------------------------

IDLE_GAP_US = 100.0
UNSCOPED_LIMIT = 0.02
# a caller's own annotation follows the program's convention
# (``bench/window``); the runtime's internal TraceMe names do not
_CALLER_SPAN = re.compile(r'^[a-z][a-z0-9_]*(/[a-z0-9_]+)+$')
_SPAN_FAMILIES = frozenset(n.split('/')[0] for n in REGISTERED_SPANS)


def find_profile(directory: str) -> str:
  """Newest ``*.trace.json.gz`` under ``directory`` (a file is itself)."""
  if os.path.isfile(directory):
    return directory
  paths = sorted(glob.glob(os.path.join(directory, '**', '*.trace.json.gz'),
                           recursive=True), key=os.path.getmtime)
  if not paths:
    raise TraceFormatError(f'{directory}: no *.trace.json.gz under it')
  return paths[-1]


def load_profile(path: str) -> List[Dict[str, Any]]:
  try:
    with gzip.open(path, 'rt', encoding='utf-8') as f:
      payload = json.load(f)
  except (OSError, EOFError) as e:
    raise TraceFormatError(f'{path}: unreadable/truncated: {e}') from e
  except json.JSONDecodeError as e:
    raise TraceFormatError(f'{path}: malformed/truncated JSON: {e}') from e
  events = payload.get('traceEvents') if isinstance(payload, dict) else None
  if not isinstance(events, list):
    raise TraceFormatError(f'{path}: no traceEvents list (not a trace file)')
  return events


def _self_times(ops):
  """``[(event, self_us)]`` for one thread's X events: an op that
  encloses others (``while``, ``conditional``) keeps only the time its
  children do not cover, so the self times sum to the union."""
  out, stack = [], []   # stack of [event, end, child_us]

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


def profile_report(events, program: Optional[str] = None, ops_n: int = 0
                   ) -> Dict[str, Any]:
  """The analysis dict of one profiler trace (``format_profile`` renders
  it, ``--json`` emits it).  Times are ms per step on the busiest chip."""
  proc, thread = {}, {}
  for e in events:
    if e.get('ph') == 'M' and e.get('name') == 'process_name':
      proc[e['pid']] = e['args']['name']
    elif e.get('ph') == 'M' and e.get('name') == 'thread_name':
      thread[(e['pid'], e['tid'])] = e['args']['name']
  xs = [e for e in events if e.get('ph') == 'X' and 'dur' in e]
  host = [e for e in xs if proc.get(e['pid'], '').startswith('/host:')]
  spans = [e for e in host if e['name'] in REGISTERED_SPANS
           or _CALLER_SPAN.match(str(e['name']))]
  host_spans: Dict[str, Dict[str, Any]] = {}
  for e in spans:
    h = host_spans.setdefault(e['name'], {'count': 0, 'total_ms': 0.0})
    h['count'] += 1
    h['total_ms'] += e['dur'] / 1000.0
  for h in host_spans.values():
    h['total_ms'] = round(h['total_ms'], 3)
  rep: Dict[str, Any] = {
      'chip': None, 'chips': [], 'program': None, 'steps': 0,
      'step_period_ms': None, 'busy_ms': 0.0, 'phases': {},
      'unscoped': {'ms': 0.0, 'share': 0.0, 'ops': []},
      'no_source': {'ms': 0.0, 'share': 0.0, 'ops': []},
      'idle_gaps': [], 'host_spans': dict(sorted(host_spans.items())),
      'unregistered': sorted(
          n for n in host_spans if n not in REGISTERED_SPANS
          and n.split('/')[0] in _SPAN_FAMILIES),
  }
  chips = sorted(p for p, n in proc.items() if n.startswith('/device:'))
  per_chip = {}
  for pid in chips:
    ops = [e for e in xs if e['pid'] == pid
           and thread.get((pid, e['tid'])) == 'XLA Ops']
    if ops:
      per_chip[pid] = _self_times(ops)
  rep['chips'] = [proc[p] for p in per_chip]
  if not per_chip:
    return rep
  busiest = max(per_chip, key=lambda p: sum(s for _, s in per_chip[p]))
  rep['chip'] = proc[busiest]
  timed = per_chip[busiest]
  runs_of: Dict[str, list] = {}
  for e in xs:
    if e['pid'] == busiest and thread.get((busiest, e['tid'])) \
        == 'XLA Modules':
      runs_of.setdefault(re.sub(r'\(\d+\)$', '', e['name']), []).append(e)
  device_us = {n: sum(e['dur'] for e in r) for n, r in runs_of.items()}
  pick = None
  if program:
    pick = max((n for n in runs_of if program in n), key=device_us.get,
               default=None)
  if pick is None and runs_of:
    pick = max(runs_of, key=device_us.get)
  if pick is not None:
    runs = sorted(runs_of[pick], key=lambda e: e['ts'])
    rep['program'] = pick
    rep['steps'] = len(runs)
    rep['step_period_ms'] = round(
        ((runs[-1]['ts'] - runs[0]['ts']) / (len(runs) - 1)
         if len(runs) > 1 else runs[0]['dur']) / 1000.0, 4)
    # only what ran inside a run of the program counts as the step
    bounds = [(r['ts'], r['ts'] + r['dur']) for r in runs]

    def in_step(e):
      mid = e['ts'] + e['dur'] / 2.0
      return any(lo <= mid <= hi for lo, hi in bounds)

    timed = [(e, s) for e, s in timed if in_step(e)]
  steps = max(1, rep['steps'])
  per_ms = 1.0 / 1000.0 / steps
  busy = sum(s for _, s in timed)
  rep['busy_ms'] = round(busy * per_ms, 4)
  phases: Dict[str, Dict[str, Any]] = {}
  loose = {'unscoped': {}, 'no_source': {}}
  produced_in: Dict[str, str] = {}   # instruction -> the phase that made it
  for e, self_us in timed:
    args = e.get('args') or {}
    tf_op = args.get('tf_op', '')
    found = phase_of(tf_op) if tf_op else None
    if found is None:
      kind = 'unscoped' if tf_op else 'no_source'
      row = loose[kind].setdefault(
          e['name'], {'name': e['name'], 'ms': 0.0, 'tf_op': tf_op,
                      'hlo': args.get('long_name', '')})
      row['ms'] += self_us * per_ms
      continue
    name, child = found
    produced_in[e['name']] = f'{name}/{child}' if child else name
    p = phases.setdefault(name, {
        'layer': REGISTERED_PHASES[name], 'ms': 0.0, 'children': {},
        'by_primitive': {}, 'ops': {}})
    ms = self_us * per_ms
    p['ms'] += ms
    key = child or '-'
    p['children'][key] = p['children'].get(key, 0.0) + ms
    prim = tf_op.rstrip(':').rsplit('/', 1)[-1]
    p['by_primitive'][prim] = p['by_primitive'].get(prim, 0.0) + ms
    p['ops'][e['name']] = p['ops'].get(e['name'], 0.0) + ms

  def top(d, n):
    return {k: round(v, 4) for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:n or None]}

  for name in sorted(phases, key=lambda n: -phases[n]['ms']):
    p = phases[name]
    rep['phases'][name] = {
        'layer': p['layer'], 'ms': round(p['ms'], 4),
        'share': round(p['ms'] / (busy * per_ms), 5) if busy else 0.0,
        'children': top(p['children'], 0),
        'by_primitive': top(p['by_primitive'], 0),
        'ops': top(p['ops'], ops_n) if ops_n else {},
    }
  # an op XLA made has no source, but its operands may: name the phases
  # that produced what it reads (a copy of a scatter's output)
  for row in loose['no_source'].values():
    operands = re.findall(r'%([\w.\-]+)', row['hlo'])[1:]
    row['reads'] = {o: produced_in[o] for o in operands if o in produced_in}
  for kind, rows in loose.items():
    total = sum(r['ms'] for r in rows.values())
    rep[kind] = {
        'ms': round(total, 4),
        'share': round(total / (busy * per_ms), 5) if busy else 0.0,
        'ops': [dict(r, ms=round(r['ms'], 4)) for r in
                sorted(rows.values(), key=lambda r: -r['ms'])],
    }
  # idle gaps between two ops on the chip, each named by the innermost
  # host span over its middle
  all_ops = sorted(per_chip[busiest], key=lambda es: es[0]['ts'])
  edge = None
  for e, _ in all_ops:
    if edge is not None and e['ts'] - edge > IDLE_GAP_US:
      mid = (edge + e['ts']) / 2.0
      cover = [s for s in spans
               if s['ts'] <= mid <= s['ts'] + s['dur']]
      inner = min(cover, key=lambda s: s['dur'], default=None)
      rep['idle_gaps'].append({
          'ms': round((e['ts'] - edge) / 1000.0, 4),
          'span': inner['name'] if inner else 'no span',
          'before': e['name']})
    edge = max(edge or 0.0, e['ts'] + e['dur'])
  rep['idle_gaps'].sort(key=lambda g: -g['ms'])
  return rep


def format_profile(rep: Dict[str, Any], children: bool = False) -> str:
  if rep['chip'] is None:
    lines = ['profile: no device plane with XLA ops in this trace '
             '(a CPU session has none); host spans only']
  else:
    lines = [
        f"profile: {rep['program']} x {rep['steps']} step(s) on "
        f"{rep['chip']} (busiest of {len(rep['chips'])}); step period "
        f"{rep['step_period_ms']} ms, busy {rep['busy_ms']:.3f} ms/step",
        '',
        f"{'phase':<24} {'layer':<18} {'ms/step':>10} {'share':>8}"]
    for name, p in rep['phases'].items():
      lines.append(f"{name:<24} {p['layer']:<18} {p['ms']:>10.3f} "
                   f"{100 * p['share']:>7.2f}%")
      if children:
        for child, ms in p['children'].items():
          lines.append(f"  {child:<22} {'':<18} {ms:>10.3f}")
      for op, ms in p['ops'].items():
        lines.append(f"    {op:<38} {ms:>10.3f}")
    for kind in ('unscoped', 'no_source'):
      r = rep[kind]
      lines.append(f"{kind:<24} {'-':<18} {r['ms']:>10.3f} "
                   f"{100 * r['share']:>7.2f}%")
    for kind, what in (('unscoped', 'tf_op'), ('no_source', 'hlo')):
      if rep[kind]['ops']:
        lines += ['', f'{kind} ops (ms/step, name, {what}):']
        for r in rep[kind]['ops'][:20]:
          reads = ''.join(f' [{o} <- {ph}]'
                          for o, ph in r.get('reads', {}).items())
          lines.append(f"  {r['ms']:>9.3f}  {r['name']}{reads}  "
                       f"{r[what][:160]}")
    lines += ['', f"idle gaps over {IDLE_GAP_US:.0f} us: "
              f"{len(rep['idle_gaps'])}"]
    for g in rep['idle_gaps'][:20]:
      lines.append(f"  {g['ms']:>9.3f} ms under {g['span']} "
                   f"(before {g['before']})")
  if rep['host_spans']:
    lines += ['', f"{'host span':<24} {'count':>6} {'total_ms':>10}"]
    for name, h in rep['host_spans'].items():
      lines.append(f"{name:<24} {h['count']:>6} {h['total_ms']:>10.3f}")
  if rep['unregistered']:
    lines += ['', 'WARNING: unregistered span name(s): '
              + ', '.join(rep['unregistered'])]
  return '\n'.join(lines)


def main(argv: Optional[List[str]] = None) -> int:
  ap = _cli.make_parser(
      'trace_report',
      description='Per-step phase breakdown: device ms per registered '
      'phase from a profiler trace (--profile), or host spans + stall '
      'attribution over an obs Chrome-trace file; nonzero exit on a '
      'malformed trace (pipeline-gate friendly).',
      strict_help='exit 3 when any span name is not in '
      'obs.REGISTERED_SPANS (--profile: also when unscoped device time '
      'passes 2%% of busy)')
  ap.add_argument('trace', nargs='?', default=None,
                  help='trace JSON written by obs.trace.save()')
  ap.add_argument('--profile', default=None, metavar='DIR',
                  help="a JAX profiler directory (obs.trace.profile's) or "
                  'its *.trace.json.gz: report device time by phase')
  ap.add_argument('--program', default=None,
                  help='--profile: substring of the module whose runs are '
                  'the steps (default: the one with most device time)')
  ap.add_argument('--children', action='store_true',
                  help='--profile: split each phase by table group')
  ap.add_argument('--ops', type=int, default=0, metavar='N',
                  help="--profile: list each phase's N longest ops")
  ap.add_argument('--require', default=None,
                  help='comma-separated span (--profile: phase or span) '
                  'names that must appear; exit 4 otherwise')
  args = ap.parse_args(argv)
  if (args.trace is None) == (args.profile is None):
    ap.error('give a trace file or --profile DIR (one of them)')
  if args.profile is not None:
    return _main_profile(args)
  try:
    events = load_trace(args.trace)
  except TraceFormatError as e:
    return _cli.fail('trace_report', 'MALFORMED', e)
  rep = report(events)
  _cli.emit(rep, args.json, lambda: format_report(rep))
  if args.strict and rep['unregistered']:
    return _cli.fail('trace_report', 'STRICT',
                     f"unregistered span name(s) {rep['unregistered']}")
  if args.require:
    missing = [n for n in args.require.split(',')
               if n and n not in rep['phases']]
    if missing:
      return _cli.fail('trace_report', 'REQUIRE',
                       f'missing span(s) {missing}')
  return _cli.EXIT_OK


def _main_profile(args) -> int:
  try:
    events = load_profile(find_profile(args.profile))
  except TraceFormatError as e:
    return _cli.fail('trace_report', 'MALFORMED', e)
  rep = profile_report(events, program=args.program, ops_n=args.ops)
  _cli.emit(rep, args.json, lambda: format_profile(rep, args.children))
  if args.strict and rep['unregistered']:
    return _cli.fail('trace_report', 'STRICT',
                     f"unregistered span name(s) {rep['unregistered']}")
  if args.strict and rep['unscoped']['share'] > UNSCOPED_LIMIT:
    return _cli.fail(
        'trace_report', 'STRICT',
        f"unscoped device time is {100 * rep['unscoped']['share']:.2f}% "
        f'of busy (limit {100 * UNSCOPED_LIMIT:.0f}%): a section of the '
        'program has no registered phase')
  if args.require:
    have = set(rep['phases']) | set(rep['host_spans'])
    missing = [n for n in args.require.split(',') if n and n not in have]
    if missing:
      return _cli.fail('trace_report', 'REQUIRE',
                       f'missing phase(s)/span(s) {missing}')
  return _cli.EXIT_OK


if __name__ == '__main__':
  sys.exit(main())
