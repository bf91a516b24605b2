"""Span tracer and device phases: one vocabulary for where a step's time goes.

The capture half of the observability layer (docs/design.md §15).  Two
kinds of name, both registered here:

- a HOST SPAN (``REGISTERED_SPANS``) times host code.  Call sites wrap a
  phase in ``with span('feed/build'): ...`` or the ``begin``/``end``
  token pair; each completed span becomes one complete-duration event
  (``ph='X'``) in an in-memory buffer and, for as long as it is open,
  one ``jax.profiler.TraceAnnotation`` — so the same span shows in the
  JAX profiler's own trace, on the thread that ran it and on the clock
  of the device planes;
- a DEVICE PHASE (``REGISTERED_PHASES``) names a section of a compiled
  program.  ``with phase('fwd/exchange'): ...`` is ``jax.named_scope``:
  metadata on every operation traced inside it, no operation added, so
  the profiler's device ops carry the phase in their ``tf_op`` path.
  It scopes whether the tracer is enabled or not (it costs Python time
  only while jit traces the program).

``obs.trace.profile(directory)`` starts a capture of both;
``tools/trace_report.py --profile <directory>`` reads it.  ``save()``
writes the tracer's own buffer as the standard wrapper object

    {"traceEvents": [...], "displayTimeUnit": "ms", "otherData": {...}}

that Perfetto (https://ui.perfetto.dev) and ``chrome://tracing`` open
directly, and ``tools/trace_report.py`` parses for the stall
attribution tables.

Disabled (the default) ``span`` is ONE module-flag check returning a
shared no-op object — no allocation, no lock, no event, no annotation;
``begin`` is that check plus one clock read (its token is then the bare
start time, so ``end`` can still hand the caller's stats the seconds).
Spans never go inside jit-traced code: there they would time Python's
tracing, not the device — that is what ``phase`` is for.

Span-name discipline: every runtime call site must use a name from
``REGISTERED_SPANS`` (source-scanned by tests/test_obs.py, mirroring
``resilience.REGISTERED_EVENTS``).  The emit functions themselves stay
permissive so a user extension can trace its own phases; unregistered
names surface in ``tools/trace_report.py --strict``.

Three event shapes:

- ``span``/``begin``+``end``: a synchronous phase on one thread
  (``ph='X'``).  Same-thread spans follow ``with``-statement stack
  discipline, so per-track events are always properly nested.
  ``end`` returns the seconds it measured: a site that also feeds a
  stats counter takes them from there, one measurement for both.
  ``complete`` emits an interval measured elsewhere (the devprof lane)
  into the tracer's own file only: it cannot be annotated after the
  fact.
- ``async_span``: a logical interval not owned by any one thread — a
  serving request's queue residency (``serve/enqueue``) overlaps its
  neighbours arbitrarily — emitted as a ``ph='b'``/``'e'`` pair keyed
  by ``id`` (Perfetto renders each id on its own async track).
- ``instant``: a point marker (``ph='i'``).

Timestamps in the tracer's own file are microseconds on the
``time.perf_counter`` clock, re-based to ``enable()``.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import re
import threading
import time

from typing import Any, Dict, List, Optional

# The complete span taxonomy (docs/design.md §15).  Add a name HERE in
# the same change that introduces the call site — tests/test_obs.py
# source-scans every span()/begin()/complete()/async_span() literal.
# No span goes inside jit-traced code (see REGISTERED_PHASES).
REGISTERED_SPANS = frozenset({
    # training driver (parallel/grad.py fit)
    'train/step', 'train/sync',
    # host CSR feed (parallel/csr_feed.py)
    'feed/build', 'feed/wait',
    # cold tier (parallel/coldtier.py)
    'coldtier/prepass', 'coldtier/wait', 'coldtier/fetch',
    'coldtier/writeback',
    # state-integrity auditor (parallel/audit.py)
    'audit/check',
    # checkpoints (parallel/checkpoint.py)
    'ckpt/save', 'ckpt/restore',
    # serving request path (serving/batcher.py + serving/engine.py);
    # serve/merge, serve/execute and serve/demux are the pipelined
    # dispatcher's three stages (design §16) — on separate threads when
    # the pipeline is on, nested under serve/dispatch when serial
    'serve/submit', 'serve/enqueue', 'serve/dispatch', 'serve/merge',
    'serve/lookup', 'serve/execute', 'serve/demux',
    # SLO-aware overload layer (serving/batcher.py + serving/pool.py,
    # design §23): a shed request's queue residency, a degraded
    # hot-only low-priority serve, and a failover retry's resubmit leg
    'serve/shed', 'serve/degraded', 'serve/failover',
    # device-time attribution lane (obs/devprof.py, design §19): each
    # phase of the step measured as an individually synced sub-program
    # and emitted as an X event on the dedicated 'device' track
    # (``device_tid``) — never from inside a measured headline window
    'dev/fwd/exchange', 'dev/fwd/lookup_combine', 'dev/bwd/exchange',
    'dev/bwd/grad', 'dev/apply/update', 'dev/serve/execute',
    # dcn/ici sub-lanes of the exchange phases under hierarchical
    # (dcn x data)-product sharding (design §20): the ICI-only twin
    # program is measured directly, the DCN remainder derived — nested
    # inside the parent exchange span so union_ms never double-counts
    'dev/fwd/exchange/ici', 'dev/fwd/exchange/dcn',
    'dev/bwd/exchange/ici', 'dev/bwd/exchange/dcn',
})

# Device phases of the compiled step (docs/design.md §15): the value is
# the layer of PERF.md section 3 the phase belongs to.  Add a name HERE
# in the same change that introduces its ``phase()`` call site (same
# source scan as the spans).  A phase's LEAF may not be, or start with,
# a primitive that trace reductions class ops by (``PRIMITIVE_LEAVES``):
# some device ops carry a ``tf_op`` that ends at the scope, and a
# reduction that reads the last path component would book them to that
# class.  Hence ``read_rows``/``write_rows``, not ``gather``/``scatter``.
REGISTERED_PHASES: Dict[str, str] = {
    # send buffers, slot selection, owner-side id routing, sort/unique
    # of ids; backward: cotangent send buffers, per-unique-row sums
    'fwd/route': 'route + exchange',
    'bwd/route': 'route + exchange',
    # the all-to-alls with their pack/unpack (ids out, rows back; the
    # cross-slice DCN pair; backward: cotangents, hot-row psum) and the
    # reorder of what came back
    'fwd/exchange': 'route + exchange',
    'bwd/exchange': 'route + exchange',
    # row gather + combine over the hotness axis, one child scope per
    # plan group (``fwd/lookup_combine/g<index>`` = params ``group_<index>``)
    'fwd/lookup_combine': 'gather + combine',
    # head_loss_fn under the step's vjp (its backward reads
    # ``transpose(jvp(head))``) and the optax update of the dense params
    'head': 'dense head',
    'dense_update': 'dense head',
    # inside ``head``, the parts of a hybrid state-space / attention
    # stack (models/hybrid_ssm.py), forward and backward alike: the
    # mixer's projections and gated norm, its causal convolution, the
    # selective scan; attention; the SwiGLU; logits and loss
    'mixer/proj': 'dense head',
    'mixer/conv': 'dense head',
    'mixer/selective_scan': 'dense head',
    'attention': 'dense head',
    'mlp': 'dense head',
    'vocab': 'dense head',
    # both stacks: the norm before (in ``trinity-mini`` also after) each
    # sub-layer, the residual multiplier and the add, which ``layer``
    # calls outside every sub-layer's own phase
    'residual': 'dense head',
    # inside ``attention`` (``hybrid_ssm.blocked_attention``, which both
    # stacks reach): the masked softmax products themselves, as fused
    # kernels with their transposes or as unrolled blocks, apart from
    # the projections, per-head norms, rotary and gate around them
    'attention/core': 'dense head',
    # inside ``head``, a mixture-of-experts stack (models/moe_lm.py): its
    # two kinds of attention (both under ``attention``), and the routed
    # layer (layers/routed_experts.py): router product, top-k and
    # weights; keys, sort and the gather into the buffer; the grouped
    # products; weighting and the sum back to tokens; the shared expert
    'attention/window': 'dense head',
    'attention/full': 'dense head',
    # a gated short convolution, whole: in-projection, both gates, the
    # depthwise convolution, out-projection (models/moe_lm.short_conv)
    'mixer/short_conv': 'dense head',
    'moe/route': 'routed experts',
    'moe/dispatch': 'routed experts',
    'moe/experts': 'routed experts',
    'moe/combine': 'routed experts',
    'moe/shared': 'routed experts',
    # the sparse optimizer step, each under a child scope per group:
    # the update stream's assembly and cross-slice merge ...
    'apply/stream': 'sparse apply',
    # ... sort + segment sums + compaction of duplicate rows ...
    'apply/dedup': 'sparse apply',
    # ... rows and optimizer state fetched for the rows updated ...
    'apply/read_rows': 'sparse apply',
    # ... the optimizer's arithmetic ...
    'apply/update': 'sparse apply',
    # ... and the write back into table and state
    'apply/write_rows': 'sparse apply',
    # a table the head also multiplies by (design §25): the head's dense
    # gradient joined to the row sums, and the one whole-table update
    'apply/tied': 'sparse apply',
}

PRIMITIVE_LEAVES = ('gather', 'scatter', 'sort', 'cumsum',
                    'reduce_window_sum', 'cumlogsumexp', 'dot_general',
                    'conv_general', 'all_to_all', 'psum', 'all_gather',
                    'copy')

# Report classification (tools/trace_report.py): 'wait' spans are
# blocked time (the stall-attribution numerator), 'device' spans are
# measured device time on the devprof lane (design §19), everything
# else is measured host work.
SPAN_CATEGORIES: Dict[str, str] = {
    'feed/wait': 'wait', 'coldtier/wait': 'wait', 'train/sync': 'wait',
    'serve/enqueue': 'wait', 'serve/shed': 'wait',
    'dev/fwd/exchange': 'device', 'dev/fwd/lookup_combine': 'device',
    'dev/bwd/exchange': 'device', 'dev/bwd/grad': 'device',
    'dev/apply/update': 'device', 'dev/serve/execute': 'device',
    'dev/fwd/exchange/ici': 'device', 'dev/fwd/exchange/dcn': 'device',
    'dev/bwd/exchange/ici': 'device', 'dev/bwd/exchange/dcn': 'device',
}


def span_category(name: str) -> str:
  return SPAN_CATEGORIES.get(name, 'host')


class _NoopSpan:
  """Shared do-nothing context manager: the whole disabled path."""
  __slots__ = ()

  def __enter__(self):
    return self

  def __exit__(self, *exc):
    return False


_NOOP = _NoopSpan()

_DEFAULT_MAX_EVENTS = 1_000_000

_enabled = False
_lock = threading.Lock()
_events: List[Dict[str, Any]] = []
_dropped = 0
_t0 = 0.0
_path: Optional[str] = None
_max_events = _DEFAULT_MAX_EVENTS
_tids: Dict[Any, int] = {}
_pid = os.getpid()
_pins = 0
_segments = 0
_rotated_dropped = 0  # dropped-counter value at the last rotation

# Reserved track key for the device-time lane (obs/devprof.py): device
# phases are measured offline, not on any live thread, so they render
# on one dedicated labelled track instead of whichever thread ran the
# profiler.
_DEVICE_TRACK_KEY = ('device', 'device')


def enabled() -> bool:
  return _enabled


def now() -> float:
  """The tracer's clock (seconds): starts for ``complete()`` and
  ``async_span()``, so their intervals land on the timeline of the live
  spans."""
  return time.perf_counter()


def enable(path: Optional[str] = None, max_events: Optional[int] = None,
           pin: bool = False):
  """Arm the tracer (idempotent; re-arming keeps buffered events).
  ``path`` is remembered as the default ``save()`` target;
  ``max_events`` bounds the buffer — past it events are counted as
  dropped instead of growing host memory without bound.  Both are
  sticky: a re-arm without them (another component calling
  ``enable()``) keeps the previously configured values instead of
  silently lifting a user-set memory bound.

  ``pin=True`` takes a re-entrancy pin: while any pin is held,
  ``disable()`` is a no-op (a long-running owner — the streaming/online
  training loop — stays traced across nested components whose teardown
  calls ``disable()``; release with ``unpin()`` or force with
  ``disable(force=True)``)."""
  global _enabled, _t0, _path, _max_events, _pid, _pins
  with _lock:
    if not _enabled and not _events:
      _t0 = time.perf_counter()
    _pid = os.getpid()
    if path is not None:
      _path = path
    if max_events is not None:
      _max_events = int(max_events)
    if pin:
      _pins += 1
    _enabled = True


def disable(force: bool = False) -> bool:
  """Disarm the tracer.  While an ``enable(pin=True)`` pin is held this
  is a no-op returning False (the owner's capture survives a nested
  component's teardown); ``force=True`` clears every pin and disarms
  unconditionally.  Returns whether the tracer is now disarmed."""
  global _enabled, _pins
  with _lock:
    if force:
      _pins = 0
    if _pins > 0:
      return False
    _enabled = False
    return True


def unpin():
  """Release one ``enable(pin=True)`` re-entrancy pin (floored at 0);
  the tracer stays armed until a subsequent ``disable()``."""
  global _pins
  with _lock:
    _pins = max(0, _pins - 1)


def clear():
  """Drop buffered events and restore the default buffer bound/path
  (keeps the enabled flag untouched) — a fresh capture starts from the
  defaults, while a mid-capture ``enable()`` re-arm keeps whatever the
  user configured (see ``enable``)."""
  global _dropped, _t0, _max_events, _path, _segments, _rotated_dropped
  with _lock:
    _events.clear()
    _tids.clear()
    _dropped = 0
    _max_events = _DEFAULT_MAX_EVENTS
    _path = None
    _segments = 0
    _rotated_dropped = 0
    _t0 = time.perf_counter()


def _tid() -> int:
  """Small stable per-thread track id + a thread_name metadata event on
  first sight (Perfetto labels the track).  Keyed by (ident, name): the
  OS reuses thread idents after a thread exits (a respawned feed
  producer can inherit a dead dispatcher's ident), and a bare-ident
  cache would silently put the new thread's spans on the dead thread's
  labelled track."""
  name = threading.current_thread().name
  key = (threading.get_ident(), name)
  tid = _tids.get(key)
  if tid is None:
    tid = len(_tids) + 1
    _tids[key] = tid
    _events.append({
        'name': 'thread_name', 'ph': 'M', 'pid': _pid, 'tid': tid,
        'args': {'name': name},
    })
  return tid


def device_tid() -> int:
  """Track id of the dedicated 'device' lane (obs/devprof.py emits its
  per-phase X events here via ``complete(..., tid=device_tid())``).
  Allocates the track + its ``thread_name`` label on first use; returns
  0 without allocating when tracing is disabled (the emit that would
  use it is a no-op anyway)."""
  if not _enabled:
    return 0
  with _lock:
    tid = _tids.get(_DEVICE_TRACK_KEY)
    if tid is None:
      tid = len(_tids) + 1
      _tids[_DEVICE_TRACK_KEY] = tid
      _events.append({
          'name': 'thread_name', 'ph': 'M', 'pid': _pid, 'tid': tid,
          'args': {'name': 'device'},
      })
    return tid


def _emit(event: Dict[str, Any]):
  global _dropped
  with _lock:
    if len(_events) >= _max_events:
      _dropped += 1
      return
    event.setdefault('tid', _tid())
    _events.append(event)


def _annotation(name: str, args: Optional[Dict[str, Any]]):
  """An entered ``jax.profiler.TraceAnnotation`` for one open span
  (``StepTraceAnnotation`` for ``train/step``, so profiler tools group
  the device work by step).  Outside a profiler session it costs the
  TraceMe's own flag check."""
  import jax
  args = args or {}
  if name == 'train/step':
    ann = jax.profiler.StepTraceAnnotation(
        name, step_num=int(args.get('step', 0)), **args)
  else:
    ann = jax.profiler.TraceAnnotation(name, **args)
  ann.__enter__()
  return ann


class _Span:
  __slots__ = ('name', 'args', 'ann', 't0')

  def __init__(self, name: str, args: Optional[Dict[str, Any]]):
    self.name = name
    self.args = args
    self.ann = _annotation(name, args)
    self.t0 = time.perf_counter()

  def __enter__(self):
    return self

  def __exit__(self, *exc):
    end(self)
    return False


def span(name: str, **args):
  """Context manager timing one phase on the current thread; the shared
  no-op when tracing is disabled."""
  if not _enabled:
    return _NOOP
  return _Span(name, args or None)


def begin(name: str, **args):
  """Token form of ``span``, for a block that cannot be a ``with`` and
  for a site whose stats counter wants the seconds ``end`` returns.
  Disabled, the token is the bare start time: no span, no annotation,
  and ``end`` still measures."""
  if not _enabled:
    return time.perf_counter()
  return _Span(name, args or None)


def end(tok) -> float:
  """Close ``tok`` on the thread that opened it; returns the seconds
  from ``begin`` to now — the one measurement the event, the profiler's
  annotation and the caller's counter share."""
  t1 = time.perf_counter()
  if not isinstance(tok, _Span):
    return t1 - tok if tok is not None else 0.0
  tok.ann.__exit__(None, None, None)
  if _enabled:
    ev = {
        'name': tok.name, 'cat': span_category(tok.name), 'ph': 'X',
        'ts': (tok.t0 - _t0) * 1e6, 'dur': (t1 - tok.t0) * 1e6,
        'pid': _pid,
    }
    if tok.args:
      ev['args'] = tok.args
    _emit(ev)
  return t1 - tok.t0


_group_scope = threading.local()


class phase(contextlib.ContextDecorator):
  """Context manager (or decorator) naming a DEVICE phase: every
  operation traced inside carries ``name`` in its name stack
  (``jax.named_scope``), under a child scope ``name/<group>`` inside a
  ``phase_group``.  Scopes whether or not the tracer is enabled; adds
  no operation to the program.  The group is read when the scope opens,
  so a decorated function picks up the group of each call."""

  def __init__(self, name: str):
    self.name = name

  def _recreate_cm(self):
    return phase(self.name)  # one scope object per call of a decorated fn

  def __enter__(self):
    import jax
    group = getattr(_group_scope, 'name', None)
    self._scope = jax.named_scope(
        f'{self.name}/{group}' if group else self.name)
    self._scope.__enter__()
    return self

  def __exit__(self, *exc):
    return self._scope.__exit__(*exc)


_TRANSFORM = re.compile(r'^(?:transpose|jvp|vmap)\((.*)\)$')
_GROUP = re.compile(r'^g\d+$')  # what ``phase_group`` callers pass
_PHASE_PATHS = [tuple(p.split('/')) for p in REGISTERED_PHASES]


@functools.lru_cache(maxsize=None)   # a trace repeats each op every step
def phase_of(op_name: str):
  """The registered phase an operation belongs to, read off its name
  stack (HLO ``op_name``, the profiler's ``tf_op``): ``(phase, child)``
  for the INNERMOST registered phase in the path, ``child`` the table
  group one level down (``g<index>``) or None; None when the path holds
  no registered phase.  ``jvp(x)`` and ``transpose(jvp(x))`` unwrap to
  ``x``: a phase's backward belongs to the phase."""
  parts, depth, cur = [], 0, ''
  for ch in op_name.rstrip(':'):
    depth += (ch == '(') - (ch == ')')
    if ch == '/' and depth == 0:
      parts.append(cur)
      cur = ''
    else:
      cur += ch
  parts.append(cur)
  path = []
  for part in parts:
    m = _TRANSFORM.match(part)
    while m:
      part = m.group(1)
      m = _TRANSFORM.match(part)
    # a function's name (``jit(head)``) is no scope
    path += [part] if '(' in part else part.split('/')
  for end in range(len(path), 0, -1):
    for p in _PHASE_PATHS:
      if end >= len(p) and tuple(path[end - len(p):end]) == p:
        child = path[end] if end < len(path) else ''
        return '/'.join(p), (child if _GROUP.match(child) else None)
  return None


@contextlib.contextmanager
def phase_group(group: str):
  """While open (on this thread), every ``phase`` opened gets the child
  scope ``<phase>/<group>`` — the per-table-group level of the phase
  table, set once by the loop that walks the plan's groups so the
  optimizers' own ``phase`` sites need not know their group."""
  prev = getattr(_group_scope, 'name', None)
  _group_scope.name = group
  try:
    yield
  finally:
    _group_scope.name = prev


@contextlib.contextmanager
def profile(directory: str, path: Optional[str] = None):
  """Capture one profile under ``directory``: enables the tracer (its
  own file goes to ``path`` on ``save()``), starts the JAX profiler with
  Python's call tracer off (it slows every host call severalfold) and
  stops both on exit.  Read it with ``tools/trace_report.py --profile
  <directory>``.

  The phases of a compiled program are metadata of its executable; the
  persistent compile cache's key takes them in
  (``utils/compile_cache.configure``), so a program whose phases changed
  is compiled again and the trace shows today's."""
  import jax
  options = jax.profiler.ProfileOptions()
  options.python_tracer_level = 0
  was_enabled = _enabled
  enable(path)
  jax.profiler.start_trace(directory, profiler_options=options)
  try:
    yield directory
  finally:
    jax.profiler.stop_trace()
    if not was_enabled:
      disable()


def complete(name: str, start_s: float, dur_s: float,
             tid: Optional[int] = None, **args):
  """Emit an interval measured elsewhere (``start_s`` from ``now()``)
  into the tracer's own file: the devprof lane's offline measurements.
  Host code that times itself uses ``begin``/``end``, which also reach
  the profiler's trace."""
  if not _enabled:
    return
  ev = {
      'name': name, 'cat': span_category(name), 'ph': 'X',
      'ts': (start_s - _t0) * 1e6, 'dur': max(0.0, dur_s) * 1e6,
      'pid': _pid,
  }
  if tid is not None:
    ev['tid'] = tid
  if args:
    ev['args'] = args
  _emit(ev)


def async_span(name: str, span_id, start_s: float, end_s: float, **args):
  """Emit one logical (cross-thread) interval as a ``ph='b'``/``'e'``
  pair keyed by ``span_id`` — queue residency and other phases whose
  neighbours overlap arbitrarily and therefore cannot keep X-event
  stack discipline on any one track."""
  if not _enabled:
    return
  base = {'name': name, 'cat': span_category(name), 'pid': _pid,
          'id': str(span_id)}
  start_s = max(start_s, _t0)  # an interval begun before enable()
  b = dict(base, ph='b', ts=(start_s - _t0) * 1e6)
  if args:
    b['args'] = args
  e = dict(base, ph='e', ts=(max(start_s, end_s) - _t0) * 1e6)
  with _lock:
    tid = _tid()
    b['tid'] = tid
    e['tid'] = tid
    global _dropped
    if len(_events) + 2 > _max_events:
      _dropped += 2
      return
    _events.extend((b, e))


def instant(name: str, **args):
  if not _enabled:
    return
  ev = {'name': name, 'cat': span_category(name), 'ph': 'i', 's': 't',
        'ts': (time.perf_counter() - _t0) * 1e6, 'pid': _pid}
  if args:
    ev['args'] = args
  _emit(ev)


def events() -> List[Dict[str, Any]]:
  """Snapshot of the buffered events (metadata included)."""
  with _lock:
    return list(_events)


def dropped() -> int:
  with _lock:
    return _dropped


def event_count() -> int:
  with _lock:
    return len(_events)


def truncate(count: int, dropped_to: Optional[int] = None):
  """Drop events past index ``count`` — the overhead microbench
  (``obs.measure_overhead``) measures real emission cost, then removes
  its own scaffolding events so they never pollute a saved trace.
  ``thread_name`` metadata events in the removed range are KEPT (the
  thread registry still holds those tids — deleting the label would
  leave every later span on an unnamed track).  ``dropped_to``
  restores the dropped-event counter to its pre-scaffolding value, so
  a full buffer never misreports the scaffolding as lost real spans."""
  global _dropped
  with _lock:
    meta = [e for e in _events[int(count):] if e.get('ph') == 'M']
    del _events[int(count):]
    _events.extend(meta)
    if dropped_to is not None:
      _dropped = int(dropped_to)


def _payload(events: List[Dict[str, Any]], dropped_count: int,
             **other) -> Dict[str, Any]:
  """The one Perfetto-loadable wrapper shape shared by ``save`` and
  ``save_rotating`` (a schema change must hit both paths at once)."""
  return {
      'traceEvents': events,
      'displayTimeUnit': 'ms',
      'otherData': {
          'producer': 'distributed_embeddings_tpu.obs.trace',
          'dropped_events': dropped_count,
          **other,
      },
  }


def _atomic_write(path: str, payload: Dict[str, Any]) -> str:
  tmp = f'{path}.tmp.{os.getpid()}'
  with open(tmp, 'w', encoding='utf-8') as f:
    json.dump(payload, f)
  os.replace(tmp, path)
  return path


def save(path: Optional[str] = None) -> str:
  """Write the buffered trace as one Perfetto-loadable JSON object;
  returns the path written.  Raises ``ValueError`` without a path (no
  silent default location)."""
  path = path or _path
  if not path:
    raise ValueError('trace.save() needs a path (or enable(path=...))')
  with _lock:
    payload = _payload(list(_events), _dropped)
  return _atomic_write(path, payload)


def segment_count() -> int:
  """Segments written by ``save_rotating`` since the last ``clear``."""
  with _lock:
    return _segments


def save_rotating(path: Optional[str] = None,
                  max_events: int = 100_000) -> Optional[str]:
  """Rotate the buffer into a numbered segment file once it holds
  ``max_events`` events; the long-run twin of ``save``.

  The bounded buffer drops-with-count past its limit — correct for a
  bench window, but a multi-hour streaming/online-training run would
  lose the HEAD of the trace (the interesting warmup/compile phases)
  or grow host memory without bound.  Call this periodically (each log
  point): below the threshold it is a no-op returning None; at or past
  it, the buffered events flush to ``<path minus .json>.segNNNN.json``
  (atomic tmp+replace, same payload shape as ``save``) and the buffer
  empties — keeping the ``thread_name`` track labels and the clock
  base, so segments share one timeline and concatenating their
  ``traceEvents`` reconstructs the full run.  Returns the segment path
  written."""
  global _segments, _rotated_dropped
  path = path or _path
  if not path:
    raise ValueError(
        'trace.save_rotating() needs a path (or enable(path=...))')
  with _lock:
    real = [e for e in _events if e.get('ph') != 'M']
    # a buffer whose own bound (enable(max_events=...)) sits at or
    # below the rotation threshold stops growing before the threshold
    # is ever reached — if NEW drops happened since the last rotation,
    # the buffer is full and waiting loses events: flush now
    hit_bound = _dropped > _rotated_dropped and bool(real)
    if len(real) < max(1, int(max_events)) and not hit_bound:
      return None
    _rotated_dropped = _dropped
    seg = _segments
    _segments += 1
    meta = [e for e in _events if e.get('ph') == 'M']
    payload = _payload(list(_events), _dropped, segment=seg)
    # the thread registry still maps live threads to these tids: keep
    # the labels so the next segment's spans land on named tracks
    _events.clear()
    _events.extend(meta)
  base = path[:-5] if path.endswith('.json') else path
  return _atomic_write(f'{base}.seg{seg:04d}.json', payload)
