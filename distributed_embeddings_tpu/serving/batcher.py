"""Dynamic request batcher: many small requests -> one static device batch.

The admission half of serving (docs/design.md §14 "Batcher admission
policy"; §16 for the dispatch pipeline).  Concurrent user requests
(each a per-input list of id arrays for ``n`` samples) enqueue through
``submit``; a dispatcher thread merges them — launching as soon as the
batch is FULL (``max_batch`` samples) or the OLDEST queued request has
waited ``max_delay_ms``, whichever comes first — into one ``-1``-padded
batch at the SMALLEST compiled ladder rung that holds it
(``engine.bucket_for``; design §16), runs the lookup, and demuxes each
request's ``[n, output_dim]`` slice back to its ``ServeFuture``.

Admission rules (all pinned in tests/test_serving.py):

- an EMPTY request (0 samples) resolves immediately with empty outputs
  — it never occupies batch space;
- a request larger than ``max_batch`` REFUSES at ``submit`` with an
  actionable error (split it, or build a bigger engine batch) — silent
  splitting would break the one-request-one-result contract;
- a request that does not fit the in-flight batch's remaining space
  rides the NEXT batch (requests are never split);
- demux is BIT-EXACT vs running the same request through
  ``engine.lookup_padded`` alone (hotness-1; multi-hot within the
  pinned 1e-6 fold-order bound) AT EVERY LADDER RUNG: per-sample
  lookup+combine is independent of batch composition AND of the
  launched rung, so batching (and rung selection) is pure scheduling.

SLO-aware admission under overload (docs/design.md §23): ``submit``
takes ``priority=`` (``'high'`` | ``'low'``, default high — existing
callers are unchanged) and ``deadline_ms=``.  The two classes share the
one physical arrival queue (preserving the zero-idle-wakeup contract:
an idle dispatcher parks in ONE untimed blocking get), but admission
and dispatch treat them differently:

- LOW-priority requests are bounded separately (``low_queue_depth``,
  default half the queue) and SHED at admission when their class is
  full — the future resolves with ``RequestSheddedError``
  (``reason='queue_full'``) instead of blocking the submitter.
  HIGH-priority requests keep the blocking-put backpressure (the
  bounded queue IS the admission throttle; see the baseline waiver).
- a request whose ``deadline_ms`` has already passed when the
  dispatcher would merge it is shed AT DISPATCH (``reason='deadline'``)
  — dead work never reaches the device;
- the dispatcher drains arrivals into per-class ready queues and fills
  each batch HIGH-first, so under overload the high class rides every
  launch while the low class absorbs the shedding;
- every shed resolves its future (a shed caller is never stranded),
  counts per class/reason in ``stats()``, increments the
  ``serve.shed`` metric and journals a throttled ``serve_shed``
  resilience event; ``close()`` journals the final per-class
  admit/shed counters (``serve_admission``).

Pipelined dispatch (``pipeline=True``, the default; design §16): the
merge -> execute -> demux stages double-buffer across three threads the
way ``CsrFeed`` hides the host CSR build — the dispatcher merges batch
N+1 and the demux thread slices/resolves batch N-1 while the device
executes batch N.  Stage hand-offs are bounded queues with liveness
checks (a dead stage fails the batch fast, never wedges upstream),
results demux in FIFO launch order, and a failed stage fails exactly
its batch's futures — the admission policy, the
exception-fails-the-batch contract and the stats-before-resolve rule
are the serial path's, verbatim.  ``stats()['pipeline']`` measures the
hidden host share from consumer blocked time (``OverlapStat``, the
csr_feed/coldtier accounting): build = merge + demux walls, blocked =
the executor's wait for a merged batch (bounded by that batch's merge
wall — admission/idle waits are policy, not pipeline cost) plus its
backpressure wait on the demux queue.

With ``csr_feed=True`` merged batches additionally flow through a
``CsrFeed`` over a bounded in-memory ``QueueSource`` (no disk touch):
batch N+1's padded static-CSR host buffers build on worker threads
while the device runs batch N, and the feed's build/parity/queue
counters fold into ``stats()``.  csr_feed mode launches every batch at
the FULL engine signature and keeps its lookup+demux on the feed
consumer thread — the feed's static CSR capacities calibrate once and
must hold for every batch, so the bucket ladder and the stage pipeline
stay out of its way.  Same contract as the training pipeline (see
``csr_feed.py``): on SparseCore hardware the custom-call binding
consumes the buffers directly; on the XLA/emulation backends they are
the measured host-side feed cost the overlap exists to hide, while the
jitted lookup recomputes the same content via the traced twin.
"""

from __future__ import annotations

import collections
import queue
import threading
import time

from typing import Callable, List, Optional

import numpy as np

from distributed_embeddings_tpu.obs import metrics as obs_metrics
from distributed_embeddings_tpu.obs import trace as obs_trace
from distributed_embeddings_tpu.utils import resilience

# admission classes, dispatch-preference order (docs/design.md §23)
PRIORITIES = ('high', 'low')


class RequestSheddedError(RuntimeError):
  """The request was SHED by overload policy — a deliberate admission
  decision, not a wedge: ``reason`` is ``'queue_full'`` (low-priority
  class bound hit at submit), ``'deadline'`` (``deadline_ms`` expired
  before dispatch) or ``'closed'`` (batcher/pool shut down before the
  request launched).  Subclasses ``RuntimeError`` so pre-existing
  broad handlers keep working."""

  def __init__(self, message: str, reason: str = 'closed'):
    super().__init__(message)
    self.reason = reason


class DeadlineExceededError(TimeoutError):
  """``ServeFuture.result(timeout)`` gave up WAITING — distinct from a
  shed (the request may still resolve later).  Subclasses
  ``TimeoutError`` so pre-existing handlers keep working."""


class ReplicaLostError(RuntimeError):
  """Every replica in a ``ServingEnginePool`` is quarantined — the
  request cannot be retried anywhere (docs/design.md §23)."""


class ServeFuture:
  """Resolution handle of one submitted request."""

  def __init__(self):
    self._ev = threading.Event()
    self._out: Optional[List[np.ndarray]] = None
    self._err: Optional[BaseException] = None
    self.latency_ms: Optional[float] = None
    # completion subscribers (the replica pool's failover chain); the
    # tiny lock only orders subscribe vs resolve — callbacks always run
    # OUTSIDE it, so no foreign lock is ever taken under it
    self._cb_lock = threading.Lock()
    self._cbs: List[Callable[['ServeFuture'], None]] = []

  def _resolve(self, out=None, err=None, latency_ms=None):
    self._out = out
    self._err = err
    self.latency_ms = latency_ms
    with self._cb_lock:
      self._ev.set()
      cbs, self._cbs = self._cbs, []
    for cb in cbs:
      cb(self)

  def _subscribe(self, cb: Callable[['ServeFuture'], None]):
    """Run ``cb(self)`` once resolved (immediately if already done) —
    on the RESOLVING thread; keep it non-blocking."""
    with self._cb_lock:
      if not self._ev.is_set():
        self._cbs.append(cb)
        return
    cb(self)

  def error(self) -> Optional[BaseException]:
    """The resolution error, if resolved with one (None otherwise)."""
    return self._err if self._ev.is_set() else None

  def done(self) -> bool:
    return self._ev.is_set()

  def result(self, timeout: Optional[float] = None) -> List[np.ndarray]:
    """Per-input ``[n, output_dim]`` activations; raises the serving
    error (``RequestSheddedError`` when overload policy shed the
    request, ``DeadlineExceededError`` when the WAIT here expired)
    instead of returning partial data."""
    if not self._ev.wait(timeout):
      raise DeadlineExceededError('serving request not resolved within '
                                  f'{timeout}s')
    if self._err is not None:
      raise self._err
    return self._out


class _Slot:
  __slots__ = ('cats', 'n', 'future', 't0', 't0p', 'priority',
               'deadline')

  def __init__(self, cats, n, t0, priority='high', deadline=None):
    self.cats = cats
    self.n = n
    self.future = ServeFuture()
    self.t0 = t0
    self.priority = priority
    # absolute monotonic shed deadline (None: never sheds on age)
    self.deadline = deadline
    # admission on the TRACE clock: the wait from here to dispatch is
    # ONE measurement for stats() (queue_wait_p50/p99_ms), the
    # serve.queue_wait_ms histogram and the 'serve/enqueue' async span
    self.t0p = obs_trace.now()


_CLOSE = object()


class DynamicBatcher:
  """Merge concurrent requests into the engine's compiled batch ladder.

  Args:
    engine: a warmed (or warm-on-first-batch) ``ServingEngine``.
    max_delay_ms: admission deadline — the longest the OLDEST queued
      request waits for co-riders before its batch launches anyway.
      The knob trades tail latency against batch fill (the off/on A/B
      bench journals).
    max_batch: samples per launched batch (default and upper bound: the
      engine's ``batch_size`` — the padded remainder is sentinel rows).
    queue_depth: bound on queued requests (backpressure: ``submit``
      blocks when full — the HIGH class; see ``low_queue_depth``).
    low_queue_depth: bound on queued LOW-priority requests (default
      half of ``queue_depth``).  A low submit past the bound SHEDS —
      its future resolves with ``RequestSheddedError('queue_full')``
      instead of blocking the caller (docs/design.md §23).
    pipeline: double-buffer merge/execute/demux across stage threads
      (design §16; default on).  ``False`` runs the three stages
      serially on the dispatcher thread — the pre-ladder monolithic
      dispatch, kept as the bench A/B's middle arm.
    bucket_ladder: launch each merged batch at the smallest engine
      ladder rung that holds it (default on).  ``False`` launches every
      batch at the full ``engine.batch_size`` signature.
    csr_feed: also build each merged batch's static-CSR host buffers
      through a ``CsrFeed`` over a bounded in-memory ``QueueSource``
      (see module docstring; forces full-signature launches and the
      feed-consumer execute path).
  """

  def __init__(self, engine, max_delay_ms: float = 2.0,
               max_batch: Optional[int] = None, queue_depth: int = 256,
               csr_feed: bool = False,
               csr_feed_kwargs: Optional[dict] = None,
               pipeline: bool = True, bucket_ladder: bool = True,
               low_queue_depth: Optional[int] = None):
    self.engine = engine
    self.max_batch = int(max_batch if max_batch is not None
                         else engine.batch_size)
    if not 1 <= self.max_batch <= engine.batch_size:
      raise ValueError(
          f'max_batch {self.max_batch} must be in [1, engine.batch_size'
          f' = {engine.batch_size}]')
    self.max_delay_ms = float(max_delay_ms)
    self._q: queue.Queue = queue.Queue(maxsize=max(1, int(queue_depth)))
    self.low_queue_depth = int(low_queue_depth
                               if low_queue_depth is not None
                               else max(1, int(queue_depth) // 2))
    self._closed = threading.Event()
    self._lock = threading.Lock()
    # per-class admission/outcome accounting (docs/design.md §23); the
    # ready deques are dispatcher-owned between launches but swept by
    # close() after the join, so they live on the instance
    self._depth = {p: 0 for p in PRIORITIES}
    self._admitted = {p: 0 for p in PRIORITIES}
    self._served = {p: 0 for p in PRIORITIES}
    self._shed_class = {p: 0 for p in PRIORITIES}
    self._shed_reason = {'queue_full': 0, 'deadline': 0, 'closed': 0}
    self._lat_class = {p: obs_metrics.LatencyWindow()
                       for p in PRIORITIES}
    self._ready = {p: collections.deque() for p in PRIORITIES}
    # admission lock: makes submit's {closed-check, enqueue} atomic
    # against close's {set-closed} — a put racing past the flag would
    # land after close's final sweep and strand its future forever.
    # Separate from self._lock (the stats lock the dispatcher takes
    # mid-batch), so a submit blocked on a full queue can never
    # deadlock the dispatcher that must drain it.
    self._submit_lock = threading.Lock()
    self._submitted = 0
    self._completed = 0
    self._batches = 0
    self._fill_sum = 0.0
    # bucket-ladder padding accounting (design §16): rows launched vs
    # the sentinel rows among them, plus per-rung launch counts
    self._rows_launched = 0
    self._pad_rows = 0
    self._bucket_launches: dict = {}
    # the shared bounded exact-latency primitive (obs/metrics.py
    # LatencyWindow) — stats() keys and percentile arithmetic unchanged
    self._latencies = obs_metrics.LatencyWindow()
    self._queue_waits = obs_metrics.LatencyWindow()
    self.bucket_ladder = bool(bucket_ladder) and not csr_feed
    self._feed = None
    self._queue_source = None
    self._consumer = None
    self._inflight: List[_Slot] = []  # pushed to the feed, not yet run
    if csr_feed:
      from distributed_embeddings_tpu.parallel.csr_feed import QueueSource
      self._queue_source = QueueSource(maxsize=4)
      self._feed = engine.dist.make_csr_feed(
          self._queue_source,
          cats_fn=lambda item: [np.asarray(c) for c in item[0]],
          **(csr_feed_kwargs or {}))
      self._consumer = threading.Thread(target=self._consume_feed,
                                        name='serve-feed-consumer',
                                        daemon=True)
      self._consumer.start()
    # pipelined dispatch stages (design §16); csr_feed mode keeps its
    # own overlap machinery (the feed IS the pipeline there)
    self.pipeline = bool(pipeline) and not csr_feed
    self._pipe = obs_metrics.OverlapStat() if self.pipeline else None
    self._exec_q: Optional[queue.Queue] = None
    self._demux_q: Optional[queue.Queue] = None
    self._executor: Optional[threading.Thread] = None
    self._demuxer: Optional[threading.Thread] = None
    if self.pipeline:
      self._exec_q = queue.Queue(maxsize=2)
      self._demux_q = queue.Queue(maxsize=2)
      self._demuxer = threading.Thread(target=self._demux_loop,
                                       name='serve-demux', daemon=True)
      self._demuxer.start()
      self._executor = threading.Thread(target=self._execute_loop,
                                        name='serve-executor',
                                        daemon=True)
      self._executor.start()
    self._dispatcher = threading.Thread(target=self._dispatch_loop,
                                        name='serve-batcher',
                                        daemon=True)
    self._dispatcher.start()

  # ----------------------------------------------------------- submission

  def submit(self, cats, priority: str = 'high',
             deadline_ms: Optional[float] = None) -> ServeFuture:
    """Enqueue one request (per-input id arrays for ``n`` samples);
    returns its ``ServeFuture``.  MALFORMED requests raise HERE,
    synchronously, so the caller can repair them; OVERLOAD sheds (a
    full low-priority class, an expired ``deadline_ms``) resolve the
    returned future with ``RequestSheddedError`` instead — shedding is
    a normal outcome the caller observes through ``result()``."""
    with obs_trace.span('serve/submit'):
      fut = self._submit(cats, priority, deadline_ms)
    obs_metrics.inc('serve.submitted')
    return fut

  def _submit(self, cats, priority: str = 'high',
              deadline_ms: Optional[float] = None) -> ServeFuture:
    if self._closed.is_set():
      raise RuntimeError('batcher is closed')
    if priority not in PRIORITIES:
      raise ValueError(f'priority {priority!r} must be one of '
                       f'{PRIORITIES}')
    if deadline_ms is not None and deadline_ms <= 0:
      raise ValueError(f'deadline_ms must be positive, got {deadline_ms}')
    cats = [np.asarray(x) for x in cats]
    if len(cats) != self.engine.dist.num_inputs:
      raise ValueError(f'expected {self.engine.dist.num_inputs} inputs, '
                       f'got {len(cats)}')
    n = int(cats[0].shape[0]) if cats else 0
    for i, x in enumerate(cats):
      if x.ndim not in (1, 2):
        raise ValueError(
            f'input {i}: expected 1-D or 2-D ids, got shape {x.shape}')
      if int(x.shape[0]) != n:
        raise ValueError(
            f'input {i} has {x.shape[0]} samples, input 0 has {n}')
      h = x.shape[1] if x.ndim == 2 else 1
      if h > self.engine.hotness[i]:
        raise ValueError(
            f'input {i}: request hotness {h} exceeds the compiled hot '
            f'cap {self.engine.hotness[i]}')
    if n > self.max_batch:
      raise ValueError(
          f'request of {n} samples exceeds max_batch {self.max_batch}: '
          'split the request, or build the batcher/engine with a '
          'larger batch (requests are never silently split)')
    t0 = time.monotonic()
    deadline = t0 + deadline_ms / 1000.0 if deadline_ms else None
    slot = _Slot(cats, n, t0, priority=priority, deadline=deadline)
    with self._lock:
      self._submitted += 1
      self._admitted[priority] += 1
    if n == 0:
      # empty request: resolves immediately, occupies no batch space
      slot.future._resolve(
          out=[np.zeros((0, d), np.float32)
               for d in self.engine.output_dims],
          latency_ms=0.0)
      with self._lock:
        self._completed += 1
        self._served[priority] += 1
      return slot.future
    if priority == 'low':
      # the low class is bounded on its own: past the bound the
      # request SHEDS here instead of blocking the submitter — the
      # overload throttle the high class's blocking put deliberately
      # is NOT (docs/design.md §23)
      with self._lock:
        full = self._depth['low'] >= self.low_queue_depth
        if not full:
          self._depth['low'] += 1
      if full:
        self._shed(slot, 'queue_full', dec_depth=False)
        return slot.future
    else:
      with self._lock:
        self._depth['high'] += 1
    # atomic with close()'s flag-set (see _submit_lock): every slot
    # that enqueues here is guaranteed a consumer — the live
    # dispatcher, its exit drain, or close()'s final sweep
    with self._submit_lock:
      if self._closed.is_set():
        with self._lock:
          self._depth[priority] -= 1
        raise RuntimeError('batcher is closed')
      self._q.put(slot)
    return slot.future

  # throttle the per-shed journal line: under a sustained overload the
  # journal must show the shedding without itself becoming the load
  _SHED_JOURNAL_EVERY = 64

  def _shed(self, slot: _Slot, reason: str, dec_depth: bool = True):
    """Resolve one slot as SHED: typed error, per-class/per-reason
    counters, the ``serve.shed`` metric, a throttled ``serve_shed``
    journal event and (when tracing) a ``serve/shed`` span covering
    the request's queue residency.  ``dec_depth=False`` for sheds of
    slots that never entered the queue (the queue_full refusal)."""
    with self._lock:
      if dec_depth:
        self._depth[slot.priority] -= 1
      self._shed_class[slot.priority] += 1
      self._shed_reason[reason] += 1
      n_class = self._shed_class[slot.priority]
      shed_total = sum(self._shed_class.values())
      admitted = dict(self._admitted)
    if n_class == 1 or n_class % self._SHED_JOURNAL_EVERY == 0:
      resilience.journal('serve_shed', priority=slot.priority,
                         reason=reason, shed_class=n_class,
                         shed_total=shed_total, admitted=admitted)
    obs_metrics.inc('serve.shed')
    if obs_trace.enabled():
      # queue residency of a request that left unserved: no thread owns
      # it, so it is an async interval of the obs file like serve/enqueue
      obs_trace.async_span('serve/shed', id(slot), slot.t0p,
                           obs_trace.now(), priority=slot.priority,
                           reason=reason, samples=slot.n)
    if reason == 'closed':
      msg = 'batcher closed before the request was served'
    else:
      msg = (f'request shed ({reason}): {slot.priority}-priority '
             'admission policy under overload — retry later, raise '
             'the deadline, or submit at high priority '
             '(docs/design.md §23)')
    slot.future._resolve(err=RequestSheddedError(msg, reason=reason))

  # ------------------------------------------------------------- dispatch

  def _pop_ready(self) -> Optional[_Slot]:
    """Next dispatchable slot, HIGH class first; expired slots are
    shed here — at dispatch, before any merge work — so dead work
    never reaches the device (docs/design.md §23)."""
    now = time.monotonic()
    for p in PRIORITIES:
      dq = self._ready[p]
      while dq:
        slot = dq.popleft()
        if slot.deadline is not None and now > slot.deadline:
          self._shed(slot, 'deadline')
          continue
        return slot
    return None

  def _push_ready(self, slot: _Slot):
    self._ready[slot.priority].append(slot)

  def _dispatch_loop(self):
    while True:
      first = self._pop_ready()
      if first is None:
        if self._closed.is_set():
          break
        # IDLE: block indefinitely — an idle serving process burns
        # zero scheduled wakeups (no 50 ms polling; pinned in
        # tests/test_serving.py).  close() guarantees the _CLOSE
        # sentinel lands, so this get always wakes on shutdown.
        got = self._q.get()
        if got is _CLOSE:
          break
        self._push_ready(got)
        continue
      batch = [first]
      n = first.n
      deadline = first.t0 + self.max_delay_ms / 1000.0
      while n < self.max_batch:
        nxt = self._pop_ready()
        if nxt is None:
          wait = deadline - time.monotonic()
          try:
            # past the deadline the batch must not WAIT any longer —
            # but requests already queued (a backlog built while the
            # previous batch executed) still merge in, non-blockingly:
            # under load the batch fills from the backlog instead of
            # launching singletons
            got = (self._q.get(timeout=wait) if wait > 0
                   else self._q.get_nowait())
          except queue.Empty:
            break
          if got is _CLOSE:
            self._closed.set()
            break
          self._push_ready(got)
          continue
        if n + nxt.n > self.max_batch:
          # does not fit: rides the NEXT batch, unsplit — back to the
          # FRONT of its class so arrival order within a class holds
          self._ready[nxt.priority].appendleft(nxt)
          break
        batch.append(nxt)
        n += nxt.n
      # each merged request's wait from admission to dispatch, read once
      t1 = obs_trace.now()
      waits = [(t1 - slot.t0p) * 1000.0 for slot in batch]
      with self._lock:
        for slot in batch:
          self._depth[slot.priority] -= 1
        self._queue_waits.extend(waits)
      for wait_ms in waits:
        obs_metrics.observe('serve.queue_wait_ms', wait_ms)
      if obs_trace.enabled():
        # the same interval as an ASYNC span (b/e pair): neighbours
        # overlap arbitrarily, so no one thread's track could hold them
        # nested, and no thread owns a wait to annotate it
        for slot in batch:
          obs_trace.async_span('serve/enqueue', id(slot), slot.t0p, t1,
                               samples=slot.n)
      try:
        with obs_trace.span('serve/dispatch', requests=len(batch),
                            samples=n):
          self._launch(batch, n)
      except BaseException as e:
        # a failed merge/launch fails THIS batch's futures — the
        # dispatcher itself must survive, or every later request
        # would hang unresolved against a silently dead thread
        for slot in batch:
          if not slot.future.done():
            slot.future._resolve(err=e)
    # drain: fail anything still ready or queued after close
    leftovers = []
    for p in PRIORITIES:
      while self._ready[p]:
        leftovers.append(self._ready[p].popleft())
    while True:
      try:
        s = self._q.get_nowait()
      except queue.Empty:
        break
      if s is not _CLOSE:
        leftovers.append(s)
    for s in leftovers:
      self._shed(s, 'closed')
    if self._queue_source is not None:
      self._queue_source.close()

  def _merge(self, batch, bucket: int) -> List[np.ndarray]:
    """One ``-1``-padded batch at the ``bucket`` rung signature from
    the requests' per-input arrays (request r's samples occupy rows
    ``[off_r, off_r + n_r)`` of every input)."""
    eng = self.engine
    merged = []
    for i in range(eng.dist.num_inputs):
      h = eng.hotness[i]
      buf = np.full((bucket, h), -1, np.int32)
      off = 0
      for slot in batch:
        x = slot.cats[i]
        x2 = x[:, None] if x.ndim == 1 else x
        buf[off:off + slot.n, :x2.shape[1]] = x2
        off += slot.n
      merged.append(buf[:, 0] if h == 1 else buf)
    return merged

  # a wedged (alive but stuck) downstream stage must not spin the
  # upstream thread forever: past this deadline the hand-off gives up
  # and fails the batch.  Generous — a legitimately busy executor is
  # mid-device-lookup, which is seconds at worst, not minutes.
  _STAGE_PUT_DEADLINE_S = 120.0

  def _put_stage(self, q: queue.Queue, item, consumer, batch) -> bool:
    """Bounded hand-off to a downstream stage thread with a liveness
    check AND an overall deadline: a dead stage fails this batch's
    futures fast, a wedged one fails them after the deadline — the
    upstream thread (and with it every later request) never spins
    forever on a queue nothing will drain."""
    t0 = time.monotonic()
    why = None
    while why is None:
      if consumer is None or not consumer.is_alive():
        why = (f'({getattr(consumer, "name", "consumer")} exited)')
      elif time.monotonic() - t0 > self._STAGE_PUT_DEADLINE_S:
        why = (f'({getattr(consumer, "name", "consumer")} wedged: '
               f'hand-off blocked > {self._STAGE_PUT_DEADLINE_S:g}s)')
      else:
        try:
          q.put(item, timeout=0.2)
          return True
        except queue.Full:
          continue
    err = RuntimeError(
        f'serving dispatch pipeline stage is stuck {why}; '
        'request not served')
    for slot in batch:
      if not slot.future.done():
        slot.future._resolve(err=err)
    return False

  def _launch(self, batch, n):
    # stage 1: MERGE — at the smallest ladder rung holding n (csr_feed
    # mode pins the full signature; see module docstring)
    eng = self.engine
    bucket = (eng.bucket_for(n) if self.bucket_ladder
              else eng.batch_size)
    tok = obs_trace.begin('serve/merge', requests=len(batch), samples=n,
                          bucket=bucket)
    try:
      merged = self._merge(batch, bucket)
    finally:
      merge_ms = obs_trace.end(tok) * 1000.0
    obs_metrics.observe('serve.merge_ms', merge_ms)
    if self._queue_source is not None:
      # csr_feed mode: the merged batch rides the in-memory queue into
      # the CsrFeed; the consumer thread executes + demuxes in feed
      # order (the CSR host build overlaps the previous device lookup).
      # TIMED puts with a consumer-liveness check: a dead feed pipeline
      # must fail this batch's futures fast, never wedge the
      # dispatcher (and with it every later request) on a full queue
      # nothing will ever drain.
      with self._lock:
        self._inflight.extend(batch)
      err = None
      while err is None:
        if self._consumer is None or not self._consumer.is_alive():
          err = RuntimeError(
              'serving feed pipeline is dead (CsrFeed consumer '
              'exited); request not served')
          break
        try:
          if self._queue_source.put((merged, batch, n), timeout=0.2):
            return
        except RuntimeError as e:  # source closed under us
          err = e
      with self._lock:
        self._inflight = [s for s in self._inflight if s not in batch]
      for slot in batch:
        if not slot.future.done():
          slot.future._resolve(err=err)
      return
    if self.pipeline:
      with self._lock:
        self._pipe.add_build(merge_ms)
      # stage hand-off: the executor thread runs the device lookup for
      # this batch while the dispatcher merges the next
      self._put_stage(self._exec_q, (merged, batch, n, merge_ms),
                      self._executor, batch)
      return
    self._execute(merged, batch, n)

  def _execute_loop(self):
    """Stage 2 thread: device execution.  The pipeline's CONSUMER for
    the blocked-time overlap accounting — its wait for a merged batch
    (bounded by that batch's merge wall: admission/idle waits are
    policy, not pipeline cost) plus its backpressure wait on the demux
    queue is exactly the host pipeline time the device felt."""
    while True:
      t0 = time.perf_counter()
      item = self._exec_q.get()
      try:
        wait_ms = (time.perf_counter() - t0) * 1000.0
        if item is None:
          # forward shutdown downstream, FIFO — via the liveness-checked
          # bounded hand-off (a dead demuxer must not wedge this thread
          # on the full queue; detlint concurrency/untimed-put-bounded)
          self._put_stage(self._demux_q, None, self._demuxer, [])
          return
        merged, batch, n, merge_ms = item
        with self._lock:
          self._pipe.add_blocked(min(wait_ms, merge_ms))
        self._execute(merged, batch, n)
      except BaseException as e:
        # an injected kill (faultinject) can land between the dequeue
        # and _execute's own guard: the dequeued batch must still fail
        # loudly — an unresolved future is a lost request, and the
        # pool's failover contract needs the error to surface
        if item is not None:
          for slot in item[1]:
            if not slot.future.done():
              slot.future._resolve(err=e)
        raise

  def _demux_loop(self):
    """Stage 3 thread: host demux in FIFO launch order (a single
    consumer of a FIFO queue — order is structural, not scheduled)."""
    while True:
      item = self._demux_q.get()
      if item is None:
        return
      host, batch, n = item
      try:
        self._demux(host, batch, n)
      except BaseException as e:
        # a torn demux fails exactly its batch; the stage survives
        for slot in batch:
          if not slot.future.done():
            slot.future._resolve(err=e)

  def _execute(self, merged, batch, n):
    try:
      with obs_trace.span('serve/execute', requests=len(batch),
                          samples=n):
        outs = self.engine.lookup(merged, samples=n)
        host = [np.asarray(o) for o in outs]
    except BaseException as e:
      for slot in batch:
        slot.future._resolve(err=e)
      return
    if self.pipeline:
      t0 = time.perf_counter()
      if self._put_stage(self._demux_q, (host, batch, n),
                         self._demuxer, batch):
        put_ms = (time.perf_counter() - t0) * 1000.0
        with self._lock:
          self._pipe.add_blocked(put_ms)  # demux backpressure
      return
    self._demux(host, batch, n)

  def _demux(self, host, batch, n):
    bucket = int(host[0].shape[0]) if host else 0
    tok = obs_trace.begin('serve/demux', requests=len(batch))
    t0 = time.perf_counter()
    now = time.monotonic()
    lats = [(now - slot.t0) * 1000.0 for slot in batch]
    # the demux WORK (per-request slicing) happens before any future
    # fires, so demux_ms — the stat and the pipeline build share the
    # one measurement — covers it without racing the stats contract
    off = 0
    outs = []
    for slot in batch:
      outs.append([h[off:off + slot.n] for h in host])
      off += slot.n
    demux_ms = (time.perf_counter() - t0) * 1000.0
    # EVERY stat updates BEFORE the futures resolve (pipeline
    # accounting included): a caller reading stats() the moment
    # result() returns must already see this batch fully counted
    # (measure_serving journals straight off that read, and the
    # pipeline.batches == batches pin reads the same way)
    with self._lock:
      self._batches += 1
      self._fill_sum += n / self.max_batch
      self._completed += len(batch)
      self._latencies.extend(lats)
      for slot, lat in zip(batch, lats):
        self._served[slot.priority] += 1
        self._lat_class[slot.priority].record(lat)
      self._rows_launched += bucket
      self._pad_rows += bucket - n
      self._bucket_launches[bucket] = \
          self._bucket_launches.get(bucket, 0) + 1
      if self._pipe is not None:
        self._pipe.add_build(demux_ms)
        self._pipe.count_batch()
    obs_metrics.inc('serve.batches')
    obs_metrics.inc('serve.completed', len(batch))
    obs_metrics.set_gauge('serve.batch_fill', n / self.max_batch)
    obs_metrics.observe('serve.demux_ms', demux_ms)
    for slot, lat in zip(batch, lats):
      obs_metrics.observe('serve.latency_ms', lat)
      if slot.priority == 'high':
        obs_metrics.observe('serve.latency_high_ms', lat)
      else:
        obs_metrics.observe('serve.latency_low_ms', lat)
    for slot, out, lat in zip(batch, outs, lats):
      slot.future._resolve(out=out, latency_ms=lat)
    obs_trace.end(tok)

  def _consume_feed(self):
    try:
      for fed in self._feed:
        merged, batch, n = fed.item
        with self._lock:
          self._inflight = [s for s in self._inflight
                            if s not in batch]
        self._execute(merged, batch, n)
      stranded = []
    except BaseException as e:
      with self._lock:
        stranded, self._inflight = self._inflight, []
      for slot in stranded:
        slot.future._resolve(err=e)
      return
    # clean feed shutdown (close()): fail whatever never ran
    with self._lock:
      stranded, self._inflight = self._inflight, []
    for slot in stranded:
      slot.future._resolve(err=RequestSheddedError(
          'batcher closed before the request was served',
          reason='closed'))

  # ----------------------------------------------------------- lifecycle

  def _put_sentinel(self, q: queue.Queue, item, thread,
                    deadline_s: float = 30.0):
    """Land a shutdown sentinel on a stage queue: retries while the
    consuming thread is alive (it is draining, so space appears) up to
    ``deadline_s`` — a WEDGED consumer must not make close() spin
    forever; the joins below time out and the final sweep still fails
    whatever never launched.  A dead consumer needs no sentinel."""
    t0 = time.monotonic()
    while thread is not None and thread.is_alive() \
        and time.monotonic() - t0 <= deadline_s:
      try:
        q.put(item, timeout=0.1)
        return
      except queue.Full:
        continue

  def close(self):
    """Stop the dispatcher and the pipeline stages; launched batches
    complete, never-launched requests fail with a clear error.
    Idempotent."""
    with self._submit_lock:
      if self._closed.is_set():
        return
      self._closed.set()
    # the sentinel MUST land: the idle dispatcher blocks indefinitely
    # on the queue (zero idle wakeups), so only the sentinel — or a
    # drained backlog item — wakes it.  submit refuses once _closed is
    # set, so the queue only drains from here and the retry put cannot
    # livelock.
    self._put_sentinel(self._q, _CLOSE, self._dispatcher)
    self._dispatcher.join(timeout=30.0)
    if self.pipeline:
      # flush the stages in launch order; the executor forwards the
      # sentinel so every in-flight batch demuxes before the threads
      # exit (a direct put covers an already-dead executor)
      self._put_sentinel(self._exec_q, None, self._executor)
      self._executor.join(timeout=30.0)
      self._put_sentinel(self._demux_q, None, self._demuxer)
      self._demuxer.join(timeout=30.0)
      # a KILLED stage (the pool's quarantine drill) leaves batches in
      # its queue that no thread will ever drain: demux-stage items
      # already executed — finish them here; executor-stage items never
      # launched — shed them.  Only once the stage thread is provably
      # gone (a merely wedged thread still owns its queue).
      if not self._demuxer.is_alive():
        while True:
          try:
            it = self._demux_q.get_nowait()
          except queue.Empty:
            break
          if it is not None:
            self._demux(*it)
      if not self._executor.is_alive():
        while True:
          try:
            it = self._exec_q.get_nowait()
          except queue.Empty:
            break
          if it is not None:
            for s in it[1]:
              if not s.future.done():
                self._shed(s, 'closed', dec_depth=False)
    # nothing can enqueue past this point (the _submit_lock pairing in
    # submit re-checks the flag before its put): one final sweep and
    # no future is ever stranded unresolved
    while True:
      try:
        s = self._q.get_nowait()
      except queue.Empty:
        break
      if s is not _CLOSE:
        self._shed(s, 'closed')
    # the dispatcher owns the ready deques while alive; after its join
    # (or its death) this sweep is the only consumer left
    for p in PRIORITIES:
      while self._ready[p]:
        self._shed(self._ready[p].popleft(), 'closed')
    if self._queue_source is not None:
      self._queue_source.close()
    if self._consumer is not None:
      self._consumer.join(timeout=30.0)
    if self._feed is not None:
      self._feed.close()
    with self._lock:
      admitted = dict(self._admitted)
      served = dict(self._served)
      shed_class = dict(self._shed_class)
      shed_reason = dict(self._shed_reason)
    # the per-class admission ledger, journaled once at shutdown so an
    # unattended overload leaves evidence (docs/design.md §23)
    resilience.journal('serve_admission', admitted=admitted,
                       served=served, shed=shed_class,
                       shed_reason=shed_reason)

  def __enter__(self):
    return self

  def __exit__(self, *exc):
    self.close()
    return False

  # --------------------------------------------------------------- stats

  def _class_stats(self) -> dict:
    """Per-admission-class block of ``stats()`` (caller holds
    ``_lock``): admitted/served/shed/depth counters plus the class's
    own latency percentiles (every key is in
    ``obs.metrics.REGISTERED_STATS_KEYS``)."""
    out = {}
    for p in PRIORITIES:
      w = self._lat_class[p]
      cp50, cp99, cp999 = (w.percentile(50), w.percentile(99),
                           w.percentile(99.9))
      out[p] = {
          'admitted': self._admitted[p],
          'served': self._served[p],
          'shed': self._shed_class[p],
          'depth': self._depth[p],
          'p50_ms': round(cp50, 3) if cp50 is not None else None,
          'p99_ms': round(cp99, 3) if cp99 is not None else None,
          'p999_ms': round(cp999, 3) if cp999 is not None else None,
      }
    return out

  def stats(self) -> dict:
    """Latency / fill accounting: ``p50_ms``/``p99_ms``/``p999_ms``
    over resolved request latencies (submit -> demux),
    ``queue_wait_p50_ms``/``queue_wait_p99_ms`` over the waits from
    admission to dispatch (the part of the latency spent queued, the
    ``serve/enqueue`` span's own measurement), the per-class
    admission ledger (``classes`` + the per-reason ``shed`` block;
    docs/design.md §23), mean ``batch_fill`` (samples /
    ``max_batch``), the bucket-ladder padding accounting
    (``rows_launched``/``pad_rows``/``pad_waste_pct`` +
    ``bucket_launches`` per rung), the ``pipeline`` overlap block when
    the staged dispatch is on, and the feed's build/queue counters in
    csr_feed mode."""
    with self._lock:
      p50 = self._latencies.percentile(50)
      p99 = self._latencies.percentile(99)
      p999 = self._latencies.percentile(99.9)
      wait50 = self._queue_waits.percentile(50)
      wait99 = self._queue_waits.percentile(99)
      launched = self._rows_launched
      classes = self._class_stats()
      out = {
          'submitted': self._submitted,
          'completed': self._completed,
          'batches': self._batches,
          'max_batch': self.max_batch,
          'max_delay_ms': self.max_delay_ms,
          'batch_fill': (round(self._fill_sum / self._batches, 4)
                         if self._batches else None),
          'p50_ms': round(p50, 3) if p50 is not None else None,
          'p99_ms': round(p99, 3) if p99 is not None else None,
          'p999_ms': round(p999, 3) if p999 is not None else None,
          'queue_wait_p50_ms': (round(wait50, 3) if wait50 is not None
                                else None),
          'queue_wait_p99_ms': (round(wait99, 3) if wait99 is not None
                                else None),
          'classes': classes,
          'shed': dict(self._shed_reason),
          'low_queue_depth': self.low_queue_depth,
          'bucket_ladder': self.bucket_ladder,
          'buckets': (list(self.engine.buckets) if self.bucket_ladder
                      else [self.engine.batch_size]),
          'bucket_launches': dict(self._bucket_launches),
          'rows_launched': launched,
          'pad_rows': self._pad_rows,
          'pad_waste_pct': (round(100.0 * self._pad_rows / launched, 3)
                            if launched else None),
      }
      if self._pipe is not None:
        out['pipeline'] = {
            'batches': self._pipe.batches,
            'merge_demux_ms': round(self._pipe.build_ms, 3),
            'blocked_ms': round(self._pipe.blocked_ms, 3),
            'overlap_pct': round(self._pipe.overlap_frac(), 4),
        }
    if self._feed is not None:
      out['csr_feed'] = self._feed.stats()
    return out
