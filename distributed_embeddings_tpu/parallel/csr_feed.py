"""Double-buffered host->device input pipeline for the SparseCore feed.

``docs/perf_notes.md`` ("Static-CSR host preprocessing cost") measured
the per-batch host transform at ~260 ns/id single-threaded NumPy — ~9x
the v5e on-chip gather floor — and named the production fix: pipeline
the build (batch N+1's buffers are built while the device executes
batch N) and parallelise it over (group, device) pairs.  This module is
that pipeline:

- a single ordered PRODUCER thread walks the caller's batch source and
  runs ``sparsecore.preprocess_batch_host`` for each batch — which
  itself fans the (group, device) build jobs out over the shared worker
  pool (native C++ builder when built, NumPy oracle otherwise);
- a bounded ring (``depth``, default 2 = classic double buffering)
  holds finished batches, giving backpressure: the producer can run at
  most ``depth`` batches ahead of the consumer, so host memory for the
  padded buffers stays bounded;
- the consumer iterates ``FedBatch``es; ``__next__`` blocks only when
  the build has NOT finished under the device step it should hide
  behind — and records exactly that blocked time, so
  ``stats()['overlap_pct']`` is a DIRECT measurement of how much host
  build time the device step hid (the metric ``bench.py`` journals),
  not a subtraction of two noisy walls.

Batches arrive strictly in source order and ``close()`` (or the context
manager, or source exhaustion) drains the pipeline cleanly; a producer
exception surfaces on the consumer's next ``__next__`` rather than
dying silently on a background thread.

The feed is the degraded-mode boundary of an unattended run
(docs/userguide.md "Fault tolerance"): transient ``IOError``/``OSError``
from the source or the build retry with bounded exponential backoff, a
producer thread that dies outright is respawned with its in-flight
batch intact (zero loss), and a poison batch follows the
``on_batch_error`` policy — ``'raise'`` (default) or ``'skip'`` with
the skip counted in ``stats()`` and journaled
(``utils/resilience.journal``), never silent.

The buffers each ``FedBatch`` carries are the hardware feed layout
(``HostCsr`` per (group, hotness) x device): on SparseCore hardware the
custom-call binding consumes them directly; on the emulation backend
they are the measured host-side cost the pipeline exists to hide, while
the jitted step recomputes the same content via the traced twin (the
executable specification).
"""

from __future__ import annotations

import queue
import threading
import time
import weakref

from typing import Any, Callable, Dict, Iterable, List, NamedTuple, Optional, Tuple

import numpy as np

from distributed_embeddings_tpu.obs import metrics as obs_metrics
from distributed_embeddings_tpu.obs import trace as obs_trace
from distributed_embeddings_tpu.parallel import sparsecore
from distributed_embeddings_tpu.utils import resilience


class FedBatch(NamedTuple):
  """One prefetched batch: the caller's original item, its built CSR
  buffers (``{(group_index, hotness): [HostCsr per device]}``), and the
  build's wall time on the workers."""
  item: Any
  csrs: Dict[Tuple[int, int], List[Any]]
  build_ms: float


class _Done:
  pass


class _Error(NamedTuple):
  exc: BaseException


class _Item(NamedTuple):
  """Ring message: a built batch tagged with its source ordinal so the
  consumer can drop the duplicate a respawned producer may re-deliver
  (the producer keeps the in-flight item across a worker death — zero
  loss — at the cost of a possible re-build of an already-delivered
  batch)."""
  seq: int
  fed: FedBatch


_NO_ITEM = object()  # cursor sentinel: no source item pulled yet


class QueueSource:
  """Bounded IN-MEMORY batch source for a ``CsrFeed`` — the producer
  side of the serving batcher (docs/design.md §14), where merged
  request batches exist only in RAM and must reach the feed without a
  reader/file detour.

  ``put(item)`` enqueues one batch (blocking while the bound is full —
  backpressure toward the submitter; ``block=False`` instead DROPS the
  batch and counts it, for callers that prefer shedding to stalling).
  ``close()`` ends the stream: the feed's producer drains what is
  queued, then sees ``StopIteration`` and shuts down cleanly — ALWAYS
  close the source before (or instead of) closing the feed, otherwise
  the feed's producer blocks inside the source pull until the feed's
  own join times out.

  A ``CsrFeed`` constructed over a ``QueueSource`` reports the queue's
  live depth and drop count in its ``stats()``
  (``queue_depth`` / ``queue_dropped``).
  """

  def __init__(self, maxsize: int = 8):
    self._q: queue.Queue = queue.Queue(maxsize=max(1, int(maxsize)))
    self._closed = threading.Event()
    self._dropped = 0

  def put(self, item, block: bool = True,
          timeout: Optional[float] = None) -> bool:
    """Enqueue one batch; returns False when the queue stays full.  A
    NON-blocking put against a full queue is a shed — counted in
    ``dropped``; a timed blocking put that runs out is merely "not yet
    enqueued" (the caller retries) and counts nothing.  Raises on a
    closed source — feeding a finished stream is a caller bug, never
    silent."""
    if self._closed.is_set():
      raise RuntimeError('QueueSource is closed')
    try:
      self._q.put(item, block=block, timeout=timeout)
      return True
    except queue.Full:
      if not block:
        self._dropped += 1
        obs_metrics.inc('feed.queue_dropped')
      return False

  def close(self):
    """End the stream (idempotent): queued items still drain, then the
    consumer sees ``StopIteration``."""
    self._closed.set()

  @property
  def closed(self) -> bool:
    return self._closed.is_set()

  @property
  def dropped(self) -> int:
    """Batches shed by non-blocking ``put`` against a full queue."""
    return self._dropped

  def qsize(self) -> int:
    return self._q.qsize()

  def __iter__(self):
    return self

  def __next__(self):
    while True:
      try:
        return self._q.get(timeout=0.05)
      except queue.Empty:
        if self._closed.is_set():
          raise StopIteration from None


def _producer_main(ref: 'weakref.ref'):
  """Producer thread body: a trampoline over bounded work units that
  holds the feed only WEAKLY between units (the ``_ReadAhead`` pattern,
  utils/data.py) — a feed abandoned without drain or ``close()`` gets
  garbage-collected, the next deref returns None, and the thread exits
  instead of blocking forever on the full ring."""
  while True:
    feed = ref()
    if feed is None:
      return  # feed abandoned: nobody will ever consume
    try:
      more = feed._produce_unit()
    except (SystemExit, KeyboardInterrupt, GeneratorExit):
      # abrupt worker death (fault-injected kill or interpreter
      # teardown): no terminal marker — the consumer detects the dead
      # thread and respawns it; feed._cursor/_pending still hold the
      # batch that was in progress, so nothing is lost
      return
    if not more:
      return
    del feed


class CsrFeed:
  """Double-buffered prefetching feed over a batch source.

  Args:
    dist: the ``DistributedEmbedding`` whose plan routes the ids.
    source: iterable of batch items (consumed on the producer thread).
    cats_fn: ``item -> list of per-table id arrays`` (the
      ``preprocess_batch_host`` input); default treats the item itself
      as the cats list.
    max_ids_per_partition: calibrated per-group capacities
      (``sparsecore.calibrate_max_ids_per_partition``); None sizes each
      batch to its own worst partition.
    depth: ring capacity — how many built batches may wait ahead of the
      consumer (2 = double buffering).
    num_workers: per-batch build fan-out (None = the shared pool).
    native: builder selection ('auto' | 'native' | 'numpy').
    on_batch_error: poison-batch policy.  ``'raise'`` (default)
      surfaces a batch whose build fails (after transient retries) on
      the consumer's next ``__next__``; ``'skip'`` drops the batch,
      counts it in ``stats()['skipped']`` and journals a
      ``csr_feed_skipped_batch`` event — never silent.
    io_retries: bounded-backoff retries for transient ``IOError`` /
      ``OSError`` from the source pull or the build (zero data loss on
      a recovered transient; ``resilience.retry_io``).
    retry_base_s: backoff base delay (doubles per retry, capped 2 s).
    max_respawns: how many times a producer thread that DIED without a
      terminal message (e.g. a killed pool worker) is respawned.  The
      in-flight item survives a death during the build or the delivery
      — essentially all of producer wall time — so the stream continues
      with zero loss; a kill landing INSIDE the source pull itself can
      lose at most that one batch (unavoidable for a consuming
      iterator, whose internal state the kill may already have
      advanced).  Each respawn is journaled (``csr_feed_respawn``).

  Iterate it (``for fed in feed:``) or use it as a context manager;
  ``close()`` is idempotent and always drains the producer.
  """

  def __init__(self, dist, source: Iterable,
               cats_fn: Optional[Callable[[Any], List[np.ndarray]]] = None,
               max_ids_per_partition: Optional[Tuple[int, ...]] = None,
               depth: int = 2,
               num_workers: Optional[int] = None,
               native: str = 'auto',
               on_batch_error: str = 'raise',
               io_retries: int = 3,
               retry_base_s: float = 0.05,
               max_respawns: int = 2):
    if depth < 1:
      raise ValueError(f'depth must be >= 1, got {depth}')
    if on_batch_error not in ('raise', 'skip'):
      raise ValueError(
          f"on_batch_error must be 'raise' or 'skip', got {on_batch_error!r}")
    self._dist = dist
    # queue-backed sources surface their depth/drop counters in stats()
    self._queue_source = source if isinstance(source, QueueSource) else None
    self._source = iter(source)
    self._cats_fn = cats_fn if cats_fn is not None else (lambda item: item)
    self._caps = max_ids_per_partition
    self._num_workers = num_workers
    self.builder = sparsecore.resolve_builder(native)
    self._on_batch_error = on_batch_error
    self._io_retries = io_retries
    self._retry_base_s = retry_base_s
    self._max_respawns = max_respawns
    self._ring: queue.Queue = queue.Queue(maxsize=depth)
    self._stop = threading.Event()
    self._closed = False
    # producer delivery state: ONE tuple (next seq to deliver, pulled
    # item or _NO_ITEM), always replaced in a single store — an async
    # kill can land on any bytecode boundary, and a half-updated
    # seq/item pair would lose or mislabel a batch after respawn
    self._cursor = (0, _NO_ITEM)
    self._pending = None   # built message waiting for ring space
    self._pending_terminal = False
    self._last_seq = -1    # last ordinal the consumer returned
    self.reset_stats()
    self._skipped = 0
    self._fast_forwarded = 0
    self._io_retry_count = 0
    self._respawns = 0
    self._thread = self._spawn()

  # ------------------------------------------------------------- producer

  def _spawn(self) -> threading.Thread:
    t = threading.Thread(target=_producer_main, args=(weakref.ref(self),),
                         name='csr-feed-producer', daemon=True)
    t.start()
    return t

  def _retry(self, fn, what: str):
    """Bounded-backoff transient-I/O retry, counting retries into
    ``stats()``."""

    def counting_sleep(d):
      self._io_retry_count += 1
      obs_metrics.inc('feed.io_retries')
      time.sleep(d)

    return resilience.retry_io(fn, retries=self._io_retries,
                               base_delay_s=self._retry_base_s,
                               what=what, sleep=counting_sleep)

  def _produce_unit(self) -> bool:
    """ONE bounded unit of producer work (the trampoline re-derefs the
    feed between units).  Returns False when the producer should exit.

    Delivery state lives on the FEED, not the thread: ``_cursor``
    (next seq + pulled-but-undelivered item, replaced in single
    stores) and ``_pending`` (built, not yet in the ring) survive a
    killed thread, so a respawned producer resumes exactly where its
    predecessor died — zero loss, duplicates fenced by the consumer's
    seq check.  Kill-ordering invariant around a delivery: put, THEN
    advance the cursor, THEN clear pending — a kill between any two of
    those re-delivers a seq the consumer already fenced, never skips
    one."""
    if self._stop.is_set():
      return False
    seq, item = self._cursor
    if self._pending is not None:
      try:
        self._ring.put(self._pending, timeout=0.05)
      except queue.Full:
        return True  # ring full: yield to the trampoline and retry
      terminal = self._pending_terminal
      if not terminal:
        self._cursor = (seq + 1, _NO_ITEM)
      self._pending = None
      return not terminal
    # NOTE the one hole in the zero-loss window: a kill landing between
    # the source pull returning and the cursor store below (or inside
    # the source's own __next__ after it advanced) loses that single
    # batch — nanoseconds against the milliseconds of build time the
    # cursor does protect, and unavoidable for a consuming iterator.
    try:
      if item is _NO_ITEM:
        try:
          # StopIteration passes through retry_io untouched (it is
          # not an I/O error): source exhausted, clean shutdown
          item = self._retry(lambda: next(self._source),
                             'csr-feed source pull')
        except StopIteration:
          self._pending, self._pending_terminal = _Done(), True
          return True
        self._cursor = (seq, item)
      try:
        tok = obs_trace.begin('feed/build', seq=seq)
        try:
          csrs = self._retry(
              lambda: sparsecore.preprocess_batch_host(
                  self._dist, self._cats_fn(item),
                  max_ids_per_partition=self._caps, native=self.builder,
                  num_workers=self._num_workers),
              'csr-feed batch build')
        finally:
          # a FAILED build still emits its span: the retry-inclusive
          # wall of a poison batch is exactly what stall attribution
          # must not lose when the feed misbehaves
          build_ms = obs_trace.end(tok) * 1000.0
        obs_metrics.observe('feed.build_ms', build_ms)
      except Exception as e:  # poison batch (or exhausted retries)
        if self._on_batch_error == 'skip':
          self._skipped += 1
          obs_metrics.inc('feed.skipped')
          resilience.journal('csr_feed_skipped_batch', seq=seq,
                             error=repr(e))
          self._cursor = (seq + 1, _NO_ITEM)
          return True
        raise
      self._pending = _Item(seq, FedBatch(item, csrs, build_ms))
      self._pending_terminal = False
      return True
    except (SystemExit, KeyboardInterrupt, GeneratorExit):
      raise  # abrupt kill: handled by the trampoline (respawnable)
    except BaseException as e:  # surfaces on the consumer's next __next__
      self._pending, self._pending_terminal = _Error(e), True
      return True

  # ------------------------------------------------------------- consumer

  def __iter__(self):
    return self

  def skip_to(self, seq: int) -> int:
    """Fast-forward the consumer past the window ``[next, seq)`` —
    the self-healing skip leg (design §13): after an anomaly rollback
    decides a window of batches is poisoned, the feed's seq fence
    (``_last_seq``) advances so every batch below ``seq`` is discarded
    on delivery, whether it was already built, is in flight on the
    producer's cursor, or gets re-built after a respawn.  No producer
    coordination is needed — delivery-side fencing is exactly the
    mechanism that already de-duplicates respawned batches.  Journals
    ``csr_feed_fast_forward``; returns the number of seqs fenced off
    (0 when ``seq`` is already behind the stream)."""
    fenced = max(0, int(seq) - 1 - self._last_seq)
    if fenced:
      self._last_seq = int(seq) - 1
      self._fast_forwarded += fenced
      resilience.journal('csr_feed_fast_forward', to_seq=int(seq),
                         fenced=fenced)
    return fenced

  def __next__(self) -> FedBatch:
    if self._closed:
      raise StopIteration
    # the wait that followed batch ``after``; ONE measurement feeds the
    # span, the profiler's annotation and the histogram
    tok = obs_trace.begin('feed/wait', after=self._last_seq)
    try:
      msg = self._next_message()
    finally:
      blocked_ms = obs_trace.end(tok) * 1000.0
    obs_metrics.observe('feed.blocked_ms', blocked_ms)
    obs_metrics.inc('feed.batches')
    if self._queue_source is not None:
      obs_metrics.set_gauge('feed.queue_depth', self._queue_source.qsize())
    self._last_seq = msg.seq
    self._overlap.count_batch()
    self._overlap.add_build(msg.fed.build_ms)
    self._overlap.add_blocked(blocked_ms)
    return msg.fed

  def _next_message(self):
    """Block until the ring yields the next undelivered batch; raises
    what the producer raised, ``StopIteration`` at the end."""
    while True:
      try:
        msg = self._ring.get(timeout=0.1)
      except queue.Empty:
        # no message AND no live producer: the thread died without a
        # terminal marker (a killed pool worker).  Respawn it — the
        # in-flight item survived on self._cursor/_pending, so the
        # stream resumes with zero loss — up to max_respawns, then
        # fail loudly.
        if not self._thread.is_alive():
          if self._respawns < self._max_respawns:
            self._respawns += 1
            obs_metrics.inc('feed.respawns')
            resilience.journal('csr_feed_respawn', count=self._respawns,
                               next_seq=self._cursor[0])
            self._thread = self._spawn()
          else:
            self.close()
            raise RuntimeError(
                f'csr-feed producer died {self._respawns + 1} times '
                f'(max_respawns={self._max_respawns} exhausted); see the '
                f'journal at {resilience.journal_path()}')
        continue
      if isinstance(msg, _Done):
        self.close()
        raise StopIteration
      if isinstance(msg, _Error):
        self.close()
        raise msg.exc
      if msg.seq <= self._last_seq:
        continue  # duplicate re-built after a respawn: already delivered
      return msg

  def __enter__(self):
    return self

  def __exit__(self, *exc):
    self.close()
    return False

  def close(self):
    """Stop the producer and drain the ring; idempotent.  Batches
    already built but not consumed are discarded."""
    if self._closed:
      return
    self._closed = True
    self._stop.set()
    while True:  # unblock a producer waiting on a full ring
      try:
        self._ring.get_nowait()
      except queue.Empty:
        break
    # GC can drop the last feed reference inside the producer's own
    # trampoline (running __del__ -> close there): never self-join
    if self._thread is not threading.current_thread():
      self._thread.join(timeout=30.0)

  def __del__(self):
    # an abandoned feed (iterator dropped without drain or close) must
    # not leak a producer blocked forever on the full ring
    try:
      self.close()
    except Exception:
      pass  # interpreter teardown: module globals may be gone

  # ---------------------------------------------------------------- stats

  def reset_stats(self):
    """Zero the overlap accounting — e.g. after the first batch, whose
    build has no prior device step to hide behind, so steady-state
    overlap is reported."""
    # the shared blocked-time primitive (obs/metrics.py OverlapStat):
    # one accounting for CsrFeed, ColdFetchPipeline, and the serving
    # batcher, with this class's pre-existing stats() keys unchanged
    self._overlap = obs_metrics.OverlapStat()

  def stats(self) -> Dict[str, Any]:
    """Overlap accounting since the last ``reset_stats()``.

    ``build_ms`` is the total wall time the workers spent building the
    consumed batches; ``blocked_ms`` is the total time ``__next__``
    waited for a build — i.e. host build time NOT hidden behind the
    device step.  ``overlap_pct`` = share of build time hidden.

    The resilience counters are feed-lifetime (NOT zeroed by
    ``reset_stats``, which only re-bases the overlap accounting):
    ``skipped`` poison batches dropped under ``on_batch_error='skip'``,
    ``io_retries`` transient-I/O retries taken, ``respawns`` producer
    threads respawned after a worker death."""
    ov = self._overlap
    pct = ov.overlap_pct()
    out = {
        'batches': ov.batches,
        'build_ms': round(ov.build_ms, 3),
        'blocked_ms': round(ov.blocked_ms, 3),
        'overlap_pct': (round(pct, 1) if pct is not None else None),
        'builder': self.builder,
        'skipped': self._skipped,
        'fast_forwarded': self._fast_forwarded,
        'io_retries': self._io_retry_count,
        'respawns': self._respawns,
    }
    if self._queue_source is not None:
      # in-memory queue source (serving batcher): live depth + batches
      # shed by non-blocking puts against the full bound
      out['queue_depth'] = self._queue_source.qsize()
      out['queue_dropped'] = self._queue_source.dropped
    return out
