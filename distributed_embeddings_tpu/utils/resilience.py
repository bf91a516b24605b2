"""Fault-tolerance primitives shared by the training runtime.

Three small tools the robustness layer (checkpoint integrity, resilient
input pipeline, step watchdog — docs/userguide.md "Fault tolerance")
builds on:

- ``journal(kind, **fields)``: append-only jsonl event log.  Every
  degraded-mode decision the runtime takes (a rejected checkpoint, a
  skipped poison batch, an I/O retry, a watchdog fire) lands here with
  its reason, so an unattended multi-hour run leaves evidence instead
  of a mystery (VERDICT Weak #1: two rounds of artifacts misled for
  operational reasons).  The sink is ``DET_FT_JOURNAL`` (default
  ``/tmp/det_ft_journal.jsonl``); a bounded in-memory ring
  (``recent()``) backs the tests and never depends on the filesystem.
- ``retry_io(fn, ...)``: bounded exponential backoff around a
  transient-I/O-prone call.  The reference leaned on TF's checkpoint /
  ``tf.data`` retry machinery (SURVEY §2); this is the JAX rewrite's
  native equivalent for the raw-binary loader and the CSR feed.
- ``call_with_timeout(fn, ...)``: run a blocking call on a watchdog
  thread and fail FAST with thread dumps when it wedges (a backend
  that dies mid-run makes device syncs hang rather than raise),
  applied to the device-step sync inside ``fit``/bench.
"""

from __future__ import annotations

import collections
import errno as _errno
import faulthandler
import json
import os
import sys
import threading
import time

from typing import Any, Callable, Dict, List, Optional, Tuple, Type

_JOURNAL_ENV = 'DET_FT_JOURNAL'
_DEFAULT_JOURNAL = '/tmp/det_ft_journal.jsonl'
_RING_CAP = 256

# The complete journal-event schema.  Every ``journal(...)`` call site in
# the runtime must use one of these names (pinned by
# tests/test_fault_tolerance.py test_journal_event_names_registered):
# stringly-typed scattered names caused two classes of bug before this
# registry — a dashboard filtering on a misspelled kind silently shows
# nothing, and a renamed event orphans every consumer.  Add the name
# HERE in the same change that introduces the call site.
REGISTERED_EVENTS = frozenset({
    # transient-I/O retry (retry_io)
    'io_retry', 'io_retry_exhausted',
    # step watchdog (call_with_timeout); on_timeout_error: the caller's
    # extra-diagnostics hook itself failed (detlint concurrency pass —
    # a swallowed hook failure must leave evidence, design §17)
    'watchdog_fired', 'watchdog_on_timeout_error',
    # input pipeline (parallel/csr_feed.py)
    'csr_feed_skipped_batch', 'csr_feed_respawn', 'csr_feed_fast_forward',
    # native-builder degradation (parallel/sparsecore.py)
    'csr_native_fallback',
    # checkpoint integrity + retention (parallel/checkpoint.py)
    'checkpoint_rejected', 'checkpoint_pruned', 'checkpoint_quarantined',
    'resume',
    # anomaly policy (parallel/grad.py fit on_anomaly; design §13)
    'terminate_on_nan', 'anomaly_detected', 'rollback', 'rollback_failed',
    'rollback_budget_exhausted', 'skip_window',
    # state-integrity auditor (parallel/audit.py + coldtier.py)
    'audit_failure', 'tier_integrity_failure',
    # observability layer (obs/metrics.py periodic registry snapshots)
    'metrics_snapshot',
    # device-time attribution (obs/devprof.py, design §19): one event
    # per profile run with the per-phase device ms + cost cross-check
    'devprof_profile',
    # longitudinal perf sentinel (tools/perf_sentinel.py, design §19):
    # one event per flagged regression with key/delta/baseline sha
    'perf_regression',
    # hierarchical DCNxICI exchange cost model (parallel/planner.py
    # ExchangeCostModel, design §20): one event per planning run with
    # the priced per-axis exchange bytes and the DCN:ICI ratio used
    'exchange_cost_model',
    # wire-dtype compression (parallel/planner.py reconcile_exchange,
    # design §24): priced capacity bytes vs the traced plan's counted
    # on-wire leg bytes, per axis, at the layer's wire dtype; and the
    # bench/dryrun off-vs-on wire A/B with measured bytes + parity
    # drift (bench.py --wire_ab)
    'exchange_reconciliation', 'wire_ab',
    # runtime rendezvous sanitizer (analysis/commsan.py, design §22):
    # one digest event per barrier check inside a capture window, one
    # mismatch event per divergence witness raised at a barrier
    'commsan_digest', 'commsan_mismatch',
    # SLO-aware serving overload layer (serving/batcher.py +
    # serving/pool.py, design §23): throttled per-shed evidence, the
    # per-class admission ledger at close, replica
    # quarantine/failover, and the degraded-mode watermark crossings
    'serve_shed', 'serve_admission', 'serve_replica_quarantined',
    'serve_failover', 'serve_degraded_enter', 'serve_degraded_exit',
})

_lock = threading.Lock()
_ring: collections.deque = collections.deque(maxlen=_RING_CAP)


def journal_path() -> str:
  return os.environ.get(_JOURNAL_ENV, _DEFAULT_JOURNAL)


def journal(kind: str, **fields) -> Dict[str, Any]:
  """Record one fault-tolerance event: append a jsonl line to
  ``journal_path()`` (best-effort — the journal must never take the
  run down with it) and to the in-memory ring.  Returns the event.

  Runtime call sites must use a name from ``REGISTERED_EVENTS`` (the
  schema consumers filter on; enforced by a source-scan test) — the
  function itself stays permissive so a user extension can journal its
  own kinds without touching this module."""
  event = {'kind': kind, 'ts': time.time(), **fields}
  with _lock:
    _ring.append(event)
  try:
    line = json.dumps(event, default=str)
    with open(journal_path(), 'a', encoding='utf-8') as f:
      f.write(line + '\n')
  except (OSError, TypeError, ValueError):
    pass
  return event


def recent(kind: Optional[str] = None) -> List[Dict[str, Any]]:
  """Events recorded this process (newest last), optionally filtered by
  kind — the test-facing view of the journal."""
  with _lock:
    events = list(_ring)
  return [e for e in events if kind is None or e['kind'] == kind]


def clear_recent():
  with _lock:
    _ring.clear()


# --------------------------------------------------------------------------
# transient-I/O retry
# --------------------------------------------------------------------------

RETRYABLE_IO = (IOError, OSError)  # IOError is an OSError alias since 3.3;
#                                    both named for reader clarity

# errno classes that can never succeed on retry — a missing file, a bad
# descriptor, or a permission wall fails identically 4 times while
# burning the backoff budget and flooding the journal with io_retry
# events that were never recoverable.  Errors WITHOUT an errno (e.g. a
# short-read IOError raised by our own readers) stay retryable: on a
# flaky mount a short read IS the transient signature.
PERMANENT_ERRNOS = frozenset({
    _errno.ENOENT, _errno.EACCES, _errno.EPERM, _errno.EBADF,
    _errno.EISDIR, _errno.ENOTDIR, _errno.EROFS, _errno.ENOSPC,
})


def retry_io(fn: Callable[[], Any],
             *,
             retries: int = 3,
             base_delay_s: float = 0.05,
             max_delay_s: float = 2.0,
             retry_on: Tuple[Type[BaseException], ...] = RETRYABLE_IO,
             what: str = 'io',
             sleep: Callable[[float], None] = time.sleep):
  """Call ``fn`` with bounded exponential backoff on transient errors.

  Attempt k (0-based) failing with one of ``retry_on`` sleeps
  ``min(base_delay_s * 2**k, max_delay_s)`` and retries, up to
  ``retries`` retries (``retries + 1`` attempts total); each retry is
  journaled (``io_retry``) so recovered transients are visible, never
  silent.  The final failure journals ``io_retry_exhausted`` and
  re-raises the last error unchanged.  ``OSError``s whose errno marks a
  deterministic failure (``PERMANENT_ERRNOS``: missing file, bad fd,
  permissions, ...) re-raise immediately — retrying them only delays
  the inevitable and pollutes the journal.
  """
  last: Optional[BaseException] = None
  for attempt in range(retries + 1):
    try:
      return fn()
    except retry_on as e:  # noqa: PERF203 — the loop IS the feature
      last = e
      if (isinstance(e, OSError)
          and getattr(e, 'errno', None) in PERMANENT_ERRNOS):
        raise
      if attempt >= retries:
        journal('io_retry_exhausted', what=what, attempts=attempt + 1,
                error=repr(e))
        raise
      delay = min(base_delay_s * (2 ** attempt), max_delay_s)
      journal('io_retry', what=what, attempt=attempt + 1,
              delay_s=round(delay, 4), error=repr(e))
      sleep(delay)
  raise last  # unreachable; keeps type-checkers honest


# --------------------------------------------------------------------------
# hang watchdog
# --------------------------------------------------------------------------


class StepHangError(RuntimeError):
  """A blocking call (typically a device-step sync) exceeded its
  watchdog timeout; diagnostics were dumped and journaled."""


def dump_diagnostics(what: str, stream=None):
  """Dump all-thread tracebacks (the primary evidence for a wedged
  device sync) to ``stream`` (default stderr); best-effort."""
  stream = stream if stream is not None else sys.stderr
  try:
    print(f'--- watchdog diagnostics: {what} ---', file=stream, flush=True)
    faulthandler.dump_traceback(file=stream, all_threads=True)
  except Exception:  # diagnostics must never mask the timeout itself
    pass


def call_with_timeout(fn: Callable[[], Any],
                      timeout_s: float,
                      what: str = 'blocking call',
                      on_timeout: Optional[Callable[[], None]] = None):
  """Run ``fn`` on a daemon thread; join with ``timeout_s``.

  On timeout: dump all-thread tracebacks, journal a ``watchdog_fired``
  event, run ``on_timeout`` (extra caller diagnostics) and raise
  ``StepHangError`` — failing the run FAST instead of wedging an
  unattended window (the bench's no-artifact failure mode).  The hung
  worker thread is daemonic and abandoned; the process is expected to
  exit on this error.  On normal completion the result (or the
  original exception) propagates unchanged.
  """
  result: list = []
  error: list = []

  def run():
    try:
      result.append(fn())
    except BaseException as e:  # re-raised on the caller thread
      error.append(e)

  t = threading.Thread(target=run, name=f'watchdog:{what}', daemon=True)
  t.start()
  t.join(timeout=timeout_s)
  if t.is_alive():
    dump_diagnostics(what)
    journal('watchdog_fired', what=what, timeout_s=timeout_s)
    if on_timeout is not None:
      try:
        on_timeout()
      except Exception as e:
        # the hook must never mask the timeout, but its failure is
        # evidence too — journaled, never silent (detlint
        # concurrency/silent-except)
        journal('watchdog_on_timeout_error', what=what, error=repr(e))
    raise StepHangError(
        f'{what} exceeded the {timeout_s:g}s watchdog timeout; '
        'all-thread tracebacks dumped to stderr and the event journaled '
        f'({journal_path()})')
  if error:
    raise error[0]
  return result[0]
