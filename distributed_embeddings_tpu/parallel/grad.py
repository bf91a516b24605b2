"""Hybrid data+model-parallel training glue.

TPU-native re-design of the reference's Horovod monkey-patches
(`dist_model_parallel.py:678-736`, SURVEY.md C18).  Under XLA SPMD the two
jobs those patches do happen automatically, which is the point of the
re-design (SURVEY.md §2.4 "TPU-native equivalent"):

- ``hvd.broadcast_variables`` synchronised initial DP weights across
  processes; JAX initialises from one key on one logical program, so
  replicated params are bit-identical by construction.
- ``DistributedGradientTape`` allreduced DP grads and locally scaled MP
  grads; with a global-mean loss under `jit` over the mesh, XLA inserts the
  psum for replicated (DP) params and keeps sharded (MP, embedding) grads
  local — exactly the reference's split, derived instead of hand-routed.

The 3-line-change API surface is preserved so reference users find the same
names; ``make_train_step`` is the idiomatic entry point.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distributed_embeddings_tpu.analysis import commsan
from distributed_embeddings_tpu.obs import metrics as obs_metrics
from distributed_embeddings_tpu.obs import trace as obs_trace
from distributed_embeddings_tpu.parallel import mesh as mesh_lib
from distributed_embeddings_tpu.parallel.coldtier import TierIntegrityError
from distributed_embeddings_tpu.utils import resilience

ANOMALY_POLICIES = (None, 'terminate', 'rollback', 'rollback_skip')


class _Anomaly(Exception):
  """Internal control flow of ``fit``'s anomaly policy: a detected
  anomaly unwinds to the policy handler, which terminates or rolls
  back in-process."""

  def __init__(self, kind: str, step: int, detail: str = ''):
    self.kind = kind
    self.step = int(step)
    self.detail = detail
    super().__init__(f'{kind} at step {step}: {detail}')


def broadcast_variables(params, root_rank: int = 0):
  """No-op parity shim for ``dmp.broadcast_variables``
  (dist_model_parallel.py:678-692).

  The reference broadcasts data-parallel variables from ``root_rank`` after
  step 0 and skips model-parallel (``de_local``) ones.  JAX SPMD params are
  created consistently from the PRNG key on every host, so there is nothing
  to synchronise; the function exists so ported training loops keep working.
  """
  del root_rank
  return params


class DistributedGradientTape:
  """Parity shim for ``dmp.DistributedGradientTape``
  (dist_model_parallel.py:695-736).

  The reference patches Horovod's tape so DP grads get allreduce(Average)
  and MP grads get a local 1/world_size scale.  In JAX, take gradients of a
  *global mean* loss under `jit` over the mesh and both happen inside XLA.
  This class wraps a loss function to provide a tape-like ``gradient`` call
  for ported code.
  """

  def __init__(self, loss_fn: Callable):
    self._loss_fn = loss_fn

  def gradient(self, params, *args, **kwargs):
    return jax.grad(self._loss_fn)(params, *args, **kwargs)


class TrainState(NamedTuple):
  params: Any
  opt_state: Any
  step: jax.Array


def make_train_step(loss_fn: Callable,
                    optimizer,
                    donate: bool = True) -> Callable:
  """Build a jitted hybrid-parallel train step.

  Args:
    loss_fn: ``loss_fn(params, batch) -> scalar`` where the scalar is a
      *global* mean over the batch.  Embedding params inside ``params`` are
      mesh-sharded, dense params replicated; XLA derives DP averaging and
      local MP grads from the shardings (replacing the reference's
      ``DistributedGradientTape`` routing).
    optimizer: an optax ``GradientTransformation``.
    donate: donate state buffers (in-place update, halves HBM).

  Returns:
    ``step(state: TrainState, batch) -> (TrainState, loss)``.
  """

  def step(state: TrainState, batch):
    loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
    updates, opt_state = optimizer.update(grads, state.opt_state,
                                          state.params)
    params = jax.tree.map(lambda p, u: p + u.astype(p.dtype), state.params,
                          updates)
    return TrainState(params, opt_state, state.step + 1), loss

  return jax.jit(step, donate_argnums=(0,) if donate else ())


def init_train_state(params, optimizer) -> TrainState:
  return TrainState(params=params,
                    opt_state=optimizer.init(params),
                    step=jnp.zeros((), jnp.int32))


def fit(step_fn: Callable,
        state: TrainState,
        data,
        steps: Optional[int] = None,
        *,
        log_every: int = 100,
        eval_fn: Optional[Callable] = None,
        eval_every: Optional[int] = None,
        callbacks=(),
        verbose: bool = True,
        print_fn: Callable = print,
        resume_from: Optional[str] = None,
        dist=None,
        terminate_on_nan: bool = False,
        step_timeout_s: Optional[float] = None,
        on_anomaly: Optional[str] = None,
        rollback_dir: Optional[str] = None,
        rollback_budget: int = 3,
        data_factory: Optional[Callable] = None,
        auditor=None,
        spike_zscore: Optional[float] = None,
        spike_warmup: int = 10):
  """Keras-``fit``-like driver for the train steps built here.

  The reference's integration test trains its distributed layer through
  plain ``model.fit``
  (`/root/reference/distributed_embeddings/python/layers/
  dist_model_parallel_test.py:303-335`); its DLRM example hand-rolls the
  same loop (`examples/dlrm/main.py:201-210`).  This is that driver for the
  functional steps: iterate, keep losses on-device between log points (one
  host sync per ``log_every``, not per step), run periodic eval, invoke
  callbacks — while the state stays an explicit value the caller owns.

  Args:
    step_fn: from ``make_train_step`` / ``make_hybrid_train_step`` — called
      as ``step_fn(state, *batch_args)``.
    state: initial ``TrainState``.
    data: iterable yielding per-step *argument tuples* (everything after
      ``state``): ``(batch,)`` for ``make_train_step``, ``(cats, batch)``
      for the hybrid step.
    steps: stop after this many steps (``None`` drains ``data``).
    log_every: steps between loss syncs / history entries / callbacks.
    eval_fn: optional ``eval_fn(state) -> dict`` of python metrics.
    eval_every: steps between ``eval_fn`` calls (default: ``log_every``).
    callbacks: callables ``cb(step: int, state, logs: dict)`` run at every
      log/eval point (mutating ``logs`` is allowed; e.g. early stopping by
      raising ``StopIteration``).
    verbose: print one line per log point via ``print_fn``.
    resume_from: a resumable checkpoint ``.npz`` path or a checkpoint
      DIRECTORY (newest valid file wins; corrupt/plan-mismatched files
      are rejected with a journaled reason —
      ``checkpoint.load_latest_valid``).  Restores params + optimizer
      state + step into ``state`` via ``checkpoint.restore_train_state``
      and continues bit-exactly; the step counter resumes, so ``steps``
      keeps meaning the TOTAL step budget and ``data`` must be
      positioned at the first un-trained batch (deterministic sources:
      skip ``int(state.step)`` batches).  Requires ``dist``.
    dist: the model's ``DistributedEmbedding`` (needed only with
      ``resume_from`` — it defines the resharding layout).
    terminate_on_nan: DEPRECATED alias for ``on_anomaly='terminate'``
      (kept so existing callers work unchanged; the journal event name
      ``terminate_on_nan`` is also kept).  Without any anomaly policy a
      NaN flows through silently AND defeats ``EarlyStopping`` (NaN
      comparisons are always False, so ``patience`` never fires).
    on_anomaly: the self-healing policy (docs/design.md §13).  An
      ANOMALY is any of: a non-finite loss in a log window; a loss
      spike past the EMA z-score gate (``spike_zscore``); a failed
      state-integrity audit (``auditor``); a host-tier integrity error
      raised by the step (``coldtier.TierIntegrityError``).  Every
      detection journals ``anomaly_detected`` and lands in
      ``history['anomalies']``.  Policies:

      - ``None`` (default): no detection — pre-§13 behaviour.
      - ``'terminate'``: stop the run with a journaled reason (the
        promoted ``terminate_on_nan``; non-finite-loss terminations
        keep that legacy event name and ``history`` key).
      - ``'rollback'``: restore the newest VALID checkpoint under
        ``rollback_dir`` IN-PROCESS (``restore_train_state`` with
        quarantine: corrupt candidates are renamed ``*.corrupt``,
        never deleted, and excluded from later scans), reposition the
        input at the restored step via ``data_factory`` and retry the
        same window — for transient state corruption (SDC), the replay
        is bit-exact vs an undisturbed run.
      - ``'rollback_skip'``: like ``'rollback'``, but the input
        fast-forwards PAST the offending window ``(ckpt_step,
        detect_step]`` (journaled ``skip_window``) — for poison data
        that would re-trigger on replay (feed-driven loops fence the
        same window with ``CsrFeed.skip_to``).

      Each run takes at most ``rollback_budget`` rollbacks; the next
      anomaly past the budget journals ``rollback_budget_exhausted``
      and terminates — a persistent fault must page a human, not loop.
      After a rollback the log/eval history simply continues (steps in
      the replayed window appear twice, annotated by the journal).
    rollback_dir: checkpoint directory the rollback policies scan
      (normally the same directory a ``CheckpointCallback`` in
      ``callbacks`` writes; retention never prunes the newest verified
      file or an in-flight rollback target).
    rollback_budget: max in-process rollbacks per ``fit`` call.
    data_factory: ``step -> iterable`` positioned at the batch that
      trains ``step + 1`` (deterministic sources:
      ``lambda s: iter(batches[s:])``; feed-driven loops can combine a
      fresh reader with ``CsrFeed.skip_to``).  Required by the
      rollback policies — a bare iterator cannot rewind.
    auditor: a ``parallel.audit.StateAuditor``; ``fit`` calls
      ``auditor.check_state(state)`` every ``auditor.every`` steps
      (before the same step's log-point callbacks, so a failing audit
      blocks the checkpoint that would have persisted the damage) and
      feeds any finding into the anomaly policy.
    spike_zscore: arm the EMA z-score loss-spike gate
      (``audit.LossSpikeGate``) at this threshold; ``spike_warmup``
      observations train the gate before it can fire.  Spikes journal
      through ``anomaly_detected`` with ``kind='loss_spike'``.
    step_timeout_s: hung-device-step watchdog — every step dispatch and
      every log-point device sync runs under this timeout (mirroring
      bench.py's 180 s backend-probe guard: a downed TPU backend makes
      syncs HANG, not raise).  On expiry: all-thread tracebacks dump to
      stderr, a ``watchdog_fired`` event is journaled, and
      ``resilience.StepHangError`` is raised — failing an unattended
      window fast instead of wedging it.  Must exceed the worst-case
      XLA compile of the first step.  ``None`` (default) adds zero
      overhead; when set, each dispatch pays one watchdog thread
      (~0.1 ms) — the cost of catching HOST-side hangs (a wedged feed
      or loader inside ``step_fn``), which never reach the guarded
      sync point; negligible against real device steps, but don't arm
      it for microbenchmarks.

  Returns:
    ``(state, history)`` — ``history['step']`` / ``history['loss']`` hold
    one entry per log point; eval metrics land in their own lists aligned
    with ``history['eval_step']`` (eval cadence can differ from the log
    cadence).  An eval metric named like a reserved train series
    (``step`` / ``loss`` / ``eval_step``) is namespaced to ``eval_<name>``
    instead of corrupting that series' alignment.
  """
  eval_every = eval_every or log_every
  if on_anomaly not in ANOMALY_POLICIES:
    raise ValueError(f'on_anomaly must be one of {ANOMALY_POLICIES}, '
                     f'got {on_anomaly!r}')
  if on_anomaly is None and (terminate_on_nan or auditor is not None
                             or spike_zscore is not None):
    # terminate_on_nan is the deprecated alias of the policy; an armed
    # detector (auditor / spike gate) without an explicit policy
    # defaults to the conservative one
    on_anomaly = 'terminate'
  if on_anomaly in ('rollback', 'rollback_skip'):
    if dist is None or rollback_dir is None:
      raise ValueError(
          f'fit(on_anomaly={on_anomaly!r}) needs rollback_dir= (the '
          'checkpoint directory to restore from — normally where a '
          'CheckpointCallback in callbacks= writes) and dist= (the '
          'DistributedEmbedding defining the resharding layout)')
    if data_factory is None:
      raise ValueError(
          f'fit(on_anomaly={on_anomaly!r}) needs data_factory= — a '
          'callable step -> iterable positioned at the batch that '
          'trains step+1 (deterministic sources: '
          'lambda s: iter(batches[s:])); a bare iterator cannot be '
          'rewound after a rollback')
  gate = None
  if spike_zscore is not None:
    from distributed_embeddings_tpu.parallel.audit import LossSpikeGate
    gate = LossSpikeGate(zscore=spike_zscore, warmup=spike_warmup)
  _RESERVED = ('step', 'loss', 'eval_step')
  history: dict = {'step': [], 'loss': [], 'eval_step': []}
  window = []  # on-device losses since the last sync
  i = 0
  it = iter(data) if data is not None else None
  if resume_from is not None:
    if dist is None:
      raise ValueError('fit(resume_from=...) needs dist= (the '
                       'DistributedEmbedding defining the resharding '
                       'layout)')
    from distributed_embeddings_tpu.parallel.checkpoint import (
        restore_train_state)
    state, ckpt_path = restore_train_state(dist, state, resume_from)
    i = int(state.step)
    if verbose:
      print_fn(f'resumed from {ckpt_path} at step {i}')
  if it is None:
    if data_factory is None:
      raise ValueError('fit() needs data= or data_factory=')
    it = iter(data_factory(i))
  last_eval_at = None  # step of the last eval: the exit flush must not
  #                      re-eval a state already evaluated at this step

  def sync_window(i):
    """Host-sync the loss window — THE blocking point where a wedged
    device program manifests, so the watchdog lives here (and around
    each dispatch below).  The obs 'train/sync' span records exactly
    this wait: host time blocked on the device, the per-window stall
    the trace report attributes (docs/design.md §15)."""
    stacked = jnp.stack(window)
    window.clear()
    tok = obs_trace.begin('train/sync', step=i)
    try:
      if step_timeout_s is None:
        host = np.asarray(stacked)
      else:
        host = resilience.call_with_timeout(
            lambda: np.asarray(jax.block_until_ready(stacked)),
            step_timeout_s, what=f'device-step sync at step {i}')
    finally:
      sync_s = obs_trace.end(tok)
    obs_metrics.observe('train.sync_ms', sync_s * 1000.0)
    return host

  def flush(i, final=False):
    nonlocal last_eval_at
    if not window and not final:
      return None
    logs = {}
    if window:
      n_window = len(window)
      host = sync_window(i)
      if on_anomaly is not None:
        # scan the window in step order: the FIRST anomalous value
        # names the offending step (non-finite beats spike; a healthy
        # value trains the spike gate's EMA)
        for j, v in enumerate(host):
          step_j = i - n_window + j + 1
          if not np.isfinite(v):
            raise _Anomaly('non_finite_loss', step_j, repr(v))
          if gate is not None:
            z = gate.observe(float(v))
            if z is not None:
              raise _Anomaly(
                  'loss_spike', step_j,
                  f'loss={float(v):.6g} zscore={z:.2f} '
                  f'(gate {gate.zscore:g})')
      mean = float(host.mean())
      logs['loss'] = mean
      history['step'].append(i)
      history['loss'].append(mean)
      obs_metrics.set_gauge('train.loss', mean)
      # periodic registry snapshot through the resilience journal —
      # one jsonl line per log point when the registry is armed, ZERO
      # writes when it is not (design §15 disabled-path guarantee)
      obs_metrics.journal_snapshot(step=i)
    # final covers both exits (steps reached, data drained): the run always
    # ends with an eval of the returned state — even when the iterator
    # drained exactly at a log boundary and the loss window is empty
    if (eval_fn is not None and (i % eval_every == 0 or final)
        and last_eval_at != i):
      evals = eval_fn(state)
      history['eval_step'].append(i)
      for k, v in evals.items():
        kk = 'eval_' + k if k in _RESERVED else k
        logs[kk] = v
        history.setdefault(kk, []).append(v)
      last_eval_at = i
    if not logs:
      return None
    if verbose:
      print_fn('step %d: ' % i +
               ' '.join(f'{k}={v:.6g}' for k, v in logs.items()))
    for cb in callbacks:
      cb(i, state, logs)
    return logs

  rollbacks = 0

  def handle_anomaly(a: _Anomaly) -> bool:
    """Apply the on_anomaly policy to one detection.  Returns True
    after an in-process rollback (training continues), False when the
    run must terminate (reason printed + journaled)."""
    nonlocal state, i, it, rollbacks, last_eval_at
    obs_metrics.inc('train.anomalies')
    resilience.journal('anomaly_detected', anomaly=a.kind,
                       step=a.step, policy=on_anomaly, detail=a.detail)
    history.setdefault('anomalies', []).append(
        {'kind': a.kind, 'step': a.step})
    if on_anomaly == 'terminate':
      if a.kind == 'non_finite_loss':
        # the promoted legacy guard: same journal event name and
        # history key, so pre-§13 callers/tests see identical behaviour
        resilience.journal('terminate_on_nan', step=a.step,
                           loss=a.detail)
        history['terminated_on_nan'] = a.step
        print_fn(f'terminate_on_nan: non-finite loss at step {a.step}; '
                 'stopping (event journaled to '
                 f'{resilience.journal_path()})')
      else:
        history['terminated_on_anomaly'] = a.step
        print_fn(f'on_anomaly=terminate: {a.kind} at step {a.step}; '
                 f'stopping ({a.detail})')
      return False
    if rollbacks >= rollback_budget:
      resilience.journal('rollback_budget_exhausted',
                         budget=rollback_budget, step=a.step,
                         anomaly=a.kind)
      history['terminated_on_anomaly'] = a.step
      history['rollback_budget_exhausted'] = True
      print_fn(f'on_anomaly={on_anomaly}: {a.kind} at step {a.step} '
               f'but the rollback budget ({rollback_budget}) is '
               'exhausted; escalating to termination — a persistent '
               'fault needs a human, not a retry loop')
      return False
    from distributed_embeddings_tpu.parallel.checkpoint import (
        restore_train_state)
    try:
      state, path = restore_train_state(dist, state, rollback_dir,
                                        quarantine=True)
    except (FileNotFoundError, ValueError) as e:
      resilience.journal('rollback_failed', step=a.step,
                         anomaly=a.kind, error=str(e))
      history['terminated_on_anomaly'] = a.step
      print_fn(f'on_anomaly={on_anomaly}: {a.kind} at step {a.step} '
               f'and no valid checkpoint to roll back to ({e}); '
               'terminating')
      return False
    rollbacks += 1
    obs_metrics.inc('train.rollbacks')
    to_step = int(state.step)
    detect_at = i
    window.clear()
    last_eval_at = None  # replayed steps re-evaluate
    resilience.journal('rollback', anomaly=a.kind, detect_step=a.step,
                       at_step=detect_at, to_step=to_step, path=path,
                       attempt=rollbacks, policy=on_anomaly)
    commsan.record('fit/rollback', anomaly=a.kind, to_step=to_step,
                   attempt=rollbacks)
    if on_anomaly == 'rollback_skip' and detect_at > to_step:
      # fast-forward past the offending window: batches (to_step,
      # detect_at] never replay (poison data would re-trigger)
      resilience.journal('skip_window', from_step=to_step,
                         to_step=detect_at,
                         batches=detect_at - to_step)
      commsan.record('fit/skip_window', from_step=to_step,
                     to_step=detect_at)
      it = iter(data_factory(detect_at))
    else:
      it = iter(data_factory(to_step))
    i = to_step
    if verbose:
      print_fn(f'rollback: {a.kind} at step {a.step} -> restored '
               f'{path} at step {to_step} (attempt '
               f'{rollbacks}/{rollback_budget}'
               + (', input fast-forwarded past the offending window'
                  if on_anomaly == 'rollback_skip' else '') + ')')
    return True

  try:
    while True:
      try:
        while steps is None or i < steps:
          try:
            args = next(it)
          except StopIteration:
            break
          # 'train/step' wraps the DISPATCH (async under jit: tracing +
          # compile on the first call, enqueue after); the device wall
          # it hides shows up in the log point's 'train/sync' span
          with obs_trace.span('train/step', step=i + 1):
            if step_timeout_s is not None:
              state, loss = resilience.call_with_timeout(
                  lambda s=state, a=args: step_fn(s, *a),
                  step_timeout_s, what=f'train step dispatch at step {i}')
            else:
              state, loss = step_fn(state, *args)
          obs_metrics.inc('train.steps')
          commsan.record('fit/step', step=i + 1)
          window.append(loss)
          i += 1
          if auditor is not None and i % auditor.every == 0:
            # audit BEFORE this step's log point, so a failing state
            # never reaches the checkpoint callback that would have
            # persisted the damage
            findings = auditor.check_state(state, step=i)
            if findings:
              raise _Anomaly(
                  'audit_failure', i,
                  '; '.join(f.brief() for f in findings[:3]))
          if i % log_every == 0:
            flush(i, final=(steps == i))
        flush(i, final=True)
        break
      except _Anomaly as a:
        if not handle_anomaly(a):
          break
      except TierIntegrityError as e:
        if on_anomaly is None:
          raise
        if not handle_anomaly(_Anomaly('tier_integrity', i, str(e))):
          break
  except StopIteration:  # raised by a callback: early stop
    pass
  return state, history
