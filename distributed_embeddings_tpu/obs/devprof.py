"""Per-phase device-time attribution: the segmented-dispatch profiler
(docs/design.md §19).

The §15 host tracer times host code, so the obs file's critical path
ends at an unattributed remainder of "device + untraced host".  (The
device time of the step that RUNS is read from the profiler's trace by
phase: ``obs.trace.phase`` + ``tools/trace_report.py --profile``.)
This module predates that and estimates the device side from outside
the step: it runs the real step's phases as
INDIVIDUALLY SYNCED sub-programs on the live backend (emulation/XLA on
this host, the same programs on TPU) and attributes per-phase device
milliseconds:

- ``dev/fwd/exchange``   — the dp->mp id exchange + row-return a2a
  pair alone (``overlap.build_exchange_program``, real ids, real
  bytes), directly measured.
- ``dev/fwd/lookup_combine`` — the lookup-only forward
  (``DistributedEmbedding.compile_lookup``) minus the exchange
  program: derived as the difference of two synced sub-programs.
- ``dev/bwd/exchange``   — the cotangent-shaped row a2a alone
  (``build_exchange_program(rows_only=True)``), directly measured.
- ``dev/bwd/grad``       — forward+backward (``forward_with_residuals``
  + ``backward_to_mp`` under one jit, output-dependent cotangents so
  the forward cannot fold away) minus forward minus the backward
  exchange: derived.
- ``dev/apply/update``   — ``sparse_apply_updates`` alone on concrete
  residual/grad streams captured from the forward+backward program,
  directly measured.
- ``dev/serve/execute``  — the serving engine's compiled lookup per
  ladder rung (``profile_serving``), directly measured.

Honesty contract (design §19): this is SEGMENTED-DISPATCH attribution,
not a hardware profile — each phase is a real sub-program of the step
synced on its own, so derived phases are differences of synced walls
(floored at 0) and the whole-step coverage
(``sum(phases) / step_ms``) is journaled so segmentation drift is
visible.  The per-program XLA cost model
(``analysis.graphlint.cost_estimate`` over the SAME compiled
executables — one trace per program, reused for timing and harvest)
rides alongside and the nested-prefix contract (forward ⊆
forward+backward ⊆ step must be byte-monotone) is checked on every
profile.  devprof is OPT-IN and never runs inside a measured headline
window (bench arms it after the timed loops; the §15
``obs_overhead_pct`` disabled-path bar is untouched).

Results emit as ``ph='X'`` events on the dedicated 'device' track
(``obs.trace.device_tid``), journal as one ``devprof_profile`` event,
and feed the registered ``devprof.*`` metrics — so ``trace_report``
grows a device lane and the critical path's unattributed remainder
splits into device-attributed vs residue.
"""

from __future__ import annotations

import dataclasses
import time

from typing import Any, Dict, List, Optional

from distributed_embeddings_tpu.obs import metrics as obs_metrics
from distributed_embeddings_tpu.obs import trace as obs_trace
from distributed_embeddings_tpu.utils import resilience

# ordered phase names of the training step's device lane (the serving
# lane adds dev/serve/execute per rung)
STEP_PHASES = ('dev/fwd/exchange', 'dev/fwd/lookup_combine',
               'dev/bwd/exchange', 'dev/bwd/grad', 'dev/apply/update')

# dcn/ici sub-lanes of the two exchange phases under hierarchical
# (dcn x data)-product sharding (design §20).  They SEGMENT the parent
# phases rather than extend them — their ms nest inside the exchange
# walls, never add to coverage — so flat profiles keep the exact
# STEP_PHASES surface.  The ici lane is the directly measured ICI-only
# twin program (``build_exchange_program(dcn_leg=False)``); the dcn
# lane is the synced-wall remainder of the full exchange, floored at 0.
DCN_LANES = ('dev/fwd/exchange/ici', 'dev/fwd/exchange/dcn',
             'dev/bwd/exchange/ici', 'dev/bwd/exchange/dcn')

# nested-prefix byte slack: the cost-model BYTES-ACCESSED totals of
# fwd <= fwd+bwd <= step may wobble by backend bookkeeping (fusion
# boundaries shift a few percent); a violation past this factor means
# the segmentation no longer nests (a profiler bug, not noise).  Bytes
# carry the contract because these programs are memory-bound
# (PAPERS.md) and byte totals track program containment; post-opt FLOP
# counts are fusion-dependent and MEASURED to invert 10x across
# program boundaries on the tiny model — they ride the harvest
# unjudged.
_COST_TOL = 1.10


@dataclasses.dataclass
class StepProfile:
  """One segmented-dispatch profile of the training step.

  ``phases`` maps the ``STEP_PHASES`` names to attributed device ms
  (``direct`` marks phases measured as their own synced sub-program;
  the rest are differences of synced walls, floored at 0);
  ``step_ms`` is the full embedding step (forward + backward + apply)
  synced as one program; ``coverage_pct`` is ``sum(phases)/step_ms`` —
  100% when no floor clamped; ``cost`` holds the per-program XLA
  cost-model harvest (``{program: {'flops', 'bytes'}}``) and
  ``cost_ok`` the nested-prefix cross-check verdict (None when the
  backend exposes no cost analysis).  ``dcn_lanes`` (hierarchical
  layers only, design §20) maps the ``DCN_LANES`` names to attributed
  ms nested INSIDE the exchange phases (``dcn_direct`` mirrors
  ``direct`` for them); None on flat profiles."""
  phases: Dict[str, float]
  direct: Dict[str, bool]
  step_ms: float
  coverage_pct: float
  cost: Dict[str, Optional[Dict[str, float]]]
  cost_ok: Optional[bool]
  cost_note: str = ''
  reps: int = 0
  dcn_lanes: Optional[Dict[str, float]] = None
  dcn_direct: Optional[Dict[str, bool]] = None


def _aot(jitted, *args):
  """One trace+lower+compile of a jitted callable — the SAME compiled
  executable serves the timed calls and the cost harvest (no second
  trace)."""
  return jitted.trace(*args).lower().compile()


def _timed_ms(compiled, args, reps: int) -> float:
  """Min-of-``reps`` synced wall of one compiled program after one
  warmup execution (the bench min-of-k discipline at program scale)."""
  import jax
  jax.block_until_ready(compiled(*args))
  best = float('inf')
  for _ in range(max(1, int(reps))):
    t0 = time.perf_counter()
    jax.block_until_ready(compiled(*args))
    best = min(best, (time.perf_counter() - t0) * 1000.0)
  return best


def _timed_donating_ms(compiled, p, s, rest, reps: int):
  """``_timed_ms`` for the state-updating programs (apply, step):
  their first two args are DONATED — the headline train step donates
  its state, and an undonated twin would charge a full table-sized
  copy to the phase — so each call invalidates its state inputs and
  the outputs thread into the next rep.  Returns
  ``(best_ms, new_p, new_s)`` (the final state keeps the buffers
  alive for the next program sharing them)."""
  import jax
  p, s = compiled(p, s, *rest)
  jax.block_until_ready((p, s))
  best = float('inf')
  for _ in range(max(1, int(reps))):
    t0 = time.perf_counter()
    p, s = compiled(p, s, *rest)
    jax.block_until_ready((p, s))
    best = min(best, (time.perf_counter() - t0) * 1000.0)
  return best, p, s


def _cost_cross_check(cost: Dict[str, Optional[Dict[str, float]]]):
  """The nested-prefix contract: forward ⊆ forward+backward ⊆ step, so
  their cost-model bytes-accessed totals must be monotone (within
  ``_COST_TOL`` — see its comment for why bytes, not flops, carry the
  judgment).  Returns ``(ok, note)``; ``(None, 'unavailable')`` when
  the backend exposes no cost analysis for any program in the chain."""
  chain = [cost.get('fwd'), cost.get('fwdbwd'), cost.get('step')]
  if any(c is None or not c.get('bytes') for c in chain):
    return None, 'cost model unavailable on this backend'
  nbytes = [c['bytes'] for c in chain]
  for a, b, what in ((nbytes[0], nbytes[1], 'fwd <= fwd+bwd'),
                     (nbytes[1], nbytes[2], 'fwd+bwd <= step')):
    if a > b * _COST_TOL:
      return False, (f'nested-prefix byte monotonicity broken: {what} '
                     f'({a:.3g} > {b:.3g} bytes accessed) — the '
                     'segmented programs no longer nest (design §19)')
  return True, ''


def _refuse(dist):
  if not getattr(dist, 'dp_input', False):
    raise ValueError('devprof.profile_step needs a dp_input layer (the '
                     'segmented phases are the dp<->mp step phases; '
                     'docs/design.md §19)')
  if getattr(dist, 'hot_enabled', False):
    raise ValueError(
        'devprof.profile_step does not support hot-cache layers: the '
        'cached forward splits every phase into hot/cold legs the '
        'segmentation below would misattribute — profile the plain '
        'layer for the device lane (docs/design.md §19)')
  if getattr(dist, 'cold_tier', None) is not None:
    raise ValueError(
        'devprof.profile_step does not support cold-tier layers (the '
        'host fetch leg is not a device phase; the §12 pipeline '
        'already measures it directly) — profile the untiered twin '
        '(docs/design.md §19)')


def profile_step(dist, cats, params=None, emb_optimizer=None,
                 reps: int = 3) -> StepProfile:
  """Segmented-dispatch profile of the embedding train step on the
  live backend; see the module docstring for the phase catalog.

  Args:
    dist: a plain ``dp_input`` ``DistributedEmbedding`` (hot-cache and
      cold-tier layers refuse, actionably).
    cats: one representative batch of embedding inputs.
    params: embedding params (``dist.init(0)`` when omitted).
    emb_optimizer: the sparse optimizer whose apply to profile
      (default ``SparseSGD(0.01)`` — no accumulator copies allocated).
    reps: timed synced calls per program (min wins).

  Emits the device-lane trace events + metrics when obs is armed and
  journals one ``devprof_profile`` event either way.
  """
  import jax
  import jax.numpy as jnp

  from distributed_embeddings_tpu.analysis import graphlint
  from distributed_embeddings_tpu.parallel import overlap as overlap_lib
  from distributed_embeddings_tpu.parallel import sparse as sparse_lib

  _refuse(dist)
  if params is None:
    params = dist.init(0)
  opt = (emb_optimizer if emb_optimizer is not None
         else sparse_lib.SparseSGD(learning_rate=0.01))
  opt_state = opt.init(dist, params)
  inputs, gb, hotness = dist._prepare_inputs(cats)

  programs: Dict[str, Any] = {}
  walls: Dict[str, float] = {}
  cost: Dict[str, Optional[Dict[str, float]]] = {}

  # ---- exchange-only programs (direct) ------------------------------
  exf_fn, exf_in = overlap_lib.build_exchange_program(dist, cats)
  programs['exf'] = (_aot(exf_fn, *exf_in), exf_in)
  exb_fn, exb_in = overlap_lib.build_exchange_program(dist, cats,
                                                      rows_only=True)
  programs['exb'] = (_aot(exb_fn, *exb_in), exb_in)

  # ---- dcn/ici lane twins (hierarchical layers only, design §20):
  # the ICI-only exchange program is the flat exchange shape on the
  # same layer; the DCN lane falls out as the synced-wall remainder
  hier = (bool(getattr(dist, 'dcn_sharding', False))
          and dist.num_slices > 1)
  if hier:
    exfi_fn, exfi_in = overlap_lib.build_exchange_program(
        dist, cats, dcn_leg=False)
    programs['exf_ici'] = (_aot(exfi_fn, *exfi_in), exfi_in)
    exbi_fn, exbi_in = overlap_lib.build_exchange_program(
        dist, cats, rows_only=True, dcn_leg=False)
    programs['exb_ici'] = (_aot(exbi_fn, *exbi_in), exbi_in)

  # ---- forward (compile_lookup: the lookup-only program) ------------
  fwd_fn = dist.compile_lookup(gb, hotness)
  programs['fwd'] = (_aot(fwd_fn, params, *inputs), (params,) + tuple(inputs))

  # ---- forward + backward (output-dependent cotangents so the
  # forward stays live under DCE) -------------------------------------
  def fwd_bwd(p, *ins):
    outs, residuals, (b, h) = dist.forward_with_residuals(p, list(ins))
    d_emb = [o * jnp.asarray(1e-3, o.dtype) for o in outs]
    gsubs = dist.backward_to_mp(list(d_emb), b, h)
    return residuals, gsubs

  fb_jit = jax.jit(fwd_bwd)
  programs['fwdbwd'] = (_aot(fb_jit, params, *inputs),
                        (params,) + tuple(inputs))

  # concrete residual/grad streams for the isolated apply program
  res, gsubs = programs['fwdbwd'][0](params, *inputs)

  # the two state-UPDATING programs below donate their state args like
  # the real train step does (an undonated twin would charge a full
  # table-sized buffer copy to the phase — measured 30x the true apply
  # on tiny).  They donate a PRIVATE copy, never the caller's params.
  def _buffer_copy(x):
    return x.copy() if hasattr(x, 'copy') else x

  own_p = jax.tree.map(_buffer_copy, params)
  own_s = jax.tree.map(_buffer_copy, opt_state)

  # ---- apply alone (direct, on the captured streams) ----------------
  def apply_fn(p, s, r, g):
    return sparse_lib.sparse_apply_updates(dist, opt, p, s, tuple(r),
                                           tuple(g), opt.learning_rate,
                                           gb, hotness)

  programs['apply'] = (_aot(jax.jit(apply_fn, donate_argnums=(0, 1)),
                            own_p, own_s, res, gsubs),
                       (res, gsubs))

  # ---- the full embedding step: fwd + bwd + apply in ONE program ----
  def step_fn(p, s, *ins):
    outs, residuals, (b, h) = dist.forward_with_residuals(p, list(ins))
    d_emb = [o * jnp.asarray(1e-3, o.dtype) for o in outs]
    gsubs_t = dist.backward_to_mp(list(d_emb), b, h)
    return sparse_lib.sparse_apply_updates(dist, opt, p, s,
                                           tuple(residuals),
                                           tuple(gsubs_t),
                                           opt.learning_rate, b, h)

  programs['step'] = (_aot(jax.jit(step_fn, donate_argnums=(0, 1)),
                           own_p, own_s, *inputs),
                      tuple(inputs))

  timed = (('exf', 'exb', 'exf_ici', 'exb_ici', 'fwd', 'fwdbwd')
           if hier else ('exf', 'exb', 'fwd', 'fwdbwd'))
  for name in timed:
    compiled, args = programs[name]
    walls[name] = _timed_ms(compiled, args, reps)
    cost[name] = graphlint.cost_estimate(compiled)
  for name in ('apply', 'step'):
    compiled, rest = programs[name]
    walls[name], own_p, own_s = _timed_donating_ms(compiled, own_p,
                                                   own_s, rest, reps)
    cost[name] = graphlint.cost_estimate(compiled)

  phases = {
      'dev/fwd/exchange': walls['exf'],
      'dev/fwd/lookup_combine': max(0.0, walls['fwd'] - walls['exf']),
      'dev/bwd/exchange': walls['exb'],
      'dev/bwd/grad': max(0.0, walls['fwdbwd'] - walls['fwd']
                          - walls['exb']),
      'dev/apply/update': walls['apply'],
  }
  direct = {'dev/fwd/exchange': True, 'dev/fwd/lookup_combine': False,
            'dev/bwd/exchange': True, 'dev/bwd/grad': False,
            'dev/apply/update': True}
  # dcn/ici segmentation of the exchange phases (design §20): ici is
  # the measured ICI-only twin, dcn the remainder — nested inside the
  # parent walls, so the phase/coverage surface above is untouched
  dcn_lanes = None
  dcn_direct = None
  if hier:
    dcn_lanes = {
        'dev/fwd/exchange/ici': round(walls['exf_ici'], 4),
        'dev/fwd/exchange/dcn': round(
            max(0.0, walls['exf'] - walls['exf_ici']), 4),
        'dev/bwd/exchange/ici': round(walls['exb_ici'], 4),
        'dev/bwd/exchange/dcn': round(
            max(0.0, walls['exb'] - walls['exb_ici']), 4),
    }
    dcn_direct = {'dev/fwd/exchange/ici': True,
                  'dev/fwd/exchange/dcn': False,
                  'dev/bwd/exchange/ici': True,
                  'dev/bwd/exchange/dcn': False}
  step_ms = walls['step']
  coverage = (100.0 * sum(phases.values()) / step_ms if step_ms > 0
              else 0.0)
  cost_ok, cost_note = _cost_cross_check(cost)
  prof = StepProfile(phases={k: round(v, 4) for k, v in phases.items()},
                     direct=direct, step_ms=round(step_ms, 4),
                     coverage_pct=round(coverage, 2), cost=cost,
                     cost_ok=cost_ok, cost_note=cost_note,
                     reps=int(reps), dcn_lanes=dcn_lanes,
                     dcn_direct=dcn_direct)

  # ---- emit: device lane + metrics + journal ------------------------
  if obs_trace.enabled():
    tid = obs_trace.device_tid()
    total_s = sum(phases.values()) / 1000.0
    t = obs_trace.now() - total_s
    spans = {}
    for name in STEP_PHASES:
      spans[name] = t
      t += phases[name] / 1000.0
    obs_trace.complete('dev/fwd/exchange', spans['dev/fwd/exchange'],
                       phases['dev/fwd/exchange'] / 1000.0, tid=tid,
                       direct=True)
    obs_trace.complete('dev/fwd/lookup_combine',
                       spans['dev/fwd/lookup_combine'],
                       phases['dev/fwd/lookup_combine'] / 1000.0,
                       tid=tid, direct=False)
    obs_trace.complete('dev/bwd/exchange', spans['dev/bwd/exchange'],
                       phases['dev/bwd/exchange'] / 1000.0, tid=tid,
                       direct=True)
    obs_trace.complete('dev/bwd/grad', spans['dev/bwd/grad'],
                       phases['dev/bwd/grad'] / 1000.0, tid=tid,
                       direct=False)
    obs_trace.complete('dev/apply/update', spans['dev/apply/update'],
                       phases['dev/apply/update'] / 1000.0, tid=tid,
                       direct=True)
    if dcn_lanes is not None:
      # lanes nest INSIDE their parent exchange span's window (ici
      # first, dcn after) so trace_report's union_ms never
      # double-counts the segmented wall (design §20)
      t_lane = spans['dev/fwd/exchange']
      obs_trace.complete('dev/fwd/exchange/ici', t_lane,
                         dcn_lanes['dev/fwd/exchange/ici'] / 1000.0,
                         tid=tid, direct=True)
      t_lane += dcn_lanes['dev/fwd/exchange/ici'] / 1000.0
      obs_trace.complete('dev/fwd/exchange/dcn', t_lane,
                         dcn_lanes['dev/fwd/exchange/dcn'] / 1000.0,
                         tid=tid, direct=False)
      t_lane = spans['dev/bwd/exchange']
      obs_trace.complete('dev/bwd/exchange/ici', t_lane,
                         dcn_lanes['dev/bwd/exchange/ici'] / 1000.0,
                         tid=tid, direct=True)
      t_lane += dcn_lanes['dev/bwd/exchange/ici'] / 1000.0
      obs_trace.complete('dev/bwd/exchange/dcn', t_lane,
                         dcn_lanes['dev/bwd/exchange/dcn'] / 1000.0,
                         tid=tid, direct=False)
  obs_metrics.inc('devprof.runs')
  for ms in prof.phases.values():
    obs_metrics.observe('devprof.phase_ms', ms)
  if prof.dcn_lanes:
    for ms in prof.dcn_lanes.values():
      obs_metrics.observe('devprof.phase_ms', ms)
  resilience.journal('devprof_profile', phases=prof.phases,
                     step_ms=prof.step_ms,
                     coverage_pct=prof.coverage_pct,
                     cost=prof.cost, cost_ok=prof.cost_ok,
                     cost_note=prof.cost_note, reps=prof.reps,
                     **({'dcn_lanes': prof.dcn_lanes}
                        if prof.dcn_lanes else {}))
  return prof


def profile_serving(engine, reps: int = 3, seed: int = 0
                    ) -> Dict[int, float]:
  """Per-ladder-rung device wall of the serving execute phase: one
  synced ``dist.apply`` per compiled rung signature (min-of-``reps``
  after the engine's warmup), emitted as ``dev/serve/execute`` events
  on the device lane with the rung in ``args``.  The measurement
  includes the host-side dispatch of the cached signature — the same
  code path a live request pays (design §19 honesty note).  Returns
  ``{rung: ms}`` and journals one ``devprof_profile`` event."""
  import jax
  import numpy as np

  engine.warmup()
  rng = np.random.default_rng(seed)
  out: Dict[int, float] = {}
  for bucket in engine.buckets:
    cats = []
    for i, tid_ in enumerate(engine.dist.plan.input_table_map):
      vocab = engine.dist.table_configs[tid_].input_dim
      h = engine.hotness[i]
      shape = (bucket,) if h == 1 else (bucket, h)
      cats.append(rng.integers(0, vocab, size=shape).astype(np.int32))
    jax.block_until_ready(engine.dist.apply(engine.params, cats))
    best = float('inf')
    t_begin = obs_trace.now()
    for _ in range(max(1, int(reps))):
      t0 = time.perf_counter()
      jax.block_until_ready(engine.dist.apply(engine.params, cats))
      best = min(best, (time.perf_counter() - t0) * 1000.0)
    out[int(bucket)] = round(best, 4)
    obs_trace.complete('dev/serve/execute', t_begin, best / 1000.0,
                       tid=obs_trace.device_tid(), rung=int(bucket))
    obs_metrics.observe('devprof.phase_ms', best)
  obs_metrics.inc('devprof.runs')
  resilience.journal('devprof_profile',
                     serve_rung_ms={str(k): v for k, v in out.items()})
  return out


def artifact_block(prof: StepProfile,
                   serve_rung_ms: Optional[Dict[int, float]] = None
                   ) -> Dict[str, Any]:
  """The journaled bench-artifact block (keys pinned by
  tests/test_bench_artifact.py and registered in
  ``obs.metrics.REGISTERED_ARTIFACT_KEYS``)."""
  out: Dict[str, Any] = {
      'devprof_phase_ms': dict(prof.phases),
      'devprof_step_ms': prof.step_ms,
      'devprof_coverage_pct': prof.coverage_pct,
      # the per-program cost-model harvest rides next to the measured
      # walls (design §19): implied GB/s is one division away
      'devprof_cost': dict(prof.cost),
      'devprof_cost_ok': prof.cost_ok,
  }
  if prof.dcn_lanes:
    # hierarchical layers only (design §20): the dcn/ici segmentation
    # of the exchange phases, nested ms that never add to coverage
    out['devprof_dcn_lane_ms'] = dict(prof.dcn_lanes)
  if serve_rung_ms:
    out['devprof_serve_rung_ms'] = {str(k): v
                                    for k, v in serve_rung_ms.items()}
  return out
