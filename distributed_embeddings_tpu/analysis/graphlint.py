"""graphlint: IR-level program analysis over the repo's REAL traced
programs (docs/design.md §18).

detlint (design §17) gates the source tree; the contracts this repo
actually lives by — bit-exact dispatch paths, zero mid-serve compiles,
donated train-state buffers, deadlock-free chunked collectives, the
HBM fits ladder — are properties of the *traced program*, invisible to
an AST pass.  graphlint is the second analysis tier: it traces the
repo's real programs (the lookup dispatch paths, the chunked and
monolithic forward+backward+apply step, the serving ladder rungs, the
cold-tier fetch forward) and runs N passes over their jaxprs and
compiled executables, reusing detlint's core machinery — ``Finding``
ids are ``rule@program::site`` (the program name stands where detlint
puts a file path), waivers live in the SAME
``tools/detlint_baseline.toml`` with mandatory rationale, and the CLI
(``tools/graphlint.py``) keeps the ``--strict``/``--json``/exit-code
contract.

Passes (each a callable ``(programs) -> findings`` in ``PASSES``):

- ``schedule``   — the ordered collective sequence (primitive, axis,
  shape, index) per program; programs in one parity group (serving
  ladder rungs; chunked vs monolithic train step — design §11/§16 pin
  their outputs bit-exact) must agree on the collapsed
  (primitive, axis) sequence, and no collective may sit in a
  ``lax.cond`` whose branches disagree (the per-device-divergence
  deadlock shape).  The extracted schedules are also the LEDGER the
  conftest deadlock watchdog dumps when the known shard_map rendezvous
  flake wedges a test — attribution instead of a rerun note.
- ``donation``   — every param/optimizer leaf of the sparse train step
  must be donated AND actually input-output aliased in the compiled
  executable (an undonated table shard is a silent 2x HBM tax), and no
  embedding-state leaf may pass a ``cond``: XLA gives each branch of a
  conditional its own operand, so a table that enters one is copied
  whole once per branch, every step, taken or not (ISSUE 25: 47% of
  dlrm-train-4chip's step).  A zero-or-one-trip ``while`` carries the
  same rare work on one buffer.
- ``retrace``    — hash (shape, dtype, weak_type, static-arg)
  signatures per compiled function; zero retraces across a 3-step fit
  and a warmed serving ladder, naming the drifting leaf (weak_type
  promotion, captured python scalar) when one fires — design §16's
  ``compile_count`` pin generalized from serving to every path.
- ``hostsync``   — no host callback primitive inside a traced hot-path
  program, and no ``jax.device_get`` observed from the monitored step
  hot loop (``obs.trace.phase`` scopes are the sanctioned instrument, as
  in the purity pass; the cold tier's documented host leg is exempt).
- ``hbm``        — per-program memory estimate from the compiled
  executable's memory analysis, journaled next to
  ``device_hbm_budget`` and gated against it where a plan declares one
  (resident argument bytes must fit; the full peak — args + temps +
  unaliased outputs — rides along for the perf_notes fits ladder).
- ``budget``     — collective-count budget (design §21): a traced
  program may issue NO MORE collectives than its checked-in ledger
  entry records.  The fused exchange collapsed every phase from
  O(groups) collectives to one; without this gate that win regresses
  silently (a per-group loop sneaks back in, the count creeps up, and
  nothing fails).  Growth fails ``--strict`` unless the ledger is
  refreshed (``--write-ledger``) alongside a rationale-bearing waiver
  in ``tools/detlint_baseline.toml`` — the same waiver discipline as
  detlint.  Counts DROPPING is not a finding (that is the
  optimization landing); the ledger refresh records the new floor.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import re

from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

from distributed_embeddings_tpu.analysis import core as lint_core
from distributed_embeddings_tpu.analysis.core import Finding

# Collective primitives the schedule ledger records — the ops whose
# cross-device rendezvous can deadlock when traced bodies diverge.
COLLECTIVE_PRIMITIVES = frozenset({
    'all_to_all', 'psum', 'all_gather', 'reduce_scatter', 'ppermute',
    'pmax', 'pmin', 'pgather', 'psum_invariant',
})

# Host-callback primitives that must never appear inside a hot-path
# traced program: each one is a device->host rendezvous per execution.
HOST_CALLBACK_PRIMITIVES = frozenset({
    'pure_callback', 'io_callback', 'debug_callback', 'callback',
    'outside_call', 'host_callback_call', 'debug_print',
})

# Host-side frames whose device_get is a documented contract, not a
# stray sync: the cold tier's host leg (design §12) and the obs layer
# (design §15's sanctioned instrument, mirroring the purity exemption).
_HOSTSYNC_EXEMPT_FRAGMENTS = ('parallel/coldtier.py', '/obs/',
                              'utils/resilience.py')

GRAPH_PASS_NAMES = ('schedule', 'donation', 'retrace', 'hostsync', 'hbm',
                    'budget')


# --------------------------------------------------------------------------
# program model
# --------------------------------------------------------------------------


@dataclasses.dataclass
class CollectiveOp:
  """One collective in a program's schedule.  ``index`` is the issue
  order inside the traced body; ``loop`` marks ops under scan/while
  (executed per iteration); ``dtype`` is the first operand's element
  type (with ``shape``, the op's on-wire payload — what the bench's
  ``fused_exchange_bytes`` sums)."""
  primitive: str
  axis: str
  shape: Tuple[int, ...]
  index: int
  loop: bool = False
  dtype: str = ''

  def key(self) -> Tuple[str, str]:
    return (self.primitive, self.axis)

  def nbytes(self) -> int:
    """Payload bytes of one issue of this op (0 when the operand dtype
    was unavailable at extraction)."""
    import numpy as np
    if not self.dtype or not self.shape:
      return 0
    try:
      item = np.dtype(self.dtype).itemsize
    except TypeError:
      return 0
    n = 1
    for d in self.shape:
      n *= int(d)
    return n * item

  def as_dict(self) -> Dict[str, Any]:
    return {'primitive': self.primitive, 'axis': self.axis,
            'shape': list(self.shape), 'index': self.index,
            'loop': self.loop, 'dtype': self.dtype}


@dataclasses.dataclass
class RetraceRecord:
  """Observed runtime ledger for one compiled function: per-call
  argument signatures plus the ``compile_count`` movement across the
  monitored window (after the one sanctioned warmup compile)."""
  calls: int
  sigs: List[Tuple]
  compile_count_delta: int = 0


@dataclasses.dataclass
class HostSyncRecord:
  """Sites (``file:function``) that called ``jax.device_get`` inside
  the monitored hot-loop window."""
  sites: List[str]


@dataclasses.dataclass
class Program:
  """One analyzed program.  Catalog entries carry a jaxpr and usually a
  compiled executable; pseudo-programs (e.g. the warmed serving ladder
  retrace proof) may carry only runtime records."""
  name: str
  jaxpr: Any = None                    # jax ClosedJaxpr (or None)
  compiled: Any = None                 # jax Compiled (or None)
  parity: Optional[str] = None         # parity-group label
  donate_expected: Optional[List[Tuple[int, str]]] = None
  hbm_budget: Optional[int] = None     # bytes/device, when the plan pins one
  # measured per-device bytes of the program's budget-relevant state
  # (tables + their optimizer slots) — the quantity device_hbm_budget
  # actually covers; compiled argument bytes also include per-batch
  # traffic (fetch buffers, id inputs) the §12 contract does not charge
  resident_state_bytes: Optional[int] = None
  retrace: Optional[RetraceRecord] = None
  hostsync: Optional[HostSyncRecord] = None
  note: str = ''
  # commlint inputs (design §22): the plan-derived EXPECTED exchange
  # schedule (``planner.expected_collectives`` over the LookupPlans the
  # trace populated — fwd legs then bwd legs for train steps) and the
  # non-exchange collectives the program is ALLOWED to issue besides
  # them (apply-stage sync the plan does not record, e.g. the
  # dcn-replicated grad all_gather) as (primitive, axis) pairs
  plan_expect: Optional[List[Dict[str, Any]]] = None
  sync_allowance: Tuple[Tuple[str, str], ...] = ()
  # the embedding-state leaves (tables, scales, optimizer slots) as ONE
  # device holds them — ``device_state_leaves`` — which no ``cond`` of
  # the program may take or return (donation pass)
  state_leaves: Optional[List['StateLeaf']] = None
  # memoized derived facts: the HLO alias parse (a full as_text dump)
  # and the jaxpr walk are each needed by a pass AND the meta ledger —
  # computed once per program, not once per consumer
  _schedule: Optional[List[CollectiveOp]] = dataclasses.field(
      default=None, repr=False, compare=False)
  _aliased: Optional[Set[int]] = dataclasses.field(
      default=None, repr=False, compare=False)

  def schedule(self) -> List['CollectiveOp']:
    if self._schedule is None:
      self._schedule = (extract_schedule(self.jaxpr)
                        if self.jaxpr is not None else [])
    return self._schedule

  def aliased(self) -> Set[int]:
    if self._aliased is None:
      self._aliased = (aliased_param_indices(self.compiled)
                       if self.compiled is not None else set())
    return self._aliased


def measure_resident_bytes(tree) -> int:
  """Per-device resident bytes of a (sharded) state pytree: the bytes
  each leaf pins on ONE device — sharded tables count their shard,
  replicated hot buffers count in full, exactly what the planner's
  fits ladder budgets."""
  import jax
  total = 0
  for leaf in jax.tree_util.tree_leaves(tree):
    shards = getattr(leaf, 'addressable_shards', None)
    if not shards:
      total += int(getattr(leaf, 'nbytes', 0))
      continue
    dev = shards[0].device
    total += sum(int(s.data.nbytes) for s in shards if s.device == dev)
  return total


@dataclasses.dataclass(frozen=True)
class StateLeaf:
  """One embedding-state leaf as a single device holds it: the shard's
  shape without its leading unit axes (``shard_map`` hands the apply
  ``[1, rows, w]``; ``_dedup_and_apply`` works on ``[rows, w]``)."""
  label: str
  shape: Tuple[int, ...]
  dtype: str

  def held_by(self, aval) -> bool:
    """Whether ``aval`` is big enough to BE this leaf: the shard
    itself, the shard with rows appended (the cold tier concatenates
    the batch's fetched tail rows onto it), or a reshaped view of it
    (packed <-> natural storage)."""
    shape = getattr(aval, 'shape', None)
    if shape is None or str(getattr(aval, 'dtype', '')) != self.dtype:
      return False
    shape = _squeeze_leading(shape)
    if (len(shape) == len(self.shape) and shape[1:] == self.shape[1:]
        and shape[0] >= self.shape[0]):
      return True
    return math.prod(shape) == math.prod(self.shape)


def _squeeze_leading(shape) -> Tuple[int, ...]:
  shape = tuple(int(d) for d in shape)
  while len(shape) > 1 and shape[0] == 1:
    shape = shape[1:]
  return shape


def device_state_leaves(tree) -> List[StateLeaf]:
  """The table-shaped leaves (two or more axes on a device) of a
  sharded embedding-state pytree, each as ``StateLeaf``."""
  import jax
  out = []
  for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
    shape = tuple(leaf.shape)
    sharding = getattr(leaf, 'sharding', None)
    if sharding is not None:
      shape = sharding.shard_shape(shape)
    shape = _squeeze_leading(shape)
    if len(shape) >= 2:
      out.append(StateLeaf(jax.tree_util.keystr(path), shape,
                           str(leaf.dtype)))
  return out


# --------------------------------------------------------------------------
# jaxpr walking: schedule extraction, callback scan, divergent conds
# --------------------------------------------------------------------------


def _inner_jaxprs(value) -> List[Any]:
  """Sub-jaxprs reachable from one eqn param value (ClosedJaxpr, bare
  Jaxpr, or a tuple/list of either)."""
  out = []
  items = value if isinstance(value, (list, tuple)) else (value,)
  for v in items:
    inner = getattr(v, 'jaxpr', None)
    if inner is not None and hasattr(inner, 'eqns'):
      out.append(inner)
    elif hasattr(v, 'eqns'):
      out.append(v)
  return out


def _walk_eqns(jaxpr, in_loop: bool = False):
  """Yield ``(eqn, in_loop)`` over a jaxpr and every sub-jaxpr, in
  program order.  ``in_loop`` is True under scan/while bodies (the op
  executes once per iteration, so the static schedule position is a
  motif, not a count)."""
  for eqn in jaxpr.eqns:
    yield eqn, in_loop
    looping = in_loop or eqn.primitive.name in ('scan', 'while')
    for k in sorted(eqn.params):
      for sub in _inner_jaxprs(eqn.params[k]):
        yield from _walk_eqns(sub, looping)


def extract_schedule(jaxpr) -> List[CollectiveOp]:
  """The ordered collective sequence of a (closed) jaxpr — the ledger
  row the parity checks compare and the deadlock watchdog names frames
  against."""
  inner = getattr(jaxpr, 'jaxpr', jaxpr)
  out: List[CollectiveOp] = []
  for eqn, in_loop in _walk_eqns(inner):
    if eqn.primitive.name in COLLECTIVE_PRIMITIVES:
      ax = eqn.params.get('axis_name', eqn.params.get('axes', '?'))
      if isinstance(ax, (tuple, list)):
        ax = ','.join(str(a) for a in ax)
      shape: Tuple[int, ...] = ()
      dtype = ''
      for v in eqn.invars:
        aval = getattr(v, 'aval', None)
        if aval is not None and getattr(aval, 'shape', None) is not None:
          shape = tuple(int(d) for d in aval.shape)
          dtype = str(getattr(aval, 'dtype', ''))
          break
      out.append(CollectiveOp(eqn.primitive.name, str(ax), shape,
                              len(out), loop=in_loop, dtype=dtype))
  return out


def collapse_schedule(ops: Sequence[CollectiveOp]
                      ) -> List[Tuple[str, str]]:
  """Consecutive runs of one (primitive, axis) collapse to a single
  entry: a k-chunked exchange issues the same collective k times in a
  row where the monolithic program issues it once, and design §11 pins
  those two programs bit-exact — the collapsed sequences are the
  invariant that survives chunking."""
  out: List[Tuple[str, str]] = []
  for op in ops:
    if not out or out[-1] != op.key():
      out.append(op.key())
  return out


def _cond_branch_schedules(jaxpr) -> List[Tuple[int, List[List[Tuple]]]]:
  """For each ``cond`` eqn (in order): the per-branch collapsed
  collective schedules."""
  inner = getattr(jaxpr, 'jaxpr', jaxpr)
  out = []
  idx = 0
  for eqn, _ in _walk_eqns(inner):
    if eqn.primitive.name == 'cond':
      branches = []
      for b in _inner_jaxprs(eqn.params.get('branches', ())):
        branches.append(collapse_schedule(extract_schedule(b)))
      out.append((idx, branches))
      idx += 1
  return out


def _callback_sites(jaxpr) -> List[str]:
  inner = getattr(jaxpr, 'jaxpr', jaxpr)
  return [eqn.primitive.name for eqn, _ in _walk_eqns(inner)
          if eqn.primitive.name in HOST_CALLBACK_PRIMITIVES]


def state_carriers(jaxpr, leaves: Sequence[StateLeaf],
                   primitive: str = 'cond') -> List[Tuple[int, str]]:
  """``(index, leaf label)`` for every ``primitive`` eqn (counted in
  program order over the jaxpr and its sub-jaxprs) that takes or
  returns a value holding one of ``leaves``.  ``'cond'`` is what the
  donation pass refuses; ``'while'`` is how the overflow correction of
  ``parallel/sparse._dedup_and_apply`` carries its shards instead."""
  inner = getattr(jaxpr, 'jaxpr', jaxpr)
  out = []
  idx = 0
  for eqn, _ in _walk_eqns(inner):
    if eqn.primitive.name != primitive:
      continue
    avals = [getattr(v, 'aval', None)
             for v in list(eqn.invars) + list(eqn.outvars)]
    hit = next((leaf.label for leaf in leaves
                if any(leaf.held_by(a) for a in avals)), None)
    if hit is not None:
      out.append((idx, hit))
    idx += 1
  return out


# --------------------------------------------------------------------------
# compiled-executable introspection: aliasing + memory
# --------------------------------------------------------------------------

_ALIAS_BLOCK_RE = re.compile(r'input_output_alias=\{')
_ALIAS_ENTRY_RE = re.compile(r'\{[\d,\s]*\}:\s*\((\d+)')


def aliased_param_indices(compiled) -> Set[int]:
  """Flat input-parameter indices the compiled executable input-output
  aliases (the HLO entry's ``input_output_alias`` map) — donation that
  actually landed, not just donation that was requested."""
  txt = compiled.as_text()
  m = _ALIAS_BLOCK_RE.search(txt)
  if m is None:
    return set()
  # the alias map nests one level of braces: scan to the matching close
  depth, i = 1, m.end()
  while i < len(txt) and depth:
    if txt[i] == '{':
      depth += 1
    elif txt[i] == '}':
      depth -= 1
    i += 1
  block = txt[m.end():i - 1]
  return {int(g.group(1)) for g in _ALIAS_ENTRY_RE.finditer(block)}


def cost_estimate(compiled) -> Optional[Dict[str, float]]:
  """XLA cost-model totals from the compiled executable's
  ``cost_analysis()``: ``flops`` and ``bytes`` (bytes accessed).  The
  harvest the devprof device lane (design §19) cross-checks its
  measured per-phase walls against — held HERE next to
  ``memory_estimate`` so the two analysis consumers (graphlint's HBM
  ledger, devprof's cost contract) read the backend surface one way.
  None when the backend exposes no analysis."""
  try:
    ca = compiled.cost_analysis()
  except Exception:  # backend-dependent surface; absence is not a finding
    return None
  if isinstance(ca, (list, tuple)):  # older jax: one dict per device
    ca = ca[0] if ca else None
  if not ca:
    return None
  try:
    return {'flops': float(ca.get('flops', 0.0)),
            'bytes': float(ca.get('bytes accessed', 0.0))}
  except (AttributeError, TypeError, ValueError):
    return None


def memory_estimate(compiled) -> Optional[Dict[str, int]]:
  """Per-device byte estimate from the executable's memory analysis:
  ``resident`` (argument bytes — what the fits ladder budgets) and
  ``peak`` (arguments + temps + unaliased outputs — the full
  high-water estimate journaled for perf_notes).  None when the
  backend exposes no analysis."""
  try:
    ma = compiled.memory_analysis()
  except Exception:  # backend-dependent surface; absence is not a finding
    return None
  if ma is None:
    return None
  args = int(ma.argument_size_in_bytes)
  out = int(ma.output_size_in_bytes)
  alias = int(ma.alias_size_in_bytes)
  temp = int(ma.temp_size_in_bytes)
  return {'resident': args,
          'peak': args + temp + max(0, out - alias),
          'temp': temp, 'output': out, 'alias': alias}


# --------------------------------------------------------------------------
# runtime ledgers: retrace signatures + host-sync monitor
# --------------------------------------------------------------------------


def signature(*trees) -> Tuple:
  """The (shape, dtype, weak_type) signature of a call's argument
  pytrees, leaf-labelled — what jit's dispatch cache keys on (plus
  static args, which appear here as their repr).  Two calls with equal
  signatures hit the same compiled executable; a drifting leaf is a
  retrace."""
  import jax
  flat, _ = jax.tree_util.tree_flatten_with_path(tuple(trees))
  out = []
  for path, leaf in flat:
    label = jax.tree_util.keystr(path)
    if hasattr(leaf, 'shape') and hasattr(leaf, 'dtype'):
      out.append((label, tuple(leaf.shape), str(leaf.dtype),
                  bool(getattr(leaf, 'weak_type', False))))
    else:
      out.append((label, 'static', repr(leaf), False))
  return tuple(out)


def sig_drift(base: Tuple, other: Tuple) -> List[Tuple[str, str]]:
  """Human-readable per-leaf drift between two signatures:
  ``(leaf label, what changed)`` — names the weak_type promotion or
  captured-scalar change that forced the retrace."""
  if len(base) != len(other):
    return [('<structure>',
             f'{len(base)} leaves -> {len(other)} leaves')]
  out = []
  for b, o in zip(base, other):
    if b == o:
      continue
    label = b[0] if b[0] == o[0] else f'{b[0]}->{o[0]}'
    deltas = []
    names = ('leaf', 'shape', 'dtype', 'weak_type')
    for k in range(1, 4):
      if b[k] != o[k]:
        deltas.append(f'{names[k]} {b[k]} -> {o[k]}')
    out.append((label, '; '.join(deltas) or 'leaf renamed'))
  return out


class HostSyncMonitor:
  """Context manager that observes explicit device->host syncs
  (``jax.device_get``) issued from the step hot loop.

  CPU backends never raise on transfers (zero-copy), so the transfer
  guard cannot carry this gate — instead the monitor wraps
  ``jax.device_get`` for the window and attributes each call to the
  first non-jax frame, skipping the documented host legs
  (``_HOSTSYNC_EXEMPT_FRAGMENTS``)."""

  def __init__(self):
    self.sites: List[str] = []
    self._orig = None

  def _record(self):
    import traceback
    own = os.path.abspath(__file__)
    for frame in reversed(traceback.extract_stack()[:-2]):
      if os.path.abspath(frame.filename) == own:
        continue
      fn = frame.filename.replace(os.sep, '/')
      if '/jax/' in fn:
        continue
      if any(x in fn for x in _HOSTSYNC_EXEMPT_FRAGMENTS):
        return
      self.sites.append(f'{os.path.basename(fn)}:{frame.name}')
      return
    self.sites.append('<unknown>')

  def __enter__(self):
    import jax
    self._orig = jax.device_get

    def wrapper(x):
      self._record()
      return self._orig(x)

    jax.device_get = wrapper
    return self

  def __exit__(self, *exc):
    import jax
    jax.device_get = self._orig
    return False


# --------------------------------------------------------------------------
# passes
# --------------------------------------------------------------------------

PassFn = Callable[[List[Program]], List[Finding]]
PASSES: Dict[str, PassFn] = {}


def _register(name: str):
  def deco(fn: PassFn) -> PassFn:
    PASSES[name] = fn
    return fn
  return deco


@_register('schedule')
def _schedule_pass(programs: List[Program]) -> List[Finding]:
  findings: List[Finding] = []
  groups: Dict[str, List[Tuple[Program, List[Tuple[str, str]]]]] = {}
  for prog in programs:
    if prog.jaxpr is None:
      continue
    if prog.parity is not None:
      groups.setdefault(prog.parity, []).append(
          (prog, collapse_schedule(prog.schedule())))
    for idx, branches in _cond_branch_schedules(prog.jaxpr):
      flat = [b for b in branches]
      if any(flat) and any(b != flat[0] for b in flat[1:]):
        findings.append(Finding(
            rule='schedule/collective-in-divergent-cond',
            path=prog.name, line=0, symbol=f'cond#{idx}',
            message=f'cond #{idx} branches trace different collective '
            f'schedules {flat} — a predicate that differs across '
            'devices leaves some ranks inside the rendezvous and some '
            'outside it (the deadlock shape the 2-core shard_map flake '
            'wears); hoist the collective out of the cond or make the '
            'predicate mesh-uniform'))
  for label, members in sorted(groups.items()):
    ref_prog, ref = members[0]
    for prog, sched in members[1:]:
      if sched != ref:
        findings.append(Finding(
            rule='schedule/parity-divergence', path=prog.name, line=0,
            symbol=label,
            message=f'collapsed collective schedule {sched} differs '
            f'from parity peer {ref_prog.name} {ref} — programs in '
            f'parity group {label!r} are pinned bit-exact '
            '(design §11/§16) and must issue the same collective '
            'sequence, or a chunked/rung variant can wedge against '
            'its peer'))
  return findings


@_register('donation')
def _donation_pass(programs: List[Program]) -> List[Finding]:
  findings: List[Finding] = []
  for prog in programs:
    if prog.state_leaves and prog.jaxpr is not None:
      for idx, leaf in state_carriers(prog.jaxpr, prog.state_leaves):
        findings.append(Finding(
            rule='donation/state-leaf-in-cond', path=prog.name, line=0,
            symbol=f'cond#{idx}',
            message=f'cond #{idx} takes or returns a value the size of '
            f'embedding-state leaf {leaf} — XLA hands each branch of a '
            'conditional its own operand, so the whole shard is copied '
            'once per branch on every step, taken or not (ISSUE 25: '
            '%copy.346/%copy.347 of f32[20025088,128], 62 of '
            "dlrm-train-4chip's 132 ms).  Keep the shard outside: carry "
            'it through a zero-or-one-trip while_loop (one buffer '
            'through init, body and result), or let the cond yield '
            'only the rows to write'))
  for prog in programs:
    if prog.donate_expected is None or prog.compiled is None:
      continue
    aliased = prog.aliased()
    for idx, leaf in prog.donate_expected:
      if idx not in aliased:
        findings.append(Finding(
            rule='donation/undonated-leaf', path=prog.name, line=0,
            symbol=leaf,
            message=f'state leaf {leaf} (flat arg {idx}) is not '
            'input-output aliased in the compiled executable — an '
            'undonated table shard holds its old buffer alive across '
            'the update, a silent 2x HBM tax on exactly the arrays '
            'the fits ladder budgets (design §18)'))
  return findings


@_register('retrace')
def _retrace_pass(programs: List[Program]) -> List[Finding]:
  findings: List[Finding] = []
  for prog in programs:
    rec = prog.retrace
    if rec is None:
      continue
    if rec.compile_count_delta > 0:
      findings.append(Finding(
          rule='retrace/recompile', path=prog.name, line=0,
          symbol='compile_count',
          message=f'compile_count moved by {rec.compile_count_delta} '
          f'across the monitored {rec.calls}-call window after warmup '
          '— a warmed path compiled mid-run (the mid-serve compile '
          'class design §16 pins to zero)'))
    if rec.sigs:
      base = rec.sigs[0]
      for i, sig in enumerate(rec.sigs[1:], 2):
        for leaf, what in sig_drift(base, sig):
          findings.append(Finding(
              rule='retrace/signature-drift', path=prog.name, line=0,
              symbol=leaf,
              message=f'call {i} drifted the dispatch signature at '
              f'{leaf}: {what} — every drift is a full retrace + '
              'compile on the hot path (weak_type promotion and '
              'captured python scalars are the usual culprits)'))
  return findings


@_register('hostsync')
def _hostsync_pass(programs: List[Program]) -> List[Finding]:
  findings: List[Finding] = []
  for prog in programs:
    if prog.jaxpr is not None:
      for prim in sorted(set(_callback_sites(prog.jaxpr))):
        findings.append(Finding(
            rule='hostsync/callback-in-program', path=prog.name,
            line=0, symbol=prim,
            message=f'host callback primitive {prim!r} inside the '
            'traced program — every execution pays a device->host '
            'rendezvous, and under shard_map a per-device callback '
            'can wedge the mesh (obs.trace.phase scopes are the '
            'sanctioned instrument; they insert no primitive)'))
    if prog.hostsync is not None:
      for site in sorted(set(prog.hostsync.sites)):
        findings.append(Finding(
            rule='hostsync/device-get-in-hot-loop', path=prog.name,
            line=0, symbol=site,
            message=f'jax.device_get called from {site} inside the '
            'monitored step hot loop — a synchronous device->host '
            'pull serializes the pipeline (hoist it behind the loop, '
            'or journal from a completed-step snapshot)'))
  return findings


@_register('hbm')
def _hbm_pass(programs: List[Program]) -> List[Finding]:
  findings: List[Finding] = []
  for prog in programs:
    if (prog.hbm_budget is not None
        and prog.resident_state_bytes is not None
        and prog.resident_state_bytes > prog.hbm_budget):
      findings.append(Finding(
          rule='hbm/over-budget', path=prog.name, line=0,
          symbol='resident_bytes',
          message=f'measured per-device resident state bytes '
          f"{prog.resident_state_bytes} exceed the plan's "
          f'device_hbm_budget {prog.hbm_budget} — the program pins '
          'more table/optimizer state than the fits ladder budgeted '
          'for this plan (design §12/§18)'))
  return findings


@_register('budget')
def _budget_pass(programs: List[Program]) -> List[Finding]:
  """Collective-count budget (design §21): each traced program's live
  collective count gated against its checked-in ledger entry."""
  findings: List[Finding] = []
  try:
    with open(default_ledger_path(), encoding='utf-8') as f:
      ledger = json.load(f)
  except (OSError, ValueError):
    # no checked-in ledger (fresh checkout mid-bootstrap): nothing to
    # budget against; the freshness test owns ledger existence
    return findings
  for prog in programs:
    if prog.jaxpr is None:
      continue
    entry = ledger.get(prog.name)
    if entry is None:
      continue  # new program: --write-ledger records its first budget
    budget = len(entry.get('collectives', []))
    live = len(prog.schedule())
    if live > budget:
      findings.append(Finding(
          rule='budget/collective-count-exceeded', path=prog.name,
          line=0, symbol='collectives',
          message=f'traced program issues {live} collectives but its '
          f'ledger entry budgets {budget} — a collective crept into a '
          'pinned program (each one is a latency-bound mesh rendezvous; '
          "the fused exchange's O(groups)->O(1) win, design §21, "
          'regresses silently without this gate).  Remove it, or '
          'refresh tools/graphlint_ledger.json (--tier full '
          '--write-ledger) WITH a rationale-bearing waiver in '
          'tools/detlint_baseline.toml'))
  return findings


# --------------------------------------------------------------------------
# runner + ledger
# --------------------------------------------------------------------------


def schedule_ledger(programs: List[Program]) -> Dict[str, Any]:
  """The per-program collective-schedule ledger — what
  ``--write-ledger`` persists to ``tools/graphlint_ledger.json`` and
  the conftest deadlock watchdog dumps when a shard_map collective
  wedges, so the rendezvous flake is attributable from the tier-1
  log."""
  out: Dict[str, Any] = {}
  for prog in programs:
    if prog.jaxpr is None:
      continue
    out[prog.name] = {
        'parity': prog.parity,
        'collectives': [op.as_dict() for op in prog.schedule()],
    }
  return out


def default_ledger_path(root: Optional[str] = None) -> str:
  return os.path.join(root or lint_core.default_root(), 'tools',
                      'graphlint_ledger.json')


def write_ledger(programs: List[Program],
                 path: Optional[str] = None) -> str:
  path = path or default_ledger_path()
  with open(path, 'w', encoding='utf-8') as f:
    json.dump(schedule_ledger(programs), f, indent=2, sort_keys=True)
    f.write('\n')
  return path


def run_programs(programs: List[Program],
                 passes: Optional[List[str]] = None,
                 baseline: Optional[lint_core.Baseline] = None
                 ) -> lint_core.Result:
  """Run the requested graph passes (default: all) over an analyzed
  program set and apply the shared waiver baseline — detlint's
  ``run_passes`` shape with programs in place of a parse."""
  names = list(GRAPH_PASS_NAMES) if passes is None else list(passes)
  findings: List[Finding] = []
  for name in names:
    if name not in PASSES:
      raise ValueError(f'unknown graphlint pass {name!r}; available: '
                       f'{sorted(PASSES)}')
    findings.extend(PASSES[name](programs))
  meta: Dict[str, Any] = {
      'graphlint_programs': sorted(p.name for p in programs),
      'graphlint_schedule': schedule_ledger(programs),
      'graphlint_donation': {
          p.name: {
              'expected': len(p.donate_expected),
              'aliased': len(p.aliased()
                             & {i for i, _ in p.donate_expected}),
          }
          for p in programs
          if p.donate_expected is not None and p.compiled is not None
      },
      'graphlint_retrace': {
          p.name: {'calls': p.retrace.calls,
                   'compile_count_delta': p.retrace.compile_count_delta}
          for p in programs if p.retrace is not None
      },
      'graphlint_hbm': {
          p.name: dict(est, budget=p.hbm_budget,
                       resident_state=p.resident_state_bytes)
          for p in programs if p.compiled is not None
          and (est := memory_estimate(p.compiled)) is not None
      },
  }
  return lint_core.apply_baseline(findings, baseline, set(names), meta)


def run_repo(root: Optional[str] = None, tier: str = 'flagship',
             passes: Optional[List[str]] = None,
             programs: Optional[List[Program]] = None
             ) -> lint_core.Result:
  """The one-call CI entry: trace the catalog, run every graph pass
  under the shared checked-in baseline — what ``tools/graphlint.py``,
  ``bench.py``'s journaled ``graphlint_*`` counts, dryrun_multichip
  stage 13 and tier-1's ``tests/test_graphlint.py`` all share."""
  root = root or lint_core.default_root()
  if programs is None:
    programs = build_programs(tier=tier)
  baseline = lint_core.Baseline.load(
      lint_core.default_baseline_path(root))
  return run_programs(programs, passes=passes, baseline=baseline)


# --------------------------------------------------------------------------
# the program catalog: the repo's real traced programs
# --------------------------------------------------------------------------


def build_programs(tier: str = 'flagship') -> List[Program]:
  """Trace (and compile) the repo's real programs on the available
  mesh (up to 8 devices — the dryrun/test topology).

  ``tier='flagship'`` is the tier-1/bench/CI set: one program per
  pass-bearing path — the XLA and hot-cache-split lookup paths, the
  monolithic + chunked sparse train step (donation, retrace, hostsync
  and schedule-parity proofs ride on these), two serving ladder rungs
  and the warmed-ladder retrace proof, and the cold-tier fetch
  forward.  ``tier='full'`` adds the SparseCore-emulation and Pallas
  dispatch paths (the Pallas program is trace-only off-TPU: its
  kernel lowers on TPU hardware alone).
  """
  if tier not in ('flagship', 'full'):
    raise ValueError(f"tier must be 'flagship' or 'full', got {tier!r}")
  import jax
  import jax.numpy as jnp
  import numpy as np
  import optax

  from distributed_embeddings_tpu import serving as serving_lib
  from distributed_embeddings_tpu.parallel import (
      DistributedEmbedding, SparseAdagrad, TableConfig, create_mesh,
      hotcache, init_hybrid_train_state, make_hybrid_train_step,
      set_weights)
  from distributed_embeddings_tpu.parallel import dist_embedding as de
  from distributed_embeddings_tpu.parallel import planner as planner_mod

  programs: List[Program] = []
  devs = jax.devices()[:8]
  world = len(devs)
  mesh = create_mesh(devs)
  on_cpu = devs[0].platform == 'cpu'
  rng = np.random.default_rng(0)
  batch = 2 * world

  cfg2 = [TableConfig(32, 8, 'sum'), TableConfig(48, 8, 'sum')]

  def make_ids(configs, n):
    return [jnp.asarray(rng.integers(0, c.input_dim, size=(n,))
                        .astype(np.int32)) for c in configs]

  def plan_expectation(dist, paths=(None,), global_batch=None):
    """The plan-predicted exchange schedule for the program a trace
    just populated: ``planner.expected_collectives`` over the
    most-recent ``LookupPlan`` per requested path (``None`` = the most
    recent plan of any path — correct immediately after the trace that
    built it; the serving ladder shares one engine across rungs, so
    rung programs pin ``global_batch`` to select THEIR signature's
    plan).  ``None`` when a requested plan was never built."""
    ops: List[Dict[str, Any]] = []
    for path in paths:
      try:
        plan = dist.lookup_plan(global_batch=global_batch, path=path)
      except KeyError:
        return None
      ops.extend(planner_mod.expected_collectives(plan))
    return ops

  def forward_program(name, dist, params, cats, parity=None,
                      fetch=None, compile_ok=True, note=''):
    hot = tuple([1] * len(cats))
    fwd = dist.compile_lookup(int(cats[0].shape[0]), hot)
    args = (params,) + ((fetch,) if fetch is not None else ()) \
        + tuple(cats)
    traced = fwd.trace(*args)
    compiled = None
    if compile_ok:
      compiled = traced.lower().compile()
    programs.append(Program(
        name, jaxpr=traced.jaxpr, compiled=compiled, parity=parity,
        hbm_budget=dist.plan.device_hbm_budget,
        resident_state_bytes=measure_resident_bytes(params),
        plan_expect=plan_expectation(
            dist, global_batch=int(cats[0].shape[0])),
        note=note))
    return programs[-1]

  # ---- lookup dispatch paths ----------------------------------------
  d_xla = DistributedEmbedding(cfg2, mesh=mesh, dp_input=True,
                               lookup_impl='xla')
  forward_program('lookup/xla', d_xla, d_xla.init(0),
                  make_ids(cfg2, batch))

  hs = {0: hotcache.HotSet(0, np.array([0, 1, 2]))}
  d_hot = DistributedEmbedding(cfg2, mesh=mesh, dp_input=True,
                               hot_cache=hs)
  forward_program('lookup/hot', d_hot, d_hot.init(0),
                  make_ids(cfg2, batch), fetch={})

  # ---- fused vs per-group exchange twins (design §21) ---------------
  # TWO fusion groups (widths differ, so the tables cannot merge): the
  # fused program ships both groups' buffers in ONE all_to_all per
  # phase where the per-group twin issues one per group.  The raw
  # ledger rows show the O(groups)->O(1) drop; the parity group pins
  # the two programs bit-exact on the collapsed schedule (per-group
  # consecutive same-axis runs collapse to the fused program's single
  # entry — the invariant that survives both chunking and fusion).
  cfg_m = [TableConfig(32, 8, 'sum'), TableConfig(40, 16, 'sum')]
  w_m = [rng.normal(size=(c.input_dim, c.output_dim))
         .astype(np.float32) * 0.1 for c in cfg_m]
  cats_m = make_ids(cfg_m, batch)
  for fused, name, bname in ((True, 'lookup/fused', 'bwd/fused'),
                             (False, 'lookup/pergroup', 'bwd/pergroup')):
    d_m = DistributedEmbedding(cfg_m, mesh=mesh, dp_input=True,
                               fused_exchange=fused)
    p_m = set_weights(d_m, w_m)
    forward_program(name, d_m, p_m, cats_m, parity='lookup-fuse')
    # the matching backward twin: the dedup cotangent exchange, fused
    # vs per-group (trace-only — the bench's exchange_collectives_bwd
    # counts read these rows)
    outs_m, _, (gb_m, hot_m) = d_m.forward_with_residuals(p_m, cats_m)
    bwd_m = d_m._build_backward(gb_m, hot_m)
    traced_b = bwd_m.trace(*[jnp.ones_like(o) for o in outs_m])
    programs.append(Program(bname, jaxpr=traced_b.jaxpr,
                            parity='bwd-fuse',
                            plan_expect=plan_expectation(d_m, ('bwd',))))

  # ---- wire-dtype twins (design §24) --------------------------------
  # Same tables + id streams per pair; the only delta is the wire
  # codec.  The parity pass compares the COLLAPSED (primitive, axis)
  # schedule — dtype-blind by design — so each off/on pair shares a
  # parity group: the codec must narrow payloads without adding or
  # reordering a single collective.  The raw ledger rows DO carry
  # dtype, so the checked-in ledger is the dtype assertion: the
  # wire-on forward's cold-row leg must show uint8 (int8 payload +
  # packed po2 scale) and the wire-on backward's cotangent leg
  # bfloat16, where the off twins show float32.
  hs_w = {0: hotcache.HotSet(0, np.array([0, 1, 2])),
          1: hotcache.HotSet(1, np.array([1, 5, 9]))}
  w_wq = [rng.normal(size=(c.input_dim, c.output_dim))
          .astype(np.float32) * 0.1 for c in cfg_m]
  for wire, name in ((None, 'lookup/wire-off'),
                     ('table', 'lookup/wire-on')):
    d_w = DistributedEmbedding(cfg_m, mesh=mesh, dp_input=True,
                               table_dtype='int8', hot_cache=dict(hs_w),
                               wire_dtype=wire)
    forward_program(name, d_w, set_weights(d_w, w_wq), cats_m,
                    parity='wire-fwd', fetch={})
  for wire, bname in ((None, 'bwd/wire-off'),
                      ('bfloat16', 'bwd/wire-on')):
    d_b = DistributedEmbedding(cfg_m, mesh=mesh, dp_input=True,
                               wire_dtype=wire)
    p_b = set_weights(d_b, w_m)
    outs_b, _, (gb_b, hot_b) = d_b.forward_with_residuals(p_b, cats_m)
    bwd_b = d_b._build_backward(gb_b, hot_b)
    traced_wb = bwd_b.trace(*[jnp.ones_like(o) for o in outs_b])
    programs.append(Program(bname, jaxpr=traced_wb.jaxpr,
                            parity='wire-bwd',
                            plan_expect=plan_expectation(d_b, ('bwd',))))

  if tier == 'full':
    d_sc = DistributedEmbedding(cfg2, mesh=mesh,
                                lookup_impl='sparsecore')
    forward_program('lookup/sparsecore', d_sc, d_sc.init(0),
                    make_ids(cfg2, batch))
    # Pallas: table-wise placement (one table per device keeps the
    # logical width >= 8 the kernel supports); the kernel only LOWERS
    # on TPU, so off-TPU this program is trace-only — schedule and
    # callback passes still cover it
    cfg_p = [TableConfig(24 + 8 * i, 8, 'sum') for i in range(world)]
    d_pl = DistributedEmbedding(cfg_p, mesh=mesh, dp_input=True,
                                lookup_impl='pallas',
                                column_slice_threshold=10**9)
    forward_program('lookup/pallas', d_pl, d_pl.init(0),
                    make_ids(cfg_p, batch), compile_ok=not on_cpu,
                    note='trace-only off-TPU (Pallas lowers on TPU)')

  # ---- sparse train step: monolithic vs chunked ---------------------
  def head_loss(dense_params, emb_outs, hb):
    h = jnp.concatenate(list(emb_outs), axis=-1)
    return jnp.mean((h @ dense_params['kernel'] - hb) ** 2)

  kernel = rng.standard_normal((8 * len(cfg2), 1)).astype(np.float32) * 0.1
  weights = [rng.normal(size=(c.input_dim, c.output_dim))
             .astype(np.float32) * 0.1 for c in cfg2]
  labels = jnp.asarray(rng.normal(size=(batch, 1)).astype(np.float32))
  cats_t = make_ids(cfg2, batch)

  for chunks, name in ((1, 'train/monolithic'), (2, 'train/chunked')):
    dist = DistributedEmbedding(cfg2, mesh=mesh, dp_input=True,
                                overlap_chunks=chunks)
    opt = SparseAdagrad(learning_rate=0.05)
    state = init_hybrid_train_state(
        dist, {'embedding': set_weights(dist, weights),
               'kernel': jnp.asarray(kernel)}, optax.sgd(0.05), opt)
    step = make_hybrid_train_step(dist, head_loss, optax.sgd(0.05),
                                  opt)
    traced = step.jitted.trace(state, cats_t, labels)
    compiled = traced.lower().compile()
    # the step's own donation contract decides what the pass expects:
    # a donate=False step (supported) must not be charged for leaves
    # it never promised to alias
    donate_expected = None
    if 0 in step.donate_argnums:
      flat, _ = jax.tree_util.tree_flatten_with_path(state)
      donate_expected = [(i, jax.tree_util.keystr(path))
                         for i, (path, _) in enumerate(flat)]
    prog = Program(name, jaxpr=traced.jaxpr, compiled=compiled,
                   parity='train-step',
                   donate_expected=donate_expected,
                   hbm_budget=dist.plan.device_hbm_budget,
                   resident_state_bytes=measure_resident_bytes(
                       (state.params['embedding'],
                        state.opt_state[1])),
                   plan_expect=plan_expectation(dist, ('dp', 'bwd')),
                   state_leaves=device_state_leaves(
                       (state.params['embedding'], state.opt_state[1])))
    if chunks == 1:
      # the 3-step-fit retrace + host-sync proof rides on the
      # monolithic step: execute the AOT executable (no second trace),
      # signature-ledger every call, monitor the post-warmup window
      c0 = dist.compile_count
      sigs = []
      mon = HostSyncMonitor()
      cur = state
      for i in range(3):
        sigs.append(signature(cur, cats_t, labels))
        if i == 0:
          cur, _ = compiled(cur, cats_t, labels)
        else:
          with mon:
            cur, _ = compiled(cur, cats_t, labels)
      prog.retrace = RetraceRecord(
          calls=3, sigs=sigs,
          compile_count_delta=dist.compile_count - c0)
      prog.hostsync = HostSyncRecord(sites=mon.sites)
    programs.append(prog)

  # ---- hierarchical (dcn x ici) train step — design §20 -------------
  # Flat-vs-hierarchical schedules are DISTINCT BY DESIGN: the
  # hierarchical step adds the cross-slice DCN all_to_all pair per
  # chunk, so pinning the two into ONE parity group would assert a
  # falsehood.  Each arm is its own single-member group instead — the
  # ledger records BOTH schedules (drift in either trips the ledger
  # diff) without ever claiming they match.  The hierarchical arm also
  # carries the donation/aliasing expectation (all state leaves — the
  # two-level exchange must not cost a second copy of the tables) and
  # its own 3-call zero-retrace + host-sync proof, exactly like the
  # monolithic flat step above.
  if world >= 4 and world % 2 == 0:
    mesh_h = create_mesh((2, world // 2))
    for shard, name, par in ((False, 'train/hier-flat-twin',
                              'train-hier-flat'),
                             (True, 'train/hierarchical', 'train-hier')):
      dist = DistributedEmbedding(cfg2, mesh=mesh_h, dp_input=True,
                                  packed_storage=False,
                                  dcn_sharding=shard)
      opt = SparseAdagrad(learning_rate=0.05)
      # fresh kernel leaf per arm: the monolithic retrace proof above
      # DONATED (and thereby deleted) the shared `kernel` buffer
      kernel_h = jnp.asarray(np.full((8 * len(cfg2), 1), 0.1,
                                     dtype=np.float32))
      state = init_hybrid_train_state(
          dist, {'embedding': dist.init(0), 'kernel': kernel_h},
          optax.sgd(0.05), opt)
      step = make_hybrid_train_step(dist, head_loss, optax.sgd(0.05),
                                    opt)
      traced = step.jitted.trace(state, cats_t, labels)
      compiled = traced.lower().compile()
      donate_expected = None
      if 0 in step.donate_argnums:
        flat, _ = jax.tree_util.tree_flatten_with_path(state)
        donate_expected = [(i, jax.tree_util.keystr(path))
                           for i, (path, _) in enumerate(flat)]
      prog = Program(name, jaxpr=traced.jaxpr, compiled=compiled,
                     parity=par,
                     donate_expected=donate_expected,
                     hbm_budget=dist.plan.device_hbm_budget,
                     resident_state_bytes=measure_resident_bytes(
                         (state.params['embedding'],
                          state.opt_state[1])),
                     plan_expect=plan_expectation(dist, ('dp', 'bwd')),
                     state_leaves=device_state_leaves(
                         (state.params['embedding'], state.opt_state[1])),
                     # the apply stage syncs grads across slices with a
                     # collective the plan records no leg for — the
                     # sharded arm's per-group DCN update all_to_all
                     # (sparse.py hierarchical update exchange), the
                     # flat twin's replicated-grad all_gather.  A
                     # DECLARED allowance, not an unpredicted collective
                     sync_allowance=((('all_to_all', 'dcn'),) if shard
                                     else (('all_gather', 'dcn'),)))
      if shard:
        c0 = dist.compile_count
        sigs = []
        mon = HostSyncMonitor()
        cur = state
        for i in range(3):
          sigs.append(signature(cur, cats_t, labels))
          if i == 0:
            cur, _ = compiled(cur, cats_t, labels)
          else:
            with mon:
              cur, _ = compiled(cur, cats_t, labels)
        prog.retrace = RetraceRecord(
            calls=3, sigs=sigs,
            compile_count_delta=dist.compile_count - c0)
        prog.hostsync = HostSyncRecord(sites=mon.sites)
      programs.append(prog)

  # ---- serving ladder rungs + the warmed-ladder retrace proof -------
  eng = serving_lib.ServingEngine(cfg2, weights, batch_size=batch,
                                  mesh=mesh)
  eng.warmup()
  for rung in eng.buckets:
    forward_program(f'serve/rung{rung}', eng.dist, eng.params,
                    make_ids(cfg2, rung), parity='serve-ladder')
  c0 = eng.dist.compile_count
  mon = HostSyncMonitor()
  with mon:
    for rung in eng.buckets:
      eng.lookup_padded([np.asarray(c)[:max(1, rung - 1)]
                         for c in make_ids(cfg2, rung)])
  programs.append(Program(
      'serve/ladder-warm',
      retrace=RetraceRecord(calls=len(eng.buckets), sigs=[],
                            compile_count_delta=eng.dist.compile_count
                            - c0),
      hostsync=HostSyncRecord(sites=mon.sites),
      note='warmed-ladder proof: one request per rung after warmup, '
      'zero compiles, zero hot-loop device_gets'))

  # ---- cold-tier fetch forward --------------------------------------
  cfg_t = [TableConfig(64 * world, 8, None), TableConfig(40, 8, None)]
  hs_t = {0: hotcache.HotSet(0, np.array([0, 1, 3]))}
  probe = DistributedEmbedding(cfg_t, mesh=mesh, dp_input=True,
                               hot_cache=hs_t, table_dtype='int8')
  budget = int(probe.plan.resident_table_bytes() * 0.6)
  d_tier = DistributedEmbedding(cfg_t, mesh=mesh, dp_input=True,
                                hot_cache=hs_t, table_dtype='int8',
                                cold_tier=True,
                                device_hbm_budget=budget)
  p_tier = set_weights(d_tier, [
      (rng.normal(size=(c.input_dim, c.output_dim)) * 0.1)
      .astype(np.float32) for c in cfg_t])
  cats_c = make_ids(cfg_t, batch)
  d_tier.apply(p_tier, cats_c)  # calibrates the rung's fetch capacity
  fetch = d_tier.build_cold_fetch(cats_c)
  forward_program('serve/coldfetch', d_tier, p_tier, cats_c,
                  fetch=de._forward_fetch(fetch.device))

  # ---- the train step of that int8 cold-tier layer (trace-only) ------
  # the apply's operand here is the (payload, scale) PAIR with the
  # batch's fetched tail rows concatenated on, optimizer rows likewise
  # (design §12): the widest thing the overflow correction ever carries.
  # capacity_rows=8 keeps the correction in the program at this size.
  opt_t = SparseAdagrad(learning_rate=0.05,
                        capacity_rows=(8,) * len(d_tier.plan.groups))
  kernel_t = jnp.asarray(np.full((8 * len(cfg_t), 1), 0.1, np.float32))

  def head_loss_t(dense_params, emb_outs, hb):
    h = jnp.concatenate([o.reshape(o.shape[0], -1) for o in emb_outs],
                        axis=-1)
    return jnp.mean((h @ dense_params['kernel'] - hb) ** 2)

  state_t = init_hybrid_train_state(
      d_tier, {'embedding': p_tier, 'kernel': kernel_t}, optax.sgd(0.05),
      opt_t)
  step_t = make_hybrid_train_step(d_tier, head_loss_t, optax.sgd(0.05),
                                  opt_t, donate=False)
  # a fetch built now carries the accumulator rows the tier just gained
  traced_t = step_t.jitted.trace(
      state_t, cats_c, labels, d_tier.build_cold_fetch(cats_c).device)
  programs.append(Program(
      'train/tiered-int8', jaxpr=traced_t.jaxpr,
      state_leaves=device_state_leaves(
          (state_t.params['embedding'], state_t.opt_state[1])),
      note='trace-only: the donation pass walks its jaxpr for state '
      'leaves inside a cond'))
  return programs
