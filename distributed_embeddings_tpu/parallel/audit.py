"""Online state-integrity auditing: SDC detection for live train state.

The detection half of docs/design.md §13.  Silent data corruption (a
flipped DRAM/HBM bit, a mis-executed kernel on one chip) does not crash
a run — it quietly diverges one replica, denormalizes one quantized
row, or poisons one optimizer slot, and every checkpoint written after
that moment inherits the damage.  ``StateAuditor`` runs a pluggable set
of CHEAP invariant checks over the live state every K steps, off the
critical path, each failure journaled (``audit_failure``) with device,
leaf and row provenance so the anomaly policy in ``fit``
(``parallel/grad.py on_anomaly=``) can roll back in-process instead of
paging a human:

- ``replicated``: every fully-replicated leaf — the design-§10 hot-row
  buffers ``hot_group_{gi}`` / ``hot_scale_group_{gi}`` and their
  optimizer slots — must be BIT-IDENTICAL across the mesh.  One
  all-gathered per-device digest (position-weighted uint32 sum over the
  raw bit patterns, computed under ``shard_map`` so each device hashes
  its own physical copy) catches a diverged replica; the mismatching
  device and rows localize host-side from the per-device buffers.
- ``quantized``: the design-§12 row contract — every per-row scale is a
  finite, positive, EXACT power of two (``frexp`` mantissa 0.5), int8
  payloads stay on the clipped grid (never -128), fp8 payloads are
  never NaN.  A bit flip in a scale or an off-grid payload byte is a
  contract violation no training step can produce.
- ``finite``: params and optimizer state carry no NaN/Inf (per-device
  counts; the localization names the rows).
- ``tier``: the host-DRAM cold tier's write-back-maintained per-row
  digests (``coldtier.HostTier``) verify over the FULL tier — the
  periodic sweep behind the per-fetch verification ``build_fetch``
  already performs.

The checks are deliberately one-sided: a healthy run NEVER fails them
(pinned by the fuzz draw in tests/test_fuzz_equivalence.py), so a
finding is always actionable.  Cost: one small jitted reduction program
per state signature plus one host sync per audit — bench.py journals
the measured ``audit_overhead_pct`` off/on A/B.
"""

from __future__ import annotations

import dataclasses
import time

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from distributed_embeddings_tpu.analysis import commsan
from distributed_embeddings_tpu.obs import metrics as obs_metrics
from distributed_embeddings_tpu.obs import trace as obs_trace
from distributed_embeddings_tpu.parallel.quantization import (
    payload_bad_mask_np, scale_bad_mask_np)
from distributed_embeddings_tpu.utils import resilience

CHECKS = ('replicated', 'quantized', 'finite', 'tier')

# provenance row lists are bounded: the journal needs the first few
# damaged rows to aim a repair, not a megabyte of indices
MAX_ROWS = 8

# per-audit byte budget (rotating coverage): the invariant sweep is
# MEMORY-BOUND (it must read every audited byte), so a full pass over a
# multi-GB state would cost seconds per audit on a host backend.  Each
# audit instead checks one rotating row window per leaf sized so the
# whole audit reads at most this many bytes; consecutive audits advance
# the windows until every row has been covered (full coverage every
# ``ceil(state_bytes / budget)`` audits — the detection window the
# docstring quotes).  States under the budget get FULL coverage every
# audit.  64 MiB ≈ 60 ms on a 1 GB/s host sweep, microseconds of HBM
# time on chip; pass ``bytes_per_audit=None`` for unconditional full
# sweeps.
BYTES_PER_AUDIT = 64 << 20


@dataclasses.dataclass
class AuditFinding:
  """One detected invariant violation, with provenance."""
  check: str                     # which invariant ('replicated', ...)
  leaf: str                      # state leaf name (or tier_group_{gi})
  devices: Tuple[int, ...]       # flat mesh positions that disagree/fail
  rows: Tuple[int, ...]          # first MAX_ROWS damaged local rows
  detail: str

  def brief(self) -> str:
    return (f'{self.check}:{self.leaf} dev={list(self.devices)} '
            f'rows={list(self.rows)}')

  def journal(self, step: Optional[int] = None):
    resilience.journal('audit_failure', check=self.check, leaf=self.leaf,
                       devices=[int(d) for d in self.devices],
                       rows=[int(r) for r in self.rows],
                       detail=self.detail, step=step)


class AuditError(RuntimeError):
  """Raised by ``StateAuditor.assert_healthy`` (and convertible into the
  ``fit`` anomaly policy): the state failed one or more integrity
  invariants; ``findings`` carries the journaled provenance."""

  def __init__(self, findings: Sequence[AuditFinding],
               step: Optional[int] = None):
    self.findings = list(findings)
    self.step = step
    super().__init__(
        f'state-integrity audit failed at step {step}: '
        + '; '.join(f.brief() for f in self.findings[:4])
        + (f' (+{len(self.findings) - 4} more)'
           if len(self.findings) > 4 else ''))


# ---------------------------------------------------------------------------
# device-side primitives (traced inside ONE shard_map per state signature)
# ---------------------------------------------------------------------------


def _bits_u32(x):
  """The leaf's raw bit patterns as uint32 (f32/int32 exact; narrower
  dtypes zero-extend) — what the replica digest hashes, so a flip in
  ANY bit (mantissa, exponent, sign, int payload) changes the digest."""
  import jax
  import jax.numpy as jnp
  dt = np.dtype(x.dtype)
  if dt.itemsize == 4:
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
  elif dt.itemsize == 2:
    b = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
  else:
    b = jax.lax.bitcast_convert_type(x, jnp.uint8).astype(jnp.uint32)
  return b.reshape(-1)


def _digest_u32(x):
  """Position-weighted wraparound sum over the bit patterns: any single
  flipped element changes the digest (its weighted delta is nonzero mod
  2**32); the position weight also catches swapped rows."""
  import jax
  import jax.numpy as jnp
  bits = _bits_u32(x)
  w = (jax.lax.iota(jnp.uint32, bits.shape[0]) & 0xFFFF) | 1
  return jnp.sum(bits * w, dtype=jnp.uint32)


def _scale_bad(s):
  """Count of rows violating the §12 scale contract: finite, positive,
  exact power of two."""
  import jax.numpy as jnp
  s = s.astype(jnp.float32)
  m, _ = jnp.frexp(s)
  ok = jnp.isfinite(s) & (s > 0) & (m == jnp.float32(0.5))
  return jnp.sum(~ok, dtype=jnp.int32)


def _payload_bad(p, spec):
  """Count of payload elements off the quantized grid: int8 payloads
  are clipped to ±qmax so -128 never occurs; every fp8_e4m3fn bit
  pattern except NaN is a grid value."""
  import jax.numpy as jnp
  if spec.integer:
    return jnp.sum(p == jnp.asarray(-128, p.dtype), dtype=jnp.int32)
  return jnp.sum(jnp.isnan(p.astype(jnp.float32)), dtype=jnp.int32)


def _nonfinite(x):
  import jax.numpy as jnp
  return jnp.sum(~jnp.isfinite(x.astype(jnp.float32)), dtype=jnp.int32)


# host-side localization twins (only run on failure); the
# quantized-contract masks are THE shared invariant definitions in
# quantization.py (also what tools/verify_checkpoint tests offline)


def nonfinite_mask_np(x: np.ndarray) -> np.ndarray:
  return ~np.isfinite(np.asarray(x, np.float32))


_MASKS = {'quantized_scale': scale_bad_mask_np,
          'quantized_payload': payload_bad_mask_np,
          'finite': nonfinite_mask_np}


def _bad_rows(mask: np.ndarray, limit: int = MAX_ROWS) -> Tuple[int, ...]:
  """First damaged (physical) row indices of one device's leaf copy
  (a 0-d mask — a scalar leaf — reports as row 0)."""
  mask = np.atleast_1d(mask)
  flat = mask.reshape(mask.shape[0], -1) if mask.ndim > 1 else mask[:, None]
  rows = np.nonzero(flat.any(axis=1))[0]
  return tuple(int(r) for r in rows[:limit])


# ---------------------------------------------------------------------------
# loss-spike gate (the EMA z-score anomaly trigger used by fit)
# ---------------------------------------------------------------------------


class LossSpikeGate:
  """Journaled EMA z-score gate over the per-step loss series.

  Maintains exponential moving estimates of the loss mean and variance;
  a value whose z-score exceeds ``zscore`` is flagged as a spike (and
  NOT absorbed into the estimates, so a single bad window cannot mask
  itself).  The first ``warmup`` observations only train the estimates
  — early-loss transients never false-positive.  Pure host arithmetic:
  zero device cost.
  """

  def __init__(self, zscore: float = 8.0, warmup: int = 10,
               decay: float = 0.95, min_std: float = 1e-6,
               rel_floor: float = 1e-3):
    if zscore <= 0:
      raise ValueError(f'zscore must be > 0, got {zscore}')
    if not 0.0 < decay < 1.0:
      raise ValueError(f'decay must be in (0, 1), got {decay}')
    self.zscore = float(zscore)
    self.warmup = int(warmup)
    self.decay = float(decay)
    self.min_std = float(min_std)
    # the std floor must scale with the loss magnitude: a run whose
    # loss plateaus to float-identical values would otherwise floor at
    # the absolute min_std, making ANY later healthy wiggle a
    # several-sigma "spike" — the exact false positive the one-sided
    # contract forbids.  With rel_floor, a spike must exceed
    # zscore * rel_floor * |mean| even on a flat series.
    self.rel_floor = float(rel_floor)
    self._mean = 0.0
    self._var = 0.0
    self._n = 0

  def observe(self, value: float) -> Optional[float]:
    """Feed one loss value; returns its z-score when it spikes past the
    gate (the caller journals/acts), else ``None`` after absorbing the
    value into the moving estimates."""
    v = float(value)
    if self._n >= self.warmup:
      std = max(float(np.sqrt(self._var)), self.min_std,
                self.rel_floor * abs(self._mean))
      z = (v - self._mean) / std
      if z > self.zscore:
        return z
    if self._n == 0:
      self._mean = v
    else:
      d = self.decay
      self._mean = d * self._mean + (1 - d) * v
      self._var = d * self._var + (1 - d) * (v - self._mean) ** 2
    self._n += 1
    return None


# ---------------------------------------------------------------------------
# the auditor
# ---------------------------------------------------------------------------


class StateAuditor:
  """Pluggable cheap-invariant auditor over a live embedding train state.

  Args:
    dist: the model's ``DistributedEmbedding`` (defines the mesh, leaf
      layout, quantization spec and cold tier to audit against).
    every: audit cadence in steps — what ``fit(auditor=...)`` keys off.
    checks: subset of ``CHECKS`` to run (default: all that apply; the
      ``tier`` check also arms the cold tier's write-back digests so
      ``build_fetch`` verifies every fetched row from then on).
    max_rows: provenance row cap per finding.
    bytes_per_audit: per-audit read budget (``BYTES_PER_AUDIT``
      default; ``None`` = always sweep everything).  A state larger
      than the budget is audited through ROTATING row windows — each
      audit reads at most the budget, consecutive audits advance the
      windows, and every row is covered within
      ``full_coverage_audits`` audits.  The detection guarantee is
      therefore ``every * full_coverage_audits`` steps for
      budget-capped states and ``every`` steps below the budget
      (``coverage_frac`` / ``full_coverage_audits`` report the live
      values; bench journals them beside ``audit_overhead_pct``).

  ``run``/``check_state`` return the (possibly empty) finding list and
  journal every failure; they never raise — ``assert_healthy`` raises
  ``AuditError`` for callers that want an exception.
  """

  def __init__(self, dist, every: int = 100,
               checks: Sequence[str] = CHECKS,
               max_rows: int = MAX_ROWS,
               bytes_per_audit: Optional[int] = BYTES_PER_AUDIT):
    unknown = set(checks) - set(CHECKS)
    if unknown:
      raise ValueError(f'unknown audit checks {sorted(unknown)}; '
                       f'expected a subset of {list(CHECKS)}')
    if every < 1:
      raise ValueError(f'audit cadence must be >= 1, got {every}')
    if bytes_per_audit is not None and bytes_per_audit < 1:
      raise ValueError(f'bytes_per_audit must be >= 1 or None, '
                       f'got {bytes_per_audit}')
    self.dist = dist
    self.every = int(every)
    self.checks = tuple(checks)
    self.max_rows = int(max_rows)
    self.bytes_per_audit = bytes_per_audit
    self.coverage_frac = 1.0        # set per audit by _window_plan
    self.full_coverage_audits = 1   # audits until every row was checked
    self.audits = 0
    self.findings_total = 0
    self._fn_cache: Dict[Any, Any] = {}
    # the plan names its fully-replicated leaves; optimizer slots of a
    # replicated buffer ({leaf}/{k}) replicate with it
    from distributed_embeddings_tpu.parallel.hotcache import (
        replicated_leaf_names)
    self._replicated = frozenset(replicated_leaf_names(dist.plan))
    tier = getattr(dist, 'cold_tier', None)
    if 'tier' in self.checks and tier is not None:
      tier.enable_digests()

  def _is_replicated(self, name: str) -> bool:
    return (name in self._replicated
            or name.partition('/')[0] in self._replicated)

  # -- leaf classification --------------------------------------------------

  def _leaf_checks(self, name: str, arr, is_param: bool) -> List[str]:
    import jax.numpy as jnp
    quant = getattr(self.dist, 'quant', None)
    out = []
    if 'replicated' in self.checks and self._is_replicated(name):
      out.append('replicated')
    if 'scale_group_' in name:
      if 'quantized' in self.checks:
        out.append('quantized_scale')
    elif is_param and quant is not None and 'group_' in name:
      if 'quantized' in self.checks:
        out.append('quantized_payload')
    elif ('finite' in self.checks
          and jnp.issubdtype(jnp.asarray(arr).dtype, jnp.inexact)):
      out.append('finite')
    return out

  def _collect_leaves(self, params, opt_state):
    """Flatten the embedding state into ``{name: (array, checks)}``;
    optimizer leaves are named ``{group}/{leaf}``."""
    leaves = {}
    for k, v in (params or {}).items():
      cs = self._leaf_checks(k, v, is_param=True)
      if cs:
        leaves[k] = (v, cs)
    for gk, entry in (opt_state or {}).items():
      if not isinstance(entry, dict):
        continue
      for lk, v in entry.items():
        name = f'{gk}/{lk}'
        cs = self._leaf_checks(name, v, is_param=False)
        if cs:
          leaves[name] = (v, cs)
    return leaves

  # -- device pass ----------------------------------------------------------

  def _window_plan(self, leaves):
    """Per-leaf rotating row windows under the byte budget: ``{name:
    (row_axis, rows, window_len)}``.  One uniform coverage fraction
    across leaves, so full coverage completes for every leaf within the
    same number of audits (``self.full_coverage_audits``)."""
    plan = {}
    total = 0
    for k, (v, _) in leaves.items():
      row_axis = 0 if self._is_replicated(k) else 1
      total += int(np.prod(np.shape(v))) * np.dtype(v.dtype).itemsize
      plan[k] = row_axis
    frac = 1.0
    if self.bytes_per_audit is not None and total > self.bytes_per_audit:
      frac = self.bytes_per_audit / total
    out = {}
    worst = 1
    for k, (v, _) in leaves.items():
      row_axis = plan[k]
      rows = int(np.shape(v)[row_axis])
      win = max(1, min(rows, int(np.ceil(rows * frac))))
      out[k] = (row_axis, rows, win)
      worst = max(worst, -(-rows // win))
    self.coverage_frac = round(min(1.0, frac), 6)
    self.full_coverage_audits = worst
    return out

  def _device_pass(self, leaves) -> Dict[str, np.ndarray]:
    """ONE jitted shard_map over every audited leaf's CURRENT rotating
    row window, returning per-check per-device vectors (digests for
    replicated leaves, violation counts otherwise), all-gathered so the
    host reads one small dict.  Window offsets ride in as data — the
    program compiles once per state signature."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    dist = self.dist
    windows = self._window_plan(leaves)
    sig = tuple(sorted((k, tuple(np.shape(v)), str(v.dtype), tuple(cs),
                        windows[k]) for k, (v, cs) in leaves.items()))
    if sig not in self._fn_cache:
      ax = dist.axis_name
      names = tuple(dist.mesh.axis_names)
      checks_of = {k: tuple(cs) for k, (v, cs) in leaves.items()}
      win_of = dict(windows)
      in_specs = {}
      off_specs = {}
      out_specs = {}
      for k, (v, cs) in leaves.items():
        nd = np.ndim(v)
        if self._is_replicated(k):
          in_specs[k] = P(*([None] * nd))
        else:
          in_specs[k] = P(ax, *([None] * (nd - 1)))
        off_specs[k] = P()
        for c in cs:
          out_specs[f'{c}:{k}'] = P(None)
      quant = getattr(dist, 'quant', None)

      def local_fn(xs, offs):
        import jax
        out = {}
        for k, x in xs.items():
          row_axis, rows, win = win_of[k]
          if win < rows:
            x = jax.lax.dynamic_slice_in_dim(x, offs[k], win,
                                             axis=row_axis)
          for c in checks_of[k]:
            if c == 'replicated':
              val = _digest_u32(x)
            elif c == 'quantized_scale':
              val = _scale_bad(x)
            elif c == 'quantized_payload':
              val = _payload_bad(x, quant)
            else:
              val = _nonfinite(x)
            out[f'{c}:{k}'] = jax.lax.all_gather(val, names)
        return out

      self._fn_cache[sig] = jax.jit(
          jax.shard_map(local_fn, mesh=dist.mesh,
                        in_specs=(in_specs, off_specs),
                        out_specs=out_specs, check_vma=False))
    # rotating offsets: audit a visits window position a % n_positions
    # (tail window clamped so the last rows are always covered)
    offsets = {}
    for k, (row_axis, rows, win) in windows.items():
      n_pos = -(-rows // win)
      j = self.audits % n_pos
      offsets[k] = jnp.asarray(min(j * win, rows - win), jnp.int32)
    outs = self._fn_cache[sig]({k: v for k, (v, _) in leaves.items()},
                               offsets)
    return {k: np.asarray(jax.device_get(v)).reshape(-1)
            for k, v in outs.items()}

  # -- host-side localization (failure path only) ---------------------------

  def _device_copies(self, name: str, leaf) -> List[np.ndarray]:
    """Each device's PHYSICAL copy of one leaf, ordered by flat mesh
    position — addressable-shard reads, so a diverged replica's actual
    local bytes are inspected (``device_get`` of a nominally-replicated
    array would read only one copy).  Sharded ``[D, ...]`` leaves
    return their per-device slices (one per data-axis position)."""
    import jax
    if self._is_replicated(name):
      order = {d: i for i, d in
               enumerate(self.dist.mesh.devices.ravel().tolist())}
      copies: List[Optional[np.ndarray]] = [None] * len(order)
      for s in leaf.addressable_shards:
        copies[order[s.device]] = np.asarray(s.data)
      return [c for c in copies if c is not None]
    a = np.asarray(jax.device_get(leaf))
    return [a[d] for d in range(a.shape[0])]

  def _localize_replicated(self, name, leaf) -> Tuple[Tuple[int, ...],
                                                      Tuple[int, ...]]:
    copies = self._device_copies(name, leaf)
    import collections
    counts = collections.Counter(c.tobytes() for c in copies)
    ranked = counts.most_common()
    if len(ranked) > 1 and ranked[0][1] == ranked[1][1]:
      # majority TIE (e.g. a 2-device mesh, or a 2-2 split): the vote
      # cannot say which copy is healthy — naming only the non-first
      # variant would point repair at the wrong chip half the time.
      # Report EVERY device holding a non-unanimous copy; rows from
      # the pairwise diff of the two most common variants.
      a = next(c for c in copies if c.tobytes() == ranked[0][0])
      b = next(c for c in copies if c.tobytes() == ranked[1][0])
      diff = (a.view(np.uint8).reshape(a.shape[0], -1)
              != b.view(np.uint8).reshape(b.shape[0], -1))
      return tuple(range(len(copies))), _bad_rows(diff, self.max_rows)
    ref_bytes = ranked[0][0]
    ref = next(c for c in copies if c.tobytes() == ref_bytes)
    devices, rows = [], []
    for d, c in enumerate(copies):
      if c.tobytes() == ref_bytes:
        continue
      devices.append(d)
      diff = (c.view(np.uint8).reshape(c.shape[0], -1)
              != ref.view(np.uint8).reshape(ref.shape[0], -1))
      rows.extend(_bad_rows(diff, self.max_rows))
    return tuple(devices), tuple(rows[:self.max_rows])

  def _localize_mask(self, check, name, leaf, devices):
    quant = getattr(self.dist, 'quant', None)
    mask_fn = _MASKS[check]
    copies = self._device_copies(name, leaf)
    rows = []
    for d in devices:
      # the all-gathered counts index flat mesh positions; a sharded
      # [D, ...] leaf has one slice per DATA-axis position (replicated
      # across any slice axis), so fold the flat index back
      c = copies[d % len(copies)]
      m = (mask_fn(c, quant) if check == 'quantized_payload'
           else mask_fn(c))
      rows.extend(_bad_rows(m, self.max_rows))
    return tuple(rows[:self.max_rows])

  def _tier_pass(self, tier) -> List[AuditFinding]:
    """Host-tier digest sweep under the SAME rotating byte budget as
    the device pass: each audit re-hashes at most ``bytes_per_audit``
    of tier rows per (group, device), windows advancing with the audit
    counter (full tier coverage within ``full_coverage_audits`` — a
    multi-GB tier must not turn the 'cheap' audit into a full memory
    sweep the budget contract forbids)."""
    findings: List[AuditFinding] = []
    plan = self.dist.plan
    groups = list(plan.cold_tier_groups)
    if not groups:
      return findings
    total = sum(tier.row_nbytes(gi) * plan.groups[gi].tier_rows
                * plan.world_size for gi in groups)
    frac = 1.0
    if self.bytes_per_audit is not None and total > self.bytes_per_audit:
      frac = self.bytes_per_audit / total
    for gi in groups:
      rows = plan.groups[gi].tier_rows
      win = max(1, min(rows, int(np.ceil(rows * frac))))
      n_pos = -(-rows // win)
      self.full_coverage_audits = max(self.full_coverage_audits, n_pos)
      off = min((self.audits % n_pos) * win, rows - win)
      idx = np.arange(off, off + win)
      for dev in range(plan.world_size):
        bad = tier.verify_rows(gi, dev, idx)
        if bad.size:
          findings.append(AuditFinding(
              'tier', f'tier_group_{gi}', (int(dev),),
              tuple(int(r) for r in bad[:self.max_rows]),
              'host-tier row bytes disagree with the write-back '
              'digest'))
    return findings

  # -- public API -----------------------------------------------------------

  def run(self, params=None, opt_state=None, dense=None,
          step: Optional[int] = None) -> List[AuditFinding]:
    """Audit one state snapshot: embedding ``params``/``opt_state`` get
    the device-side invariant pass, ``dense`` (a small pytree of
    replicated head params) a host-side finiteness sweep, and the cold
    tier its digest sweep.  Journals and returns the findings."""
    import jax
    self.audits += 1
    # the audit IS a rendezvous (the device pass all_gathers): fold it
    # into the commsan sequence and cross-check digests here — every
    # rank reaches this cadence point or the mesh was already split
    # (design §22)
    commsan.record('audit/run', audit=self.audits)
    tok = obs_trace.begin('audit/check', step=step)
    findings: List[AuditFinding] = []
    leaves = self._collect_leaves(params, opt_state)
    if leaves:
      outs = self._device_pass(leaves)
      for key, vec in sorted(outs.items()):
        check, _, name = key.partition(':')
        leaf = leaves[name][0]
        if check == 'replicated':
          if np.all(vec == vec[0]):
            continue
          devices, rows = self._localize_replicated(name, leaf)
          findings.append(AuditFinding(
              'replicated', name, devices, rows,
              f'replica digests diverged: {vec.tolist()}'))
        else:
          if not np.any(vec):
            continue
          devices = tuple(int(d) for d in np.nonzero(vec)[0])
          rows = self._localize_mask(check, name, leaf, devices)
          label = ('quantized' if check.startswith('quantized_')
                   else 'finite')
          what = {'quantized_scale': 'non-power-of-two/invalid scale',
                  'quantized_payload': 'off-grid payload value',
                  'finite': 'non-finite value'}[check]
          findings.append(AuditFinding(
              label, name, devices, rows,
              f'{int(vec.sum())} {what}(s); per-device {vec.tolist()}'))
    if dense is not None and 'finite' in self.checks:
      flat, _ = jax.tree_util.tree_flatten_with_path(dense)
      for path, v in flat:
        a = np.asarray(jax.device_get(v))
        if not np.issubdtype(a.dtype, np.floating):
          continue
        m = nonfinite_mask_np(a)
        if m.any():
          findings.append(AuditFinding(
              'finite', 'dense' + jax.tree_util.keystr(path), (),
              _bad_rows(m.reshape(m.shape[0], -1) if m.ndim > 1
                        else m, self.max_rows),
              f'{int(m.sum())} non-finite value(s) in a dense leaf'))
    tier = getattr(self.dist, 'cold_tier', None)
    if 'tier' in self.checks and tier is not None and tier.digests_enabled:
      findings.extend(self._tier_pass(tier))
    for f in findings:
      f.journal(step=step)
    self.findings_total += len(findings)
    # ONE measurement feeds both the span and the histogram (the
    # trace-vs-stats agreement contract, obs/trace.py)
    call_ms = obs_trace.end(tok) * 1000.0
    obs_metrics.inc('audit.calls')
    obs_metrics.observe('audit.call_ms', call_ms)
    if findings:
      obs_metrics.inc('audit.findings', len(findings))
    commsan.barrier_check(f'audit:{self.audits}')
    return findings

  def check_state(self, state, step: Optional[int] = None
                  ) -> List[AuditFinding]:
    """``run`` over a ``TrainState``: splits the hybrid layout (the
    ``'embedding'`` params subtree + the sparse table optimizer in
    ``opt_state[1]``) and host-checks the dense remainder.  Non-hybrid
    states get the dense sweep only."""
    from distributed_embeddings_tpu.parallel.checkpoint import (
        is_hybrid_opt_state)
    params = state.params
    if isinstance(params, dict) and 'embedding' in params:
      emb = params['embedding']
      dense = {k: v for k, v in params.items() if k != 'embedding'}
      emb_opt = None
      if is_hybrid_opt_state(self.dist, state.opt_state):
        emb_opt = state.opt_state[1]
        dense = {'params': dense, 'opt': state.opt_state[0]}
      return self.run(emb, emb_opt, dense=dense, step=step)
    return self.run(dense={'params': params}, step=step)

  def assert_healthy(self, state, step: Optional[int] = None):
    """``check_state`` that raises ``AuditError`` on any finding."""
    findings = self.check_state(state, step=step)
    if findings:
      raise AuditError(findings, step=step)
