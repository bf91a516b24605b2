"""Host-DRAM cold tier: host arrays, per-batch fetch, writeback, pipeline.

The host half of docs/design.md §12.  A cold-tier plan keeps only each
fusion group's device-resident head (``GroupSpec.resident_rows``) in
HBM; the tail rows live here, in per-(group, device) host arrays
(``HostTier``), quantized exactly like the device payload.  Per batch:

1. ``compute_fetch_rows`` mirrors the runtime routing in NumPy (the
   same id->owner map ``hotcache.measure_exchange_counters`` uses):
   clip valid ids, strip hot ids, route to each owner device's fused
   local rows, keep rows ``>= resident_rows``, and DEDUPLICATE — the
   fetch list is exactly the tail slice of the deduplicated cold
   exchange the hot-cache forward already performs.
2. ``build_fetch`` gathers those rows (payload + scale + optimizer
   rows) from the host tier into padded, static-shape device buffers.
3. The device step gathers tail rows from the buffers
   (``dist_embedding._tiered_gather``), the sparse apply updates them
   alongside the resident head, and returns the touched rows as a
   writeback output.
4. ``write_back`` stores the updated (re-quantized) rows into the tier.

``ColdFetchPipeline`` double-buffers step 1 — the expensive host pass —
on a worker thread while the device runs the previous step (the same
shape as ``CsrFeed``'s host-build overlap); the payload gather of step
2 stays on the consumer side, AFTER the previous step's writeback, so
pipelining never reads stale rows.  Its ``stats()`` measure the hidden
fraction directly from consumer blocked time (``cold_tier_overlap_pct``
is measured, never inferred).
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
import weakref

from typing import Dict, List, Optional, Tuple

import numpy as np

from distributed_embeddings_tpu.obs import metrics as obs_metrics
from distributed_embeddings_tpu.obs import trace as obs_trace
from distributed_embeddings_tpu.parallel import quantization
from distributed_embeddings_tpu.utils import resilience

_FETCH_MARGIN = 1.5
_FETCH_ALIGN = 64

# deterministic per-byte odd multipliers for the row digests: odd, so a
# single corrupted byte always changes the weighted sum (odd * nonzero
# delta is never 0 mod 2**64); fixed seed, so digests are comparable
# across processes
_DIGEST_SEED = 0x5DC0FF5E7


def _byte_weights(n: int) -> np.ndarray:
  rng = np.random.default_rng(_DIGEST_SEED)
  return (rng.integers(0, 1 << 62, size=n, dtype=np.uint64) << np.uint64(1)
          ) | np.uint64(1)


class TierIntegrityError(RuntimeError):
  """A host-tier row's bytes disagree with its write-back-maintained
  digest (design §13): silent corruption of host-DRAM state, detected
  at fetch time before the damaged row reaches the device.  ``findings``
  lists ``(group, device, rows)`` provenance; the event is journaled
  (``tier_integrity_failure``) before raising, and ``fit``'s
  ``on_anomaly`` rollback policy treats it like any other anomaly."""

  def __init__(self, findings: List[Tuple[int, int, List[int]]]):
    self.findings = findings
    detail = '; '.join(
        f'group {gi} device {dev} rows {rows}' for gi, dev, rows
        in findings)
    super().__init__(
        f'host-tier integrity check failed: {detail}. The tier rows '
        'were corrupted in host memory after their last write-back '
        '(checksum mismatch) — roll back to the last valid checkpoint '
        '(fit on_anomaly=rollback) instead of training on damaged '
        'state (docs/design.md §13).')


class HostTier:
  """Per-(group, device) host arrays holding the tail rows
  ``[resident_rows, rows_cap)`` of every cold-tier group: quantized
  payload, per-row scales (quantized plans), and optimizer-state rows
  (``ensure_opt``)."""

  def __init__(self, plan, quant):
    self.plan = plan
    self.quant = quant
    self.frozen = False
    dt = np.dtype(quant.dtype) if quant is not None else np.float32
    self.payload: Dict[int, np.ndarray] = {}
    self.scale: Dict[int, np.ndarray] = {}
    self.opt: Dict[int, Dict[str, np.ndarray]] = {}
    # write-back-maintained per-row digests (design §13): None until
    # enable_digests() arms them — the default (off) path is
    # byte-for-byte the pre-auditor program.  Bulk installs (checkpoint
    # restore: set_tail twice + one set_opt_tail per optimizer leaf,
    # all per group) only MARK the group dirty; the full re-hash runs
    # ONCE, lazily, at the next digest read — a rollback restore of a
    # beyond-HBM tier must not pay 3-4 redundant memory-bound sweeps
    # on the recovery critical path.
    self._digests: Optional[Dict[int, np.ndarray]] = None
    self._dirty: set = set()
    self._weights: Dict[int, np.ndarray] = {}
    for gi in plan.cold_tier_groups:
      g = plan.groups[gi]
      self.payload[gi] = np.zeros(
          (plan.world_size, g.tier_rows, g.width), dt)
      if quant is not None:
        self.scale[gi] = np.ones(
            (plan.world_size, g.tier_rows, 1), np.float32)
      self.opt[gi] = {}

  def freeze(self):
    """Mark the tier READ-ONLY (the §14 serving contract): every later
    ``set_tail`` / ``set_opt_tail`` / ``ensure_opt`` / ``write_back``
    refuses.  Fetches (``build_fetch``) keep working — and keep
    digest-verifying every gathered row when digests are armed.
    Irreversible by design: a serving tier that could quietly thaw
    would void the read-only guarantee the engine states."""
    self.frozen = True

  def _check_writable(self, what: str):
    if self.frozen:
      raise RuntimeError(
          f'HostTier is frozen (read-only serving tier, docs/design.md '
          f'§14): {what} refused. Serving engines never write table '
          'state; rebuild the tier from a checkpoint to change it.')

  def set_tail(self, gi: int, leaf: str, arr: np.ndarray):
    """Install one group's full tail (``[D, tier_rows, ...]``)."""
    self._check_writable(f'set_tail(group {gi}, {leaf!r})')
    target = self.payload if leaf == 'payload' else self.scale
    want = target[gi].shape if gi in target else None
    arr = np.asarray(arr)
    if want is not None and arr.shape != want:
      raise ValueError(f'tier tail for group {gi}/{leaf}: expected '
                       f'shape {want}, got {arr.shape}')
    target[gi] = arr.astype(target[gi].dtype) if gi in target else arr
    if self._digests is not None:
      self._dirty.add(gi)

  def ensure_opt(self, leaf: str, fill: float, dtype):
    """Create (idempotently) one optimizer-state leaf's tail arrays,
    filled with the optimizer's init value — the host half of e.g.
    Adagrad's accumulator for tier rows."""
    self._check_writable(f'ensure_opt({leaf!r})')
    created = False
    for gi in self.plan.cold_tier_groups:
      if leaf in self.opt[gi]:
        continue
      g = self.plan.groups[gi]
      self.opt[gi][leaf] = np.full(
          (self.plan.world_size, g.tier_rows, g.width), fill,
          np.dtype(dtype))
      created = True
    if created and self._digests is not None:
      # a new leaf changes the per-row byte layout the digest covers
      self._weights.clear()
      self._dirty.update(self.plan.cold_tier_groups)

  def set_opt_tail(self, gi: int, leaf: str, arr: np.ndarray):
    """Install one group's full optimizer-state tail (the checkpoint
    restore leg) — routed here, not assigned directly, so the row
    digests stay in sync with the bytes they certify."""
    self._check_writable(f'set_opt_tail(group {gi}, {leaf!r})')
    self.opt[gi][leaf] = np.asarray(arr)
    if self._digests is not None:
      self._weights.pop(gi, None)
      self._dirty.add(gi)

  # -- row digests (design §13; the state the auditor + build_fetch
  # verify against) ---------------------------------------------------------

  @property
  def digests_enabled(self) -> bool:
    return self._digests is not None

  def _flush_dirty(self, gi: Optional[int] = None):
    """Run the deferred full-group re-hash for ``gi`` (or every dirty
    group) — the ONE sweep all the bulk installs since the last digest
    read collapse into."""
    if self._digests is None or not self._dirty:
      return
    targets = (list(self._dirty) if gi is None
               else ([gi] if gi in self._dirty else []))
    for g in targets:
      self._refresh_group(g)
      self._dirty.discard(g)

  def enable_digests(self):
    """Arm the write-back-maintained per-row digests: every row's
    payload+scale+optimizer bytes hash into ``[D, tier_rows]`` uint64
    checksums, refreshed by ``write_back``/``set_tail``/``set_opt_tail``
    and verified for every fetched row in ``build_fetch`` (mismatch
    raises ``TierIntegrityError``).  Idempotent; default off — the
    unarmed tier is program-identical to pre-§13 behaviour."""
    if self._digests is None:
      self._digests = {}
      self._dirty.clear()
      for gi in self.plan.cold_tier_groups:
        self._refresh_group(gi)

  def _row_bytes(self, gi: int, dev, idx) -> np.ndarray:
    """``[n, B]`` uint8 view of the selected rows' full byte content
    (payload, then scale, then optimizer leaves in sorted order)."""
    sel = (slice(None) if idx is None else idx)
    parts = [self.payload[gi][dev, sel]]
    if gi in self.scale:
      parts.append(self.scale[gi][dev, sel])
    for k in sorted(self.opt[gi]):
      parts.append(self.opt[gi][k][dev, sel])
    rows = parts[0].shape[0]
    flat = [np.ascontiguousarray(p).view(np.uint8).reshape(rows, -1)
            for p in parts]
    return np.concatenate(flat, axis=1)

  # bound on the uint64 temporary the hash materializes (~9x the bytes
  # it covers): a full-slice hash of a beyond-HBM tier would otherwise
  # transiently allocate multiples of the tier itself and OOM the very
  # process the detector protects — full-group passes chunk through
  # this window instead
  _DIGEST_CHUNK_BYTES = 8 << 20

  def row_nbytes(self, gi: int) -> int:
    """Bytes ONE tier row contributes to its digest (payload + scale +
    every optimizer leaf) — what budgeted sweeps size their row
    windows with."""
    g = self.plan.groups[gi]
    n = self.payload[gi].dtype.itemsize * g.width
    if gi in self.scale:
      n += 4
    for k in self.opt[gi]:
      n += self.opt[gi][k].dtype.itemsize * g.width
    return n

  def _digest_rows(self, gi: int, dev, idx=None) -> np.ndarray:
    if idx is None:
      # full device slice: chunk the row range so the ~9x uint64
      # temporary stays bounded regardless of tier size
      rows = self.payload[gi].shape[1]
      step = max(1, self._DIGEST_CHUNK_BYTES // max(1, self.row_nbytes(gi)))
      if rows > step:
        return np.concatenate([
            self._digest_rows(gi, dev, np.arange(lo, min(lo + step, rows)))
            for lo in range(0, rows, step)
        ])
      idx = np.arange(rows)
    b = self._row_bytes(gi, dev, idx)
    w = self._weights.get(gi)
    if w is None or w.size != b.shape[1]:
      w = _byte_weights(b.shape[1])
      self._weights[gi] = w
    return (b.astype(np.uint64) * w).sum(axis=1, dtype=np.uint64)

  def _refresh_group(self, gi: int):
    self._digests[gi] = np.stack([
        self._digest_rows(gi, dev)
        for dev in range(self.plan.world_size)
    ])

  def refresh_rows(self, gi: int, dev: int, idx: np.ndarray):
    if self._digests is None:
      return
    if gi in self._dirty:
      self._flush_dirty(gi)  # the full re-hash covers these rows too
      return
    if len(idx):
      self._digests[gi][dev, idx] = self._digest_rows(gi, dev, idx)

  def verify_rows(self, gi: int, dev: int, idx: np.ndarray) -> np.ndarray:
    """Tail-local indices among ``idx`` whose bytes disagree with the
    stored digest (empty when healthy or digests are off)."""
    if self._digests is None or not len(idx):
      return np.zeros((0,), np.int64)
    self._flush_dirty(gi)
    got = self._digest_rows(gi, dev, idx)
    want = self._digests[gi][dev, idx]
    return np.asarray(idx, np.int64)[got != want]

  def verify_all(self, max_rows: int = 8
                 ) -> List[Tuple[int, int, List[int]]]:
    """Full-tier digest sweep (the auditor's periodic ``tier`` check):
    ``(group, device, first damaged rows)`` per failing device."""
    out: List[Tuple[int, int, List[int]]] = []
    if self._digests is None:
      return out
    self._flush_dirty()
    for gi in self.plan.cold_tier_groups:
      for dev in range(self.plan.world_size):
        got = self._digest_rows(gi, dev)
        bad = np.nonzero(got != self._digests[gi][dev])[0]
        if bad.size:
          out.append((gi, dev, [int(r) for r in bad[:max_rows]]))
    return out

  def host_bytes(self) -> int:
    total = sum(a.nbytes for a in self.payload.values())
    total += sum(a.nbytes for a in self.scale.values())
    total += sum(a.nbytes for d in self.opt.values() for a in d.values())
    return int(total)


@dataclasses.dataclass
class ColdFetch:
  """One batch's host->device fetch: ``device`` is the jit-safe pytree
  the forward/apply consume; ``rows_np``/``counts`` are the host-side
  bookkeeping ``write_back`` needs."""
  device: Dict[int, Dict]
  rows_np: Dict[int, List[np.ndarray]]
  counts: Dict[int, List[int]]


def _cold_ids_per_input(dist, inputs):
  """Per input: valid, vocab-clipped, hot-stripped ids of the GLOBAL
  batch — the id population of the deduplicated cold exchange (mirrors
  ``hotcache.measure_exchange_counters``)."""
  plan = dist.plan
  out = {}
  for i, x in enumerate(inputs):
    tid = plan.input_table_map[i]
    vocab = plan.table_configs[tid].input_dim
    a = np.asarray(x).reshape(-1)
    a = np.minimum(a[a >= 0], vocab - 1)
    hs = plan.hot_sets.get(tid)
    if hs is not None and hs.ids.size:
      pos = np.searchsorted(hs.ids, a)
      safe = np.minimum(pos, hs.ids.size - 1)
      a = a[hs.ids[safe] != a]
    out[i] = a
  return out


def compute_fetch_rows(dist, inputs):
  """The host pre-pass: per (tiered group, owner device), the SORTED
  deduplicated fused-local tail rows this batch's cold exchange will
  gather there.  Returns ``(rows, counts)``."""
  plan = dist.plan
  cold = _cold_ids_per_input(dist, inputs)
  rows: Dict[int, List[np.ndarray]] = {}
  counts: Dict[int, List[int]] = {}
  for gi in plan.cold_tier_groups:
    g = plan.groups[gi]
    res = g.device_rows
    rows[gi] = []
    counts[gi] = []
    for dev in range(plan.world_size):
      parts = []
      for r in g.requests[dev]:
        v = cold[r.input_id]
        mine = v[(v >= r.row_start) & (v < r.row_end)]
        local = r.row_offset + (mine - r.row_start)
        parts.append(local[local >= res])
      u = (np.unique(np.concatenate(parts)).astype(np.int64)
           if parts else np.zeros((0,), np.int64))
      rows[gi].append(u)
      counts[gi].append(int(u.size))
  return rows, counts


def _ensure_caps(dist, counts, global_batch: int):
  """First-batch calibration of the static per-group fetch capacity
  (margin + alignment) — tracked PER GLOBAL BATCH, so every serving
  ladder rung carries its own right-sized fetch shape (design §16); a
  later batch at the same rung needing more rows than the calibrated
  cap REFUSES actionably, naming the bucket, instead of silently
  dropping."""
  caps = dist.fetch_caps_for(global_batch)
  for gi, per_dev in counts.items():
    need = max(per_dev) if per_dev else 0
    cap = caps.get(gi)
    if cap is None:
      cap = max(_FETCH_ALIGN,
                -(-int(need * _FETCH_MARGIN) // _FETCH_ALIGN)
                * _FETCH_ALIGN)
      cap = min(cap, dist.plan.groups[gi].tier_rows)
      cap = max(cap, min(_FETCH_ALIGN, dist.plan.groups[gi].tier_rows))
      caps[gi] = cap
    if need > cap:
      raise ValueError(
          f'cold-tier fetch overflow on group {gi} at batch bucket '
          f'{global_batch}: this batch needs {need} tail rows on one '
          f'device but the bucket\'s static fetch capacity is {cap}. '
          f'Construct the layer with cold_fetch_rows={{{gi}: '
          f'{int(need * _FETCH_MARGIN)}}} (or a larger global value), '
          'or warm the engine on traffic representative of this '
          'bucket, so the buffers are sized for the workload — silent '
          'dropping is never an option (docs/design.md §12, §16).')


def build_fetch(dist, inputs, rows=None) -> ColdFetch:
  """Assemble one batch's device-ready fetch buffers from the tier.

  ``rows``: optional precomputed ``(rows, counts)`` from
  ``compute_fetch_rows`` (the pipelined path — the payload gather
  below must still run AFTER the previous step's writeback)."""
  with obs_trace.span('coldtier/fetch'):
    return _build_fetch(dist, inputs, rows)


def _build_fetch(dist, inputs, rows=None) -> ColdFetch:
  import jax.numpy as jnp
  plan = dist.plan
  tier = dist.cold_tier
  if tier is None:
    return ColdFetch(device={}, rows_np={}, counts={})
  if rows is None:
    rows, counts = compute_fetch_rows(dist, inputs)
  else:
    rows, counts = rows
  global_batch = int(inputs[0].shape[0]) if len(inputs) else 0
  _ensure_caps(dist, counts, global_batch)
  caps = dist.fetch_caps_for(global_batch)
  obs_metrics.inc('coldtier.fetch_rows',
                  sum(sum(per) for per in counts.values()))
  if tier.digests_enabled:
    # fetch-time integrity (design §13): every row about to be gathered
    # is re-hashed against its write-back digest BEFORE it can reach
    # the device — corrupted host-DRAM state fails loudly with
    # provenance, never trains
    bad_all = []
    for gi in plan.cold_tier_groups:
      res = plan.groups[gi].device_rows
      for dev in range(plan.world_size):
        n = counts[gi][dev]
        if not n:
          continue
        bad = tier.verify_rows(gi, dev, rows[gi][dev][:n] - res)
        if bad.size:
          bad_all.append((gi, dev, [int(r) for r in bad[:8]]))
    if bad_all:
      for gi, dev, rws in bad_all:
        resilience.journal('tier_integrity_failure', group=gi,
                           device=dev, rows=rws)
      raise TierIntegrityError(bad_all)
  device = {}
  for gi in plan.cold_tier_groups:
    g = plan.groups[gi]
    res = g.device_rows
    cap = caps[gi]
    D = plan.world_size
    rows_pad = np.full((D, cap), g.rows_cap, np.int32)
    payload = np.zeros((D, cap, g.width), tier.payload[gi].dtype)
    scale = (np.ones((D, cap, 1), np.float32)
             if gi in tier.scale else None)
    opt = {k: np.zeros((D, cap, g.width), v.dtype)
           for k, v in tier.opt[gi].items()}
    for dev in range(D):
      n = counts[gi][dev]
      if not n:
        continue
      idx = rows[gi][dev][:n] - res
      rows_pad[dev, :n] = rows[gi][dev][:n]
      payload[dev, :n] = tier.payload[gi][dev, idx]
      if scale is not None:
        scale[dev, :n] = tier.scale[gi][dev, idx]
      for k in opt:
        opt[k][dev, :n] = tier.opt[gi][k][dev, idx]
    entry = {'rows': jnp.asarray(rows_pad),
             'payload': jnp.asarray(payload)}
    if scale is not None:
      entry['scale'] = jnp.asarray(scale)
    if opt:
      entry['opt'] = {k: jnp.asarray(v) for k, v in opt.items()}
    device[gi] = entry
  return ColdFetch(device=device, rows_np=rows, counts=counts)


def write_back(dist, fetch: ColdFetch, writeback):
  """Store one step's updated tail rows (payload/scale/optimizer rows,
  already re-quantized device-side) into the host tier, aligned with
  the fetch's row lists."""
  with obs_trace.span('coldtier/writeback'):
    _write_back(dist, fetch, writeback)


def _write_back(dist, fetch: ColdFetch, writeback):
  import jax
  tier = dist.cold_tier
  if getattr(tier, 'frozen', False):
    tier._check_writable('write_back')
  for gi, wb in writeback.items():
    g = dist.plan.groups[gi]
    res = g.device_rows
    host = {k: np.asarray(jax.device_get(v)) for k, v in wb.items()
            if k != 'opt'}
    host_opt = {k: np.asarray(jax.device_get(v))
                for k, v in wb.get('opt', {}).items()}
    for dev in range(dist.plan.world_size):
      n = fetch.counts[gi][dev]
      if not n:
        continue
      idx = fetch.rows_np[gi][dev][:n] - res
      if 'payload' in host:
        tier.payload[gi][dev, idx] = host['payload'][dev, :n]
      if 'scale' in host and gi in tier.scale:
        tier.scale[gi][dev, idx] = host['scale'][dev, :n]
      for k, v in host_opt.items():
        tier.opt[gi][k][dev, idx] = v[dev, :n].astype(
            tier.opt[gi][k].dtype)
      # the digest certifies exactly the bytes this write-back landed
      tier.refresh_rows(gi, dev, idx)


# ---------------------------------------------------------------------------
# journaled counters (bench.py; design §12)
# ---------------------------------------------------------------------------


def fetch_stats(dist, fetch: ColdFetch) -> dict:
  """Exact per-batch fetch accounting: rows and bytes crossing
  host->device, per group and total.  The cross-check pinned by
  tests/test_bench_artifact.py: ``cold_tier_fetch_bytes`` equals the
  sum over groups of fetched rows x that group's quantized payload
  row bytes, with scale bytes counted by name alongside."""
  plan = dist.plan
  spec = plan.table_spec
  item = plan.param_itemsize
  per_group_rows = []
  per_group_row_bytes = []
  total_rows = 0
  total_bytes = 0
  total_scale_bytes = 0
  for gi in plan.cold_tier_groups:
    g = plan.groups[gi]
    n = int(sum(fetch.counts.get(gi, [])))
    rb = quantization.payload_bytes_per_row(g.width, spec, item)
    per_group_rows.append(n)
    per_group_row_bytes.append(rb)
    total_rows += n
    total_bytes += n * rb
    if spec is not None:
      total_scale_bytes += n * quantization.SCALE_BYTES
  # fused cold-exchange legs (design §21): the traced LookupPlan's
  # cold id/row wire sizes, when the runtime has traced one — the
  # fetched rows above feed exactly these fused buffers (the cold-tier
  # fetch is the gather stage of the same plan)
  cold_leg_bytes = {}
  cold_leg_dtypes = {}
  for lp in getattr(dist, '_lookup_plans', {}).values():
    for leg in lp.legs:
      if 'cold' in leg.name or leg.name.startswith('dcn/'):
        key = f'{lp.path}:{leg.name}'
        cold_leg_bytes[key] = int(leg.nbytes)
        # §24 wire ledger for the cold legs: the cold row legs are the
        # passthrough candidates (pre-combine rows ship the stored
        # int8/fp8 payload + po2 scale on a 'q8' wire), so the dtype
        # row is the evidence the narrowing actually happened
        cold_leg_dtypes[key] = {'dtype': leg.dtype,
                                'wire': leg.wire,
                                'nbytes': int(leg.nbytes),
                                'payload_nbytes': int(leg.payload_bytes)}
  return {
      'cold_tier_fetch_rows': int(total_rows),
      'cold_tier_fetch_bytes': int(total_bytes),
      'cold_tier_fetch_scale_bytes': int(total_scale_bytes),
      'cold_tier_fetch_rows_per_group': per_group_rows,
      'cold_tier_row_bytes_per_group': per_group_row_bytes,
      'cold_exchange_leg_bytes': cold_leg_bytes,
      'cold_exchange_leg_dtypes': cold_leg_dtypes,
  }


def tier_stats(dist) -> dict:
  """Static tier geometry for the artifact: resident vs host bytes and
  the per-group head/tail row split."""
  plan = dist.plan
  return {
      'cold_tier_groups': list(plan.cold_tier_groups),
      'cold_tier_resident_rows': [
          plan.groups[gi].device_rows for gi in plan.cold_tier_groups
      ],
      'cold_tier_tail_rows': [
          plan.groups[gi].tier_rows for gi in plan.cold_tier_groups
      ],
      'cold_tier_resident_bytes': int(plan.resident_table_bytes()),
      'cold_tier_host_bytes': (int(dist.cold_tier.host_bytes())
                               if dist.cold_tier else 0),
      'device_hbm_budget': plan.device_hbm_budget,
  }


class ColdFetchPipeline:
  """Double-buffer the host fetch pre-pass behind device execution.

  Wraps an iterator of ``cats`` batches; a worker thread runs
  ``compute_fetch_rows`` for batch N+1 while the consumer's device step
  runs batch N.  The payload gather (``build_fetch``) stays on the
  CONSUMER side, after the previous step's writeback landed, so
  prefetching never reads stale tier rows — only the routing/dedup
  (the expensive part) overlaps.

  ``stats()['overlap_pct']`` is DIRECTLY measured: 1 - blocked/build,
  where ``blocked_ms`` is the consumer's wait inside ``__next__`` and
  ``build_ms`` the worker's wall — the same accounting ``CsrFeed``
  journals for the static-CSR host build.
  """

  def __init__(self, dist, cats_iter, depth: int = 2):
    self.dist = dist
    self._q: queue.Queue = queue.Queue(maxsize=max(1, int(depth)))
    # shared blocked-time primitive (obs/metrics.py OverlapStat) —
    # stats() keys unchanged
    self._overlap = obs_metrics.OverlapStat()
    self._err_box: list = []
    self._stop = threading.Event()
    # the producer closes over the QUEUE and the stop event, never over
    # the pipeline itself (the CsrFeed weakref discipline): an abandoned
    # pipeline can be collected, __del__ -> close() sets the stop, and
    # the timed puts below observe it instead of wedging forever on a
    # full ring nobody will drain (detlint concurrency/
    # untimed-put-bounded + thread-no-join)
    q, stop, err_box = self._q, self._stop, self._err_box
    ref = weakref.ref(self)

    def put_or_stop(item) -> bool:
      """The stop-aware bounded put (CsrFeed's timed-put discipline):
      False when the stop flag ended the wait."""
      while not stop.is_set():
        try:
          q.put(item, timeout=0.1)
          return True
        except queue.Full:
          continue
      return False

    def producer():
      try:
        for cats in cats_iter:
          if stop.is_set():
            return
          tok = obs_trace.begin('coldtier/prepass')
          prepped, _, _ = dist._prepare_inputs(list(cats))
          rows = compute_fetch_rows(dist, prepped)
          prepass_ms = obs_trace.end(tok) * 1000.0
          live = ref()
          if live is not None:
            live._overlap.add_build(prepass_ms)
            del live
          obs_metrics.observe('coldtier.prepass_ms', prepass_ms)
          if not put_or_stop((cats, prepped, rows)):
            return
      except BaseException as e:  # surfaced on the consumer side
        err_box.append(e)
      finally:
        put_or_stop(None)

    self._thread = threading.Thread(target=producer, daemon=True,
                                    name='cold-tier-prefetch')
    self._thread.start()

  def __iter__(self):
    return self

  def __next__(self):
    tok = obs_trace.begin('coldtier/wait')
    try:
      while True:
        try:
          item = self._q.get(timeout=0.1)
          break
        except queue.Empty:
          if self._stop.is_set():
            raise StopIteration from None
    finally:
      blocked_ms = obs_trace.end(tok) * 1000.0
    self._overlap.add_blocked(blocked_ms)
    obs_metrics.observe('coldtier.blocked_ms', blocked_ms)
    if item is None:
      if self._err_box:
        raise self._err_box[0]
      raise StopIteration
    cats, prepped, rows = item
    fetch = build_fetch(self.dist, prepped, rows=rows)
    self._overlap.count_batch()
    obs_metrics.inc('coldtier.batches')
    return cats, fetch

  def close(self, join_timeout: float = 30.0):
    """Stop the producer and drain the ring; idempotent.  Pre-passes
    already built but not consumed are discarded."""
    self._stop.set()

    def drain():
      while True:
        try:
          self._q.get_nowait()
        except queue.Empty:
          return

    drain()  # frees a producer blocked mid-put so the join can land
    if join_timeout > 0 and self._thread is not threading.current_thread():
      self._thread.join(timeout=join_timeout)
    # a producer that was ALREADY inside its timed put when the drain
    # freed a slot may have landed one more item before observing the
    # stop flag — drain again after the join so no stale pre-pass can
    # ever be served as live
    drain()

  def __del__(self):
    # an abandoned pipeline (iterator dropped without drain or close)
    # must not leak a producer blocked on the full ring.  NO join here:
    # GC can run on any thread, and waiting for a mid-build pre-pass
    # would stall an unrelated (e.g. serving) thread — the stop flag +
    # the producer's timed puts already guarantee the daemon exits
    try:
      self.close(join_timeout=0.0)
    except Exception:
      pass  # interpreter teardown: module globals may be gone

  def reset_stats(self):
    self._overlap = obs_metrics.OverlapStat()

  def stats(self) -> dict:
    ov = self._overlap
    return {
        'batches': ov.batches,
        'build_ms': round(ov.build_ms, 3),
        'blocked_ms': round(ov.blocked_ms, 3),
        'overlap_pct': round(ov.overlap_frac(), 4),
    }
