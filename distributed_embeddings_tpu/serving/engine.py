"""ServingEngine: a LADDER of compiled lookup-only forwards over a
frozen state.

The device half of serving (docs/design.md §14, §16).  The engine owns
a ``DistributedEmbedding`` built for the SERVING mesh (which is
routinely smaller than the training mesh — the canonical checkpoint
layout reshards on restore), a frozen parameter pytree holding table
leaves only (no optimizer state anywhere in the compiled program), and
a bucketed compiled-shape ladder of forward signatures
``(bucket, hotness)`` for ``bucket`` in ``buckets`` (default the pow-2
ladder ``{B/8, B/4, B/2, B}`` rounded to device multiples): every
lookup launches at the SMALLEST rung that holds its samples, so a
deadline-launched straggler batch of 5 samples no longer pays the
full-width device program.  ``warmup()`` AOT-compiles every rung —
after it returns, a request never eats a mid-serve compile (pinned by
test via ``DistributedEmbedding.compile_count``).

- the read-only hot cache reuses the §10 replicated-buffer forward with
  a serving-sized hot set (``hotcache.serving_hot_sets`` — no optimizer
  copies to fund, so the same HBM budget buys far more coverage);
- the read-only cold tier reuses the §12 host tier fetch-ONLY: row
  digests are armed at load and verified for every fetched row, the
  tier is frozen (any write_back refuses), and the fetch carries no
  optimizer rows because none exist;
- quantized bundles keep their payload narrow end to end: the bundle's
  payload+scale slices straight into the serving shards
  (``checkpoint.set_weights``'s §12 identity fast path) and every
  lookup dequantizes at the gather exactly as in training — so serving
  output is bit-exact vs the training forward (hotness-1; multi-hot
  within the pinned 1e-6 fold-order bound).
"""

from __future__ import annotations

import threading

from typing import List, Optional, Sequence

import numpy as np

from distributed_embeddings_tpu.obs import metrics as obs_metrics
from distributed_embeddings_tpu.obs import trace as obs_trace
from distributed_embeddings_tpu.parallel import checkpoint
from distributed_embeddings_tpu.parallel import mesh as mesh_lib
from distributed_embeddings_tpu.parallel.dist_embedding import (
    DistributedEmbedding)


def default_bucket_ladder(batch_size: int, denom: int):
  """The default compiled-shape ladder for one engine batch: the pow-2
  rungs ``{B/8, B/4, B/2, B}``, each rounded UP to a multiple of the
  device count ``denom`` and clamped to ``[denom, B]`` (design §16).
  Duplicate rungs collapse, so tiny batches degrade gracefully toward
  the monolithic single-signature engine."""
  batch_size = int(batch_size)
  denom = max(1, int(denom))
  rungs = set()
  for shift in (3, 2, 1, 0):
    raw = max(1, batch_size >> shift)
    rung = -(-raw // denom) * denom          # round up to device multiple
    rungs.add(min(max(rung, denom), batch_size))
  rungs.add(batch_size)
  return tuple(sorted(rungs))


def _resolve_bundle_dtype(weights) -> Optional[str]:
  """'auto' table_dtype: serve a uniformly quantized bundle at its own
  narrow dtype (rows never widen on device); anything else — plain f32
  entries or mixed dtypes — serves as f32 (dequantization is exact,
  §12), which is the safe default, never a silent narrowing."""
  if not weights:
    return None
  names = set()
  for w in weights:
    if not isinstance(w, checkpoint.QuantizedWeight):
      return None
    names.add(w.dtype_name)
  return names.pop() if len(names) == 1 else None


class ServingEngine:
  """Lookup-only inference runtime over a frozen table set.

  Args:
    table_configs: the model's ``TableConfig`` list (bundle-embedded
      configs via ``from_bundle``).
    weights: global canonical per-table entries (arrays, ``.npy`` paths
      or ``QuantizedWeight`` pairs) — what ``load_serving_bundle``
      returns.
    batch_size: the LARGEST static device batch (the top ladder rung);
      must be a multiple of the serving mesh's device count.  The
      dynamic batcher fills it from concurrent requests; smaller
      requests launch at the smallest ladder rung that holds them
      (``lookup_padded``).
    buckets: the compiled-shape ladder — batch-size rungs every lookup
      snaps up to (design §16).  ``None`` (default) builds the pow-2
      ladder ``default_bucket_ladder(batch_size, device_count)``; pass
      an explicit sequence (each rung a positive device-count multiple
      ``<= batch_size``; the full rung is always included) to shrink
      or widen it, e.g. ``buckets=(batch_size,)`` for the monolithic
      single-signature engine.
    mesh / axis_name: serving mesh (default: all local devices).
    input_table_map: as in ``DistributedEmbedding``.
    hotness: per-input static hot caps (default 1 per input) — the one
      compiled signature's trailing dims; requests with fewer ids pad
      with ``-1``, more refuse.
    hot_sets: serving-sized read-only hot sets
      (``hotcache.serving_hot_sets``); hot rows replicate per device
      and are served with zero exchange.
    table_dtype: ``'auto'`` (default) serves a uniformly quantized
      bundle at its own narrow dtype; ``None``/'int8'/'float8_e4m3'
      force a storage dtype.
    cold_tier / device_hbm_budget / cold_fetch_rows: §12 tiering for
      tables beyond serving HBM — fetch-only here: digests are armed
      (``verify_tier_digests``) and the tier is frozen, so damaged
      host rows refuse before reaching the device and nothing can
      write back.
    fused_exchange: ship all groups' buffers through ONE fused
      collective per exchange phase (design §21; default on) — the
      serving ``compile_lookup`` program is a stage implementation
      over the same ``LookupPlan`` as training, so ``lookup_plan()``
      exposes each rung's traced fused schedule.
    wire_dtype: per-leg wire compression for the fused exchange
      (design §24) — ``None`` (default, f32 wire), ``'bfloat16'``
      (rows cross at bf16; quantized pre-combine rows ship their
      stored payload + po2 scale, bit-exact), or ``'table'``
      (passthrough only — fully bit-exact serving at the narrow
      wire; requires a quantized ``table_dtype``).
    compute_dtype / lookup_impl / strategy / column_slice_threshold /
      row_slice: as in ``DistributedEmbedding``.

  ``warmup()`` compiles EVERY ladder rung (and, for tiered plans
  without explicit ``cold_fetch_rows``, calibrates each rung's static
  fetch capacity from a representative — or uniform-random, which
  over-provisions — sample batch).
  """

  def __init__(self, table_configs, weights, *, batch_size: int,
               mesh=None, axis_name: str = mesh_lib.DEFAULT_AXIS,
               input_table_map: Optional[Sequence[int]] = None,
               hotness: Optional[Sequence[int]] = None,
               buckets: Optional[Sequence[int]] = None,
               hot_sets=None,
               table_dtype='auto',
               compute_dtype=None,
               lookup_impl: str = 'auto',
               strategy: str = 'basic',
               column_slice_threshold: Optional[int] = None,
               row_slice=None,
               cold_tier: bool = False,
               device_hbm_budget: Optional[int] = None,
               cold_fetch_rows=None,
               fused_exchange: bool = True,
               wire_dtype: Optional[str] = None,
               verify_tier_digests: bool = True,
               bundle_meta: Optional[dict] = None):
    weights = list(weights)
    if table_dtype == 'auto':
      table_dtype = _resolve_bundle_dtype(weights)
    self.dist = DistributedEmbedding(
        list(table_configs),
        strategy=strategy,
        column_slice_threshold=column_slice_threshold,
        row_slice=row_slice,
        dp_input=True,
        input_table_map=input_table_map,
        mesh=mesh,
        axis_name=axis_name,
        lookup_impl=lookup_impl,
        compute_dtype=compute_dtype,
        hot_cache=hot_sets,
        table_dtype=table_dtype,
        cold_tier=cold_tier,
        device_hbm_budget=device_hbm_budget,
        cold_fetch_rows=cold_fetch_rows,
        fused_exchange=fused_exchange,
        wire_dtype=wire_dtype)
    denom = self.dist.world_size * self.dist.num_slices
    batch_size = int(batch_size)
    if batch_size < 1 or batch_size % denom:
      raise ValueError(
          f'batch_size {batch_size} must be a positive multiple of the '
          f'serving mesh device count {denom} (the one compiled '
          'signature is a static device batch)')
    self.batch_size = batch_size
    if buckets is None:
      self.buckets = default_bucket_ladder(batch_size, denom)
    else:
      rungs = {int(b) for b in buckets}
      rungs.add(batch_size)  # the full rung must exist (max_batch)
      for b in sorted(rungs):
        if b < 1 or b % denom or b > batch_size:
          raise ValueError(
              f'bucket {b} must be a positive multiple of the serving '
              f'mesh device count {denom}, <= batch_size {batch_size} '
              '(every ladder rung is a static device batch — '
              'docs/design.md §16)')
      self.buckets = tuple(sorted(rungs))
    self._bucket_set = frozenset(self.buckets)
    self.hotness = tuple(
        int(h) for h in (hotness if hotness is not None
                         else (1,) * self.dist.num_inputs))
    if len(self.hotness) != self.dist.num_inputs:
      raise ValueError(
          f'hotness has {len(self.hotness)} entries for '
          f'{self.dist.num_inputs} inputs')
    self.params = checkpoint.set_weights(self.dist, weights)
    if self.dist.cold_tier is not None:
      # read-only tier contract (design §14): every fetched row is
      # digest-verified, and nothing may write back
      if verify_tier_digests:
        self.dist.cold_tier.enable_digests()
      self.dist.cold_tier.freeze()
    self.output_dims = [
        self.dist.table_configs[tid].output_dim
        for tid in self.dist.plan.input_table_map
    ]
    self.bundle_meta = bundle_meta
    self._warm = False
    self._lock = threading.Lock()
    self._batches_served = 0
    self._samples_served = 0
    # bucket-ladder padding accounting (design §16): rows each launch
    # actually paid for vs the sentinel-padding rows among them, plus
    # per-rung launch counts — what the bench's serve_pad_waste_pct
    # and per-bucket keys read
    self._rows_launched = 0
    self._pad_rows = 0
    self._bucket_launches = {b: 0 for b in self.buckets}
    # the serving hot sets, kept for the degraded-mode hot-only filter
    # (design §23); per-table membership masks build lazily on first
    # degraded serve — an engine that never degrades pays nothing
    self._hot_sets = dict(hot_sets) if hot_sets else {}
    self._hot_members: dict = {}

  @classmethod
  def from_bundle(cls, path: str, *, table_configs=None, **kwargs
                  ) -> 'ServingEngine':
    """Build an engine from an exported bundle.  ``table_configs``
    overrides (or supplies, for bundles exported without embedded
    configs) the per-table meta."""
    from distributed_embeddings_tpu.serving.export import (
        load_serving_bundle)
    weights, meta = load_serving_bundle(path)
    configs = table_configs if table_configs is not None \
        else meta['table_configs']
    if configs is None:
      raise ValueError(
          f'{path}: bundle carries no embedded table configs (exported '
          'without table_configs) — pass table_configs= explicitly.')
    return cls(configs, weights, bundle_meta=meta, **kwargs)

  # ---------------------------------------------------------------- lookup

  def hot_only_filter(self, cats):
    """Degraded-mode accuracy filter (docs/design.md §23): mask every
    id OUTSIDE the serving hot sets to the ``-1`` pad sentinel, so the
    request serves entirely from the replicated hot cache — no cold
    exchange, no cold-tier fetch — at an EXPLICIT accuracy cost (a
    dropped id contributes nothing to its sample's combine, exactly
    like a pad slot).  Returns ``(filtered, dropped, total)``:
    the filtered per-input arrays plus the dropped/total valid-id
    counts the caller journals.  Inputs whose table has no hot set
    (or an engine built without ``hot_sets``) pass through unfiltered
    — the pool only degrades when ``hot_filter_available``."""
    out = []
    dropped = 0
    total = 0
    for i, c in enumerate(cats):
      c = np.asarray(c)
      valid = c >= 0
      n_valid = int(valid.sum())
      total += n_valid
      tid = int(self.dist.plan.input_table_map[i])
      hs = self._hot_sets.get(tid)
      if hs is None or n_valid == 0:
        out.append(c)
        continue
      member = self._hot_members.get(tid)
      if member is None:
        rows = int(self.dist.table_configs[tid].input_dim)
        member = np.zeros(rows, bool)
        ids = np.asarray(getattr(hs, 'ids', hs), np.int64)
        member[ids[(ids >= 0) & (ids < rows)]] = True
        self._hot_members[tid] = member
      keep = np.zeros(c.shape, bool)
      idx = np.clip(c[valid].astype(np.int64), 0, member.size - 1)
      keep[valid] = member[idx]
      dropped += n_valid - int(keep.sum())
      out.append(np.where(keep, c, -1).astype(c.dtype))
    return out, dropped, total

  @property
  def hot_filter_available(self) -> bool:
    """True when this engine can serve degraded hot-only traffic (it
    was built with serving hot sets; design §23)."""
    return bool(self._hot_sets)

  def bucket_for(self, n: int) -> int:
    """The SMALLEST ladder rung holding ``n`` samples (design §16) —
    the shape every lookup/launch snaps up to."""
    n = int(n)
    if n > self.batch_size:
      raise ValueError(
          f'request of {n} samples exceeds the engine batch '
          f'{self.batch_size}: split the request or build the engine '
          'with a larger batch_size')
    for b in self.buckets:
      if b >= n:
        return b
    return self.batch_size  # unreachable: buckets always include B

  def _pad_input(self, i: int, x, width: Optional[int] = None
                 ) -> np.ndarray:
    """One input padded to the compiled ``[width(, hot_cap)]`` rung
    signature (``-1`` sentinel = no id, dropped by every lookup path).
    ``width`` defaults to the full batch."""
    x = np.asarray(x)
    h = self.hotness[i]
    width = self.batch_size if width is None else int(width)
    # already at the compiled rung signature (the batcher's merged
    # buffers, or lookup_padded's own padding): no second alloc+copy
    # on the per-batch hot path
    if (x.dtype == np.int32
        and ((h == 1 and x.shape == (width,))
             or (h > 1 and x.shape == (width, h)))):
      return x
    x2 = x[:, None] if x.ndim == 1 else x
    if x2.ndim != 2:
      raise ValueError(f'input {i}: expected 1-D or 2-D ids, '
                       f'got shape {x.shape}')
    if x2.shape[1] > h:
      raise ValueError(
          f'input {i}: request hotness {x2.shape[1]} exceeds the '
          f'compiled hot cap {h} — build the engine with '
          f'hotness[{i}] >= {x2.shape[1]}')
    n = x2.shape[0]
    if n > width:
      raise ValueError(
          f'input {i}: {n} samples exceed the launch bucket {width}')
    buf = np.full((width, h), -1, np.int32)
    buf[:n, :x2.shape[1]] = x2
    return buf[:, 0] if h == 1 else buf

  def lookup(self, cats, samples: Optional[int] = None) -> List:
    """One device lookup at a compiled ladder-rung signature.

    ``cats``: per-input ``[bucket]`` / ``[bucket, h<=cap]`` id arrays
    (``-1`` padding) whose leading dim is a ladder rung (``buckets``).
    ``samples``: the REAL sample count inside the rung (the rest being
    sentinel padding) — callers that padded (``lookup_padded``, the
    batcher) thread it through so ``samples_served``/``engine.samples``
    count served samples, never padding; ``None`` counts the full rung
    (an un-padded direct call).  Returns the per-input
    ``[bucket, output_dim]`` activations (jax arrays — callers demuxing
    to hosts ``np.asarray`` them once per batch)."""
    cats = list(cats)
    if len(cats) != self.dist.num_inputs:
      raise ValueError(f'expected {self.dist.num_inputs} inputs, '
                       f'got {len(cats)}')
    b = int(np.asarray(cats[0]).shape[0]) if cats else 0
    for x in cats:
      if np.asarray(x).shape[0] != b:
        raise ValueError(
            f'inputs disagree on batch: {np.asarray(x).shape[0]} vs '
            f'{b}')
    if b not in self._bucket_set:
      raise ValueError(
          f'batch {b} is not a compiled ladder rung {self.buckets} — '
          'pad requests to a rung (lookup_padded picks the smallest '
          'fitting one) or batch them (DynamicBatcher)')
    real = b if samples is None else int(samples)
    if not 0 <= real <= b:
      raise ValueError(f'samples {real} outside [0, bucket {b}]')
    # ONE measurement feeds both the span and the histogram (the
    # trace-vs-stats agreement contract, obs/trace.py)
    tok = obs_trace.begin('serve/lookup', batch=b)
    try:
      padded = [self._pad_input(i, x, b) for i, x in enumerate(cats)]
      outs = self.dist.apply(self.params, padded)
    finally:
      lookup_ms = obs_trace.end(tok) * 1000.0
    with self._lock:
      self._batches_served += 1
      self._samples_served += real
      self._rows_launched += b
      self._pad_rows += b - real
      self._bucket_launches[b] += 1
    obs_metrics.inc('engine.lookups')
    obs_metrics.inc('engine.samples', real)
    obs_metrics.inc('engine.rows_launched', b)
    obs_metrics.inc('engine.pad_rows', b - real)
    obs_metrics.observe('engine.lookup_ms', lookup_ms)
    return list(outs)

  def lookup_padded(self, cats) -> List[np.ndarray]:
    """One request (``n <= batch_size`` samples) through the smallest
    compiled rung that holds it: pad with ``-1`` sentinel samples to
    the rung, run, slice ``[:n]``.  The no-batching serving arm — and
    the per-request reference the batcher's demux is pinned bit-exact
    against at every ladder rung."""
    cats = list(cats)
    n = int(np.asarray(cats[0]).shape[0]) if cats else 0
    if n == 0:
      return [np.zeros((0, d), np.float32) for d in self.output_dims]
    bucket = self.bucket_for(n)
    padded = [self._pad_input(i, x, bucket) for i, x in enumerate(cats)]
    outs = self.lookup(padded, samples=n)
    return [np.asarray(o)[:n] for o in outs]

  def warmup(self, sample_cats=None, seed: int = 0) -> 'ServingEngine':
    """AOT-compile EVERY ladder rung (idempotent) — after ``warmup``
    returns, no request can eat a mid-serve compile (design §16; the
    pin reads ``dist.compile_count`` across warmed traffic).

    ``sample_cats`` (a representative full batch) drives the compiles
    — and, on cold-tier plans without explicit ``cold_fetch_rows``,
    calibrates each rung's static fetch capacity from its leading
    slice, so pass REAL traffic there when you can.  Without a sample,
    uniform-random ids over each full vocabulary are used: they touch
    MORE distinct tail rows than any skewed real stream, so the
    calibrated capacity over-provisions rather than under- (a
    too-small cap would refuse mid-serve)."""
    if self._warm:
      return self
    if sample_cats is None:
      rng = np.random.default_rng(seed)
      sample_cats = []
      for i, tid in enumerate(self.dist.plan.input_table_map):
        vocab = self.dist.table_configs[tid].input_dim
        h = self.hotness[i]
        shape = (self.batch_size,) if h == 1 else (self.batch_size, h)
        sample_cats.append(
            rng.integers(0, vocab, size=shape).astype(np.int32))
    sample_cats = [np.asarray(c) for c in sample_cats]
    if int(sample_cats[0].shape[0]) < self.batch_size:
      # a short sample still warms every rung: tile it up to the full
      # batch so each rung's slice below is non-degenerate
      reps = -(-self.batch_size // int(sample_cats[0].shape[0]))
      sample_cats = [
          np.concatenate([c] * reps, axis=0)[:self.batch_size]
          for c in sample_cats
      ]
    for bucket in sorted(self.buckets, reverse=True):
      self.lookup_padded([c[:bucket] for c in sample_cats])
    self._warm = True
    return self

  def compiled(self, bucket: Optional[int] = None):
    """The underlying cached jitted forward for one rung signature
    (``DistributedEmbedding.compile_lookup``; the full batch by
    default) — introspection/AOT hook; plain serving goes through
    ``lookup``."""
    return self.dist.compile_lookup(
        self.batch_size if bucket is None else int(bucket),
        self.hotness)

  def lookup_plan(self, bucket: Optional[int] = None):
    """The traced ``LookupPlan`` of one rung's compiled forward
    (design §21): the fused exchange legs, their per-group offset
    tables and on-wire bytes — what the graphlint ledger's serve
    entries are the compiled mirror of.  Rungs trace on first launch
    (``warmup``), so call after warming."""
    return self.dist.lookup_plan(
        global_batch=self.batch_size if bucket is None else int(bucket))

  def stats(self) -> dict:
    with self._lock:
      launched = self._rows_launched
      return {
          'batches_served': self._batches_served,
          'samples_served': self._samples_served,
          'batch_size': self.batch_size,
          'buckets': list(self.buckets),
          'bucket_launches': dict(self._bucket_launches),
          'rows_launched': launched,
          'pad_rows': self._pad_rows,
          'pad_waste_pct': (round(100.0 * self._pad_rows / launched, 3)
                            if launched else None),
          'world_size': self.dist.world_size,
          'hot_cache': bool(self.dist.hot_enabled),
          'cold_tier': self.dist.cold_tier is not None,
          'fused_exchange': bool(self.dist.fused_exchange),
          'wire_dtype': self.dist.wire_dtype,
          'table_dtype': (self.dist.quant.name
                          if self.dist.quant else None),
      }
