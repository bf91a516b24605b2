"""SparseCore lookup path: static-CSR preprocessing + executable emulation.

This is the host/SPMD side of the SparseCore offload designed in
docs/design.md §8, implemented end to end so every stage runs and is
testable on the faked 8-device CPU mesh today; only the final custom-call
binding (``custom_call_lookup``) stays hardware-gated behind the ONE
adapter seam at the bottom of this file.  The lookup only: a layer built
with it trains through the sparse apply every layer takes
(``parallel/sparse.py``).

The SparseCore contract (TPU v4 paper, arXiv:2304.01433 §3; the
jax-tpu-embedding surface): tables are MOD-sharded over
``num_chips * num_sc`` partitions (``ShardingPlan(mod_sharding=True)``
emits the device-level windows; this module handles the per-device SC
tile split), and lookups arrive as statically-shaped partition-sorted CSR
buffers built host-side:

- ``row_pointers``: per-partition end offsets into the id buffers,
- ``embedding_ids``: partition-LOCAL row ids (``local_row // num_sc``),
- ``sample_ids``: which output row each id contributes to,
- ``gains``: per-id multiplier (1 for 'sum'; 1/count carries 'mean'),

padded to a calibrated ``max_ids_per_partition`` (8-aligned, SC's f32
lane granularity).  Two builders produce the SAME logical content:

- ``build_csr_host``: pure NumPy, the real per-batch host preprocessing
  whose ms/batch cost the bench measures and journals (the
  "including preprocessing" term of the v5p projection,
  docs/perf_notes.md);
- ``csr_from_routed``: the traced XLA twin the EMULATION backend uses
  inside the jitted train step (flat exact-capacity variant: padding is
  a hardware buffer-sizing concern, not a semantics one).

The emulation backend then executes the buffers with TensorCore XLA ops
(``emulated_lookup``): gather at the CSR's reconstituted fused rows,
scatter back to the dense (sample, hot) grid, and run the SHARED
combine tail (``dist_embedding._combine_rows``) — identical masking
and summation order to the TensorCore path, hence bit-identical f32
outputs (the equivalence fuzz asserts exact equality).

Requesting the real binding without the library always raises the
contract error below — never a silent fallback to TensorCore or to the
emulation on a TPU backend, where a "SparseCore" measurement must never
secretly be something else.
"""

from __future__ import annotations

import os
import threading
import time

from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Groups the SparseCore path declines, staying on the TensorCore paths
# (docs/design.md §8 #3): combiner=None pass-through (SC is a reducing
# engine) and very wide rows (SC tile SRAM holds rows up to a few
# hundred lanes; 256 is the conservative published bound).
SC_WIDTH_LIMIT = 256

_CONTRACT_MSG = (
    "lookup_impl='sparsecore' custom-call backend requires SparseCore "
    "hardware (v5p/v6e) and the jax-tpu-embedding custom-call surface "
    "(tpu_sparse_dense_matmul / tpu_sparse_dense_matmul_grad_with_*), "
    "which are not present. The host/SPMD side — mod-sharded planner, "
    "static-CSR preprocessing, executable emulation backend — runs "
    "everywhere: pass sparsecore_backend='emulate' for functional work "
    "on TensorCore/CPU backends, or install jax-tpu-embedding on SC "
    "hardware for the real binding. See docs/design.md §8.")


class StaticCsr(NamedTuple):
  """Statically-shaped partition-sorted CSR buffers for one (device,
  group, hotness-class) lookup.  All fields are arrays (the tuple is a
  pytree, so it flows through jit/shard_map); ``num_sc`` travels as a
  Python-level argument to the consumers.

  ``hot_ids`` is an EMULATION-ONLY auxiliary (the hardware ABI carries
  only the first four buffers): it lets the emulated forward scatter
  entries back onto the dense (sample, hot) grid for the bit-exact
  shared combine tail.
  """
  row_pointers: jax.Array   # [num_sc] end offsets per partition
  embedding_ids: jax.Array  # [N] partition-local row ids (row // num_sc)
  sample_ids: jax.Array     # [N] output row; == num_samples marks padding
  gains: jax.Array          # [N] f32 multiplier (0 at padding)
  partition_ids: jax.Array  # [N] partition of each entry (num_sc = pad)
  hot_ids: jax.Array        # [N] hot-axis position (emulation aux)


def group_supported(table_aval, combiner: Optional[str],
                    hotness: int) -> bool:
  """Per-group SparseCore eligibility — the measurement-style gate the
  ``_lookup`` dispatch applies, mirroring ``pallas_lookup.supported``.
  Unsupported groups keep the TensorCore paths (by design, not as a
  silent substitute for the whole layer)."""
  del hotness  # any hotness routes through the CSR transform
  if combiner not in ('sum', 'mean'):
    return False  # pass-through (combiner=None) stays on TensorCore
  if table_aval.shape[1] > SC_WIDTH_LIMIT:
    return False  # very-wide rows stay on TensorCore
  # SC accumulates f32; bf16 tables would need the pair-fetch layout the
  # hardware does not expose through this surface.  Per-row-scaled
  # QUANTIZED payloads (int8 / float8_e4m3, design §12) qualify for the
  # EMULATION: its gather dequantizes to f32 before the combine (the
  # custom_call backend refuses them at the dispatch — the hardware
  # binding's table contract is f32).
  dt = jnp.dtype(table_aval.dtype)
  if dt == jnp.float32:
    return True
  try:
    from distributed_embeddings_tpu.parallel.quantization import (
        resolve_table_dtype)
    return resolve_table_dtype(dt) is not None
  except ValueError:
    return False


def engaged_groups(plan, param_dtype) -> List[int]:
  """Indices of the plan's fusion groups the SC lookup path serves at
  ``param_dtype`` — the ONE definition of "engaged" shared by the
  layer's zero-engagement guard (``DistributedEmbedding.__init__``) and
  the bench artifact label, so the two can never disagree about which
  groups actually take the SC path.  Quantized plans (design §12) are
  judged at their STORAGE dtype: the emulation dequantizes at the
  gather, so int8/fp8 groups stay engaged."""
  spec = getattr(plan, 'table_spec', None)
  dt = jnp.dtype(spec.dtype) if spec is not None else jnp.dtype(param_dtype)
  return [
      gi for gi, g in enumerate(plan.groups)
      if g.storage_pack == 1 and group_supported(
          jax.ShapeDtypeStruct((g.rows_cap, g.width), dt), g.combiner, 1)
  ]


# --------------------------------------------------------------------------
# backend resolution
# --------------------------------------------------------------------------


def custom_call_available() -> bool:
  """Whether the jax-tpu-embedding custom-call surface is importable."""
  try:
    import jax_tpu_embedding  # noqa: F401
  except ImportError:
    return False
  return True


def resolve_backend(requested: str, platform: Optional[str] = None) -> str:
  """Resolve 'auto' | 'emulate' | 'custom_call' to a concrete backend.

  'auto' picks the real binding when the library is importable on a TPU
  backend; on non-TPU backends it picks the executable emulation (the
  functional testbed this module exists for).  On a TPU backend WITHOUT
  the library it raises: a TPU measurement labelled sparsecore must
  never silently be the emulation (same discipline as the stub this
  module replaces — never a silent fallback).
  """
  if requested not in ('auto', 'emulate', 'custom_call'):
    raise ValueError(f'Unknown sparsecore backend {requested!r}')
  if requested == 'emulate':
    return 'emulate'
  if requested == 'custom_call':
    if not custom_call_available():
      raise NotImplementedError(_CONTRACT_MSG)
    return 'custom_call'
  platform = platform if platform is not None else jax.default_backend()
  if platform == 'tpu':
    if custom_call_available():
      return 'custom_call'
    raise NotImplementedError(_CONTRACT_MSG)
  return 'emulate'


# --------------------------------------------------------------------------
# COO -> partition-sorted static CSR: traced (XLA) builder
# --------------------------------------------------------------------------


def csr_from_routed(routed: jax.Array, rows_cap: int, num_sc: int,
                    combiner: Optional[str] = 'sum') -> StaticCsr:
  """Traced COO -> partition-sorted static-CSR transform.

  ``routed``: ``[n_cap, GB, h]`` fused local-row ids from ``_route_ids``
  (values ``>= rows_cap`` mark padding).  Each valid position becomes a
  COO entry ``(sample = slot*GB + b, id, gain)``; entries sort stably by
  SC partition ``id % num_sc`` (padding to the back), local ids divide
  by ``num_sc``.  This is the flat exact-capacity variant (buffer length
  = the static stream length): per-partition padding to
  ``max_ids_per_partition`` is how the HARDWARE buffers are sized
  (``build_csr_host``), not a semantics difference — the logical
  content, section by section, is identical and the tests assert it.
  """
  n_cap, gb, h = routed.shape
  samples = n_cap * gb
  flat = routed.reshape(-1).astype(jnp.int32)
  valid = flat < rows_cap
  part = jnp.where(valid, flat % num_sc, num_sc).astype(jnp.int32)
  order = jnp.argsort(part, stable=True).astype(jnp.int32)
  part_sorted = part[order]
  rows_sorted = flat[order]
  sample = order // h
  hot = order % h
  valid_sorted = valid[order]
  if combiner == 'mean':
    counts = jnp.sum(valid.reshape(samples, h), axis=1)
    gain_per_sample = 1.0 / jnp.maximum(counts, 1).astype(jnp.float32)
    gains = jnp.where(valid_sorted, gain_per_sample[sample], 0.0)
  else:
    gains = jnp.where(valid_sorted, 1.0, 0.0)
  return StaticCsr(
      row_pointers=jnp.searchsorted(
          part_sorted, jnp.arange(num_sc, dtype=jnp.int32),
          side='right').astype(jnp.int32),
      embedding_ids=jnp.where(valid_sorted, rows_sorted // num_sc,
                              rows_cap).astype(jnp.int32),
      sample_ids=jnp.where(valid_sorted, sample, samples).astype(jnp.int32),
      gains=gains,
      partition_ids=part_sorted,
      hot_ids=hot.astype(jnp.int32),
  )


# --------------------------------------------------------------------------
# COO -> partition-sorted static CSR: NumPy host builder (the real feed)
# --------------------------------------------------------------------------


class HostCsr(NamedTuple):
  """Padded per-partition CSR buffers, the hardware feed layout: section
  ``p`` occupies ``[p*cap, p*cap + count_p)`` of each buffer (``cap`` =
  8-aligned ``max_ids_per_partition``), ``row_pointers[p]`` is the
  section's end offset, padding slots hold sentinel ids / one-past
  sample ids / zero gains.  ``dropped`` counts entries past a
  partition's capacity (0 under a correctly calibrated cap; the bench
  journals it so an undersized cap is visible, never silent)."""
  row_pointers: np.ndarray   # [num_sc]
  embedding_ids: np.ndarray  # [num_sc * cap]
  sample_ids: np.ndarray     # [num_sc * cap]
  gains: np.ndarray          # [num_sc * cap]
  max_ids_per_partition: int
  dropped: int


def _round_up8(x: int) -> int:
  return -(-int(x) // 8) * 8


def build_csr_host(routed: np.ndarray, rows_cap: int, num_sc: int,
                   combiner: Optional[str] = 'sum',
                   max_ids_per_partition: Optional[int] = None) -> HostCsr:
  """NumPy twin of ``csr_from_routed`` producing the PADDED hardware
  layout.  Vectorised throughout — this is the per-batch host cost the
  bench measures (``measure_preprocess_ms``), so it must be the fast
  path, not a reference loop.

  ``max_ids_per_partition``: per-partition static capacity (8-aligned
  internally); ``None`` sizes to the batch's worst partition (never
  drops).  Calibrate with ``calibrate_max_ids_per_partition``.
  """
  n_cap, gb, h = routed.shape
  samples = n_cap * gb
  flat = np.ascontiguousarray(routed, dtype=np.int32).reshape(-1)
  valid = flat < rows_cap
  part = np.where(valid, flat % num_sc, num_sc).astype(np.int32)
  order = np.argsort(part, kind='stable').astype(np.int32)
  part_sorted = part[order]
  ends = np.searchsorted(part_sorted, np.arange(num_sc), side='right')
  starts = np.concatenate([[0], ends[:-1]])
  counts = ends - starts
  cap = _round_up8(max_ids_per_partition if max_ids_per_partition
                   is not None else max(int(counts.max(initial=0)), 1))
  kept = np.minimum(counts, cap)
  dropped = int((counts - kept).sum())
  # rank of each valid sorted entry within its partition; keep the
  # first `cap` of every partition (the rest are the `dropped` count)
  nvalid = int(counts.sum())
  rank = np.arange(nvalid) - np.repeat(starts, counts)
  keep = rank < cap
  src = order[:nvalid][keep]
  dst = part_sorted[:nvalid][keep].astype(np.int64) * cap + rank[keep]
  eids = np.full(num_sc * cap, rows_cap, np.int32)
  sids = np.full(num_sc * cap, samples, np.int32)
  gains = np.zeros(num_sc * cap, np.float32)
  eids[dst] = flat[src] // num_sc
  sids[dst] = src // h
  if combiner == 'mean':
    cnt = np.maximum(valid.reshape(samples, h).sum(axis=1), 1)
    gains[dst] = 1.0 / cnt[src // h].astype(np.float32)
  else:
    gains[dst] = 1.0
  return HostCsr(
      row_pointers=(np.arange(num_sc) * cap + kept).astype(np.int32),
      embedding_ids=eids, sample_ids=sids, gains=gains,
      max_ids_per_partition=cap, dropped=dropped)


def native_available() -> bool:
  """Whether the C++ builder (cc/csr_builder.cc via csr_native) loads on
  this host — building it on first call when a toolchain exists."""
  from distributed_embeddings_tpu.parallel import csr_native
  return csr_native.available()


def resolve_builder(native: str = 'auto') -> str:
  """Resolve the host-builder request 'auto' | 'native' | 'numpy' to the
  concrete builder.  'auto' takes the C++ builder when it loads (the
  production feed path, ~10-20x the NumPy transform on this host) and
  falls back to NumPy otherwise; 'native' raises when unavailable so a
  measurement labelled native can never silently be NumPy."""
  if native not in ('auto', 'native', 'numpy'):
    raise ValueError(f'unknown csr builder mode {native!r}')
  if native == 'numpy':
    return 'numpy'
  if native_available():
    return 'native'
  if native == 'native':
    raise RuntimeError(
        'native CSR builder requested but cc/libdetcsr.so is not '
        'buildable/loadable on this host (make -C '
        'distributed_embeddings_tpu/cc)')
  return 'numpy'


def build_csr(routed: np.ndarray, rows_cap: int, num_sc: int,
              combiner: Optional[str] = 'sum',
              max_ids_per_partition: Optional[int] = None,
              native: str = 'auto') -> HostCsr:
  """The ONE builder entry the host feed uses: the native C++ twin when
  built, else the NumPy oracle (``build_csr_host``) — bit-identical
  output either way (fuzzed in tests/test_csr_native.py)."""
  if resolve_builder(native) == 'native':
    from distributed_embeddings_tpu.parallel import csr_native
    return csr_native.build_csr(routed, rows_cap, num_sc, combiner,
                                max_ids_per_partition)
  return build_csr_host(routed, rows_cap, num_sc, combiner,
                        max_ids_per_partition)


# The (group, device) build jobs are embarrassingly parallel and the
# native builder releases the GIL for the whole call, so shared thread
# pools (one per requested size, process-lifetime, lock-guarded
# creation) parallelise every feed on this host: CsrFeed's producer
# calls this per BATCH, so pools must never be created/torn down on
# that hot path.  The default size is the core count (capped): the
# build is CPU-bound, more threads only contend.
_POOLS: Dict[int, ThreadPoolExecutor] = {}
_POOL_LOCK = threading.Lock()


def default_build_workers() -> int:
  return max(1, min(8, os.cpu_count() or 1))


def _worker_pool(num_workers: Optional[int] = None) -> ThreadPoolExecutor:
  size = num_workers if num_workers else default_build_workers()
  with _POOL_LOCK:
    pool = _POOLS.get(size)
    if pool is None:
      pool = _POOLS[size] = ThreadPoolExecutor(
          max_workers=size, thread_name_prefix=f'csr-build-{size}')
    return pool


# --------------------------------------------------------------------------
# executable emulation backend
# --------------------------------------------------------------------------


def emulated_lookup(table: jax.Array, routed: jax.Array,
                    combiner: Optional[str], compute_dtype,
                    num_sc: int, scale=None) -> jax.Array:
  """Executable TensorCore emulation of ``tpu_sparse_dense_matmul``.

  ``table``: ``[rows_cap, w]`` natural fused shard; ``routed``:
  ``[n_cap, GB, h]`` (``_route_ids`` output).  Pipeline: the traced CSR
  transform, then ONE gather at the partition-reconstituted fused rows
  (``eid * num_sc + partition`` — the emulation keeps the natural row
  layout and reconstitutes; hardware stores partition-major), ONE
  scatter back onto the dense (sample, hot) grid (indices unique by
  construction), and the combine tail SHARED with the TensorCore path
  (``_combine_rows``) — identical masking and h-axis summation order,
  so the output is bit-identical f32 to ``_fused_lookup``.  ``gains``
  are built per the hardware contract (mean rides them there) but the
  emulated combine divides after the sum exactly like the TensorCore
  path, keeping the bit-exactness the equivalence fuzz asserts.
  """
  from distributed_embeddings_tpu.parallel.dist_embedding import _combine_rows
  rows_cap, w = table.shape
  n_cap, gb, h = routed.shape
  samples = n_cap * gb
  csr = csr_from_routed(routed, rows_cap, num_sc, combiner)
  fused = jnp.where(csr.sample_ids < samples,
                    csr.embedding_ids * num_sc + csr.partition_ids, rows_cap)
  safe = jnp.minimum(fused, rows_cap - 1)
  rows = jnp.take(table, safe, axis=0)  # [N, w]
  table_dtype = table.dtype
  if scale is not None:
    # quantized storage (design §12): dequantize at the gather — the
    # scatter/combine below then moves f32 values exactly like the
    # TensorCore path, preserving the bit-exactness contract
    rows = rows.astype(jnp.float32) * jnp.take(scale, safe, axis=0)
    table_dtype = jnp.float32
  # padding entries scatter out of bounds (dropped) at DISTINCT indices
  # (samples*h + entry position): several padding entries sharing one
  # index would break the unique_indices promise, which XLA documents
  # as undefined even for dropped slots (see sparse._distinct_oob)
  n_entries = csr.sample_ids.shape[0]
  idx = jnp.where(csr.sample_ids < samples,
                  csr.sample_ids * h + csr.hot_ids,
                  samples * h + jnp.arange(n_entries, dtype=jnp.int32))
  dense = jnp.zeros((samples * h, w), table_dtype).at[idx].set(
      rows, mode='drop', unique_indices=True)
  mask = jnp.zeros((samples * h,), bool).at[idx].set(
      True, mode='drop', unique_indices=True)
  return _combine_rows(dense.reshape(n_cap, gb, h, w),
                       mask.reshape(n_cap, gb, h), combiner, table_dtype,
                       compute_dtype)


# --------------------------------------------------------------------------
# capacity calibration + host preprocessing measurement
# --------------------------------------------------------------------------


def calibrate_max_ids_per_partition(dist, cats, margin: float = 1.3,
                                    params=None,
                                    prefer_cpu: bool = True
                                    ) -> Tuple[int, ...]:
  """Measure per-group worst (device, SC partition) id counts on a
  sample batch and return calibrated ``max_ids_per_partition`` per
  fusion group — the capacity statics of the HOST CSR buffers, derived
  by the same machinery as the compaction capacities
  (``sparse.calibrate_capacity_rows``: CPU plan mirror, one
  representative batch, multiplicative margin, 8-aligned)."""
  from distributed_embeddings_tpu.parallel.sparse import _calibration_mirror
  if (prefer_cpu
      and dist.mesh.devices.ravel()[0].platform != 'cpu'):
    try:
      cpus = jax.devices('cpu')
    except RuntimeError:
      cpus = []
    if len(cpus) >= dist.world_size:
      mirror, zeros = _calibration_mirror(dist, cpus)
      host_cats = [np.asarray(x) for x in cats]
      return calibrate_max_ids_per_partition(mirror, host_cats,
                                             margin=margin, params=zeros,
                                             prefer_cpu=False)
  if params is None:
    params = dist.init(0)
  _, residuals, (_, hotness) = dist.forward_with_residuals(params, cats)
  subs = dist._subgroups(hotness)
  num_sc = getattr(dist.plan, 'num_sc', 4)
  per_group: Dict[int, List[np.ndarray]] = {}
  for si, sub in enumerate(subs):
    ids = np.asarray(residuals[si])  # [D, n_cap, GB, h]
    per_group.setdefault(sub.gi, []).append(ids.reshape(ids.shape[0], -1))
  caps = []
  for gi, group in enumerate(dist.plan.groups):
    streams = per_group.get(gi)
    if not streams:
      caps.append(8)
      continue
    per_dev = np.concatenate(streams, axis=1)
    worst = 0
    for row in per_dev:
      v = row[row < group.rows_cap]
      if v.size:
        worst = max(worst, int(np.bincount(v % num_sc,
                                           minlength=num_sc).max()))
    caps.append(_round_up8(max(8, int(worst * margin))))
  return tuple(caps)


def _route_ids_np(ids: np.ndarray, offs, vocab, rows_cap: int,
                  lo, hi, stride) -> np.ndarray:
  """NumPy twin of ``dist_embedding._route_ids`` (incl. mod windows),
  used by the host preprocessing path where the routing must happen on
  the CPU before the device program runs."""
  mask = ids >= 0
  clipped = np.clip(ids, 0, vocab[:, None, None] - 1)
  lo = lo[:, None, None]
  stride = stride[:, None, None]
  mask = (mask & (clipped >= lo) & (clipped < hi[:, None, None])
          & ((clipped - lo) % stride == 0))
  local = (clipped - lo) // stride
  return np.where(mask, local + offs[:, None, None], rows_cap).astype(
      np.int32)


_native_fallback_journaled = False
_native_fallback_lock = threading.Lock()


def _journal_native_fallback(e: BaseException):
  """Journal the native→NumPy degradation once per process (the feed
  calls the builder per (group, device) per batch — unthrottled, a
  broken .so would flood the journal)."""
  global _native_fallback_journaled
  with _native_fallback_lock:
    if _native_fallback_journaled:
      return
    _native_fallback_journaled = True
  from distributed_embeddings_tpu.utils import resilience
  resilience.journal('csr_native_fallback', error=repr(e))


def _route_and_build(dist, cats, sub, dev, cap, num_sc: int, stride,
                     builder: str) -> HostCsr:
  """ONE (subgroup, device) unit of the host feed: stage the slot ids,
  route them into this device's fused local-row space, and build the
  padded partition-sorted CSR buffers.  Pure NumPy/native — safe to run
  on any worker thread (the native calls release the GIL)."""
  g = dist.plan.groups[sub.gi]
  slot_ids = []
  for s in range(sub.n_cap):
    if s < len(sub.requests[dev]):
      x = cats[sub.requests[dev][s].input_id]
      x = x[:, None] if x.ndim == 1 else x
    else:
      x = np.full((cats[0].shape[0], sub.hotness), -1, np.int32)
    slot_ids.append(np.ascontiguousarray(x, np.int32))
  ids = np.stack(slot_ids)  # [n_cap, GB, h]
  if builder == 'native':
    from distributed_embeddings_tpu.parallel import csr_native
    try:
      routed = csr_native.route_ids(ids, sub.offsets[dev], sub.vocab[dev],
                                    g.rows_cap, sub.row_lo[dev],
                                    sub.row_hi[dev], stride[dev])
      return csr_native.build_csr(routed, g.rows_cap, num_sc,
                                  combiner=sub.lookup_combiner,
                                  max_ids_per_partition=cap)
    except Exception as e:
      # a native builder that breaks MID-RUN (unloadable .so, rejected
      # call) degrades to the bit-exact NumPy oracle for this job
      # instead of killing the feed; journaled once per process so the
      # slowdown is visible, never silent
      _journal_native_fallback(e)
  routed = _route_ids_np(ids, sub.offsets[dev], sub.vocab[dev],
                         g.rows_cap, sub.row_lo[dev], sub.row_hi[dev],
                         stride[dev])
  return build_csr_host(routed, g.rows_cap, num_sc,
                        combiner=sub.lookup_combiner,
                        max_ids_per_partition=cap)


def preprocess_batch_host(dist, cats,
                          max_ids_per_partition: Optional[Tuple[int, ...]]
                          = None, native: str = 'auto',
                          num_workers: Optional[int] = None
                          ) -> Dict[Tuple[int, int], List[HostCsr]]:
  """Per-batch HOST preprocessing for the real SC feed: route every
  subgroup's raw ids into each device's fused local-row space (the
  native/NumPy twin of ``_route_ids``) and build the padded
  partition-sorted CSR buffers per (subgroup, device).

  The transform is embarrassingly parallel over (subgroup, device)
  pairs (docs/perf_notes.md), so the build fans out over the shared
  worker pool by default; results are identical at ANY worker count
  (each pair's buffers depend only on its own inputs — asserted by the
  thread-invariance test).  ``num_workers``: None = the shared
  default-size pool (``default_build_workers()``), 0/1 = inline
  serial, N > 1 = a cached process-lifetime pool of exactly N
  workers.  ``native`` picks the builder (``resolve_builder``).

  Returns ``{(group_index, hotness): [HostCsr per device]}``.  This is
  the function ``bench.py`` times (``measure_preprocess_ms``) and the
  pipelined feed (``parallel/csr_feed.CsrFeed``) runs on its workers.
  """
  cats = [np.asarray(c) for c in cats]
  hotness = tuple(1 if c.ndim == 1 else c.shape[1] for c in cats)
  subs = dist._subgroups(hotness)
  num_sc = getattr(dist.plan, 'num_sc', 4)
  builder = resolve_builder(native)
  # the SAME [D, n_cap] stride table the traced routing selects from
  # (_SubGroup.row_stride) — re-deriving it here could silently drift
  # from the real routed ids
  strides = [(sub.row_stride if sub.row_stride is not None else
              np.ones((dist.world_size, sub.n_cap), np.int32))
             for sub in subs]
  caps = [None if max_ids_per_partition is None else
          max_ids_per_partition[sub.gi] for sub in subs]
  serial = num_workers is not None and num_workers <= 1
  # explicit counts get a cached pool of exactly that size (never a
  # per-call pool: CsrFeed resolves this once per batch)
  pool = None if serial else _worker_pool(num_workers)
  jobs = []  # (sub index within `subs`, dev, result-or-future)
  for si, sub in enumerate(subs):
    for dev in range(dist.world_size):
      args = (dist, cats, sub, dev, caps[si], num_sc, strides[si],
              builder)
      jobs.append((si, dev, _route_and_build(*args) if serial else
                   pool.submit(_route_and_build, *args)))
  per_sub: Dict[int, List[HostCsr]] = {si: [] for si in range(len(subs))}
  for si, dev, job in jobs:  # device order preserved (si asc, dev asc)
    per_sub[si].append(job if serial else job.result())
  out: Dict[Tuple[int, int], List[HostCsr]] = {}
  for si, sub in enumerate(subs):
    out[(sub.gi, sub.hotness)] = per_sub[si]
  return out


def _csrs_equal(a: Dict[Tuple[int, int], List[HostCsr]],
                b: Dict[Tuple[int, int], List[HostCsr]]) -> bool:
  """Bit-exact equality of two full preprocessed batches (every buffer
  of every (group, device) pair) — the live oracle check the bench
  journals alongside the native builder's numbers."""
  if a.keys() != b.keys():
    return False
  for k in a:
    if len(a[k]) != len(b[k]):
      return False
    for x, y in zip(a[k], b[k]):
      if (x.max_ids_per_partition != y.max_ids_per_partition
          or x.dropped != y.dropped):
        return False
      for fa, fb in zip(x[:4], y[:4]):
        if not np.array_equal(fa, fb):
          return False
  return True


def measure_preprocess_ms(dist, cats, repeats: int = 3,
                          max_ids_per_partition: Optional[Tuple[int, ...]]
                          = None) -> Dict[str, Any]:
  """Time the per-batch host feed on this host, for the bench artifact
  and docs/perf_notes.md ("host feed pipeline").

  Three measurements from the same batch and caps:

  - ``csr_numpy_ns_per_id``: the single-threaded NumPy oracle — the
    260 ns/id baseline of the round-6 note;
  - ``csr_native_ns_per_id``: the C++ builder, single-threaded (absent
    when no toolchain);
  - ``csr_preprocess_ns_per_id`` (+ ``_ms``/``_ids``): the REAL feed
    path — the resolved builder fanned out over the shared worker pool
    — i.e. what ``CsrFeed`` pays per batch.  ``csr_preprocess_builder``
    labels which builder that was, and ``csr_native_parity`` is a live
    bit-exactness check of the native buffers against the NumPy oracle
    on this very batch (never assumed from the test suite alone).

  The timed builds always run with STATIC per-group capacities — the
  caller's calibrated ``max_ids_per_partition`` when given, else caps
  derived from one untimed sizing pass (per-group max over devices and
  hotness classes) — so the measurement covers the padded layout the
  real feed pays, and the journaled ``csr_dropped`` is a live check of
  the caps against this batch rather than 0 by construction."""
  caps = max_ids_per_partition
  if caps is None:
    sizing = preprocess_batch_host(dist, cats)
    by_group: Dict[int, int] = {}
    for (gi, _), lst in sizing.items():
      by_group[gi] = max(by_group.get(gi, 8),
                         max(c.max_ids_per_partition for c in lst))
    caps = tuple(by_group.get(gi, 8)
                 for gi in range(len(dist.plan.groups)))
  n_ids = int(sum(np.asarray(c).size for c in cats))
  repeats = max(1, repeats)

  def timed(native: str, num_workers: Optional[int]):
    times, last = [], None
    for _ in range(repeats):
      t0 = time.perf_counter()
      last = preprocess_batch_host(dist, cats, max_ids_per_partition=caps,
                                   native=native, num_workers=num_workers)
      times.append((time.perf_counter() - t0) * 1000.0)
    return min(times), last

  ns = lambda ms: round(ms * 1e6 / max(n_ids, 1), 2)
  np_ms, np_csrs = timed('numpy', num_workers=1)
  out: Dict[str, Any] = {'csr_numpy_ns_per_id': ns(np_ms)}
  builder = resolve_builder('auto')
  if builder == 'native':
    nat_ms, nat_csrs = timed('native', num_workers=1)
    out['csr_native_ns_per_id'] = ns(nat_ms)
    out['csr_native_parity'] = _csrs_equal(np_csrs, nat_csrs)
  workers = default_build_workers()
  feed_ms, feed_csrs = timed(builder, num_workers=None)
  dropped = sum(c.dropped for lst in feed_csrs.values() for c in lst)
  out.update({
      'csr_preprocess_ms': round(feed_ms, 3),
      'csr_preprocess_ids': n_ids,
      'csr_preprocess_ns_per_id': ns(feed_ms),
      'csr_preprocess_builder': (f'{builder}-parallel({workers})'
                                 if workers > 1 else builder),
      'csr_dropped': dropped,
  })
  return out


# --------------------------------------------------------------------------
# THE hardware-gated adapter seam (the one remaining binding)
# --------------------------------------------------------------------------


def custom_call_lookup(table: jax.Array, csr: StaticCsr,
                       combiner: Optional[str], compute_dtype,
                       num_sc: int) -> jax.Array:
  """THE adapter between this module's CSR buffers and
  ``jax-tpu-embedding``'s ``tpu_sparse_dense_matmul`` custom call — the
  single remaining hardware-gated seam of docs/design.md §8.  Everything
  upstream (planner mod windows, routing, CSR transform) and downstream
  (assembly, sparse apply) is the code exercised by the emulation
  backend; this function only swaps the executable emulation for the
  real custom call on SC hardware, where it is validated.  Without the
  library it raises the contract error (never a silent fallback)."""
  try:
    import jax_tpu_embedding as lib
  except ImportError:
    raise NotImplementedError(_CONTRACT_MSG) from None
  raise NotImplementedError(
      'jax-tpu-embedding is importable but this binding has not been '
      'validated on SparseCore hardware in this environment; wire '
      f'{lib.__name__}.tpu_sparse_dense_matmul to the StaticCsr buffers '
      'here (row_pointers/embedding_ids/sample_ids/gains map 1:1) and '
      'validate against the emulation backend, which is the executable '
      'specification of the expected numerics.')
