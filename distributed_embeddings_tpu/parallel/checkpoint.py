"""Resharding checkpoint: global canonical table layout <-> sharded params.

TPU-native re-design of the reference ``set_weights``/``get_weights``
overrides (`dist_model_parallel.py:452-645`, SURVEY.md C17).  The contract is
identical — checkpoints are *global* per-table ``[rows, width]`` arrays (or
``.npy`` paths loaded with ``mmap_mode='r'`` for terabyte tables,
dist_model_parallel.py:473-474), so a checkpoint written under one world
size / strategy loads under any other: each load re-slices from the global
layout.

The mechanics differ: the reference needs chunked ``hvd.allgather`` on CPU
(<2e9-element chunks for MPI's 32-bit limits, :577-590) and chunked
``scatter_update`` (128M-element chunks against copy-on-write OOM,
:502-524).  Here shards are materialised per device via
``jax.make_array_from_callback`` (each host touches only bytes it stores;
mmap'd sources stream straight into shards), and gathers read
``addressable_shards`` per device — JAX arrays are immutable so no
copy-on-write hazard exists.
"""

from __future__ import annotations

import functools
import glob as glob_lib
import hashlib
import json
import os
import re
import threading

from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import dataclasses

from distributed_embeddings_tpu.analysis import commsan
from distributed_embeddings_tpu.obs import metrics as obs_metrics
from distributed_embeddings_tpu.obs import trace as obs_trace
from distributed_embeddings_tpu.parallel import quantization
from distributed_embeddings_tpu.parallel.dist_embedding import DistributedEmbedding
from distributed_embeddings_tpu.utils import resilience


@dataclasses.dataclass
class QuantizedWeight:
  """One table's canonical QUANTIZED checkpoint entry (design §12):
  ``payload`` ``[rows, width]`` at int8/float8_e4m3, ``scale``
  ``[rows]`` f32 power-of-two per-row scales.  ``values()`` is the
  exact dequantization (po2 scales only shift exponents), so restoring
  into an f32 plan — or requantizing into any quantized plan whose
  shard rows span full logical rows — is bit-lossless.

  Scale granularity contract: the canonical file carries ONE scale per
  LOGICAL row.  Shards spanning full rows (plain, row-sliced and
  cold-tier tables — the beyond-HBM regime this exists for) round-trip
  bit-exactly.  A COLUMN-sliced quantized table stores per-slice scales
  at runtime; its first save re-rounds each slice onto the coarser
  row grid (error bounded by one quantization step; every later
  save/restore of the same values is bit-stable).  Pinned in
  tests/test_quantized_storage.py.
  """
  payload: np.ndarray
  scale: np.ndarray
  dtype_name: str

  @property
  def shape(self):
    return self.payload.shape

  def values(self) -> np.ndarray:
    return quantization.dequantize_np(self.payload,
                                      self.scale.reshape(-1, 1))

  @classmethod
  def from_values(cls, values: np.ndarray, spec) -> 'QuantizedWeight':
    payload, scale = quantization.quantize_np(
        np.asarray(values, np.float32), spec)
    return cls(payload=payload, scale=scale.reshape(-1),
               dtype_name=spec.name)


WeightLike = Union[np.ndarray, str, QuantizedWeight]


def _canonical_values(w) -> np.ndarray:
  """Any weight entry (array, .npy path, QuantizedWeight) as its exact
  canonical f32 (or original-dtype) value array."""
  if isinstance(w, QuantizedWeight):
    return w.values()
  return _load(w)


def export_tables(dist: DistributedEmbedding, params,
                  gather: str = 'auto',
                  chunk_elems: int = None) -> List[WeightLike]:
  """The canonical per-table checkpoint entries for THIS plan: plain
  f32 arrays for unquantized plans, ``QuantizedWeight`` payload+scale
  pairs (4x smaller on disk for int8) for quantized ones — what
  ``save_train_npz`` should be handed so saved files carry
  payload+scales only (design §12)."""
  kw = {} if chunk_elems is None else {'chunk_elems': chunk_elems}
  tables = get_weights(dist, params, gather=gather, **kw)
  spec = getattr(dist.plan, 'table_spec', None)
  if spec is None:
    return tables
  return [QuantizedWeight.from_values(t, spec) for t in tables]

# Default streaming-gather chunk: 2**27 elements (512 MiB f32) per fetch,
# the same order as the reference's 128M-element scatter chunks
# (dist_model_parallel.py:452,502-524) - bounds host/replica memory when
# assembling terabyte tables.
CHUNK_ELEMS = 1 << 27


def _load(weight: WeightLike) -> np.ndarray:
  if isinstance(weight, str):
    return np.load(weight, mmap_mode='r')
  return np.asarray(weight)


def _refuse_dcn_sharding(dist, op: str):
  """Checkpoint resharding is not yet defined for hierarchical
  (``dcn_sharding=True``) layers: their group leaves are ``[S*D,
  rows_cap_h, ...]`` stacks over the (dcn, data) axis PRODUCT with
  permuted per-slice row windows (design §20), while every gather/
  scatter path here walks ``dist.world_size`` flat shards — reading
  them as flat would silently drop or misplace rows.  Refuse loudly;
  the supported route is the flat-twin one: checkpoint the flat model
  with the same plan geometry, restore it, and reshard its params with
  ``dist_embedding.hierarchical_params`` (exact row relocation — the
  same conversion the §20 parity suite uses).
  """
  if getattr(dist, 'dcn_sharding', False):
    raise NotImplementedError(
        f'{op} does not support dcn_sharding=True layers yet: the '
        f'hierarchical (dcn x ici) layout shards over the axis product '
        f'with per-slice row permutations (design §20). Checkpoint a '
        f'flat twin of the same plan geometry and convert with '
        f'dist_embedding.hierarchical_params(dist, flat_params).')


def _chunked_shards(dist: DistributedEmbedding, arr: jax.Array,
                    chunk_elems: int) -> List[np.ndarray]:
  """Stream one ``[D, rows_cap, ...]`` group array to host, device by
  device, in row chunks of at most ``chunk_elems`` elements.

  Each fetch is a jitted SPMD ``dynamic_slice`` whose output is REPLICATED
  over the mesh, so it works when shards are not host-addressable
  (multi-host): every process runs the same program and reads its local
  replica.  The reference needs chunked ``hvd.allgather`` for the same
  reason (dist_model_parallel.py:577-590); here the chunk cap bounds
  per-process peak memory instead of MPI's 32-bit limits.
  """
  rows_cap = arr.shape[1]
  row_elems = int(np.prod(arr.shape[2:])) if arr.ndim > 2 else 1
  step = max(1, min(rows_cap, chunk_elems // max(row_elems, 1)))
  key = ('ckpt_fetch', arr.shape, str(arr.dtype), step)
  if key not in dist._fn_cache:
    sizes = (1, step) + arr.shape[2:]

    @functools.partial(jax.jit,
                       out_shardings=NamedSharding(dist.mesh, P()))
    def fetch(a, d, r):
      start = (d, r) + (0,) * (a.ndim - 2)
      return jax.lax.dynamic_slice(a, start, sizes)

    dist._fn_cache[key] = fetch
  fetch = dist._fn_cache[key]

  shards = []
  for dev in range(dist.world_size):
    chunks = []
    for r0 in range(0, rows_cap, step):
      r0c = min(r0, rows_cap - step)  # clamp the tail chunk; trim below
      out = np.asarray(jax.device_get(fetch(arr, dev, r0c)))[0]
      chunks.append(out[r0 - r0c:])
    shards.append(np.concatenate(chunks, axis=0) if len(chunks) > 1
                  else chunks[0])
  return shards


def _host_shards(dist: DistributedEmbedding, arr: jax.Array, gather: str,
                 chunk_elems: int) -> List[np.ndarray]:
  """Per-device host copies of one group array's ``[rows_cap, ...]``
  shards, via local-shard reads when addressable, else chunked SPMD
  streaming."""
  if gather == 'chunked':
    return _chunked_shards(dist, arr, chunk_elems)
  shards: List[Optional[np.ndarray]] = [None] * dist.world_size
  for s in arr.addressable_shards:
    dev = s.index[0].start if s.index[0].start is not None else 0
    shards[dev] = np.asarray(s.data)[0]
  if any(s is None for s in shards):
    if gather == 'addressable':
      raise ValueError('gather="addressable" but some shards are remote; '
                       'use gather="chunked" (or "auto") on multi-host')
    return _chunked_shards(dist, arr, chunk_elems)
  return shards


def _value_shards(dist: DistributedEmbedding, params, gi: int,
                  gather: str, chunk_elems: int) -> List[np.ndarray]:
  """Per-device ``[rows_cap, width]`` VALUE shards of one fusion group.

  The one place storage layout (design §12) unfolds back into values:
  the device payload is gathered and — for quantized plans —
  dequantized against its ``scale_group_{gi}`` leaf (exact: po2 scales
  only shift exponents), and cold-tier groups append their host-DRAM
  tail rows, so every caller downstream of here sees the full fused
  natural rows regardless of ``table_dtype`` or tier split."""
  g = dist.plan.groups[gi]
  quant = getattr(dist, 'quant', None)
  shards = [
      s.reshape(g.device_rows, g.width) for s in
      _host_shards(dist, params[f'group_{gi}'], gather, chunk_elems)
  ]
  if quant is not None:
    sshards = _host_shards(dist, params[f'scale_group_{gi}'], gather,
                           chunk_elems)
    shards = [
        quantization.dequantize_np(p, s.reshape(-1, 1))
        for p, s in zip(shards, sshards)
    ]
  if g.tier_rows and dist.cold_tier is not None:
    tails = []
    for dev in range(dist.world_size):
      t = dist.cold_tier.payload[gi][dev]
      if quant is not None:
        t = quantization.dequantize_np(t, dist.cold_tier.scale[gi][dev])
      tails.append(np.asarray(t, shards[dev].dtype))
    shards = [
        np.concatenate([h, t], axis=0) for h, t in zip(shards, tails)
    ]
  return shards


def set_weights(dist: DistributedEmbedding,
                weights: Sequence[WeightLike]) -> Dict[str, jax.Array]:
  """Build the sharded parameter pytree from global per-table weights.

  Args:
    dist: the distributed layer whose plan defines the layout.
    weights: one ``[rows, width]`` array or ``.npy`` path per table, in
      global table order.

  Returns:
    Params pytree with the same structure as ``dist.init``.

  Raises:
    ValueError: on length or shape mismatch.
  """
  _refuse_dcn_sharding(dist, 'set_weights')
  plan = dist.plan
  weights = list(weights)
  if len(weights) != len(plan.table_configs):
    raise ValueError(
        f'You called set_weights with a weight list of length '
        f'{len(weights)}, but the layer was expecting '
        f'{len(plan.table_configs)} weights.')
  for tid, (w, cfg) in enumerate(zip(weights, plan.table_configs)):
    shape = tuple(w.shape if isinstance(w, QuantizedWeight)
                  else _load(w).shape)
    if shape != (cfg.input_dim, cfg.output_dim):
      raise ValueError(
          f'table {tid}: expected shape {(cfg.input_dim, cfg.output_dim)}, '
          f'got {shape}')

  quant = getattr(dist, 'quant', None)

  # canonical VALUES, materialised LAZILY per table: a QuantizedWeight
  # entry restoring into a plan of the SAME table dtype never takes
  # this path at all — its payload+scale slice straight into the
  # shards (quant -> dequant -> requant is the IDENTITY on po2-scaled
  # rows, design §12), so a serving host restoring a beyond-HBM
  # quantized bundle never holds the 4x-wider f32 table.  Everything
  # else (f32 entries, dtype-mismatched quantized entries, unquantized
  # plans) re-quantizes / re-tiers from the exact canonical values as
  # before — storage layout never leaks into saved state.
  _vals: Dict[int, np.ndarray] = {}

  def table_values(tid):
    if tid not in _vals:
      _vals[tid] = _canonical_values(weights[tid])
    return _vals[tid]

  def direct_quant(tid):
    w = weights[tid]
    return (quant is not None and isinstance(w, QuantizedWeight)
            and w.dtype_name == quant.name)
  params = {}
  for gi, g in enumerate(plan.groups):
    sharding = NamedSharding(dist.mesh, P(dist.axis_name, None, None))

    def full_rows(dev, g=g, dtype=None):
      dtype = dtype or dist.param_dtype
      chunks = []
      for lt in g.member_tables[dev]:
        # row_stride > 1: a mod-sharded window (residue class) — numpy's
        # strided slice extracts exactly the shard's resident rows
        chunks.append(
            np.asarray(
                table_values(lt.table_id)
                [lt.row_start:lt.row_end:lt.row_stride,
                 lt.col_start:lt.col_end],
                dtype=dtype))
      pad_rows = g.rows_cap - g.rows[dev]
      if pad_rows or not chunks:
        chunks.append(np.zeros((pad_rows, g.width), dtype))
      return np.concatenate(chunks, axis=0)

    if quant is None and g.tier_rows == 0:
      # packed-storage groups live device-side as [rows_cap/pack, 128]
      # (GroupSpec.storage_pack); the host-side regrouping reshape is
      # free (row-major) and keeps the checkpoint contract natural-space
      def make_shard(index, g=g):
        dev = index[0].start if index[0].start is not None else 0
        return full_rows(dev, g).reshape(g.param_rows, g.param_width)[None]

      params[f'group_{gi}'] = jax.make_array_from_callback(
          (dist.world_size, g.param_rows, g.param_width), sharding,
          make_shard)
      continue
    # quantized and/or cold-tier group (design §12): quantize each
    # device's rows host-side (bitwise-identical to the traced
    # requant), split the tail off into the host tier, ship the head.
    # Quantized/tiered plans always store natural (planner contract).
    # Quantization happens on FULL-WIDTH rows — the canonical per-row
    # grid — and the payload is sliced after: a column-sliced shard
    # then carries the row scale (value-exact; the runtime's per-slice
    # refresh only ever moves to a finer grid), so untrained
    # set->get->export round-trips are bit-stable for every layout.
    res = g.device_rows

    def quant_rows(dev, g=g):
      pays, scales = [], []
      for lt in g.member_tables[dev]:
        sl = slice(lt.row_start, lt.row_end, lt.row_stride)
        if direct_quant(lt.table_id):
          # same-dtype QuantizedWeight: the stored pair IS the requant
          # fixed point (§12 identity), so payload+scale slice straight
          # into the shard — no f32 table ever materialises on the
          # restore host (the serving-mesh memory contract, §14)
          w = weights[lt.table_id]
          fp = np.asarray(w.payload)[sl]
          fs = np.asarray(w.scale, np.float32).reshape(-1, 1)[sl]
        else:
          rows = np.asarray(table_values(lt.table_id)[sl], np.float32)
          fp, fs = quantization.quantize_np(rows, quant)
        pays.append(fp[:, lt.col_start:lt.col_end])
        scales.append(fs)
      pad_rows = g.rows_cap - g.rows[dev]
      if pad_rows or not pays:
        pays.append(np.zeros((pad_rows, g.width), quant.dtype))
        scales.append(np.ones((pad_rows, 1), np.float32))
      return np.concatenate(pays, axis=0), np.concatenate(scales, axis=0)

    heads, head_scales, tails, tail_scales = [], [], [], []
    for dev in range(dist.world_size):
      if quant is not None:
        payload, scale = quant_rows(dev)
      else:
        payload, scale = full_rows(dev, g, dtype=dist.param_dtype), None
      heads.append(payload[:res])
      if scale is not None:
        head_scales.append(scale[:res])
      if g.tier_rows:
        tails.append(payload[res:])
        if scale is not None:
          tail_scales.append(scale[res:])
    if g.tier_rows:
      dist.cold_tier.set_tail(gi, 'payload', np.stack(tails))
      if tail_scales:
        dist.cold_tier.set_tail(gi, 'scale', np.stack(tail_scales))
    params[f'group_{gi}'] = jax.make_array_from_callback(
        (dist.world_size, res, g.width), sharding,
        lambda index, hs=heads: hs[index[0].start or 0][None])
    if quant is not None:
      params[f'scale_group_{gi}'] = jax.make_array_from_callback(
          (dist.world_size, res, 1), sharding,
          lambda index, ss=head_scales: ss[index[0].start or 0][None])
  params.update(_hot_leaves_from_tables(dist, weights, dist.param_dtype))
  return params


def _weight_rows(w, ids) -> np.ndarray:
  """Exact VALUE rows ``w[ids]`` of one weight entry without
  materialising the full table: QuantizedWeight entries dequantize only
  the gathered rows (the same narrow-restore contract ``set_weights``
  keeps for the sharded leaves)."""
  ids = np.asarray(ids)
  if isinstance(w, QuantizedWeight):
    return quantization.dequantize_np(
        np.asarray(w.payload)[ids],
        np.asarray(w.scale, np.float32).reshape(-1, 1)[ids])
  return np.asarray(_load(w)[ids])


def _hot_leaves_from_tables(dist, tables, dtype, leaf_prefix='hot_group_'):
  """Replicated hot-cache buffers built from GLOBAL canonical per-table
  entries (the ``set_weights``/``set_optimizer_state`` leg of the
  design-§10 canonicalization contract: hot membership is a layout
  detail, so a checkpoint restores into ANY hot set by re-slicing the
  canonical rows).  Quantized plans (design §12) quantize the
  replicated buffer per row exactly like the device init — and a
  same-dtype ``QuantizedWeight`` entry's stored payload+scale rows copy
  straight in (the §12 identity; no full-table widening), emitting the
  ``hot_scale_group_{gi}`` leaf alongside.  Returns ``{}`` for
  cache-less layers."""
  plan = dist.plan
  quant = (getattr(dist, 'quant', None)
           if leaf_prefix == 'hot_group_' else None)
  out = {}
  for gi in getattr(plan, 'hot_groups', []):
    g = plan.groups[gi]
    sharding = NamedSharding(dist.mesh, P(None, None))
    if quant is not None:
      # the canonical per-ROW grid, like the sharded leaves: quantize
      # full-width hot rows, then slice the payload per chunk
      payload = np.zeros((g.hot_rows_cap, g.width), quant.dtype)
      scale = np.ones((g.hot_rows_cap, 1), np.float32)
      for tid, cs, ce, off, k in g.hot_chunks:
        ids = plan.hot_sets[tid].ids
        w = tables[tid]
        if isinstance(w, QuantizedWeight) and w.dtype_name == quant.name:
          fp = np.asarray(w.payload)[ids]
          fs = np.asarray(w.scale, np.float32).reshape(-1, 1)[ids]
        else:
          fp, fs = quantization.quantize_np(
              np.asarray(_weight_rows(w, ids), np.float32), quant)
        payload[off:off + k] = fp[:, cs:ce]
        scale[off:off + k] = fs
      out[f'{leaf_prefix}{gi}'] = jax.make_array_from_callback(
          payload.shape, sharding, lambda index, b=payload: b[index])
      out[f'hot_scale_group_{gi}'] = jax.make_array_from_callback(
          scale.shape, sharding, lambda index, b=scale: b[index])
    else:
      buf = np.zeros((g.hot_rows_cap, g.width), dtype)
      for tid, cs, ce, off, k in g.hot_chunks:
        ids = plan.hot_sets[tid].ids
        buf[off:off + k] = np.asarray(
            _weight_rows(tables[tid], ids)[:, cs:ce], dtype=dtype)
      out[f'{leaf_prefix}{gi}'] = jax.make_array_from_callback(
          buf.shape, sharding, lambda index, buf=buf: buf[index])
  return out


def _overlay_hot_rows(dist, result, leaves):
  """Write the replicated hot-cache rows back into the global canonical
  per-table arrays (the ``get_weights``/``get_optimizer_state`` leg):
  the sharded slots of hot rows go stale while the row is hot, so the
  hot buffer is authoritative for them."""
  plan = dist.plan
  for gi in getattr(plan, 'hot_groups', []):
    g = plan.groups[gi]
    leaf = leaves.get(gi)
    if leaf is None:
      continue
    buf = np.asarray(jax.device_get(leaf))
    for tid, cs, ce, off, k in g.hot_chunks:
      ids = plan.hot_sets[tid].ids
      if result[tid] is not None:
        result[tid][ids, cs:ce] = buf[off:off + k].astype(
            result[tid].dtype)
  return result


def get_weights(dist: DistributedEmbedding,
                params: Dict[str, jax.Array],
                gather: str = 'auto',
                chunk_elems: int = CHUNK_ELEMS) -> List[np.ndarray]:
  """Reassemble global per-table weights from the sharded params.

  Inverse of ``set_weights`` (reference ``get_weights``,
  dist_model_parallel.py:555-645): un-fuse each device's tall table, undo
  column slicing by concatenating device-ordered shards along the width.

  Args:
    gather: 'auto' reads local shards when every shard is host-addressable
      and streams chunked replicated slices otherwise; 'addressable' /
      'chunked' force one path.
    chunk_elems: element cap per streamed fetch (see ``_chunked_shards``).

  Returns:
    List of ``[rows, width]`` numpy arrays in global table order.
  """
  _refuse_dcn_sharding(dist, 'get_weights')
  plan = dist.plan
  group_index = {g.key: gi for gi, g in enumerate(plan.groups)}
  host_shards = {
      gi: _value_shards(dist, params, gi, gather, chunk_elems)
      for gi in range(len(plan.groups))
  }

  hot = bool(getattr(plan, 'hot_sets', None))
  result = []
  for tid, shards in enumerate(plan.shard_layout()):
    cfg = plan.table_configs[tid]
    if len(shards) == 1 and shards[0][7] == 1:
      dev, group_key, row_offset = shards[0][:3]
      gi = group_index[group_key]
      piece = host_shards[gi][dev][row_offset:row_offset + cfg.input_dim, :]
      # hot layers overwrite hot rows below — copy so the overlay never
      # mutates the shared host shard buffer backing other tables
      result.append(np.array(piece) if hot and tid in plan.hot_sets
                    else piece)
      continue
    # paste row x column windows into the global [rows, width] canvas
    # (covers column slicing, contiguous AND mod row slicing, and plain
    # tables uniformly); zeros, not empty: the planner asserts the
    # windows tile the table, but a future layout gap must read as
    # zeros, never as uninitialised memory (ADVICE.md round 2)
    out = np.zeros((cfg.input_dim, cfg.output_dim),
                   host_shards[group_index[shards[0][1]]][0].dtype)
    for dev, group_key, row_offset, col_start, col_end, row_start, \
        row_end, row_stride in shards:
      gi = group_index[group_key]
      span = -(-(row_end - row_start) // row_stride)
      out[row_start:row_end:row_stride, col_start:col_end] = (
          host_shards[gi][dev][row_offset:row_offset + span])
    result.append(out)
  if hot:
    # the sharded slots of hot rows are stale while the rows are hot
    # (the runtime updates only the replicated buffer) — the buffer is
    # authoritative, and writing it back here is what keeps hot
    # membership invisible in saved state (design §10).  Quantized hot
    # buffers dequantize first (exact, §12) so the overlay writes
    # values like every other path.
    leaves = {}
    for gi in plan.hot_groups:
      hk = f'hot_group_{gi}'
      if hk not in params:
        continue
      buf = np.asarray(jax.device_get(params[hk]))
      if getattr(dist, 'quant', None) is not None:
        buf = quantization.dequantize_np(
            buf, np.asarray(jax.device_get(
                params[f'hot_scale_group_{gi}'])))
      leaves[gi] = buf
    _overlay_hot_rows(dist, result, leaves)
  return result


def get_optimizer_state(dist: DistributedEmbedding,
                        opt_state: Dict[str, Dict[str, jax.Array]],
                        gather: str = 'auto',
                        chunk_elems: int = CHUNK_ELEMS
                        ) -> List[Dict[str, np.ndarray]]:
  """Reassemble sparse-optimizer state into the global per-table layout.

  Same resharding contract as ``get_weights`` (the reference checkpoints
  tables only; optimizer state is an extension): a state checkpoint
  written under one world size / strategy loads under any other.

  Leaf handling: per-element leaves ``[D, param_rows, param_width]``
  (Adagrad ``acc``, Adam ``m``/``v`` — the params' possibly packed
  physical layout, regrouped to natural rows on gather) un-fuse and
  un-column-slice exactly like weights; per-row leaves ``[D, rows_cap]``
  (Adam ``t``) are IDENTICAL
  across column slices of a table (a lookup touches every slice of a
  row), so the first slice is canonical and yields a ``[rows]`` vector.

  Returns:
    Per-table dicts of numpy arrays, in global table order (e.g.
    ``[{'acc': [rows, width]}, ...]``); empty dicts for stateless
    optimizers.
  """
  _refuse_dcn_sharding(dist, 'get_optimizer_state')
  plan = dist.plan
  group_index = {g.key: gi for gi, g in enumerate(plan.groups)}
  leaf_names = sorted({k for gs in opt_state.values() for k in gs})
  host: Dict[tuple, List[np.ndarray]] = {}
  for gi, g in enumerate(plan.groups):
    for k in opt_state.get(f'group_{gi}', {}):
      shards = _host_shards(dist, opt_state[f'group_{gi}'][k],
                            gather, chunk_elems)
      # elementwise leaves follow the params' (possibly packed) physical
      # layout — regroup to natural rows; per-row leaves are natural
      host[(gi, k)] = [
          s.reshape(g.device_rows, g.width)
          if s.shape == (g.param_rows, g.param_width) else s
          for s in shards
      ]
      if g.tier_rows:
        # cold-tier groups (design §12): the tail rows' optimizer state
        # lives in the host tier — append it so the canonical layout
        # covers the full table (zeros if the leaf was never created,
        # e.g. state gathered before the first train step)
        tier = getattr(dist, 'cold_tier', None)
        tail = tier.opt[gi].get(k) if tier is not None else None
        host[(gi, k)] = [
            np.concatenate([
                h, (np.asarray(tail[dev], h.dtype) if tail is not None
                    else np.zeros((g.tier_rows,) + h.shape[1:], h.dtype))
            ]) for dev, h in enumerate(host[(gi, k)])
        ]

  result = []
  for tid, shards in enumerate(plan.shard_layout()):
    cfg = plan.table_configs[tid]
    entry = {}
    for k in leaf_names:
      canvas = None
      for dev, group_key, row_offset, col_start, col_end, row_start, \
          row_end, row_stride in shards:
        gi = group_index[group_key]
        if (gi, k) not in host:
          continue
        span = -(-(row_end - row_start) // row_stride)
        piece = host[(gi, k)][dev][row_offset:row_offset + span]
        if canvas is None:
          shape = ((cfg.input_dim,) if piece.ndim == 1
                   else (cfg.input_dim, cfg.output_dim))
          canvas = np.zeros(shape, piece.dtype)
        if piece.ndim == 1:
          # per-row leaf: identical across column slices of a row window,
          # so column shards just overwrite with the same values
          canvas[row_start:row_end:row_stride] = piece
        else:
          canvas[row_start:row_end:row_stride, col_start:col_end] = piece
      if canvas is not None:
        entry[k] = canvas
    result.append(entry)
  if getattr(plan, 'hot_sets', None):
    # hot-row optimizer state lives in the replicated split buffers
    # while the rows are hot — overlay it into the canonical per-table
    # layout exactly like the weights (hot membership never reaches
    # saved state)
    for gi in plan.hot_groups:
      leaves = opt_state.get(f'hot_group_{gi}', {})
      for k, leaf in leaves.items():
        buf = np.asarray(jax.device_get(leaf))
        g = plan.groups[gi]
        for tid, cs, ce, off, cnt in g.hot_chunks:
          ids = plan.hot_sets[tid].ids
          if k not in result[tid]:
            continue
          if result[tid][k].ndim == 2:
            result[tid][k][ids, cs:ce] = buf[off:off + cnt].astype(
                result[tid][k].dtype)
          elif result[tid][k].ndim == 1:
            # per-row leaf (e.g. SparseAdam's step counter 't'):
            # identical across column slices, so chunks of different
            # column ranges overwrite with the same values
            result[tid][k][ids] = buf[off:off + cnt].astype(
                result[tid][k].dtype)
  return result


def set_optimizer_state(dist: DistributedEmbedding,
                        opt_state: Dict[str, Dict[str, jax.Array]],
                        table_states: Sequence[Dict[str, np.ndarray]]
                        ) -> Dict[str, Dict[str, jax.Array]]:
  """Build the sharded sparse-optimizer state from global per-table state.

  Inverse of ``get_optimizer_state``.  ``opt_state`` supplies the leaf
  structure/shapes/shardings to rebuild into (e.g. a fresh
  ``optimizer.init(dist, params)``); per-row ``[rows]`` leaves broadcast
  to every column slice of their table.  Padding rows (never looked up)
  are zero-filled.
  """
  _refuse_dcn_sharding(dist, 'set_optimizer_state')
  plan = dist.plan
  if len(table_states) != len(plan.table_configs):
    raise ValueError(
        f'expected {len(plan.table_configs)} per-table states, got '
        f'{len(table_states)}')
  new_state: Dict[str, Dict[str, jax.Array]] = {}
  for gi, g in enumerate(plan.groups):
    gkey = f'group_{gi}'
    new_state[gkey] = {}
    for k, tmpl in opt_state.get(gkey, {}).items():
      def full_state_rows(dev, g=g, k=k, tmpl=tmpl):
        dtype = tmpl.dtype
        chunks = []
        for lt in g.member_tables[dev]:
          st = np.asarray(table_states[lt.table_id][k])
          if tmpl.ndim == 3:
            chunks.append(
                np.asarray(
                    st[lt.row_start:lt.row_end:lt.row_stride,
                       lt.col_start:lt.col_end],
                    dtype=dtype))
          else:
            chunks.append(
                np.asarray(st[lt.row_start:lt.row_end:lt.row_stride],
                           dtype=dtype))
        pad_rows = g.rows_cap - g.rows[dev]
        if pad_rows or not chunks:
          pad_shape = ((pad_rows, g.width) if tmpl.ndim == 3
                       else (pad_rows,))
          chunks.append(np.zeros(pad_shape, dtype))
        return np.concatenate(chunks, axis=0)

      # canonical device-major sharding (the template may still carry the
      # single-device sharding optimizer.init created it with)
      sharding = NamedSharding(
          dist.mesh, P(dist.axis_name, *([None] * (tmpl.ndim - 1))))
      if g.tier_rows:
        # cold-tier group (design §12): tail rows' state lives in the
        # host tier — split it off host-side, ship the head (tiered
        # groups are natural and elementwise-only, planner contract)
        res = g.device_rows
        heads, tails = [], []
        for dev in range(dist.world_size):
          full = full_state_rows(dev)
          heads.append(full[:res])
          tails.append(full[res:])
        if getattr(dist, 'cold_tier', None) is not None:
          # routed through set_opt_tail (not a raw dict store) so the
          # tier's write-back digests re-certify the restored bytes
          dist.cold_tier.set_opt_tail(gi, k, np.stack(tails))
        new_state[gkey][k] = jax.make_array_from_callback(
            tmpl.shape, sharding,
            lambda index, hs=heads: hs[index[0].start or 0][None])
        continue

      def make_shard(index, g=g, tmpl=tmpl,
                     full_state_rows=full_state_rows):
        dev = index[0].start if index[0].start is not None else 0
        full = full_state_rows(dev)
        if tmpl.ndim == 3 and tmpl.shape[1:] == (g.param_rows,
                                                 g.param_width):
          # elementwise leaf of a packed-storage group: regroup to the
          # physical packed layout (free row-major reshape)
          full = full.reshape(g.param_rows, g.param_width)
        return full[None]

      new_state[gkey][k] = jax.make_array_from_callback(
          tmpl.shape, sharding, make_shard)
  # replicated hot-cache split state: re-slice from the canonical
  # per-table layout into WHATEVER hot set the live plan carries (the
  # restore-into-a-different-hot-set leg of the design-§10 contract)
  for gi in getattr(plan, 'hot_groups', []):
    hkey = f'hot_group_{gi}'
    if hkey not in opt_state:
      continue
    new_state[hkey] = {}
    g = plan.groups[gi]
    for k, tmpl in opt_state[hkey].items():
      shape = ((g.hot_rows_cap, g.width) if tmpl.ndim == 2
               else (g.hot_rows_cap,))
      buf = np.zeros(shape, tmpl.dtype)
      for tid, cs, ce, off, cnt in g.hot_chunks:
        ids = plan.hot_sets[tid].ids
        st = table_states[tid].get(k) if tid < len(table_states) else None
        if st is not None:
          st = np.asarray(st)
          # per-row [rows] leaves (SparseAdam 't') slice by id only
          sl = st[ids, cs:ce] if tmpl.ndim == 2 else st[ids]
          buf[off:off + cnt] = np.asarray(sl, dtype=tmpl.dtype)
      sharding = NamedSharding(dist.mesh, P(*([None] * tmpl.ndim)))
      new_state[hkey][k] = jax.make_array_from_callback(
          buf.shape, sharding, lambda index, buf=buf: buf[index])
  return new_state


def _portable(a) -> np.ndarray:
  """Canonical on-disk dtype: ``np.savez`` writes ml_dtypes arrays
  (bfloat16 tables / accumulators) as raw void bytes that load back as
  ``V2`` and lose their dtype — up-cast exactly those (kind ``'V'``
  with no struct fields: the ml_dtypes registration) to f32 (exact: f32
  is a superset of bf16) so the file stays portable; ``set_weights`` /
  ``set_optimizer_state`` cast back to the live template dtype on load.
  Every other kind passes through unchanged: numpy serialises complex,
  string/bytes, object-free structured and bool arrays natively, and
  the old blanket up-cast silently truncated complex extras and garbled
  non-numeric ones (ADVICE.md round 5, low #3).

  ``QuantizedWeight`` entries (design §12) dequantize to their EXACT
  f32 values (po2 scales: the multiply only shifts exponents, so this
  is value-lossless) — the fallback for key schemes with no sidecar
  slot (the positional ``arr_i`` interchange format).
  ``save_train_npz`` instead keeps the pair AS payload+scale members
  (int8 natively; fp8 payloads as a uint8 bit-view plus a dtype tag —
  the blanket f32 up-cast would have kept the values but quadrupled
  the file, defeating quantized storage on disk)."""
  if isinstance(a, QuantizedWeight):
    return a.values()
  a = np.asarray(a)
  if a.dtype.kind == 'V' and a.dtype.names is None:
    return a.astype(np.float32)
  return a


def _quantized_members(i: int, w: QuantizedWeight) -> Dict[str, np.ndarray]:
  """``save_train_npz`` members of one quantized table: the payload
  under the plain ``table{i}`` key (fp8 as a uint8 bit-view — np.savez
  would garble the ml_dtypes array, see ``_portable``) plus
  ``table{i}:scale`` / ``table{i}:dtype`` sidecars.  Bit-lossless by
  construction; ``_parse_train_payload`` reassembles the pair."""
  p = np.asarray(w.payload)
  return {
      f'table{i}': p if p.dtype.kind == 'i' else p.view(np.uint8),
      f'table{i}:scale': np.asarray(w.scale, np.float32).reshape(-1),
      f'table{i}:dtype': np.array(w.dtype_name),
  }


# --------------------------------------------------------------------------
# checkpoint integrity: atomic writes, manifest + checksums, validated load
# --------------------------------------------------------------------------

MANIFEST_KEY = '__manifest__'
MANIFEST_VERSION = 1


def _atomic_savez(path: str, payload: Dict[str, np.ndarray]):
  """The ONE write path for every npz this module produces: write to a
  same-directory tmp file, flush + fsync, then ``os.replace`` — a crash
  at any point leaves either the old file or the new one under the
  canonical name, never a truncated hybrid (the non-atomic direct
  writes were ISSUE 4 satellite #1)."""
  path = os.fspath(path)
  d = os.path.dirname(os.path.abspath(path)) or '.'
  tmp = os.path.join(d, f'.{os.path.basename(path)}.tmp.{os.getpid()}')
  try:
    with open(tmp, 'wb') as f:
      np.savez(f, **payload)
      f.flush()
      os.fsync(f.fileno())
    os.replace(tmp, path)
  finally:
    if os.path.exists(tmp):
      try:
        os.remove(tmp)
      except OSError:
        pass


def plan_fingerprint(obj) -> str:
  """Stable fingerprint of the LOGICAL table set a checkpoint serialises
  (per-table rows/width/combiner) — deliberately NOT the physical
  layout: the resharding contract means a file written under one world
  size / strategy loads under any other, so only a different *model*
  (table shapes) makes a file unloadable.  Accepts a
  ``DistributedEmbedding``, a ``ShardingPlan``, a ``TableConfig``
  sequence, or an already-computed fingerprint string."""
  if isinstance(obj, str):
    return obj
  configs = getattr(obj, 'table_configs', None)
  if configs is None:
    plan = getattr(obj, 'plan', None)
    configs = plan.table_configs if plan is not None else obj
  material = json.dumps(
      [[int(c.input_dim), int(c.output_dim), c.combiner] for c in configs])
  return hashlib.sha256(material.encode()).hexdigest()[:16]


def _checksum(a: np.ndarray) -> str:
  """sha256 over dtype + shape + raw bytes of one stored array."""
  a = np.ascontiguousarray(a)
  h = hashlib.sha256(f'{a.dtype.str}:{a.shape}:'.encode())
  h.update(a.tobytes())
  return h.hexdigest()


def _build_manifest(payload: Dict[str, np.ndarray],
                    step: Optional[int] = None,
                    plan=None) -> np.ndarray:
  man = {
      'version': MANIFEST_VERSION,
      'step': None if step is None else int(step),
      'plan': None if plan is None else plan_fingerprint(plan),
      'arrays': {
          k: {'sha256': _checksum(v), 'dtype': np.asarray(v).dtype.str,
              'shape': list(np.asarray(v).shape)}
          for k, v in payload.items()
      },
  }
  return np.array(json.dumps(man))


def read_manifest(path: str) -> Optional[Dict]:
  """The file's embedded manifest, or None for a legacy (pre-manifest)
  npz — which stays loadable per the compatibility contract
  (docs/design.md "Checkpoint manifest")."""
  with np.load(path, allow_pickle=False) as data:
    if MANIFEST_KEY not in data.files:
      return None
    return json.loads(str(data[MANIFEST_KEY][()]))


def _load_verified(path: str, expect_plan=None
                   ) -> Tuple[Dict[str, np.ndarray], Optional[Dict]]:
  """ONE-pass verify + load: every member is read (and, for
  manifest-bearing files, sha256-checked) exactly ONCE — a multi-GB
  resume pays single I/O, not a verify pass followed by a re-read.
  Returns ``(arrays, manifest)`` (manifest None for legacy files, which
  pass on the structural read alone); raises ``ValueError`` carrying
  the rejection reason otherwise."""
  try:
    with np.load(path, allow_pickle=False) as data:
      files = list(data.files)
      arrays_meta = None
      man = None
      if MANIFEST_KEY in files:
        man = json.loads(str(data[MANIFEST_KEY][()]))
        if expect_plan is not None and man.get('plan') is not None:
          want = plan_fingerprint(expect_plan)
          if man['plan'] != want:
            raise ValueError(f'plan-mismatch: file plan {man["plan"]}, '
                             f'expected {want}')
        arrays_meta = man.get('arrays', {})
        missing = [k for k in arrays_meta if k not in files]
        if missing:
          raise ValueError(f'missing array {missing[0]!r}')
        stray = [k for k in files
                 if k != MANIFEST_KEY and k not in arrays_meta]
        if stray:
          raise ValueError(f'arrays not in manifest: {stray}')
      loaded = {}
      for k in files:  # decompression errors surface truncation
        if k == MANIFEST_KEY:
          continue
        a = data[k]
        if (arrays_meta is not None
            and _checksum(a) != arrays_meta[k]['sha256']):
          raise ValueError(f'checksum mismatch on {k!r}')
        loaded[k] = a
      return loaded, man
  except ValueError:
    raise
  except Exception as e:  # truncated zip, bad json, short member, ...
    raise ValueError(f'unreadable: {e!r}') from e


def verify_npz(path: str, expect_plan=None
               ) -> Tuple[bool, str, Optional[Dict]]:
  """Validate one checkpoint file: ``(ok, reason, manifest)``.

  A manifest-bearing file must decompress, carry every manifested array
  with a matching sha256, list no stray arrays, and (when
  ``expect_plan`` is given) match the plan fingerprint.  A legacy file
  without a manifest passes on a structural check only (every member
  decompresses) with reason ``'legacy-no-manifest'`` — old round-trip
  npz files keep loading.  Never raises: any unreadable file is
  ``(False, 'unreadable: ...', None)``.
  """
  try:
    _, man = _load_verified(path, expect_plan=expect_plan)
  except ValueError as e:
    return False, str(e), None
  return True, 'ok' if man is not None else 'legacy-no-manifest', man


def _step_hint(path: str) -> int:
  """Numeric step parsed from the file name (last integer group, e.g.
  ``ckpt_1000.npz`` -> 1000), -1 when absent — the mtime tie-breaker.
  A lexical tie-break would rank ckpt_999 above ckpt_1000 on
  filesystems with coarse mtime granularity (NFS, FAT)."""
  groups = re.findall(r'\d+', os.path.basename(path))
  return int(groups[-1]) if groups else -1


def _is_atomic_tmp(name: str) -> bool:
  """Matches exactly ``_atomic_savez``'s tmp naming
  (``.{basename}.tmp.{pid}``) — a user checkpoint merely CONTAINING
  '.tmp' must stay visible to resume/retention."""
  return name.startswith('.') and '.tmp.' in name


QUARANTINE_SUFFIX = '.corrupt'


_QUARANTINE_RE = re.compile(r'\.corrupt(\.\d+)?$')


def _is_quarantined(name: str) -> bool:
  """Matches exactly ``quarantine_checkpoint``'s naming
  (``*.corrupt`` / ``*.corrupt.N``) — a user checkpoint merely
  CONTAINING '.corrupt' mid-name must stay visible to
  resume/retention (same rule as ``_is_atomic_tmp``)."""
  return _QUARANTINE_RE.search(name) is not None


def _candidates(directory: str, pattern: str) -> List[str]:
  """Checkpoint files under ``directory`` newest-first (mtime, then the
  numeric step in the name, then the name); in-flight atomic tmp files
  AND quarantined ``*.corrupt`` files excluded — a quarantined file
  must never re-enter resume candidate ordering or retention counting
  (it would either resume known-bad state or push a good file out of
  the keep window)."""
  paths = [p for p in glob_lib.glob(os.path.join(directory, pattern))
           if not _is_atomic_tmp(os.path.basename(p))
           and not _is_quarantined(os.path.basename(p))]
  return sorted(paths,
                key=lambda p: (os.path.getmtime(p), _step_hint(p), p),
                reverse=True)


# files currently targeted by an in-flight rollback/restore: retention
# must never delete them mid-read (the self-healing fit rolls back while
# its own CheckpointCallback keeps pruning).  Guarded registry, not a
# lock around the whole restore: prune just skips these paths.
_PROTECTED_LOCK = threading.Lock()
_PROTECTED: set = set()


class _protect_path:
  """Context manager marking ``path`` as in-flight (prune-exempt)."""

  def __init__(self, path: str):
    self.path = os.path.abspath(path)

  def __enter__(self):
    with _PROTECTED_LOCK:
      _PROTECTED.add(self.path)
    return self.path

  def __exit__(self, *exc):
    with _PROTECTED_LOCK:
      _PROTECTED.discard(self.path)


def protected_paths() -> List[str]:
  with _PROTECTED_LOCK:
    return sorted(_PROTECTED)


# verification results for the RETENTION ANCHOR only, keyed by
# (path, mtime_ns, size): the anchor search runs after EVERY periodic
# save, and re-reading + re-checksumming the multi-GB file it verified
# one save ago would double steady-state checkpoint I/O.  An unchanged
# (mtime, size) pair re-uses the last verdict; any rewrite (atomic
# os.replace updates both) re-verifies.  Resume-time verification
# (``load_latest_valid`` / ``restore_train_state``) NEVER consults
# this cache — a file that bit-rotted without an mtime change can at
# worst be over-protected from pruning, never loaded unverified.
# Bounded: stale entries evict FIFO.
_VERIFY_CACHE: Dict[str, Tuple[Tuple[int, int], bool]] = {}
_VERIFY_CACHE_CAP = 64


def _verified_cached(path: str) -> bool:
  try:
    st = os.stat(path)
  except OSError:
    return False
  key = (st.st_mtime_ns, st.st_size)
  hit = _VERIFY_CACHE.get(os.path.abspath(path))
  if hit is not None and hit[0] == key:
    return hit[1]
  ok, _, _ = verify_npz(path)
  if len(_VERIFY_CACHE) >= _VERIFY_CACHE_CAP:
    _VERIFY_CACHE.pop(next(iter(_VERIFY_CACHE)))
  _VERIFY_CACHE[os.path.abspath(path)] = (key, ok)
  return ok


def quarantine_checkpoint(path: str) -> str:
  """Rename a checkpoint that failed verification to
  ``{path}.corrupt`` (``.corrupt.2``, ... if taken) — NEVER delete:
  the damaged bytes are the forensic evidence for the corruption
  (which offsets flipped, whether the writer or the medium is at
  fault), and deletion would destroy it.  Quarantined files are
  excluded from resume candidate ordering and retention counting
  (``_candidates``).  Journaled (``checkpoint_quarantined``); returns
  the new path."""
  target = path + QUARANTINE_SUFFIX
  n = 1
  while os.path.exists(target):
    n += 1
    target = f'{path}{QUARANTINE_SUFFIX}.{n}'
  os.replace(path, target)
  resilience.journal('checkpoint_quarantined', path=path, target=target)
  return target


def load_latest_valid(directory: str,
                      expect_plan=None,
                      pattern: str = '*.npz',
                      quarantine: bool = False):
  """Scan ``directory`` newest-first and load the first VALID resumable
  checkpoint: ``(path, (weights, table_states, extras))``.

  Every rejected candidate (truncated, checksum-mismatched,
  plan-mismatched, or structurally not a ``save_train_npz`` file) is
  journaled with its reason (``checkpoint_rejected``) and skipped — the
  auto-resume path falls back to the previous valid file instead of
  dying on the artifact a crash corrupted.  With ``quarantine=True``
  (the self-healing rollback path, design §13), candidates failing an
  INTEGRITY check are additionally renamed to ``*.corrupt``
  (``quarantine_checkpoint``) so later resumes never rescan known-bad
  bytes; plan-mismatched files are left in place — they are valid
  checkpoints of a different model, not corruption.  Raises
  ``FileNotFoundError`` with the per-file reasons when nothing valid
  remains.
  """
  reasons = []
  for path in _candidates(directory, pattern):
    # single pass: each candidate's members are read + checksummed once
    # (_load_verified), then parsed in memory — never re-read from disk.
    # The candidate is prune-protected while in flight.
    with _protect_path(path):
      try:
        arrays, _ = _load_verified(path, expect_plan=expect_plan)
      except ValueError as e:
        reason = str(e)
        resilience.journal('checkpoint_rejected', path=path,
                           reason=reason)
        reasons.append((path, reason))
        # quarantine only on INTEGRITY failure: a plan-mismatched file
        # is a valid checkpoint of a different model, not corruption
        if quarantine and not reason.startswith('plan-mismatch'):
          try:
            quarantine_checkpoint(path)
          except OSError:
            pass
        continue
      try:
        payload = _parse_train_payload(arrays, path)
      except Exception as e:  # valid npz but not a resumable train file
        # not quarantined either: the file is intact (checksums passed),
        # just not in the save_train_npz key scheme (e.g. a weights-only
        # save_npz sharing the directory)
        reason = f'not-a-train-checkpoint: {e!r}'
        resilience.journal('checkpoint_rejected', path=path,
                           reason=reason)
        reasons.append((path, reason))
        continue
      return path, payload
  detail = '; '.join(f'{os.path.basename(p)}: {r}' for p, r in reasons)
  raise FileNotFoundError(
      f'no valid checkpoint under {directory!r} (pattern {pattern!r})'
      + (f' — rejected: {detail}' if detail else ''))


def prune_checkpoints(directory: str, keep_last: int,
                      pattern: str = '*.npz') -> List[str]:
  """Retention: delete all but the newest ``keep_last`` checkpoints
  matching ``pattern``; returns the removed paths (journaled).

  Two files are exempt beyond the keep window (design §13 — retention
  must never strand a rollback):

  - the newest VERIFIED checkpoint (candidates verify newest-first
    until one passes — normally one ``verify_npz`` of the file just
    written): if every file inside the keep window is corrupt, the
    last-known-good file beyond it survives pruning, so
    ``load_latest_valid`` always has a fall-back;
  - any path currently registered by an in-flight rollback/restore
    (``_protect_path``).

  Quarantined ``*.corrupt`` files neither count toward ``keep_last``
  nor get removed here (``_candidates`` excludes them; forensics are
  kept deliberately).
  """
  if keep_last < 1:
    raise ValueError(f'keep_last must be >= 1, got {keep_last}')
  cands = _candidates(directory, pattern)
  anchor = None  # newest checkpoint that actually verifies
  for p in cands:
    if _verified_cached(p):
      anchor = p
      break
  protected = set(protected_paths())
  removed = []
  for path in cands[keep_last:]:
    if path == anchor or os.path.abspath(path) in protected:
      continue
    try:
      os.remove(path)
      removed.append(path)
    except OSError:
      continue
  if removed:
    resilience.journal('checkpoint_pruned', removed=removed,
                       keep_last=keep_last)
  return removed


def save_npz(path: str, weights: Sequence[np.ndarray]):
  """Save global weights the way the DLRM example does
  (reference `examples/dlrm/main.py:246-248`) — atomically.

  Deliberately NO embedded manifest: the weights-only ``arr_i`` archive
  is the reference DLRM interchange format, and external readers (and
  older checkouts) enumerate ``data.files`` positionally — an extra
  member would land in their weights list.  Integrity manifests belong
  to the resumable ``save_train_npz`` files, whose key scheme filters
  unknown members; ``verify_npz`` treats these files as legacy
  (structural check only)."""
  payload = {f'arr_{i}': _portable(w) for i, w in enumerate(weights)}
  _atomic_savez(path, payload)


def load_npz(path: str) -> List[np.ndarray]:
  data = np.load(path)
  return [data[k] for k in data.files if k != MANIFEST_KEY]


def save_train_npz(path: str,
                   weights: Sequence[np.ndarray],
                   table_states: Optional[Sequence[Dict[str, np.ndarray]]]
                   = None,
                   extras: Optional[Dict[str, np.ndarray]] = None,
                   plan=None):
  """Save weights plus (optionally) sparse-optimizer state in one .npz —
  atomically (``_atomic_savez``), with an embedded integrity manifest
  carrying per-array sha256 checksums, the step (from
  ``extras['step']``) and the plan fingerprint when ``plan`` is given
  (``load_latest_valid`` rejects files failing any of these).

  Keys: ``table{i}`` for weights, ``table{i}/{leaf}`` for state leaves —
  the global canonical layout, so the file reshards on load like the
  weight-only path — and ``extra/{name}`` for scalar metadata such as the
  step counter.  ``QuantizedWeight`` entries (``export_tables`` on a
  quantized plan, design §12) store payload+scale losslessly with
  ``table{i}:scale`` / ``table{i}:dtype`` sidecar members — int8 files
  carry ~4x fewer table bytes than f32 and restore bit-exactly into
  any plan.
  """
  # ONE measurement feeds both the span and the histogram (the
  # trace-vs-stats agreement contract, obs/trace.py)
  tok = obs_trace.begin('ckpt/save', path=os.path.basename(path))
  try:
    _save_train_npz(path, weights, table_states, extras, plan)
  finally:
    save_ms = obs_trace.end(tok) * 1000.0
  obs_metrics.inc('ckpt.saves')
  obs_metrics.observe('ckpt.save_ms', save_ms)
  # the periodic save is a natural rank-uniform barrier: cross-check
  # the commsan sequence digests here too (design §22)
  step = int(np.asarray(extras['step'])) if extras and 'step' in extras \
      else None
  commsan.record('ckpt/save', step=step)
  commsan.barrier_check(f'ckpt:{step}')


def _save_train_npz(path, weights, table_states, extras, plan):
  if table_states is not None and len(table_states) != len(weights):
    raise ValueError(f'got {len(table_states)} per-table states for '
                     f'{len(weights)} weight tables')
  payload = {}
  for i, w in enumerate(weights):
    if isinstance(w, QuantizedWeight):
      payload.update(_quantized_members(i, w))
    else:
      payload[f'table{i}'] = _portable(w)
  for i, entry in enumerate(table_states or []):
    for k, v in entry.items():
      payload[f'table{i}/{k}'] = _portable(v)
  for k, v in (extras or {}).items():
    payload[f'extra/{k}'] = _portable(v)
  step = None
  if extras and 'step' in extras:
    step = int(np.asarray(extras['step']))
  payload[MANIFEST_KEY] = _build_manifest(payload, step=step, plan=plan)
  _atomic_savez(path, payload)
  # seed the retention anchor's verify cache: this path just computed
  # every checksum for the manifest and atomically published the file,
  # so the prune that follows each periodic save must not re-read and
  # re-hash the multi-GB artifact it knows to be freshly valid
  try:
    st = os.stat(path)
    if len(_VERIFY_CACHE) >= _VERIFY_CACHE_CAP:
      _VERIFY_CACHE.pop(next(iter(_VERIFY_CACHE)))
    _VERIFY_CACHE[os.path.abspath(path)] = (
        (st.st_mtime_ns, st.st_size), True)
  except OSError:
    pass


def _parse_train_payload(arrays: Dict[str, np.ndarray], path: str):
  """``save_train_npz`` key scheme -> ``(weights, table_states,
  extras)``; raises ``ValueError`` when the arrays are not a resumable
  train checkpoint.  Tables with ``table{i}:scale`` sidecars reassemble
  into ``QuantizedWeight`` pairs (fp8 payloads bit-view back from their
  uint8 storage) — ``set_weights`` dequantizes them exactly on load."""
  table_keys = [k for k in arrays if k.startswith('table')]
  if not table_keys:
    raise ValueError(f'{path}: no table entries')
  n = 1 + max(
      int(k.split('/')[0].partition(':')[0][5:]) for k in table_keys)
  weights: List[Optional[WeightLike]] = [None] * n
  states: List[Dict[str, np.ndarray]] = [dict() for _ in range(n)]
  sidecars: Dict[int, Dict[str, np.ndarray]] = {}
  extras: Dict[str, np.ndarray] = {}
  for k, v in arrays.items():
    head, _, leaf = k.partition('/')
    if head == 'extra':
      extras[leaf] = v
      continue
    name, _, tag = head.partition(':')
    i = int(name[5:])
    if tag:
      sidecars.setdefault(i, {})[tag] = v
    elif leaf:
      states[i][leaf] = v
    else:
      weights[i] = v
  for i, sc in sidecars.items():
    if 'scale' not in sc or weights[i] is None:
      raise ValueError(f'{path}: incomplete quantized entry for table {i}')
    spec = quantization.resolve_table_dtype(str(sc['dtype'][()])
                                            if 'dtype' in sc else 'int8')
    p = np.asarray(weights[i])
    if p.dtype != spec.dtype:
      p = p.view(spec.dtype)  # fp8 stored as its uint8 bit-view
    weights[i] = QuantizedWeight(payload=p,
                                 scale=np.asarray(sc['scale'], np.float32),
                                 dtype_name=spec.name)
  missing = [i for i, w in enumerate(weights) if w is None]
  if missing:
    raise ValueError(f'{path}: missing weight entries for tables {missing}')
  return weights, states, extras


def load_train_npz(path: str):
  """Inverse of ``save_train_npz``:
  returns ``(weights, table_states, extras)``."""
  data = np.load(path)
  return _parse_train_payload(
      {k: data[k] for k in data.files if k != MANIFEST_KEY}, path)


# --------------------------------------------------------------------------
# full train-state restore (the fit(resume_from=...) engine)
# --------------------------------------------------------------------------


def is_hybrid_opt_state(dist: DistributedEmbedding, opt_state) -> bool:
  """Structural detection of the hybrid train-state optimizer layout:
  a 2-tuple whose second element is a dict keyed exactly by the plan's
  fusion-group names.  A plain ``isinstance(tuple)`` check is ambiguous
  (optax states are namedtuples and can carry dict fields) — advisor
  r4."""
  group_names = {f'group_{gi}' for gi in range(len(dist.plan.groups))}
  group_names |= {
      f'hot_group_{gi}' for gi in getattr(dist.plan, 'hot_groups', [])
  }
  return (isinstance(opt_state, tuple) and len(opt_state) == 2
          and isinstance(opt_state[1], dict)
          and set(opt_state[1].keys()) == group_names)


def _restore_like(template, saved: Dict[str, np.ndarray], prefix: str):
  """Rebuild a pytree from flattened ``prefix + keystr(path)`` npz
  entries, falling back to the template leaf where a key is absent."""
  import jax.numpy as jnp
  leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
  rebuilt = [
      jnp.asarray(saved[prefix + jax.tree_util.keystr(p)])
      if prefix + jax.tree_util.keystr(p) in saved else v
      for p, v in leaves
  ]
  return jax.tree_util.tree_unflatten(treedef, rebuilt)


def restore_train_state(dist: DistributedEmbedding, state, source: str,
                        quarantine: bool = False):
  """Restore a ``TrainState`` from a resumable checkpoint: embedding
  tables reshard through ``set_weights``, sparse-optimizer tables
  through ``set_optimizer_state``, dense params / optax state (incl.
  schedule counters) from the flattened ``dense:`` / ``opt:`` extras,
  and the step counter — so a resumed ``fit`` continues bit-exactly
  (tests/test_fault_tolerance.py pins this against an uninterrupted
  run).

  ``source`` is either one ``.npz`` path (verified first; raises
  ``ValueError`` on a corrupt/mismatched file) or a directory
  (``load_latest_valid``: newest valid file wins, rejects journaled).
  ``state`` supplies the structure to rebuild into — a fresh
  ``init_train_state`` / ``init_hybrid_train_state``.

  ``quarantine``: the in-process-rollback spelling (design §13; what
  ``fit(on_anomaly='rollback')`` uses) — candidates failing integrity
  verification are renamed ``*.corrupt`` instead of merely skipped,
  and the chosen file is registered prune-exempt while the restore is
  in flight.

  Returns ``(state, path)`` — the restored state and the file used.
  """
  tok = obs_trace.begin('ckpt/restore', source=os.path.basename(source))
  try:
    out = _restore_train_state(dist, state, source, quarantine)
  finally:
    restore_ms = obs_trace.end(tok) * 1000.0
  obs_metrics.inc('ckpt.restores')
  obs_metrics.observe('ckpt.restore_ms', restore_ms)
  # record WITHOUT a barrier check: a restore can legitimately run on
  # one rank only (the rollback path) — the divergence it introduces is
  # what the NEXT barrier's digest comparison detects
  commsan.record('ckpt/restore', source=os.path.basename(source))
  return out


def _restore_train_state(dist, state, source, quarantine):
  # refuse BEFORE any file I/O: the reshard below would read the
  # hierarchical axis-product leaves as flat shards (design §20)
  _refuse_dcn_sharding(dist, 'restore_train_state')
  if os.path.isdir(source):
    path, (weights, st_tables, extras) = load_latest_valid(
        source, expect_plan=dist, quarantine=quarantine)
  else:
    try:  # single pass: verified and parsed from one read
      arrays, _ = _load_verified(source, expect_plan=dist)
    except ValueError as e:
      resilience.journal('checkpoint_rejected', path=source,
                         reason=str(e))
      raise ValueError(f'{source}: invalid checkpoint: {e}') from e
    path = source
    weights, st_tables, extras = _parse_train_payload(arrays, source)
  with _protect_path(path):  # in-flight rollback target: prune-exempt
    return _rebuild_train_state(dist, state, path, weights, st_tables,
                                extras)


def _rebuild_train_state(dist, state, path, weights, st_tables, extras):
  import jax.numpy as jnp
  new_params = dict(state.params)
  new_params['embedding'] = set_weights(dist, weights)
  dense_template = {k: v for k, v in new_params.items() if k != 'embedding'}
  new_params.update(_restore_like(dense_template, extras, 'dense:'))
  if is_hybrid_opt_state(dist, state.opt_state):
    emb_opt_state = state.opt_state[1]
    if any(st_tables):
      emb_opt_state = set_optimizer_state(dist, emb_opt_state, st_tables)
    opt_state = (_restore_like(state.opt_state[0], extras, 'opt:'),
                 emb_opt_state)
  else:
    opt_state = _restore_like(state.opt_state, extras, 'opt:')
  step = int(np.asarray(extras.get('step', 0)))
  resilience.journal('resume', path=path, step=step)
  new_state = type(state)(params=new_params, opt_state=opt_state,
                          step=jnp.asarray(step, jnp.int32))
  return new_state, path
