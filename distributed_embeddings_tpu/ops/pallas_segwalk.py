"""Pallas TPU kernel: fused segment-walk sparse optimizer apply.

One streaming pass over the SORTED per-occurrence update stream that
does segment summation AND the optimizer read-modify-write together —
the "compaction+apply in one pass" kernel the round-2 perf notes
designed (docs/perf_notes.md tail; VERDICT r2 item 2).  The XLA
pipeline it replaces costs, per step on synthetic-tiny's big group
(measured): ~300 ms of compaction (full-stream cumsums, rank sort,
cap-sized gathers) plus the scatter passes of the apply (~100 ns per
static row).  This kernel reads the sorted stream once at
sequential-DMA bandwidth, reduces each id's run in VMEM with a
segmented scan, and touches HBM randomly only at each segment's LAST
position — one read + one write of the table (and accumulator) row per
UNIQUE id, at the DMA-issue floor.

Inputs are produced by plain XLA (`parallel/sparse.py:_segwalk_apply`):
``argsort`` of the raw ids (~5 ns/row) and the one unavoidable gather
of the gradient rows into sorted order — everything else the old
pipeline did per payload disappears.  There is NO capacity/overflow
machinery: every segment is applied exactly once, whatever the unique
count.

Narrow widths lane-pack: for ``width < 128`` (dividing 128, rows
divisible by the pack factor) the table is viewed as
``[rows/pack, 128]``, the id stream divides by ``pack`` (adjacent uids
sharing a packed row merge into one segment, their totals living in
disjoint lanes via an in-register mask expansion), and each unique
PACKED row costs one full-512B-burst DMA pair — both fewer random DMAs
(up to ``pack`` x) and full-burst ones, with no extra HBM stream
traffic (the expansion happens in VMEM).

Semantics supported (all exact):
- 'sgd':            ``table[uid] -= lr * seg_sum``
- 'adagrad_dedup':  ``acc += seg_sum**2`` then scaled add (reference
  dedup semantics, the default)
- 'adagrad_sq':     ``acc += seg_sum_of_squares`` (per-occurrence
  squares ride the same scan as a second payload; no extra operand)

Reference analog: the CUDA backward's sort->segment-reduce feeding
``IndexedSlices`` into the framework optimizer
(`embedding_lookup_kernels.cu:463-635`, SURVEY.md C3) — fused here with
the optimizer itself because TPU scatters are scalar-issued rather than
atomic-parallel.

Hazard discipline: reads are issued first and land while the vector
core runs the segmented scan (latency hidden behind compute); writes
are issued at tile end and stay in flight through the NEXT tile's
reads/compute, draining only when their parity's staging buffers are
about to be reused two steps later (the parity protocol inherited
from the retired round-2 rowwise kernel, with the per-tile in-flight
count carried in SMEM because the valid-row count here is
data-dependent).  This is safe because each unique row is touched at
exactly one grid step (its segment-last position in the sorted
stream), so in-flight writes can never alias a later step's reads.
The kernel is OPT-IN (``use_segwalk_apply=True``) until measured on
chip.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_embeddings_tpu.obs import trace as obs_trace

# Test hook: engage the kernel in interpreter mode on any backend so
# CI exercises the real producers.
FORCE_INTERPRET = False
# AOT hook: compile-only flows (jax.experimental.topologies) trace on a
# CPU default backend while targeting TPU, so the runtime's
# backend-sniffing dispatch would silently select the XLA path; setting
# this engages the REAL kernel (interpret=False) regardless of the
# traced-on backend.  Used by compile_check.py / test_tpu_lowering.py.
ASSUME_TPU = False


# 1-D s32 SMEM operands must block at Mosaic's SMEM tile: XLA lays
# s32[n] out as T(1024)S(1) and any other block shape fails layout
# verification.  The grid tile stays smaller (VMEM: the segmented
# scan's unrolled temps scale with it), so several grid steps share
# one SMEM block via index_map t -> (t*tile)//1024 with an in-kernel
# base offset.
_SMEM_BLOCK = 1024


def _tile_rows(width: int) -> int:
  """Stream rows per grid step: sized so the parity pairs of
  [tile, width] f32 staging arrays plus the segmented scan's unrolled
  shift temps stay inside scoped VMEM, capped at 512 scalar-walk
  iterations.  Always divides ``_SMEM_BLOCK``."""
  return max(128, min(512, 32768 // width))


def _seg_scan(vals: jax.Array, starts: jax.Array) -> jax.Array:
  """Segmented inclusive prefix sum along the sublane axis.

  Hillis-Steele with STATIC shifts only (slices + concat + elementwise;
  no cumsum/gather primitives, whose Mosaic lowering for this layout is
  uncertain).  ``starts``: [T, 1] f32, 1.0 at segment starts.  log2(T)
  unrolled steps, each a handful of vector ops.
  """
  t = vals.shape[0]
  stop = jnp.broadcast_to(starts, vals.shape)
  d = 1
  while d < t:
    pad_v = jnp.zeros((d,) + vals.shape[1:], vals.dtype)
    pad_s = jnp.ones((d,) + vals.shape[1:], vals.dtype)
    shifted_v = jnp.concatenate([pad_v, vals[:-d]], axis=0)
    shifted_s = jnp.concatenate([pad_s, stop[:-d]], axis=0)
    vals = vals + shifted_v * (1.0 - stop)
    stop = jnp.maximum(stop, shifted_s)
    d *= 2
  return vals


def _segwalk_kernel(sid_smem, islast_smem, g_ref, idv_ref, lr_smem,
                    table_in, acc_in, table_ref, acc_ref,
                    tbuf, abuf, carry, carry_id, wcount, rsem, wsem, *,
                    natural_rows, nfetch, prows, num_tiles, tile, width,
                    gw, pack, pair, sideband, op):
  """One [tile] block of the sorted stream against [*, width] rows.

  ``op``: 'sgd' | 'adagrad_dedup' | 'adagrad_sq' (static).  ``carry``
  [2, pair*width] VMEM scratch holds the running (sum, sum_sq) of the
  segment spanning the tile boundary; ``carry_id`` [1, 1] SMEM its id.
  For 'sgd' the acc refs point at a dummy buffer and are never DMA'd.

  Operand layout (round 4 — the padding rework): the sorted ORIGINAL
  ids arrive once as a 1-D SMEM stream (untiled in HBM: a [N, 1] s32
  column stores T(8,128)-padded at 128x, measured as multi-GiB temps at
  synthetic scale) plus, for the vector side, either as a bitcast f32
  SIDEBAND LANE of the gradient block (``sideband``, narrow widths:
  lanes [0, gw) gradient, lane gw the ids — the block is exactly the
  128 lanes the padded narrow block already paid for) or as one
  [tile, 1] VMEM column (width-128 tables, whose gradient block has no
  spare lane).  Packed row ids, lane slots, pair halves and segment
  starts are all DERIVED in-kernel (scalar ops in the walks, vector
  div/rem/compare on the id column) instead of travelling as four more
  padded streams.

  Lane packing (``pack > 1``): the table is viewed as
  ``[rows/pack, 128]`` (free row-major reshape — the operand itself
  when prepacked); ids divide by ``pack`` in-kernel (adjacent uids
  sharing a packed row merge into one segment) and the gradient block
  expands in-register to the packed width with a lane mask — each
  unique PACKED row costs one full-burst DMA pair serving up to
  ``pack`` original rows (untouched lanes carry zero gradient; Adagrad
  is elementwise, the exact argument of
  ``parallel/sparse.py:_lane_pack``).

  Pair fetch (``pair == 2``, bf16 tables): Mosaic rejects
  single-sublane bf16 slices (the packed-sublane layout pairs rows
  2k/2k+1 in one 32-bit word), so fetch ids further divide by 2 —
  indexing PAIRS of the 3-D table view ``[rows/(2*pack), 2, width]``
  with each row's ``packed_id % 2`` selecting its half.  The payload
  expands to ``pair*width`` lanes (one block per half) and the
  scan/carry machinery runs unchanged at that superrow width; the
  optimizer update runs per half on f32-converted staging values and
  rounds to bf16 once at write.  The write-back of a whole fetched
  pair is SAFE here — unlike a per-unique-row RMW kernel (the retired
  rowwise kernel's hazard) — because the segment key IS the
  pair: both rows of a pair merge into one segment applied at exactly
  one grid position, so no other step can race the untouched half
  (which is rewritten byte-identically: zero gradient lanes give a
  zero update, and f32(bf16) round-trips exactly).
  """
  del table_in, acc_in  # same memory as the aliased output refs
  has_acc = op != 'sgd'
  pw = pair * width
  t = pl.program_id(0)
  p = jax.lax.rem(t, 2)
  # several grid steps share one _SMEM_BLOCK-sized id/flag block (see
  # _tile_rows): this step's rows start at `base` within it
  base = jax.lax.rem(t * tile, _SMEM_BLOCK)

  def kid_of(oid):
    """Scalar/vector original id -> fetch-unit id (see ``fetch_ids``)."""
    return fetch_ids(oid, natural_rows, prows, pack, pair)

  @pl.when(t == 0)
  def _init():
    carry_id[0, 0] = -1
    carry[...] = jnp.zeros((2, pw), jnp.float32)
    wcount[0, 0] = 0
    wcount[1, 0] = 0

  def drain_writes(pp, count):
    """Wait ``count`` write pairs issued on parity ``pp``."""
    def w(k, _):
      pltpu.make_async_copy(tbuf.at[pp, pl.ds(k, 1)],
                            table_ref.at[pl.ds(0, 1)], wsem.at[pp]).wait()
      if has_acc:
        pltpu.make_async_copy(abuf.at[pp, pl.ds(k, 1)],
                              acc_ref.at[pl.ds(0, 1)], wsem.at[pp]).wait()
      return 0

    jax.lax.fori_loop(0, count, w, 0)
    return 0

  # reuse of this parity's staging buffers: the writes issued two grid
  # steps ago (same parity) must have landed — tile t-1's writes stay in
  # flight through this tile's reads/compute (rows are globally unique,
  # so no read below can touch a row still being written)
  drain_writes(p, wcount[p, 0])

  # ----- scalar walk 1: burst-read rows at segment-last positions ------
  # Issued FIRST so the random-row DMAs fly while the vector core runs
  # the segmented scan below: the read latency hides behind compute
  # instead of serializing after it.
  def read_row(k, cnt):
    kid = kid_of(sid_smem[base + k])

    def do(c):
      rid = jnp.clip(kid, 0, nfetch - 1)
      pltpu.make_async_copy(table_ref.at[pl.ds(rid, 1)],
                            tbuf.at[p, pl.ds(k, 1)], rsem).start()
      if has_acc:
        pltpu.make_async_copy(acc_ref.at[pl.ds(rid, 1)],
                              abuf.at[p, pl.ds(k, 1)], rsem).start()
      return c + 1

    return jax.lax.cond(
        (islast_smem[base + k] == 1) & (kid < nfetch), do,
        lambda c: c, cnt)

  nval = jax.lax.fori_loop(0, tile, read_row, 0)

  # ----- vector side: segmented totals (reads in flight) ---------------
  blk = g_ref[:]                             # [tile, 128] f32|bf16
  stream_bf16 = blk.dtype == jnp.bfloat16
  if sideband:
    if stream_bf16:
      # ids ride lanes gw (low 16 bits) and gw+1 (high) as raw bf16
      # bits; cross-bitwidth bitcast with a shape change is not
      # lowerable on v5e, so reassemble via same-width u16 bitcasts +
      # integer shift/or (compile-gated pattern)
      lo = jax.lax.bitcast_convert_type(blk[:, gw:gw + 1],
                                        jnp.uint16).astype(jnp.int32)
      hi = jax.lax.bitcast_convert_type(blk[:, gw + 1:gw + 2],
                                        jnp.uint16).astype(jnp.int32)
      oid_col = jnp.left_shift(hi, 16) | lo
    else:
      # ids ride lane gw of the gradient block as raw bits
      oid_col = jax.lax.bitcast_convert_type(blk[:, gw:gw + 1], jnp.int32)
    g = blk[:, :gw].astype(jnp.float32)      # [tile, gw]
  else:
    oid_col = idv_ref[:]                     # [tile, 1] int32
    g = blk.astype(jnp.float32)
  sent_col = oid_col >= natural_rows
  kid_col = kid_of(oid_col)
  prev = jnp.concatenate(
      [jnp.full((1, 1), -2, jnp.int32), kid_col[:-1]], axis=0)
  starts = jnp.concatenate(
      [jnp.ones((1, 1), jnp.float32),
       (kid_col[1:] != prev[1:]).astype(jnp.float32)], axis=0)
  if pack > 1:
    slot_col = jnp.where(sent_col, 0, jax.lax.rem(oid_col, pack))
    lane = jax.lax.broadcasted_iota(jnp.int32, (tile, width), 1) // gw
    g = jnp.tile(g, (1, pack)) * (lane == slot_col).astype(jnp.float32)
  if pair > 1:
    # expand to the pair superrow: one `width`-lane block per half,
    # masked by the row's half index (zeros in the untouched half)
    pid_col = jnp.where(sent_col, prows, oid_col // pack)
    hf = (jax.lax.rem(pid_col, 2) == 0).astype(jnp.float32)  # [tile, 1]
    g = jnp.concatenate([g * hf, g * (1.0 - hf)], axis=1)  # [tile, pw]
  # both scalars live in SMEM: scalar compare, then broadcast
  cont = (kid_of(sid_smem[base]) == carry_id[0, 0]).astype(jnp.float32)
  if op == 'adagrad_sq':
    payload = jnp.concatenate([g, g * g], axis=1)       # [tile, 2*pw]
    # lane-concat, not reshape: splitting [1, 2*pw] into [2, pw] is a
    # lane-splitting shape cast Mosaic rejects past 128 lanes
    carry_row = jnp.concatenate([carry[0:1], carry[1:2]], axis=1)
  else:
    payload = g
    carry_row = carry[0:1]
  inject = jnp.concatenate(
      [payload[0:1] + cont * carry_row, payload[1:]], axis=0)
  seg = _seg_scan(inject, starts)                       # [tile, pw|2pw]
  tot = seg[:, :pw]

  def wait_read(k, _):
    pltpu.make_async_copy(table_ref.at[pl.ds(0, 1)],
                          tbuf.at[p, pl.ds(k, 1)], rsem).wait()
    if has_acc:
      pltpu.make_async_copy(acc_ref.at[pl.ds(0, 1)],
                            abuf.at[p, pl.ds(k, 1)], rsem).wait()
    return 0

  jax.lax.fori_loop(0, nval, wait_read, 0)

  # ----- vector update (garbage at non-last rows is never written) -----
  lr = lr_smem[0, 0]
  if pair == 1:
    if op == 'sgd':
      tbuf[p] = tbuf[p] - lr * tot
    else:
      add = tot * tot if op == 'adagrad_dedup' else seg[:, width:]
      acc_new = abuf[p] + add
      eps = lr_smem[0, 1]
      tbuf[p] = tbuf[p] - lr * tot * jax.lax.rsqrt(acc_new + eps)
      abuf[p] = acc_new
  else:
    # per half: f32 math on the converted bf16 staging rows, one
    # rounding at the write.  Halves with no stream contributions see a
    # zero total (and zero acc add), so they rewrite byte-identically —
    # f32(bf16) round-trips exactly.  Slices address the REF with a
    # static middle index (fresh loads/stores; value-slicing a loaded
    # 3-D block leaves layout offsets Mosaic rejects — see
    # ops/pallas_lookup.py's `unit`).
    for s in range(2):
      tots = tot[:, s * width:(s + 1) * width]
      ts = tbuf[p, :, s, :].astype(jnp.float32)
      if op == 'sgd':
        ns = ts - lr * tots
      else:
        adds = (tots * tots if op == 'adagrad_dedup'
                else seg[:, pw + s * width:pw + (s + 1) * width])
        # abuf may be bf16 (accum_dtype='bfloat16' on a bf16 table):
        # accumulate + rsqrt in f32, round once at the store — the
        # untouched half adds zero and rewrites byte-identically
        # (bf16(f32(bf16)) is exact), preserving the pair-write safety
        # argument above
        acc_new = abuf[p, :, s, :].astype(jnp.float32) + adds
        eps = lr_smem[0, 1]
        ns = ts - lr * tots * jax.lax.rsqrt(acc_new + eps)
        abuf[p, :, s, :] = acc_new.astype(abuf.dtype)
      tbuf[p, :, s, :] = ns.astype(tbuf.dtype)

  # ----- update carries (AFTER the scan consumed the old values) -------
  if op == 'adagrad_sq':
    carry[0:1] = seg[tile - 1:tile, :pw]
    carry[1:2] = seg[tile - 1:tile, pw:]
  else:
    carry[0:1] = seg[tile - 1:tile]
  carry_id[0, 0] = kid_of(sid_smem[base + tile - 1])

  # ----- scalar walk 2: issue writes; they stay in flight through the
  # NEXT tile's reads/compute and drain when this parity comes up again
  def write_row(k, _):
    kid = kid_of(sid_smem[base + k])

    def do(_):
      rid = jnp.clip(kid, 0, nfetch - 1)
      pltpu.make_async_copy(tbuf.at[p, pl.ds(k, 1)],
                            table_ref.at[pl.ds(rid, 1)], wsem.at[p]).start()
      if has_acc:
        pltpu.make_async_copy(abuf.at[p, pl.ds(k, 1)],
                              acc_ref.at[pl.ds(rid, 1)], wsem.at[p]).start()
      return 0

    jax.lax.cond(
        (islast_smem[base + k] == 1) & (kid < nfetch), do,
        lambda _: 0, 0)
    return 0

  jax.lax.fori_loop(0, tile, write_row, 0)
  wcount[p, 0] = nval

  # last grid step: nothing runs after the kernel — drain everything
  # still in flight (the other parity's tile t-1 writes, then our own)
  @pl.when(t == num_tiles - 1)
  def _drain_all():
    drain_writes(1 - p, wcount[1 - p, 0])
    drain_writes(p, nval)


def fetch_ids(ids, natural_rows: int, prows: int, pack: int, pair: int):
  """Original row id -> fetch-unit id (the DMA-indexable granularity):
  sentinels (>= ``natural_rows``) land at ``prows // pair`` = nfetch,
  out of range, skipped by the walks.  ONE definition used by the host
  (global segment-last flags) and the kernel (both scalar walks and the
  vector segment keys) so the two can never drift."""
  pid = jnp.where(ids >= natural_rows, prows, ids // pack)
  return pid // pair if pair > 1 else pid


def packed_ids(ids: jax.Array, pack: int, rows: int):
  """Map row ids to (packed row, lane slot): ``id // pack`` with
  sentinels (``>= rows``) going to packed-sentinel ``rows // pack`` at
  slot 0.  Single source of the packed-view convention, shared with
  ``parallel/sparse.py:_lane_pack`` and the lookup backward
  (``pallas_lookup._dl_bwd``)."""
  sent = ids >= rows
  pids = jnp.where(sent, rows // pack, ids // pack)
  slots = jnp.where(sent, 0, jax.lax.rem(ids, pack))
  return pids, slots


def lane_expand(rows_w: jax.Array, slots: jax.Array, pack: int) -> jax.Array:
  """Expand natural ``[n, w]`` payload rows to packed ``[n, pack*w]``
  lanes, each row occupying the lane block of its slot (zeros
  elsewhere).  The other half of the ``packed_ids`` convention — one
  definition shared by ``parallel/sparse.py:_lane_pack`` and the
  lookup backward, so the lane layout can never drift between the
  forward, apply, and gradient paths."""
  w = rows_w.shape[1]
  lane = jnp.arange(pack * w, dtype=jnp.int32) // w
  mask = (lane[None, :] == slots[:, None]).astype(rows_w.dtype)
  return jnp.tile(rows_w, (1, pack)) * mask


def supported(table: jax.Array) -> bool:
  """f32 or bf16 2-D tables at width 128, or a narrow width dividing
  128 whose row count the packed view can absorb (``rows % (128 // w)
  == 0`` — always true for the runtime's fused groups, whose
  ``rows_cap`` granularity guarantees it; bf16 additionally needs pair
  divisibility, which the planner's doubled granularity provides).

  Narrow rows are served ONLY through the [rows/pack, 128] packed view:
  the v5e Mosaic backend rejects sub-128-lane VMEM slices outright
  ("Slice shape along dimension 2 must be aligned to tiling (128)"),
  caught by tests/test_tpu_lowering.py — a natural narrow-width kernel
  cannot compile on this hardware.  bf16 rows additionally fetch in
  PAIRS of packed rows (single-sublane bf16 slices are rejected too);
  the pair-merged segment key keeps the whole-pair write-back race-free
  (see the kernel docstring).
  """
  if not (table.ndim == 2
          and table.dtype in (jnp.float32, jnp.bfloat16)):
    return False
  rows, w = table.shape
  pair = 2 if table.dtype == jnp.bfloat16 else 1
  if w == 128:
    pack = 1
  elif 8 <= w < 128 and 128 % w == 0:
    pack = 128 // w
  else:
    return False
  return rows % (pair * pack) == 0


def acc_dtype_ok(table_dtype, accum_dtype) -> bool:
  """THE accumulator-dtype predicate: f32 always; bf16 only on bf16
  tables (a bf16 accumulator needs the pair-fetch granularity the bf16
  table establishes — Mosaic rejects single-sublane bf16 slices).
  Single source shared by this module's validation and the one function
  that picks the apply (``sparse.choose_apply``, which the eligibility
  report asks too)."""
  adt = jnp.dtype(accum_dtype)
  return adt == jnp.dtype(jnp.float32) or (
      adt == jnp.dtype(jnp.bfloat16)
      and jnp.dtype(table_dtype) == jnp.dtype(jnp.bfloat16))


@functools.partial(jax.jit, static_argnames=('op', 'eps', 'interpret',
                                             'logical_width', 'presorted',
                                             'stream_dtype'))
def segwalk_apply(table: jax.Array,
                  acc: Optional[jax.Array],
                  sorted_ids: jax.Array,
                  sorted_g: jax.Array,
                  lr,
                  *,
                  op: str,
                  eps: float = 1e-7,
                  interpret: bool = False,
                  logical_width: Optional[int] = None,
                  presorted: bool = True,
                  stream_dtype=jnp.float32,
                  g_index: Optional[jax.Array] = None):
  """Apply one optimizer step from a per-occurrence update stream.

  Args:
    table: ``[num_rows, w]`` f32 (donate for in-place) — or, when
      ``logical_width`` is set, the PHYSICAL packed view
      ``[num_rows/pack, 128]`` of a narrow group
      (``GroupSpec.storage_pack``): the kernel's packed path runs on the
      operand itself with no reshape, so the lane-padded relayout that
      barred huge narrow groups (``packed_dispatch_ok``) cannot occur.
    acc: Adagrad accumulator (same shape as ``table``), or None for
      'sgd'.  f32, or bf16 when the table is bf16 (rides the same
      pair-fetch path; f32 math, one rounding at the store — the
      ``accum_dtype='bfloat16'`` jumbo-scale configuration).
    sorted_ids: ``[n]`` int32 NATURAL row ids; sentinels (>= natural
      num_rows) mark padding.  Ascending when ``presorted`` (sentinels
      last); arbitrary order with ``presorted=False``, in which case
      the sort happens HERE so the payload gathers once, directly into
      the dense kernel operand (callers sorting separately pay an
      extra lane-padded materialisation of the narrow payload).
    sorted_g: ``[n, w]`` f32 gradient rows aligned with ``sorted_ids``
      (natural w).
    lr: scalar learning rate.
    op: 'sgd' | 'adagrad_dedup' | 'adagrad_sq'.
    logical_width: natural width when ``table`` is prepacked; None (or
      equal to ``table.shape[1]``) for natural tables.
    presorted: whether ``sorted_ids``/``sorted_g`` are already sorted.
    stream_dtype: dtype of the gradient-stream operand (f32 default).
      ``bfloat16`` HALVES the stream's HBM footprint and traffic (the
      binding temps at pod scale are the comb + sorted-gather pair,
      2x stream bytes — docs/perf_notes.md fits-ladder); gradients are
      rounded to bf16 once before the f32 segment summation, a
      quantisation the optimizer sums absorb (opt-in:
      ``SparseSGD/SparseAdagrad(stream_dtype='bfloat16')``).  Exact
      for gradients already representable in bf16.
    g_index: optional ``[n]`` int32 mapping stream position ->
      row of a COMPACT ``sorted_g`` (``[m, w]``, one row per
      (sample, bag) instead of per occurrence).  Multi-hot bags
      broadcast one cotangent row to every occurrence; with
      ``g_index`` that broadcast never materialises — the kernel
      operand gathers straight from the compact rows, cutting the
      dominant ``[n, 128]`` stream temp from two copies to one (plus a
      small ``[m, 128]``).  Requires ``presorted=False`` (the sort
      composes with the indirection as a cheap 1-D index gather).

  Returns:
    ``new_table`` ('sgd') or ``(new_table, new_acc)`` — in the same
    (packed or natural) layout the table arrived in.
  """
  if op not in ('sgd', 'adagrad_dedup', 'adagrad_sq'):
    raise ValueError(f'unknown op {op!r}')
  if not supported(table):
    raise ValueError(f'segwalk unsupported table {table.shape} '
                     f'{table.dtype}')
  if (op == 'sgd') != (acc is None):
    raise ValueError('acc must be provided iff op is an adagrad variant')
  num_rows, w = table.shape
  from distributed_embeddings_tpu.ops.pallas_lookup import (is_prepacked,
                                                            validate_prepacked)
  prepacked = is_prepacked(table.shape, logical_width)
  if prepacked:
    num_rows, w = validate_prepacked(table.shape, logical_width)
  # Lane packing for narrow rows: view the table as [rows/pack, 128]
  # (free row-major reshape — the operand itself when prepacked) so each
  # unique-row DMA moves a full 512 B burst serving up to `pack`
  # original rows.  The id stream divides by `pack` (merging adjacent
  # uids into one packed segment) and each row's original lane slot
  # rides along for the in-kernel expansion.  supported() guarantees
  # divisibility, so narrow widths ALWAYS pack (sub-128-lane VMEM
  # slices do not compile on v5e, see supported()).
  pack = 128 // w if w < 128 else 1
  kw = w * pack
  prows = num_rows // pack
  # bf16 fetches in PAIRS of (packed) rows — see the kernel docstring.
  # The accumulator may be f32 (the runtime default) or, on bf16 tables
  # ONLY, bf16 (SparseAdagrad(accum_dtype='bfloat16'), the jumbo-scale
  # lever): a bf16 accumulator needs the same pair-fetch granularity as
  # a bf16 table (Mosaic rejects single-sublane bf16 slices), so it can
  # only ride the pair path the bf16 table already established — an f32
  # table with a bf16 accumulator would mix fetch granularities and is
  # rejected (the XLA apply serves it).
  pair = 2 if table.dtype == jnp.bfloat16 else 1
  if acc is not None and not acc_dtype_ok(table.dtype, acc.dtype):
    raise ValueError(
        f'segwalk accumulator must be f32 (or bf16 on a bf16 table), '
        f'got acc {acc.dtype} with table {table.dtype}')
  if g_index is not None:
    if presorted:
      raise ValueError('g_index requires presorted=False (the sort '
                       'composes with the indirection)')
    if g_index.shape[0] != sorted_ids.shape[0]:
      # jnp.take would silently CLIP a mismatched index to the last
      # compact row — wrong gradients on real ids, not an error
      raise ValueError(f'g_index length {g_index.shape[0]} != stream '
                       f'length {sorted_ids.shape[0]}')
  tile = _tile_rows(pair * kw)
  n = sorted_ids.shape[0]
  # pad to whole _SMEM_BLOCKs (tile divides _SMEM_BLOCK), so the shared
  # 1-D SMEM id/flag blocks are always full
  n_pad = -(-n // _SMEM_BLOCK) * _SMEM_BLOCK
  if n_pad != n:
    pad = n_pad - n
    sorted_ids = jnp.pad(sorted_ids, (0, pad), constant_values=num_rows)
    if g_index is None:
      sorted_g = jnp.pad(sorted_g, ((0, pad), (0, 0)))
    else:
      # padded positions carry the sentinel id: their payload rows are
      # summed only into the sentinel segment, which the walks skip —
      # any in-range index is safe
      g_index = jnp.pad(g_index, (0, pad))
  sorted_ids = sorted_ids.astype(jnp.int32)
  sorted_g = sorted_g.astype(jnp.float32)
  if g_index is not None:
    g_index = g_index.astype(jnp.int32)
  # sort HERE (presorted=False) so the one big materialisation is the
  # dense gather of the combined block below (sentinels = num_rows
  # sort to the end); ids themselves gather 1-D, untiled, cheap
  order = None if presorted else jnp.argsort(sorted_ids)
  if pack > 1:
    table_k = table if prepacked else table.reshape(prows, kw)
    acc_k = (acc if prepacked else
             acc.reshape(prows, kw)) if acc is not None else None
  else:
    table_k, acc_k = table, acc
  if pair == 2:
    # fetch-unit granularity: the segment key merges to the PAIR (both
    # rows of a fetched pair apply at one grid position — the
    # race-freedom argument).  supported() guarantees prows is even;
    # the packed sentinel prows maps to fetch id nfetch, out of range,
    # skipped by the walks.
    nfetch = prows // 2
    table_k = table_k.reshape(nfetch, 2, kw)
    acc_k = acc_k.reshape(nfetch, 2, kw) if acc_k is not None else None
  else:
    nfetch = prows
  # Operand layout (see the kernel docstring): ids travel ONCE as a
  # 1-D untiled SMEM stream; the vector side reads them either from a
  # bitcast sideband lane of the [n, 128] gradient block (narrow
  # widths: the padded narrow block already paid for those lanes) or,
  # for width-128 tables, from one [n, 1] VMEM column.  Fetch ids,
  # lane slots, halves and starts are derived in-kernel.
  sdt = jnp.dtype(stream_dtype)
  if sdt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
    raise ValueError(f'stream_dtype must be float32 or bfloat16, '
                     f'got {sdt}')
  sid1d = sorted_ids if order is None else jnp.take(sorted_ids, order)
  # with g_index the payload gathers ONCE, straight from the compact
  # per-bag rows into the (sorted) kernel operand: the 1-D index
  # composition take(g_index, order) is cheap, and the broadcast-to-
  # occurrences never materialises
  gidx_sorted = (None if g_index is None else
                 (g_index if order is None else jnp.take(g_index, order)))
  sideband = w < 128
  if sideband:
    # lane-iota select, not concat of a [n, 1] column: a unit-width f32
    # column materialises T(8,128)-padded at 128x (a 2 GiB temp at
    # synthetic scale), while this form is elementwise over the dense
    # [n, 128] block and fuses into its one materialisation
    if gidx_sorted is not None:
      # gather the small padded compact rows into SORTED stream order,
      # then lane-select the (already sorted) ids in: one [n, 128]
      # materialisation total
      gsmall = jnp.pad(sorted_g.astype(sdt), ((0, 0), (0, 128 - w)))
      gpad = jnp.take(gsmall, gidx_sorted, axis=0)
      ids_for_lanes = sid1d
    else:
      gpad = jnp.pad(sorted_g.astype(sdt), ((0, 0), (0, 128 - w)))
      ids_for_lanes = sorted_ids
    lane = jax.lax.broadcasted_iota(jnp.int32, (n_pad, 128), 1)
    if sdt == jnp.bfloat16:
      # 32-bit ids split over two raw-bits bf16 lanes: [n, 2] with
      # element 0 the low half (little-endian bitcast order — the
      # kernel reassembles lo | hi<<16, round-tripped bit-exact in
      # tests)
      ids_bf = jax.lax.bitcast_convert_type(ids_for_lanes, jnp.bfloat16)
      comb = jnp.where(
          lane == w, ids_bf[:, 0:1],
          jnp.where(lane == w + 1, ids_bf[:, 1:2], gpad))
    else:
      comb = jnp.where(
          lane == w,
          jax.lax.bitcast_convert_type(ids_for_lanes,
                                       jnp.float32)[:, None],
          gpad)
    g_operand = (comb if order is None or gidx_sorted is not None
                 else jnp.take(comb, order, axis=0))
    idv_operand = jnp.zeros((1, 1), jnp.int32)  # statically never read
  else:
    # convert BEFORE the gather so its output buffer is already
    # sdt-sized (half the bytes for a bf16 stream)
    gs = sorted_g.astype(sdt)
    if gidx_sorted is not None:
      g_operand = jnp.take(gs, gidx_sorted, axis=0)
    else:
      g_operand = gs if order is None else jnp.take(gs, order, axis=0)
    idv_operand = sid1d[:, None]
  # fetch-unit ids for the global segment-last flags (the one lookahead
  # the kernel cannot do): adjacent uids sharing a packed row (or bf16
  # pair) are one segment whose lanes (or halves) carry their per-uid
  # totals disjointly.  1-D untiled arrays: cheap.
  kids = fetch_ids(sid1d, num_rows, prows, pack, pair)
  is_last = jnp.concatenate([
      (kids[1:] != kids[:-1]),
      jnp.ones((1,), bool)
  ]).astype(jnp.int32)
  num_tiles = n_pad // tile
  lr_arr = jnp.stack([jnp.asarray(lr, jnp.float32),
                      jnp.asarray(eps, jnp.float32)]).reshape(1, 2)
  # 'sgd' has no accumulator: a small dummy keeps the operand/alias
  # structure uniform (the kernel never issues DMAs against it)
  if acc_k is not None:
    acc_operand = acc_k
  else:
    acc_operand = jnp.zeros((8, 2, kw) if pair == 2 else (8, kw),
                            jnp.float32)

  stage = (2, tile, 2, kw) if pair == 2 else (2, tile, kw)
  kernel = functools.partial(_segwalk_kernel,
                             natural_rows=num_rows,
                             nfetch=nfetch,
                             prows=prows,
                             num_tiles=num_tiles,
                             tile=tile,
                             width=kw,
                             gw=w,
                             pack=pack,
                             pair=pair,
                             sideband=sideband,
                             op=op)
  with obs_trace.phase('apply/update'):
    outs = pl.pallas_call(
        kernel,
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((_SMEM_BLOCK,),
                         lambda t, _tl=tile: ((t * _tl) // _SMEM_BLOCK,),
                         memory_space=pltpu.SMEM),   # ids (scalar walks)
            pl.BlockSpec((_SMEM_BLOCK,),
                         lambda t, _tl=tile: ((t * _tl) // _SMEM_BLOCK,),
                         memory_space=pltpu.SMEM),   # is_last (walks)
            pl.BlockSpec((tile, 128 if sideband else kw), lambda t: (t, 0),
                         memory_space=pltpu.VMEM),   # grads (+ id sideband)
            (pl.BlockSpec(memory_space=pltpu.SMEM) if sideband else
             pl.BlockSpec((tile, 1), lambda t: (t, 0),
                          memory_space=pltpu.VMEM)),  # ids (vector, w=128)
            pl.BlockSpec(memory_space=pltpu.SMEM),   # [lr, eps]
            pl.BlockSpec(memory_space=pl.ANY),       # table
            pl.BlockSpec(memory_space=pl.ANY),       # acc (or dummy)
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(table_k.shape, table_k.dtype),
            jax.ShapeDtypeStruct(acc_operand.shape, acc_operand.dtype),
        ],
        # REQUIRED for correctness, not just memory: rows the kernel never
        # touches must retain their input values, which only the aliased
        # output buffer provides
        input_output_aliases={5: 0, 6: 1},
        scratch_shapes=[
            pltpu.VMEM(stage, table_k.dtype),        # tbuf (parity pair)
            pltpu.VMEM(stage, acc_operand.dtype),    # abuf (parity pair)
            pltpu.VMEM((2, pair * kw), jnp.float32),  # carry (sum, sum_sq)
            pltpu.SMEM((1, 1), jnp.int32),           # carry id
            pltpu.SMEM((2, 1), jnp.int32),           # in-flight write counts
            pltpu.SemaphoreType.DMA,                 # read semaphore
            pltpu.SemaphoreType.DMA((2,)),           # write semaphores
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        interpret=interpret,
    )(sid1d, is_last, g_operand, idv_operand, lr_arr, table_k,
      acc_operand)
  new_table, new_acc = outs[0], outs[1]
  if pair == 2:
    new_table = new_table.reshape(prows, kw)
    if acc_k is not None:
      new_acc = new_acc.reshape(prows, kw)
  if prepacked:
    # hand back the physical packed layout the table arrived in
    return new_table if op == 'sgd' else (new_table, new_acc)
  new_table = new_table.reshape(num_rows, w)
  if op == 'sgd':
    return new_table
  return new_table, new_acc.reshape(num_rows, w)
