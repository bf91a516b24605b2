"""Sparse (O(nnz)) embedding training: row-wise optimizers + hybrid step.

The reference's backward emits ``IndexedSlices(unique_ids, unique_grad)``
(`/root/reference/distributed_embeddings/python/ops/embedding_lookup_ops.py:105-122`,
built by the sort->unique->segment-reduce CUDA pipeline,
`cc/kernels/embedding_lookup_kernels.cu:463-635`, SURVEY.md C3) so the
optimizer touches only looked-up rows.  Plain JAX autodiff instead produces a
*dense* table-shaped gradient; for multi-GiB tables the resulting dense
optimizer update is O(vocab) HBM traffic per step and can never match the
reference.  This module restores the sparse asymptotics TPU-natively, with
every shape static:

- the forward keeps the routed fused-space ids as residuals
  (``DistributedEmbedding.forward_with_residuals``);
- the head's vjp supplies output cotangents, transposed back through the
  all-to-all by ``DistributedEmbedding.backward_to_mp``;
- row-wise optimizers apply scatter updates at the looked-up rows only:
  O(batch * hotness * width) instead of O(vocab * width).

Every update stream is sort-compacted to its unique rows before touching
the tables (``compact_segments`` — the TPU analog of the reference's
``cub::DeviceRadixSort`` + ``UniqueByKey`` dedup, `.cu:505-521`), because
XLA scatter cost is linear in the static row count (docs/perf_notes.md).
Duplicate-id SEMANTICS are preserved exactly: ``SparseSGD`` applies the
summed gradient (identical to dense); ``SparseAdagrad`` defaults to the
reference's dedup-then-square (`keras _deduplicate_indexed_slices` — sum
duplicate rows, then accumulate the square of the sum, identical to the
dense-gradient formulation; VERDICT.md round 1 weak item 5), with
``dedup=False`` opting into per-occurrence squared-gradient accumulation
— both read the post-update accumulator.  ``SparseAdam`` is nonlinear in
the row grad and always uses the deduplicated sum.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging

from typing import (Callable, Dict, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_embeddings_tpu.obs import metrics as obs_metrics
from distributed_embeddings_tpu.obs import trace as obs_trace
from distributed_embeddings_tpu.ops.ragged import RaggedBatch
from distributed_embeddings_tpu.parallel.dist_embedding import (
    DistributedEmbedding, _valid_count)
from distributed_embeddings_tpu.parallel.grad import TrainState
from distributed_embeddings_tpu.parallel.overlap import (chunk_bounds,
                                                         effective_chunks)
from distributed_embeddings_tpu.parallel.routing import (cummax0, cumsum0,
                                                          sort_with_order)

_LOG = logging.getLogger(__name__)


@obs_trace.phase('apply/dedup')
def compact_segments(ids: jax.Array,
                     grads: jax.Array,
                     cap: int,
                     sentinel: int,
                     with_sq: bool = False,
                     g_index: Optional[jax.Array] = None,
                     max_seg: Optional[int] = None):
  """Sort-dedup and COMPACT segment sums into static capacity ``cap``.

  The key fact motivating this (measured on v5e, docs/perf_notes.md):
  a scatter's cost follows its STATIC shapes, however many rows are
  sentinel-dropped — XLA's row emitter pays per static update row, its
  streaming emitter per update row plus one pass over the operand
  (``write_algorithm`` has both laws and picks) — while a two-operand
  sort is 1.5 ns a key and a gather 7 to 25 ns a row (PERF.md, PR 28).
  ``dedup_rows`` keeps the nnz-length shape, so its
  scatters still pay full price; this variant compacts the unique rows
  to the front of a ``cap``-sized buffer so the optimizer's scatters
  shrink by the duplicate factor (~6x on the power-law synthetic inputs)
  or down to the fused table's row count, whichever is smaller.  It
  sorts, gathers the payload in sorted order and hands the sorted stream
  to ``_compact_sorted``.

  Segment sums use the sorted-cumsum-difference trick (vectorised,
  contiguous); over millions of rows f32 cumsum cancellation adds a
  relative error ~1e-4 of the running-sum magnitude — well under gradient
  noise, and the distributed equivalence tests bound it.

  Args:
    ids: ``[n]`` int32 row ids; ``sentinel`` (and anything >= it) marks
      padding.
    grads: ``[n, w]`` per-occurrence gradient rows.
    cap: static output capacity.  Correct iff the number of unique ids
      (including one slot for the sentinel segment) is <= cap — callers
      guarantee this or guard with ``num_unique`` (see return).
    sentinel: value marking dropped rows in the compacted output.
    with_sq: also return per-segment sums of squared gradients (for
      per-occurrence Adagrad accumulator semantics).
    g_index: optional ``[n]`` int32 position->row map into COMPACT
      ``grads`` (``[m, w]``, one row per (sample, bag)): multi-hot
      broadcasts never materialise — the sorted payload gathers
      straight from the compact rows (same contract as
      ``pallas_segwalk.segwalk_apply``).
    max_seg: optional static bound on non-sentinel segment length.
      When given, segment totals use an EXACT unrolled left fold over
      at most ``max_seg`` positions instead of the cumsum-difference
      trick: the cumsum trick folds the running prefix into every
      total (``(P + g1 + g2) - P != g1 + g2`` in f32), so a row's sum
      depends on unrelated neighbours in the sorted stream — which
      breaks flat-vs-hierarchical bit-parity for the cross-slice
      update merge, where each row appears at most once per slice
      (design §20).  The sentinel segment may exceed the bound; its
      (garbage) total is dropped with the segment as always.

  Returns:
    ``(uids[c], sum_g[c, w], sum_sq[c, w] | None, num_unique)`` with
    ``c = min(cap, n)``; slots past the unique count hold ``sentinel`` /
    zeros, ``num_unique`` is a traced scalar (segments counted including
    the sentinel segment).
  """
  sid, order = sort_with_order(ids)
  return _compact_sorted(sid, _sorted_payload(grads, order, g_index), cap,
                         sentinel, with_sq=with_sq, max_seg=max_seg)


def _sorted_payload(grads: jax.Array, order: jax.Array,
                    g_index: Optional[jax.Array]) -> jax.Array:
  """The f32 payload rows in their stream's sorted order: the one gather
  no sort carries (``compact_segments`` has the ``g_index`` contract)."""
  if g_index is not None and g_index.shape[0] != order.shape[0]:
    raise ValueError(f'g_index length {g_index.shape[0]} != stream '
                     f'length {order.shape[0]}')  # jnp.take would silently clip
  idx = order if g_index is None else jnp.take(g_index, order)
  return take_rows(grads, idx, monotone=False).astype(jnp.float32)


def lane_packed_take(x: jax.Array, idx: jax.Array) -> jax.Array:
  """``x[idx]`` for a narrow ``[N, w]`` array (``128 % w == 0``), read
  as 128-lane rows: ``x`` viewed row-major as ``[ceil(N / p), 128]``
  with ``p = 128 // w`` (zero rows padded at the end), the packed rows
  ``idx // p`` gathered, lane block ``idx % p`` selected out of each.
  The inverse of ``pallas_segwalk.lane_expand``.  Float32 only; data
  movement only, so the result is ``x[idx]`` bit for bit: the lane block
  is picked as raw bits, never by a masked float sum, which would turn
  ``-0.0`` into ``0.0`` (and, on the chip, flush subnormals).  ``idx``
  must lie in ``[0, N)``, as a permutation's entries do."""
  n_rows, w = x.shape
  p = 128 // w
  if n_rows % p:
    x = jnp.pad(x, ((0, p - n_rows % p), (0, 0)))
  # the blocks are picked rows-minor, ``[p, w, n]``, by ONE reduction
  # over them (the other blocks' bits masked to zero, so an OR is
  # exact): narrow ``[n, w]`` slices of the gathered rows would be laid
  # out lane-padded (8x their bytes at w = 16) and held apart; ``[w, n]``
  # is dense and is the layout the result's readers keep it in
  blocks = x.reshape(-1, 128)[idx // p].T.reshape(p, w, -1)
  bits = jax.lax.bitcast_convert_type(blocks, jnp.uint32)
  mine = jnp.arange(p)[:, None, None] == (idx % p)[None, None, :]
  picked = jnp.bitwise_or.reduce(jnp.where(mine, bits, 0), axis=0)
  return jax.lax.bitcast_convert_type(picked, jnp.float32).T


def take_rows(x: jax.Array, idx: jax.Array, monotone: bool) -> jax.Array:
  """``x[idx]`` for the dedup's payload reads, through
  ``lane_packed_take`` where ``packed_gather_gains`` says the 128-lane
  read is the cheaper at the static shapes and the access kind
  (``monotone``: ascending ``idx``, as the running sum is read); each
  traced gather that takes it counts one ``apply.lane_packed_gathers``."""
  if not packed_gather_gains(idx.shape[0], *x.shape, x.dtype, monotone):
    return x[idx]
  obs_metrics.inc('apply.lane_packed_gathers')
  return lane_packed_take(x, idx)


# ns a row on a v5e of reading float32 rows of a narrow width out of an
# operand laid out rows-minor, as the step lays out the narrow arrays it
# computes, ``{0,1:T(8,128)}``: XLA's gather of the narrow rows
# (``plain``) and ``lane_packed_take`` (``packed``, the selection
# included), each (random, ascending), and the packed read's copy of the
# operand into ``[N * w / 128, 128]``, per row of the operand
# (``relayout``).  The means of ``examples/benchmarks/scatter_probe.py``'s
# ``gather-plain``, ``-sorted``, ``-packed`` and ``-packed-sorted`` at
# 1,116,536 and 2,883,584 rows read from 2,883,584 (PERF.md section 6),
# but one: at 8 lanes the step reads its random rows at 4.93 ns
# (``tiny-train-zipf``'s group 0 in its traced step, 13.25 ms for
# 2,686,976 rows; the compiler places that operand in memory space 1,
# which the probe's 10.03 did not see).  At 64 lanes and up the plain
# gather reads at the wide cost.
_GATHER_NS = {  # width: ({'plain'|'packed': (random, ascending)}, relayout)
    8: ({'plain': (4.93, 20.43), 'packed': (6.00, 6.01)}, 1.82),
    16: ({'plain': (17.02, 30.60), 'packed': (11.31, 11.44)}, 1.92),
    32: ({'plain': (30.82, 51.07), 'packed': (11.63, 11.67)}, 2.12),
    64: ({'plain': (7.82, 7.70), 'packed': (12.10, 12.06)}, 2.56),
}


def packed_gather_gains(n: int, rows: int, width: int, dtype,
                        monotone: bool) -> bool:
  """Whether ``lane_packed_take`` reads ``n`` float32 rows of ``width``
  lanes out of ``rows`` faster than XLA's gather of the narrow rows:
  ``packed n + relayout rows < plain n`` by ``_GATHER_NS``."""
  if dtype != jnp.float32 or width not in _GATHER_NS:
    return False
  ns, relayout = _GATHER_NS[width]
  plain, packed = ns['plain'][monotone], ns['packed'][monotone]
  return packed * n + relayout * rows < plain * n


@obs_trace.phase('apply/dedup')
def _compact_sorted(sid: jax.Array, sg: jax.Array, cap: int, sentinel: int,
                    with_sq: bool = False, max_seg: Optional[int] = None):
  """``compact_segments`` from the point where the stream is sorted
  (``sid`` ``[n]`` ascending, ``sg`` ``[n, w]`` its f32 payload rows):
  ranks, compaction, totals; a stream that arrives sorted (``_lane_pack``,
  ``_dedup_and_apply``) enters here.  A gather costs five to fifteen
  keys of a sort, so the sorts CARRY what they order and only payloads
  are fetched through a permutation: the sort that brings each segment's
  last position to slot ``rank`` carries the slot's id, and ``lo``, the
  running sum just before a segment's first position, is the previous
  slot's ``hi`` (segments are contiguous, slots in rank order): the
  same gather read one slot earlier."""
  n = sid.shape[0]
  is_first, is_last, first_pos, _ = _sorted_segments(sid)
  rank = cumsum0(is_first.astype(jnp.int32)) - 1
  num_unique = rank[-1] + 1
  # bring each segment's last position to slot `rank`
  key = jnp.where(is_last, rank, n)
  skey, order2, uids = (x[:cap] for x in sort_with_order(key, sid))
  valid = skey < n
  uids = jnp.where(valid, uids, sentinel)

  if max_seg is not None:
    # exact bounded-multiplicity totals (compact_segments' Args): complete
    # at each segment's last position, which is exactly what order2 selects
    sum_g = jnp.where(valid[:, None],
                      _seg_fold_bounded(sg, first_pos, max_seg)[order2],
                      0.0)
    sum_sq = (jnp.where(
        valid[:, None],
        _seg_fold_bounded(sg * sg, first_pos, max_seg)[order2], 0.0)
              if with_sq else None)
    return uids, sum_g, sum_sq, num_unique

  # Segment totals ONLY at the compacted positions: inclusive cumsum at
  # the segment's last position (hi) minus that at the previous segment's
  # last (lo): one [n, w] running-sum buffer and ONE gather a payload,
  # read through two windows.  The gather starts two slots early so that
  # both windows start past row 0 and neither is a bitcast of its
  # buffer: fused beside `hi` itself, a bitcast `hi[:-1]` let the v5e
  # compiler write `hi - lo` over `hi` in place while it still read it
  # one slot behind (tiny-train-uniform, PERF.md PR 28: wrong rows).
  order2 = jnp.pad(order2, (2, 0))
  slot0 = (jnp.arange(valid.shape[0]) == 0)[:, None]

  def seg_tot(csum):
    ext = take_rows(csum, order2, monotone=True)
    hi, lo = ext[2:], jnp.where(slot0, 0.0, ext[1:-1])
    return jnp.where(valid[:, None], hi - lo, 0.0)

  sum_g = seg_tot(cumsum0(sg))
  sum_sq = seg_tot(cumsum0(sg * sg)) if with_sq else None
  return uids, sum_g, sum_sq, num_unique


def _seg_fold_bounded(x: jax.Array, first_pos: jax.Array,
                      max_seg: int) -> jax.Array:
  """Per-position left-fold segment totals over SORTED payload ``x``
  for streams whose (non-sentinel) segments are at most ``max_seg``
  long: ``tot[p] = ((x[fp] + x[fp+1]) + ...) + x[p]`` — the same f32
  association wherever the segment lands, with NO dependence on the
  rest of the stream.  ``max_seg - 1`` vectorised shift-add passes
  (the cross-slice merge has ``max_seg = num_slices``, a handful).
  Totals are complete at each segment's LAST position; earlier
  positions hold the partial prefix folds."""
  off = (jnp.arange(x.shape[0], dtype=jnp.int32) - first_pos)
  tot = x
  for k in range(1, max_seg):
    prev = jnp.concatenate([jnp.zeros_like(tot[:1]), tot[:-1]], axis=0)
    tot = jnp.where((off == k)[:, None], prev + x, tot)
  return tot


def _sorted_segments(sid: jax.Array):
  """Segment machinery over SORTED ids:
  ``(is_first, is_last, first_pos, seg_total)`` where ``first_pos[p]`` is
  the first position of the segment containing ``p`` and ``seg_total(x)``
  puts each segment's column sums at every position of the segment via
  the cumsum-difference trick (exact value needed only at the last
  position)."""
  n = sid.shape[0]
  iota = jnp.arange(n, dtype=jnp.int32)
  change = sid[1:] != sid[:-1]
  is_first = jnp.concatenate([jnp.ones((1,), bool), change])
  is_last = jnp.concatenate([change, jnp.ones((1,), bool)])
  first_pos = cummax0(jnp.where(is_first, iota, 0))

  def seg_total(x):
    csum = cumsum0(x)
    excl = csum - x
    return csum - excl[first_pos]

  return is_first, is_last, first_pos, seg_total


@obs_trace.phase('apply/dedup')
def dedup_rows(ids: jax.Array, grads: jax.Array,
               sentinel: int) -> Tuple[jax.Array, jax.Array]:
  """Sum rows of ``grads`` sharing an id; static shapes throughout.

  Shape-static port of the reference dedup pipeline (SURVEY.md C3): sort by
  id, segment-sum via cumulative sums, emit each segment's total at its last
  occurrence and ``sentinel`` elsewhere (scatter with ``mode='drop'``
  discards those).  Returns ``(unique_ids, summed_grads)`` of the same
  length as the inputs.
  """
  sid, order = sort_with_order(ids)
  sg = grads[order].astype(jnp.float32)
  _, is_last, _, seg_total = _sorted_segments(sid)
  uids = jnp.where(is_last, sid, sentinel)
  return uids, seg_total(sg)


def _rounded_square(x: jax.Array) -> jax.Array:
  """``x * x`` forced to a ROUNDED product.

  XLA's backend emitters may contract ``acc + x*x`` into an FMA — or
  not — depending on how the surrounding ops fuse, so the SAME update
  stream can yield accumulators differing by 1 ulp between the flat
  and hierarchical layouts of one table (observed on CPU; breaks
  design §20's applied-update bit-parity contract).  The select below
  severs the mul->add contraction pattern at codegen level — neither
  ``optimization_barrier`` nor ``reduce_precision`` does, since
  contraction happens in the emitter, which sees through both.  The
  ``x == x`` predicate is false only for NaN, where the taken branch
  is NaN too, so the function is value-identical to ``x * x``.
  """
  sq = x * x
  return jnp.where(x == x, sq, jnp.asarray(jnp.nan, x.dtype))


def _distinct_oob(uids: jax.Array, limit: int) -> jax.Array:
  """Make the ``unique_indices=True`` scatter promise literally true.

  Compacted id buffers pad unused slots with ONE repeated sentinel value;
  XLA documents undefined behavior for non-unique indices under the
  uniqueness hint, even though ``mode='drop'`` discards the out-of-bounds
  slots in practice.  Replacing the tail with DISTINCT out-of-bounds ids
  (``limit + position``) keeps the buffer strictly ascending and dropped,
  at the cost of one iota+where.
  """
  n = uids.shape[0]
  return jnp.where(uids < limit,
                   uids, limit + jnp.arange(n, dtype=uids.dtype))


# XLA:TPU has two scatter emitters and ``indices_are_sorted`` alone picks
# one (compile-only, ISSUE 30: 16,355,328 B of scoped VMEM with the hint,
# 139,264 B without, whatever ``unique_indices`` says).  With the hint the
# scatter STREAMS the whole operand through VMEM, ``a * R + b * U`` for R
# operand rows and U update rows; without it it walks the update ROWS,
# ``c * U`` whatever R is where they lie 18 or more rows apart (less a
# row where neighbours share a tile, 14 to 26 ns, but the stream is the
# faster there anyway).  ns per row on a v5e at 128 float32 lanes, the
# operand every benchmark cell's waves write, fitted by
# ``examples/benchmarks/scatter_probe.py`` over R in {2.5 M, 8.775 M,
# 20.0 M} x U in {92 K, 1.12 M, 2.88 M} (the grid: PERF.md section 6,
# PR 30; add and set agree on ``a`` to 0.3%).  The times cross where a
# wave writes 2.3% of the operand's rows:
_STREAM_NS_PER_OPERAND_ROW = 1.59
_STREAM_NS_PER_UPDATE_ROW = {'add': 5.74, 'set': 3.16}
_ROWS_NS_PER_UPDATE_ROW = {'add': 73.7, 'set': 72.9}


def write_algorithm(update_rows: int, operand_rows: int,
                    op: str = 'add') -> str:
  """Which scatter emitter ``_write_rows`` takes for ``update_rows``
  unique rows into an operand of ``operand_rows``: ``'stream'`` where
  the wave is dense enough that one pass over the whole operand is the
  cheaper, ``'rows'`` where it touches so small a share that the pass
  would cost more than the rows (dlrm-train-4chip: 92,272 of 20,025,088,
  where the stream was 51% of the step)."""
  stream = (_STREAM_NS_PER_OPERAND_ROW * operand_rows
            + _STREAM_NS_PER_UPDATE_ROW[op] * update_rows)
  return ('stream' if _ROWS_NS_PER_UPDATE_ROW[op] * update_rows > stream
          else 'rows')


def _write_rows(operand: jax.Array, uids: jax.Array, rows: jax.Array,
                op: str) -> jax.Array:
  """THE scatter of compacted unique rows: ``op`` (``'add'`` or
  ``'set'``) ``rows`` into ``operand`` at ``uids``.  Compacted ids are
  ascending and ``_distinct_oob`` makes them strictly unique, so
  ``unique_indices`` holds at every call and either emitter writes each
  row once: the same values, whichever ``write_algorithm`` picks from
  the wave's static shapes."""
  with obs_trace.phase('apply/write_rows'):
    at = operand.at[_distinct_oob(uids, operand.shape[0])]
    return getattr(at, op)(
        rows.astype(operand.dtype), mode='drop', unique_indices=True,
        indices_are_sorted=write_algorithm(
            uids.shape[0], operand.shape[0], op) == 'stream')


def _apply_rows(optimizer, table, state, uids, sum_g, sum_sq, lr):
  """One step at COMPACTED unique rows (``compact_segments``): the
  optimizer's ``row_updates``, added to the table's rows."""
  delta, state = optimizer.row_updates(state, uids, sum_g, sum_sq, lr,
                                       table.shape[0])
  return _write_rows(table, uids, delta, 'add'), state


@dataclasses.dataclass(frozen=True)
class SparseSGD:
  """Row-wise SGD; exact (SGD is linear, so summed duplicate rows match
  the dense gradient).  The DLRM reference trains with plain SGD
  (`examples/dlrm/main.py:192-194`)."""
  learning_rate: float = 0.01
  capacity_fraction: float = 0.5
  capacity_rows: Optional[Tuple[Optional[int], ...]] = None
  # opt-in fused segment-walk apply (ops/pallas_segwalk.py): one
  # streaming pass does segment-sum + update together, skipping the
  # whole compaction pipeline; takes effect on TPU for f32 tables of
  # width 128 or widths 8..64 dividing 128 (at ANY group size under the
  # default packed storage, which the kernel consumes reshape-free).
  # Only with packed_storage=False do narrow groups additionally need
  # rows_cap divisible by the pack factor AND the packed_dispatch_ok
  # HBM bound (PACKED_PARAM_BYTES_LIMIT) — there a very large narrow
  # group (>~4M rows) falls back to XLA to avoid the lane-padded
  # relayout, as does any other unsupported case.
  use_segwalk_apply: bool = False
  # stream payload dtype for the segwalk kernel: 'bfloat16' halves the
  # update stream's HBM footprint and traffic (the comb + sorted-gather
  # pair are the binding temps at pod scale — docs/perf_notes.md);
  # gradients round to bf16 once before the f32 segment summation
  stream_dtype: str = 'float32'

  needs_sq = False
  needs_touch = False
  supports_lane_packing = True

  def init(self, dist: DistributedEmbedding, params) -> Dict:
    out = {f'group_{gi}': {} for gi in range(len(dist.plan.groups))}
    for gi in getattr(dist.plan, 'hot_groups', []):
      out[f'hot_group_{gi}'] = {}
    return out

  def row_updates(self, state, uids, sum_g, sum_sq, lr, limit):
    """Per-row f32 deltas at the compacted unique rows, plus the new
    optimizer state — the arithmetic core ``apply_unique`` scatters and
    the quantized adapter (``_QuantizedTableOptimizer``) requants.  ONE
    definition per optimizer so the two paths can never drift."""
    del sum_sq, limit
    with obs_trace.phase('apply/update'):
      return -lr * sum_g, state

  def tier_leaf_specs(self):
    """Optimizer-state leaves the host cold tier must carry per tail
    row (design §12): SGD is stateless."""
    return {}

  apply_unique = _apply_rows

  def apply_hot(self, hot, state, sum_g, sum_sq, lr, count=None):
    """DENSE step on a replicated hot-cache buffer (design §10):
    ``sum_g`` is the mesh-psummed per-row gradient sum — untouched
    rows carry exact zeros, so one elementwise add updates every hot
    row with the same arithmetic the scatter would."""
    del sum_sq, count
    return hot + (-lr * sum_g).astype(hot.dtype), state


@dataclasses.dataclass(frozen=True)
class SparseAdagrad:
  """Row-wise Adagrad (keras semantics: ``acc += g**2; p -= lr * g /
  sqrt(acc + eps)`` with the post-update accumulator).  The synthetic
  benchmark baseline trains with Adagrad
  (`examples/benchmarks/synthetic_models/main.py:105`).

  The default ``dedup=True`` reproduces the reference's
  dedup-then-accumulate exactly (identical to dense-gradient Adagrad, and
  cheaper: no squared-gradient segment sums); ``dedup=False`` opts into
  per-occurrence squares (see module docstring).

  ``accum_dtype='bfloat16'`` halves accumulator HBM — the lever that fits
  synthetic-jumbo's 3.1 TiB of state on a v5e pod (VERDICT r4 item 5).
  Arithmetic stays f32: rows gather up-cast, accumulate and rsqrt in f32,
  and only the store rounds to bf16 (round-to-nearest-even).  Accuracy
  cost is bounded by bf16's 8 mantissa bits on the MONOTONE accumulator:
  relative error <=2^-9 per store, so the update magnitude errs by
  <=~0.1%; once a row's accumulator exceeds ~2^8 x its increment, further
  additions can round away — embedding rows touched at power-law
  frequency rarely reach that regime (measured convergence delta in
  tests/test_sparse_train.py::test_bf16_accumulator_convergence_delta).
  """
  learning_rate: float = 0.001
  initial_accumulator_value: float = 0.1
  epsilon: float = 1e-7
  dedup: bool = True
  capacity_fraction: float = 0.5
  capacity_rows: Optional[Tuple[Optional[int], ...]] = None
  # opt-in fused segment-walk apply (ops/pallas_segwalk.py): consumes
  # the SORTED raw stream directly — segment-sum + update in one pass,
  # no compaction pipeline at all; engages on TPU for f32 tables at the
  # 128-lane width, serving narrow groups of ANY size under the default
  # packed storage (only packed_storage=False adds the
  # pack-divisibility and packed_dispatch_ok HBM gates, where huge
  # narrow groups fall back to XLA).
  use_segwalk_apply: bool = False
  # stream payload dtype for the segwalk kernel (see SparseSGD)
  stream_dtype: str = 'float32'
  # accumulator STORAGE dtype ('float32' | 'bfloat16'); see class docstring
  accum_dtype: str = 'float32'

  needs_touch = False
  supports_lane_packing = True

  @property
  def needs_sq(self):
    # per-occurrence semantics accumulate sum(g_i^2); dedup semantics
    # accumulate (sum g_i)^2, derivable from sum_g alone
    return not self.dedup

  def init(self, dist: DistributedEmbedding, params) -> Dict:
    adt = jnp.dtype(self.accum_dtype)
    if getattr(dist, 'cold_tier', None) is not None:
      # the accumulator of host-tier tail rows lives in the tier
      # (design §12); created here so a fresh train state and a
      # checkpoint restore see the same leaf set
      dist.cold_tier.ensure_opt('acc', self.initial_accumulator_value,
                                adt)
    out = {
        f'group_{gi}': {
            'acc':
                jnp.full_like(params[f'group_{gi}'],
                              self.initial_accumulator_value,
                              dtype=adt)
        } for gi in range(len(dist.plan.groups))
    }
    for gi in getattr(dist.plan, 'hot_groups', []):
      # replicated split state for the hot-cache rows (design §10);
      # the row's accumulator lives HERE while the row is hot — the
      # checkpoint boundary canonicalises it back into the per-table
      # layout, so hot membership never reaches saved state
      out[f'hot_group_{gi}'] = {
          'acc': jnp.full_like(params[f'hot_group_{gi}'],
                               self.initial_accumulator_value,
                               dtype=adt)
      }
    return out

  def tier_leaf_specs(self):
    """The host cold tier carries the accumulator per tail row (design
    §12; the ``accum_dtype`` ladder applies there too)."""
    return {'acc': (self.accum_dtype, self.initial_accumulator_value)}

  def row_updates(self, state, uids, sum_g, sum_sq, lr, limit):
    """Per-row f32 deltas + new state at COMPACTED unique rows (the
    shared arithmetic core — see ``SparseSGD.row_updates``).

    Matches the uncompacted semantics exactly: with duplicates, every
    occurrence reads the accumulator AFTER the full batch's additions,
    so the total update of a row is ``-lr * sum_g / sqrt(acc_new +
    eps)`` in both formulations.  Because ``uids`` are unique, the new
    accumulator rows are computed by a GATHER from the pre-update
    accumulator plus ``add`` and written back with one scatter-set —
    gathering from the post-scatter accumulator instead (the earlier
    formulation) creates a scatter->gather dependency that XLA broke by
    rematerialising the 4.5 GB-temp scatter, i.e. a third full scatter
    pass per step (~143 ms each at synthetic-tiny scale, trace in
    docs/perf_notes.md).
    """
    # _rounded_square: pins `acc + g*g` to mul-then-add rounding so the
    # accumulator is layout-independent (design §20 bit-parity; the
    # compacted operand is small, so the severed fusion costs nothing)
    # (the phases below keep the operations in the order they always
    # had: the program is the same, only its metadata is new)
    with obs_trace.phase('apply/update'):
      add = _rounded_square(sum_g) if self.dedup else sum_sq
    # compacted ids are ascending (clipped sentinel gathers may
    # duplicate the last row, hence unique_indices=False there): the
    # hint lets XLA vectorise the gather instead of serialising for
    # duplicates; the write is ``_write_rows``'s
    with obs_trace.phase('apply/read_rows'):
      safe = jnp.clip(uids, 0, limit - 1)
    # low-precision accumulators: gather up-casts, arithmetic (add +
    # rsqrt) stays f32, only the store rounds to accum_dtype — the
    # update this step uses the EXACT f32 running value
    with obs_trace.phase('apply/read_rows'):
      old_rows = state['acc'].at[safe].get(
          unique_indices=False,
          indices_are_sorted=True).astype(jnp.float32)
    with obs_trace.phase('apply/update'):
      acc_rows = old_rows + add
    acc = _write_rows(state['acc'], uids, acc_rows, 'set')
    with obs_trace.phase('apply/update'):
      delta = -lr * sum_g * jax.lax.rsqrt(acc_rows + self.epsilon)
    return delta, {'acc': acc}

  apply_unique = _apply_rows

  def apply_hot(self, hot, state, sum_g, sum_sq, lr, count=None):
    """DENSE Adagrad step on a replicated hot-cache buffer: the same
    accumulate-then-read arithmetic as ``apply_unique`` (dedup
    semantics square the mesh-psummed row sum; per-occurrence
    semantics consume the psummed squared channel), elementwise — no
    scatter.  Untouched rows see ``add == 0`` and ``update == 0``, so
    they are bit-preserved (incl. bf16 accumulator stores: the f32
    up-cast/round-trip of a bf16 value is exact)."""
    del count
    # same FMA-contraction pinning as row_updates (design §20)
    add = _rounded_square(sum_g) if self.dedup else sum_sq
    acc_rows = state['acc'].astype(jnp.float32) + add
    update = (-lr * sum_g * jax.lax.rsqrt(acc_rows + self.epsilon)).astype(
        hot.dtype)
    return hot + update, {'acc': acc_rows.astype(state['acc'].dtype)}


@dataclasses.dataclass(frozen=True)
class SparseAdam:
  """Row-wise *lazy* Adam: moments and bias-correction step advance only for
  rows touched this batch (the sparse-friendly variant; nonlinear in the
  row grad, so duplicates are always deduped first).

  Hot-cache layers (design §10) are supported: the replicated hot
  buffers carry split ``m``/``v`` moments plus the per-row step counter
  ``t``, and the backward ships a trailing occurrence-COUNT column with
  the hot gradients (``needs_touch``) — the touched-row mask
  ``apply_unique`` derives from stream membership, which a zero
  gradient sum cannot encode densely.  ``apply_hot`` then runs the
  exact ``apply_unique`` arithmetic elementwise on touched rows and
  bit-preserves the rest."""
  learning_rate: float = 0.001
  b1: float = 0.9
  b2: float = 0.999
  epsilon: float = 1e-8
  capacity_fraction: float = 0.5
  capacity_rows: Optional[Tuple[Optional[int], ...]] = None

  needs_sq = False
  # hot-cache backward must ship the occurrence-count channel: the lazy
  # per-row step counter advances exactly for TOUCHED rows (see above)
  needs_touch = True
  # the per-row step counter 't' is not an elementwise-lane quantity
  supports_lane_packing = False

  def init(self, dist: DistributedEmbedding, params) -> Dict:
    if getattr(dist, 'cold_tier', None) is not None:
      # §12 refusal matrix: lazy Adam's per-row step counter 't' is not
      # an elementwise [rows, w] leaf, so the tier's fetch/writeback
      # row channels cannot carry it — refuse actionably rather than
      # silently degrading the lazy semantics
      raise ValueError(
          'SparseAdam does not support cold-tier layers: the lazy '
          "per-row step counter 't' has no tier fetch/writeback "
          'channel (docs/design.md §12). Train tiered tables with '
          'SparseSGD or SparseAdagrad, or disable the cold tier.')
    out = {}
    for gi in getattr(dist.plan, 'hot_groups', []):
      # replicated split state for hot rows (design §10): moments plus
      # the per-row step counter live HERE while the row is hot; the
      # checkpoint boundary canonicalises them back into the per-table
      # layout (per-row 't' overlays like the row-window leaves)
      hp = params[f'hot_group_{gi}']
      out[f'hot_group_{gi}'] = {
          'm': jnp.zeros_like(hp, dtype=jnp.float32),
          'v': jnp.zeros_like(hp, dtype=jnp.float32),
          't': jnp.zeros(hp.shape[:1], jnp.int32),
      }
    for gi, g in enumerate(dist.plan.groups):
      if (g.storage_pack > 1
          and not packed_dispatch_ok(g.rows_cap, g.width)):
        # Adam applies in NATURAL space (the per-row step counter is
        # not a lane-wise quantity), so packed storage forces an
        # unpack/repack reshape around every apply — on a group this
        # large that reshape risks the lane-padded relayout HBM blowup
        # (docs/perf_notes.md round 3).  Fail HERE, actionably, instead
        # of OOMing mid-step.
        raise ValueError(
            f'SparseAdam with packed storage on group {gi} '
            f'({g.rows_cap} rows x {g.width}): the natural-space apply '
            f'reshape risks a lane-padded relayout past '
            f'PACKED_PARAM_BYTES_LIMIT. Construct the layer with '
            f'packed_storage=False to train this model with SparseAdam.')
      p = params[f'group_{gi}']
      out[f'group_{gi}'] = {
          'm': jnp.zeros_like(p, dtype=jnp.float32),
          'v': jnp.zeros_like(p, dtype=jnp.float32),
          # per NATURAL row, regardless of packed storage (the packed
          # fallback in _dedup_and_apply applies Adam in natural space)
          't': jnp.zeros(p.shape[:1] + (g.rows_cap,), jnp.int32),
      }
    return out

  def row_updates(self, state, uids, sum_g, sum_sq, lr, limit):
    """Per-row f32 deltas + new state at COMPACTED unique rows (the
    shared arithmetic core — see ``SparseSGD.row_updates``); duplicates
    were segment-summed by ``compact_segments``, the same dedup the old
    path did internally."""
    del sum_sq
    g = sum_g
    # ascending ids; see SparseAdagrad.row_updates
    ghints = dict(unique_indices=False, indices_are_sorted=True)
    # (operations in the order they always had; see SparseAdagrad)
    with obs_trace.phase('apply/read_rows'):
      safe = jnp.clip(uids, 0, limit - 1)
    with obs_trace.phase('apply/update'):
      valid = (uids < limit)[:, None]
    t = _write_rows(state['t'], uids, jnp.ones_like(uids), 'add')
    with obs_trace.phase('apply/read_rows'):
      m_old = state['m'].at[safe].get(**ghints)
    with obs_trace.phase('apply/update'):
      m_rows = self.b1 * m_old + (1 - self.b1) * g
    with obs_trace.phase('apply/read_rows'):
      v_old = state['v'].at[safe].get(**ghints)
    with obs_trace.phase('apply/update'):
      v_rows = self.b2 * v_old + (1 - self.b2) * g * g
    m = _write_rows(state['m'], uids, jnp.where(valid, m_rows, 0), 'set')
    v = _write_rows(state['v'], uids, jnp.where(valid, v_rows, 0), 'set')
    with obs_trace.phase('apply/read_rows'):
      t_rows = t.at[safe].get(**ghints).astype(jnp.float32)[:, None]
    with obs_trace.phase('apply/update'):
      mhat = m_rows / (1 - self.b1**t_rows)
      vhat = v_rows / (1 - self.b2**t_rows)
      delta = -lr * mhat / (jnp.sqrt(vhat) + self.epsilon)
    return delta, {'m': m, 'v': v, 't': t}

  apply_unique = _apply_rows

  def apply_hot(self, hot, state, sum_g, sum_sq, lr, count=None):
    """DENSE lazy-Adam step on a replicated hot-cache buffer.

    ``count`` is the mesh-psummed per-row occurrence count
    (``backward_to_mp(with_touch=True)``): rows with ``count > 0`` run
    exactly the ``apply_unique`` arithmetic on the deduplicated
    mesh-psummed row sum (t advances, moments decay-and-add, bias
    correction reads the advanced t); rows with ``count == 0`` are
    bit-preserved — the lazy semantics a zero gradient sum alone could
    not reproduce (a touched row with zero summed gradient still decays
    its moments and advances its step)."""
    del sum_sq
    if count is None:
      raise ValueError(
          'SparseAdam.apply_hot needs the occurrence-count channel: '
          'call backward_to_mp(with_touch=True) (make_hybrid_train_step '
          'does this for needs_touch optimizers)')
    touched = count[:, 0] > 0
    t = state['t'] + touched.astype(state['t'].dtype)
    m_rows = self.b1 * state['m'] + (1 - self.b1) * sum_g
    v_rows = self.b2 * state['v'] + (1 - self.b2) * sum_g * sum_g
    # untouched rows keep t == 0; clamp the bias-correction exponent so
    # their (masked-away) update lane never divides by zero
    tf = jnp.maximum(t, 1).astype(jnp.float32)[:, None]
    mhat = m_rows / (1 - self.b1**tf)
    vhat = v_rows / (1 - self.b2**tf)
    update = -lr * mhat / (jnp.sqrt(vhat) + self.epsilon)
    mask = touched[:, None]
    return (hot + jnp.where(mask, update, 0.0).astype(hot.dtype), {
        'm': jnp.where(mask, m_rows, state['m']),
        'v': jnp.where(mask, v_rows, state['v']),
        't': t,
    })


class _QuantizedTableOptimizer:
  """Dequant -> f32 update -> requant adapter (docs/design.md §12).

  Wraps a row-wise optimizer so the audited compact/apply pipeline
  (``_dedup_and_apply`` / ``_apply_wave`` / the correction
  wave) runs unchanged against QUANTIZED tables: the "table" operand
  becomes the ``(payload, scale)`` pair, the update arithmetic runs
  through the inner optimizer's ``row_updates`` (ONE definition of the
  math, shared with the unquantized scatter path), and exactly the
  touched rows requantize with a refreshed power-of-two scale
  (``quantization.quantize_jnp`` — the scale-refresh rule that makes
  untouched-row round-trips bit-exact).  Optimizer STATE (Adagrad
  accumulators, Adam moments) is untouched: it keeps its own
  ``accum_dtype`` ladder at full row width.
  """

  supports_lane_packing = False

  def __init__(self, inner, spec):
    self.inner = inner
    self.spec = spec
    self.capacity_fraction = getattr(inner, 'capacity_fraction', 0.5)
    self.needs_sq = bool(getattr(inner, 'needs_sq', False))
    self.needs_touch = bool(getattr(inner, 'needs_touch', False))

  def apply_unique(self, pt, state, uids, sum_g, sum_sq, lr):
    from distributed_embeddings_tpu.parallel import quantization
    payload, scale = pt
    limit = payload.shape[0]
    delta, state2 = self.inner.row_updates(state, uids, sum_g, sum_sq,
                                           lr, limit)
    ghints = dict(unique_indices=False, indices_are_sorted=True)
    with obs_trace.phase('apply/read_rows'):
      safe = jnp.clip(uids, 0, limit - 1)
      old = (payload.at[safe].get(**ghints).astype(jnp.float32)
             * scale.at[safe].get(**ghints))
    with obs_trace.phase('apply/update'):
      npay, nscale = quantization.quantize_jnp(old + delta, self.spec)
    return (_write_rows(payload, uids, npay, 'set'),
            _write_rows(scale, uids, nscale, 'set')), state2

  def apply_hot(self, pt, state, sum_g, sum_sq, lr, count=None):
    """Dense step on a quantized replicated hot buffer: dequantize the
    whole (small) buffer, run the inner dense apply, requantize every
    row — untouched rows see a zero update, and the power-of-two
    scale-refresh rule makes their dequant->requant round trip the
    bitwise identity (pinned in tests/test_quantized_storage.py)."""
    from distributed_embeddings_tpu.parallel import quantization
    payload, scale = pt
    hot = payload.astype(jnp.float32) * scale
    new_hot, state2 = self.inner.apply_hot(hot, state, sum_g, sum_sq,
                                           lr, count=count)
    npay, nscale = quantization.quantize_jnp(new_hot, self.spec)
    return (npay, nscale), state2


@obs_trace.phase('apply/dedup')
def _lane_pack(uids, sum_g, sum_sq, pack: int, rows_cap: int,
               exact: bool = False):
  """Re-compact per-row updates at packed-row granularity.

  View the ``[rows_cap, w]`` table as ``[rows_cap // pack, pack * w]``
  (free, row-major): row ``uid`` becomes packed row ``uid // pack``,
  lanes ``(uid % pack) * w ..``.  Updates whose rows share a packed row
  merge (they occupy disjoint lanes), so the scatter row count drops to
  at most ``rows_cap // pack`` — for small fused groups fed by many
  updates that is another ``pack``-fold shrink on top of the unique-row
  compaction (e.g. synthetic-tiny's 31 small tables: 60k unique rows ->
  3.8k packed rows at width 8).

  ``exact``: merge lanes with the bounded exact fold instead of the
  cumsum-difference trick.  The lanes of one packed row are DISJOINT,
  so the true merge is pure placement — but the cumsum trick folds the
  running prefix of a lane COLUMN (other packed rows' lanes) into each
  total, making the result depend on which rows share the stream.
  The parity-critical cross-slice merge (design §20) needs
  layout-independent totals: a pid segment holds at most ``pack``
  unique rows, so the fold bound is ``pack``.

  Returns ``(pids, g_packed, sq_packed)`` sized
  ``min(len(uids), rows_cap // pack + 2)``.
  """
  from distributed_embeddings_tpu.ops.pallas_segwalk import (lane_expand,
                                                             packed_ids)
  c, w = sum_g.shape
  lanes = pack * w
  cap2, psent = wave_shape(c, rows_cap, pack)
  pids, slot = packed_ids(uids, pack, rows_cap)
  g_lanes = lane_expand(sum_g, slot, pack)
  payload = (g_lanes if sum_sq is None else jnp.concatenate(
      [g_lanes, lane_expand(sum_sq, slot, pack)], axis=1))
  # uids come rank-ordered (ascending, sentinels last) from the outer
  # compaction, so pids is already sorted: no sort, no sorted gather
  pids_c, pay_c, _, _ = _compact_sorted(
      pids, payload, cap2, psent, max_seg=pack if exact else None)
  g_packed = pay_c[:, :lanes]
  sq_packed = pay_c[:, lanes:] if sum_sq is not None else None
  return pids_c, g_packed, sq_packed


def wave_shape(cap: int, rows_cap: int, pack: int) -> Tuple[int, int]:
  """``(update rows, operand rows)`` of a wave of ``cap`` compacted rows
  as ``_apply_wave`` hands it to ``apply_unique`` (unchunked):
  lane-packed, ``pack`` table rows share one of the operand's, so at most
  every packed row plus the sentinel's two slots."""
  operand = rows_cap // pack
  return (cap if pack == 1 else min(cap, operand + 2)), operand


def write_rows_line(group: str, wave: int, operand: int, write: str) -> str:
  """The dispatch's log line and ``utils/apply_eligibility``'s report:
  which emitter a group's main wave takes, and the share that decided."""
  return (f'apply/write_rows: {group} writes {wave:,} rows into '
          f'{operand:,} ({100.0 * wave / operand:.2f}%): {write}')


def _guaranteed_cap(n: int, rows_cap: int) -> int:
  """The capacity that can NEVER drop a segment: unique fused rows plus
  the one sentinel segment are at most ``rows_cap + 2`` (``_route_ids``
  maps all padding to the single sentinel value ``rows_cap``)."""
  return min(n, rows_cap + 2)


def _capacity(optimizer, n: int, rows_cap: int,
              cap_rows: Optional[int]) -> int:
  """Static compaction capacity for an ``n``-row update stream: the
  calibrated per-group row count (``calibrate_capacity_rows``) when
  given — the overflow correction wave keeps under-estimates correct —
  else ``capacity_fraction`` of the stream; always bounded by the fused
  table's own row count."""
  cap_safe = _guaranteed_cap(n, rows_cap)
  if cap_rows is not None:
    return min(cap_safe, max(8, -(-int(cap_rows) // 8) * 8))
  frac = getattr(optimizer, 'capacity_fraction', 0.5)
  return min(cap_safe, max(8, -(-int(n * frac) // 8) * 8))


class _Stream(NamedTuple):
  """One group's update stream, as the sparse apply's stages hand it on
  (docs/design.md §26).  What tells a slice's own stream from one merged
  across slices travels here, so that each kernel has one call site.

  ids: ``[n]`` int32 rows; ``rows_cap`` and anything above it is padding
    and dropped.
  rows: ``[n, w]`` gradient rows, one a position — or, with ``index``,
    the COMPACT ``[m, w]`` rows (one per (sample, bag)) the positions
    point into.
  rows_cap: static row count of the space ``ids`` index: the group's
    fused shard, the owner's hier-local shard after a hierarchical
    merge, resident plus fetched rows of a cold-tier group.
  index: ``[n]`` int32 position -> row of ``rows`` (the
    ``compact_segments`` contract): a multi-hot bag's one cotangent row
    is never broadcast, in the main wave or in the overflow
    correction's loop body (whose temps count toward peak HBM even
    untaken).  None: ``rows`` holds one row a position.  Not together
    with ``squares`` (that stream is already compact).
  squares: ``[n, w]`` squared-gradient rows that arrive SUMMED (each
    slice pre-compacts before the cross-slice merge, a hot-cache
    backward per source device; squares of those sums would be wrong,
    so the squares travel as their own additive channel).  None: the
    apply squares ``rows`` itself where the optimizer needs squares.
  max_seg: static bound on how often one row occurs (the slice count,
    after the merge: each row at most once a slice).  Totals then fold
    exactly, which keeps them layout-independent (flat-vs-hier
    bit-parity, design §20; ``compact_segments``).  None: no bound.
  """
  ids: jax.Array
  rows: jax.Array
  rows_cap: int
  index: Optional[jax.Array] = None
  squares: Optional[jax.Array] = None
  max_seg: Optional[int] = None


def _viewed(table, state, shape):
  """``table`` and every state leaf of its shape as ``shape``, a
  row-major regrouping of the same bytes (Adam's per-row step counter
  ``t`` has another shape and stays)."""
  stored = table.shape
  return table.reshape(shape), {
      k: (v.reshape(shape) if v.shape == stored else v)
      for k, v in state.items()}


def _apply_wave(optimizer, table, state, uids, sum_g, sum_sq, lr,
                pack: int, rows_cap: int, exact: bool, n_chunks: int = 1):
  """One wave of ``_dedup_and_apply``: the compacted unique rows,
  lane-packed where ``pack`` rows of the table share a row of the
  operand (``choose_apply``), through ``apply_unique`` in ``n_chunks``
  static row chunks (docs/design.md §11).  Both waves come through
  here, so the correction lane-packs whenever the main wave does: its
  ``uids`` are ascending with the sentinels last like the main wave's,
  which is all ``_lane_pack``'s sorted-pids shortcut needs.

  The compacted rows are UNIQUE, so the chunk applies touch disjoint
  table/state rows and threading the table through them is bit-exact vs
  the single call — while the one monolithic scatter/gather pipeline
  becomes ``n_chunks`` independent pieces the scheduler can interleave
  with the still-arriving chunked gradient exchange.  The compacted
  buffer is rank-ordered (ascending ids, sentinels last), so the tail
  chunks carry only dropped sentinel rows and every chunk's ids are
  ascending (``_write_rows`` picks each chunk's emitter from the
  chunk's own rows)."""
  if pack > 1:
    uids, sum_g, sum_sq = _lane_pack(uids, sum_g, sum_sq, pack, rows_cap,
                                     exact=exact)
  k = effective_chunks(n_chunks, uids.shape[0])
  if k == 1:
    return optimizer.apply_unique(table, state, uids, sum_g, sum_sq, lr)
  for lo, hi in chunk_bounds(uids.shape[0], k):
    table, state = optimizer.apply_unique(
        table, state, uids[lo:hi], sum_g[lo:hi],
        None if sum_sq is None else sum_sq[lo:hi], lr)
  return table, state


def _dedup_and_apply(optimizer, table, state, stream: _Stream, lr,
                     cap_rows: Optional[int] = None, storage_pack: int = 1,
                     n_chunks: int = 1):
  """Compact duplicate update rows, then run the optimizer on the unique
  rows only.  ``stream``: see ``_Stream``.

  ``storage_pack > 1``: ``table`` (and elementwise state leaves) arrive
  in the group's PHYSICAL packed layout ``[rows_cap/pack, 128]``
  (``GroupSpec.storage_pack``); updates are lane-packed against the
  operand itself and the results return in the same layout — no reshape
  of the parameter ever exists in the step, so the lane-padded relayout
  (``packed_dispatch_ok``) cannot occur at any group size.  The one
  exception is an optimizer without lane-wise apply semantics
  (SparseAdam's per-row step counter): its operand is viewed natural
  around both waves.  That reshape CAN provoke the relayout on huge
  narrow groups — the documented cost of pairing Adam with
  packed_storage; disable packed_storage on the layer to avoid it.
  Which view serves is ``choose_apply``'s answer.

  Scatter cost follows the STATIC update-row count, whether or not rows
  are dropped (both of XLA's emitters: ``write_algorithm``,
  docs/perf_notes.md), so the
  raw per-occurrence stream (batch x hotness x slots rows) is compacted
  first.  Capacity = min(n, rows_cap + 2, capacity_fraction * n): the
  fused table's own row count bounds uniques for small fused groups
  (e.g. the synthetic models' many tiny tables fuse into a ~60k-row group
  fed by millions of update rows), while the fraction covers big-vocab
  groups, whose duplicate factor comes from the power-law id distribution.
  When the fraction bound is exceeded (traced unique count > capacity),
  a correction wave that runs only then applies the dropped segments —
  always correct, never silently dropping updates (overflow structure
  below).

  For sub-128 widths a second, packed-granularity compaction follows
  when it shrinks the scatters further (``_lane_pack``); the optimizer
  then runs lane-wise on the packed ``[rows_cap // pack, pack * w]``
  views (exact: untouched lanes receive zero gradient, and Adagrad's
  accumulator/denominator math is elementwise).

  ``n_chunks > 1`` (``DistributedEmbedding(overlap_chunks=)``,
  docs/design.md §11): the compacted unique-row stream feeds
  ``apply_unique`` in static row chunks (``_apply_wave``) —
  bit-exact, because compacted rows are disjoint — so the apply's
  scatters pipeline against the chunked gradient exchange instead of
  forming one monolithic tail.  The correction wave stays monolithic
  (it is the rare path; chunking it would only grow a traced program
  that almost never runs).

  Overflow structure: the capped apply runs UNconditionally and only
  the rare *correction* wave for the segments the cap dropped is
  conditional.  The waves touch disjoint unique rows, so applying them
  separately is exact for every optimizer here.  The condition is a
  ``lax.while_loop`` that runs zero times or once with ``(table,
  state)`` as its carried value — NOT a ``lax.cond``: no table or
  state leaf may pass an XLA conditional.  XLA gives each branch of a
  conditional its own operand, so the shard is copied whole once per
  branch, on every step, whether or not the cap overflowed: 31.2 ms
  each, 47% of dlrm-train-4chip's step (the compiled text and the
  numbers of ISSUE 25: docs/design.md §26).  A while loop is the
  construct XLA aliases by design — one buffer through init, body and
  result — with the correction's own scatter writing the carried
  element in place.
  ``analysis/graphlint``'s donation pass holds every train program to
  this (``donation/state-leaf-in-cond``), and
  ``tests/test_tpu_lowering.py`` holds the compiled step to no
  shard-shaped copy.
  """
  flat_ids, flat_g, rows_cap, g_index, flat_sq, max_seg = stream
  if g_index is not None and flat_sq is not None:
    raise ValueError('index and squares are mutually exclusive (the '
                     'pre-summed-squares stream is already compact)')
  n = flat_ids.shape[0]
  sentinel = rows_cap
  cap_safe = _guaranteed_cap(n, rows_cap)
  cap = _capacity(optimizer, n, rows_cap, cap_rows)
  with_sq = bool(getattr(optimizer, 'needs_sq', True))
  w = flat_g.shape[1]
  choice = choose_apply(optimizer, table, rows_cap, w,
                        storage_pack=storage_pack, cap=cap)
  view, pack = choice.view, choice.pack
  exact = max_seg is not None
  stored = None
  if view in ('packed_view', 'unpacked'):
    # the operand is stored in another shape than the waves apply to
    stored = table.shape
    with obs_trace.phase('apply/read_rows'):
      table, state = _viewed(table, state, (rows_cap // pack, pack * w))

  # squares that arrive pre-accumulated are segment-summed as an extra
  # payload column block instead of squaring the (pre-summed) grads
  sq_cols = with_sq and flat_sq is not None
  with obs_trace.phase('apply/dedup'):
    # one sort serves the main wave and the correction's body
    sid, order = sort_with_order(flat_ids)
    sg = _sorted_payload(
        jnp.concatenate([flat_g.astype(jnp.float32),
                         flat_sq.astype(jnp.float32)], axis=1)
        if sq_cols else flat_g, order, g_index)
  uids, sum_g, sum_sq, num_unique = _compact_sorted(
      sid, sg, cap, sentinel, with_sq=with_sq and not sq_cols,
      max_seg=max_seg)
  if sq_cols:
    sum_g, sum_sq = sum_g[:, :w], sum_g[:, w:]
  t2, s2 = _apply_wave(optimizer, table, state, uids, sum_g, sum_sq, lr,
                       pack, rows_cap, exact, n_chunks)

  def correction(args):
    # apply the segments the cap dropped (ranks >= cap), compacted to
    # the guaranteed bound so the branch's scatters stay O(rows_cap)
    # rather than O(n) when the fused table is smaller than the stream
    t3, s3 = args
    with obs_trace.phase('apply/dedup'):
      # the payload is gathered again, not kept from the main wave: an
      # [n, w] buffer alive across the apply would count toward peak HBM
      sg = _sorted_payload(flat_g, order, g_index)
      is_first, is_last, first_pos_c, seg_total = _sorted_segments(sid)
      if exact:
        # the bounded exact fold of the main wave (layout-independent
        # totals, design §20) — the correction must sum identically
        seg_total = lambda x: _seg_fold_bounded(x, first_pos_c, max_seg)
      rank = cumsum0(is_first.astype(jnp.int32)) - 1
      keep = is_last & (rank >= cap)
      key2 = jnp.where(keep, rank, n)
      order3 = jnp.argsort(key2)[:cap_safe]
      valid3 = key2[order3] < n
      uids2 = jnp.where(valid3, sid[order3], sentinel)
      tot_g = jnp.where(valid3[:, None], seg_total(sg)[order3], 0.0)
      if with_sq:
        sq_src = (flat_sq[order].astype(jnp.float32)
                  if flat_sq is not None else sg * sg)
        tot_sq = jnp.where(valid3[:, None], seg_total(sq_src)[order3], 0.0)
      else:
        tot_sq = None
    return _apply_wave(optimizer, t3, s3, uids2, tot_g, tot_sq, lr, pack,
                       rows_cap, exact)

  if cap < cap_safe:
    (t2, s2), _ = jax.lax.while_loop(
        lambda carry: carry[1],
        lambda carry: (correction(carry[0]), False),
        ((t2, s2), num_unique > cap))
  if stored is not None:
    with obs_trace.phase('apply/write_rows'):
      t2, s2 = _viewed(t2, s2, stored)
  return t2, s2


# Ceiling on the POTENTIAL lane-padded parameter size a packed-view
# apply may provoke.  Compile-only v5e validation (compile_check.py,
# docs/perf_notes.md round 3) showed XLA can materialize a narrow
# group's parameter in a lane-padded layout to serve the packed
# reshape — 8x expansion on synthetic-tiny's 29.1M-row width-16 group
# (1.73 -> 13.88 GiB), blowing HBM.  Round 4 removed the reshape from
# the DEFAULT path entirely: qualifying narrow groups store physically
# packed (GroupSpec.storage_pack), where this bound does not apply.
# It still guards the legacy reshape path — packed_storage=False
# layers, and widths outside 8..64 — where the relayout risk remains.
PACKED_PARAM_BYTES_LIMIT = 2 << 30


def packed_dispatch_ok(rows_cap: int, width: int) -> bool:
  """Whether a narrow group may take a packed-view fused apply without
  risking the lane-padded-layout HBM blowup (width-128 groups always
  may)."""
  if width >= 128:
    return True
  return rows_cap * 128 * 4 <= PACKED_PARAM_BYTES_LIMIT


def packed_view_ok(rows_cap: int, width: int) -> bool:
  """Whether a NARROW group can engage the fused kernels through the
  lane-packed ``[rows_cap/pack, 128]`` view: width must divide 128,
  rows must divide by the pack factor, and the padded layout must fit
  the HBM bound (``choose_apply`` asks)."""
  return (width < 128 and 128 % width == 0
          and rows_cap % (128 // width) == 0
          and packed_dispatch_ok(rows_cap, width))


class ApplyChoice(NamedTuple):
  """``choose_apply``'s answer for one group."""
  kernel: str    # 'tied' | 'segwalk' | 'xla'
  view: str      # the XLA apply's view of the operand (``choose_apply``)
  pack: int      # table rows in one row of the operand the waves update
  declined: str  # why a segment-walk kernel that was asked for does not
  #                serve the group; '' where it serves or was not asked for
  write: str = ''  # the emitter the main wave's writes take
  #                  (``write_algorithm``: 'stream' | 'rows'); '' where the
  #                  capacity is not known or another kernel writes


def choose_apply(optimizer, table, rows_cap: int, width: int, *,
                 storage_pack: int = 1, cap: Optional[int] = None,
                 tied: bool = False, adapted: bool = False,
                 summed_squares: bool = False, group: Optional[str] = None,
                 active: Optional[bool] = None) -> ApplyChoice:
  """THE one place that says which apply serves a group: the group
  loop's ``_apply_group``, ``_dedup_and_apply``'s choice of view and
  ``utils/apply_eligibility``'s report all ask here.

  ``table``: the operand's aval (shape and dtype; read only where the
  kernel is asked for).  ``rows_cap`` x ``width``: the group's natural
  rows.  ``cap``: the compaction capacity, where it is known
  (``_dedup_and_apply``, and the dispatch); with it the answer names the
  emitter the main wave's writes take (``write``: ``write_algorithm``'s
  own answer, logged at INFO once per trace for the dispatch).
  ``tied``: the head also reads a table of the
  group.  ``adapted``: the operand is a quantized ``(payload, scale)``
  pair or carries a fetched cold-tier tail.  ``summed_squares``: the
  stream's squares arrive summed (``_Stream.squares``).

  kernel: ``'tied'`` (``_tied_apply``) where the head reads the group;
  else ``'segwalk'`` (``_segwalk_apply``) where ``use_segwalk_apply``
  asks for the kernel, it runs here (``active``; None: on a TPU, or
  where a test or an AOT compile stands in for one) and nothing below
  declines; else ``'xla'`` (``_dedup_and_apply``).  The kernel is
  opt-in, so a request it cannot serve must not pass in silence: for the
  dispatch (``group`` given) on a TPU, where the request is meant, each
  group's outcome is logged once per trace — INFO when the kernel
  serves it, WARNING with the reason when the group takes the XLA apply
  instead.

  view, pack — how the XLA apply sees the operand: ``'stored_packed'``,
  lane-packed updates against the physically packed operand itself;
  ``'unpacked'``, that operand viewed natural for an optimizer with no
  lane-wise apply (SparseAdam); ``'packed_view'``, a natural narrow
  operand viewed ``[rows_cap/pack, 128]`` where that shrinks the
  scatters below the compaction capacity (``packed_view_ok`` folds in
  the lane-padded-layout HBM bound); ``'natural'`` otherwise.
  """
  lane_ok = getattr(optimizer, 'supports_lane_packing', False)
  if storage_pack > 1:
    view, pack = (('stored_packed', storage_pack) if lane_ok
                  else ('unpacked', 1))
  elif (lane_ok and packed_view_ok(rows_cap, width)
        and (cap is None or rows_cap // (128 // width) + 2 < cap)):
    view, pack = 'packed_view', 128 // width
  else:
    view, pack = 'natural', 1
  if tied:
    return ApplyChoice('tied', view, pack, '')
  write = ''
  if cap is not None:
    wave, operand = wave_shape(cap, rows_cap, pack)
    write = write_algorithm(
        wave, operand,
        'set' if isinstance(optimizer, _QuantizedTableOptimizer) else 'add')
    if group is not None:
      _LOG.info(write_rows_line(group, wave, operand, write))
  if not getattr(optimizer, 'use_segwalk_apply', False):
    return ApplyChoice('xla', view, pack, '', write)
  from distributed_embeddings_tpu.ops import pallas_segwalk
  accum = getattr(optimizer, 'accum_dtype', 'float32')
  on_tpu = jax.default_backend() == 'tpu'
  if active is None:
    active = (on_tpu or pallas_segwalk.FORCE_INTERPRET
              or pallas_segwalk.ASSUME_TPU)
  declined = ''
  if adapted:
    # the kernel's table contract is f32 rows in one array
    declined = 'a quantized or cold-tier operand takes the XLA adapter'
  elif summed_squares:
    # multi-slice per-occurrence Adagrad, hot-cache streams
    declined = 'the stream carries squares the kernel cannot consume'
  elif not pallas_segwalk.acc_dtype_ok(table.dtype, accum):
    # bf16 accumulators ride the bf16 table's pair-fetch path ONLY
    declined = f'{accum} accumulators on a {table.dtype} table'
  elif not pallas_segwalk.supported(table):
    declined = f'table {table.shape} {table.dtype} is not a kernel shape'
  elif not packed_dispatch_ok(table.shape[0], table.shape[1]):
    declined = ('the lane-padded relayout of this narrow group would '
                f'exceed {PACKED_PARAM_BYTES_LIMIT >> 30} GiB')
  elif not active:
    declined = f'the kernel does not run on {jax.default_backend()}'
  if group is not None and on_tpu:
    if declined:
      _LOG.warning('use_segwalk_apply: %s takes the XLA apply (%s)',
                   group, declined)
    else:
      _LOG.info('use_segwalk_apply: %s takes the segment-walk kernel',
                group)
  return ApplyChoice('xla' if declined else 'segwalk', view, pack, declined,
                     write if declined else '')


@obs_trace.phase('apply/dedup')
def _segwalk_apply(optimizer, table, state, stream: _Stream, lr,
                   storage_pack: int = 1):
  """Hand the raw stream to the fused segment-walk kernel
  (ops/pallas_segwalk.py) — no compaction, no capacity, no correction
  wave: every segment is applied exactly once.  (Phases: the wrapper's
  sort and operand assembly are this path's ``apply/dedup``; the kernel,
  which sums, reads, updates and writes in one pass, opens
  ``apply/update`` inside.)  ``storage_pack > 1``:
  the table arrives (and returns) in the physical packed layout; the
  kernel runs its packed path on the operand itself.  A stream with an
  ``index`` goes in as it is: the kernel's one ``[n, 128]`` operand
  gathers straight from the compact rows and the multi-hot broadcast
  never materialises (pallas_segwalk.segwalk_apply docstring)."""
  from distributed_embeddings_tpu.ops import pallas_segwalk
  interp = pallas_segwalk.FORCE_INTERPRET
  lw = stream.rows.shape[1] if storage_pack > 1 else None
  # RAW stream in: the kernel wrapper sorts internally so the payload
  # gathers once, directly into its dense [n, 128] operand (sorting
  # here first would materialise an extra lane-padded narrow gather —
  # the multi-GiB [n, w<128] temps of the round-4 memory audit)
  ids = stream.ids.astype(jnp.int32)
  g = stream.rows.astype(jnp.float32)
  sdt = getattr(optimizer, 'stream_dtype', 'float32')
  if isinstance(optimizer, SparseSGD):
    t2 = pallas_segwalk.segwalk_apply(
        table, None, ids, g, lr, op='sgd', interpret=interp,
        logical_width=lw, presorted=False, stream_dtype=sdt,
        g_index=stream.index)
    return t2, state
  op = 'adagrad_dedup' if optimizer.dedup else 'adagrad_sq'
  t2, a2 = pallas_segwalk.segwalk_apply(
      table, state['acc'], ids, g, lr, op=op, eps=optimizer.epsilon,
      interpret=interp, logical_width=lw, presorted=False,
      stream_dtype=sdt, g_index=stream.index)
  return t2, {'acc': a2}


def _tied_layout(dist: DistributedEmbedding, table_ids, optimizer):
  """Where each table the head reads lies: ``{table id: (group index,
  device, first row in the group's shard, rows)}``.  Refuses by name
  what the tied apply does not serve."""
  refused = [
      ('a hot-row cache', bool(getattr(dist, 'hot_enabled', False))),
      ('quantized storage', getattr(dist, 'quant', None) is not None),
      ('a cold tier', bool(getattr(dist.plan, 'cold_tier_groups', []))),
      ('more than one slice', dist.num_slices > 1),
  ]
  for what, there in refused:
    if there:
      raise NotImplementedError(
          f'head_reads_tables: not with {what} (docs/design.md §25)')
  if not hasattr(optimizer, 'apply_hot'):
    raise NotImplementedError(
        f'head_reads_tables: {type(optimizer).__name__} has no dense '
        'apply (apply_hot)')
  group_of = {g.key: gi for gi, g in enumerate(dist.plan.groups)}
  layout = dist.plan.shard_layout()
  out = {}
  for tid in table_ids:
    cfg = dist.table_configs[tid]
    shards = layout[tid]
    whole = (0, cfg.output_dim, 0, cfg.input_dim, 1)
    if len(shards) != 1 or tuple(shards[0][3:]) != whole:
      raise NotImplementedError(
          f'head_reads_tables: table {tid} is sliced over the mesh; the '
          'head reads whole tables')
    dev, key, row_offset = shards[0][:3]
    gi = group_of[key]
    if getattr(dist.plan.groups[gi], 'storage_pack', 1) > 1:
      raise NotImplementedError(
          f'head_reads_tables: table {tid} (width {cfg.output_dim}) is '
          'stored lane-packed; construct the layer with '
          'packed_storage=False')
    out[tid] = (gi, dev, row_offset, cfg.input_dim)
  return out


def _tied_apply(optimizer, table, state, stream: _Stream, lr, head_grads):
  """One update of a group that holds a table the head also multiplies
  by (a tied vocabulary, docs/design.md §25).  The lookups' occurrence
  rows are segment-summed as ever (``compact_segments`` at the
  guaranteed capacity: nothing can overflow), scattered into the head's
  dense ``[rows, width]`` gradient, and the shard takes ONE dense
  optimizer step (``apply_hot``): every row of a head-read table counts
  as asked for (the softmax touches them all), any other row of the
  group only if the batch did, so moments and step count advance once.

  ``head_grads``: ``[(first row, rows, gradient [rows, width], mine)]``,
  ``mine`` a traced flag: whether this device owns the table."""
  needs_sq = bool(getattr(optimizer, 'needs_sq', True))
  rows_cap = stream.rows_cap
  uids, sum_g, sum_sq, _ = compact_segments(
      stream.ids, stream.rows,
      _guaranteed_cap(stream.ids.shape[0], rows_cap), rows_cap,
      with_sq=needs_sq, g_index=stream.index)
  with obs_trace.phase('apply/tied'):
    hints = dict(mode='drop', unique_indices=True, indices_are_sorted=True)
    ids = _distinct_oob(uids, rows_cap)
    dense_g = jnp.zeros(table.shape, jnp.float32).at[ids].add(sum_g, **hints)
    asked = jnp.zeros((table.shape[0], 1), jnp.float32).at[ids].add(
        1.0, **hints)
    dense_sq = (jnp.zeros_like(dense_g).at[ids].add(sum_sq, **hints)
                if needs_sq else None)
    for first, rows, grad, mine in head_grads:
      grad = jnp.where(mine, grad.astype(jnp.float32), 0.0)
      dense_g = dense_g.at[first:first + rows].add(grad)
      asked = asked.at[first:first + rows].add(mine.astype(jnp.float32))
      if needs_sq:
        dense_sq = dense_sq.at[first:first + rows].add(grad * grad)
    return optimizer.apply_hot(table, state, dense_g, dense_sq, lr,
                               count=asked)


# --------------------------------------------------------------------------
# The group loop's stages (docs/design.md §26), in the order they run:
# stream -> merge across slices -> operand -> apply -> (after the loop)
# hot groups.  A ``_Stream`` passes between them.
# --------------------------------------------------------------------------


def _group_stream(slots, residuals, gs, rows_cap: int, fence):
  """Stage 1: one group's slots -> its update stream, under the caller's
  ``apply/stream``.

  ``slots``: ``[(si, divide)]``, the indices of the group's slots into
  ``residuals`` (``[1, n_cap, GB, h]`` routed ids, padding at
  ``rows_cap``) and ``gs`` (``[1, n_cap, GB, wc]`` cotangent rows, one a
  bag), and whether a 'mean' bag is still to be divided by its id count
  in this shard's window.  Not where the cotangent arrives divided: a
  ``mean_row_sliced`` slot's was divided by the TRUE per-sample count
  (make_hybrid_train_step; the shard-local count here would be the
  window count), a hot-cache stream's by the backward.  Hot-cache
  streams are already per-(source, slot) deduplicated h=1 rows and, for
  per-occurrence-squares optimizers, carry the squared channel as
  trailing columns (``wc = 2w``) — segment-summed additively, never
  re-squared.

  Returns ``(stream, fence)``: ``fence`` is the serialisation token the
  group loop threads through the applies.
  """
  # Multi-hot bags broadcast ONE cotangent row to every occurrence.
  # When duplication is real (n >= 2m), keep the compact
  # [n_cap*GB, w] rows plus an [n] position->row index instead of
  # materialising the h-fold broadcast (the 12.6 GiB-class stream
  # temps of the jumbo memory audit); the segwalk path consumes the
  # indirection natively, the XLA paths gather it back in their sort.
  # Below 2x duplication the indirection LOSES: the compact rows
  # are a materialised array (the lazy broadcast fuses into its
  # consumer) and w<128 rows store T(8,128) lane-padded — at m ~ n
  # that re-buys the round-4 padding blowup (+3.3 GiB measured on
  # medium@32) — so those groups keep the fused broadcast.
  shapes = [residuals[si].shape[1:] for si, _ in slots]
  n_total = sum(n_cap * gb * h for n_cap, gb, h in shapes)
  m_total = sum(n_cap * gb for n_cap, gb, _ in shapes)
  use_idx = n_total >= 2 * m_total
  ids_list, grad_list, gidx_list = [], [], []
  row_off = 0
  for si, divide in slots:
    ids = residuals[si][0]            # [n_cap, GB, h]
    gg = gs[si][0].astype(jnp.float32)  # [n_cap, GB, wc]
    if divide:
      cnt = jnp.sum(ids < rows_cap, axis=2).astype(jnp.float32)
      gg = gg / jnp.maximum(cnt, 1.0)[..., None]
    n_cap, gb, h = ids.shape
    wc = gg.shape[-1]
    ids_list.append(ids.reshape(-1))
    if use_idx:
      grad_list.append(gg.reshape(-1, wc))
      gidx_list.append(
          row_off + jnp.repeat(
              jnp.arange(n_cap * gb, dtype=jnp.int32), h))
      row_off += n_cap * gb
    else:
      pos_g = jnp.broadcast_to(gg[:, :, None, :], ids.shape + (wc,))
      grad_list.append(pos_g.reshape(-1, wc))
  cat = lambda xs: jnp.concatenate(xs) if len(xs) > 1 else xs[0]
  flat_ids, g_rows = cat(ids_list), cat(grad_list)
  g_idx = cat(gidx_list) if use_idx else None
  # serialise the per-group applies: without a data dependency XLA may
  # schedule every group's sort/gather/scatter pipeline concurrently,
  # keeping all their multi-hundred-MB compaction temporaries live at
  # once — on a chip already holding params + accumulator that tips
  # peak HBM over the edge (docs/perf_notes.md, train-step section).
  # Only the IDS pass the barrier: everything downstream (sort,
  # gathers, applies) depends on them, which orders the pipelines,
  # while the gradient stream stays fusible into its consumer (a
  # barriered flat_g materialises as a full lane-padded narrow temp
  # — 2 GiB at synthetic-small scale, round-4 memory audit)
  (flat_ids, fence) = jax.lax.optimization_barrier((flat_ids, fence))
  return _Stream(flat_ids, g_rows, rows_cap, g_idx), fence


def _merge_slices(stream: _Stream, width: int, needs_sq: bool,
                  sq_in_rows: bool, dcn_axis: str, num_slices: int,
                  hier_group=None, axis_name: Optional[str] = None):
  """Stage 2, where the mesh has more than one slice: the cross-slice
  update exchange — the DP-gradient step for the slice-REPLICATED table
  shards (each slice computed updates from its own sub-batch; every
  replica must apply them all, identically).  Streams pre-compact to
  unique rows per slice, bounding the DCN gather to the fused table's
  row count instead of the raw batch*hotness stream;
  per-occurrence-squares optimizers (``needs_sq``) ship the squares as
  their own additive channel (squares of pre-summed rows would be
  wrong).  After the gather every slice holds the identical combined
  stream, so the applies (and replicas) stay in sync.

  ``sq_in_rows``: a hot-cache stream, whose rows carry the squares as
  trailing payload columns — they segment-sum additively with the grads
  and split at the same column offsets after the gather.
  ``hier_group``: the group's hierarchical (dcn x ici) layout (design
  §20) where tables shard over the axis PRODUCT: the cross-slice leg is
  then an all_to_all of per-owner hier-row streams instead of the
  replicated all_gather — each deduplicated row's update crosses DCN
  once, to its one owner (slice, device) cell, and only that cell
  applies it, in the OWNER's hier-local row space (``[rows_cap_h, w]``
  shards, sentinel ``rows_cap_h``); the pre-compaction stays in flat
  fused space, exactly like the flat path.

  Returns the merged stream: each row at most once a slice
  (``max_seg``), squares (if any) summed.
  """
  rows_cap = stream.rows_cap
  # Pre-compaction capacity must be the GUARANTEED bound
  # (uniques + sentinel <= rows_cap + 2): a fraction/calibrated
  # cap could silently drop segments here, where no correction
  # wave runs (the wave guards only the post-gather apply).
  pcap = _guaranteed_cap(stream.ids.shape[0], rows_cap)
  ship_sq = needs_sq and not sq_in_rows
  uids_s, sum_g_s, sum_sq_s, _ = compact_segments(
      stream.ids, stream.rows, pcap, rows_cap, with_sq=ship_sq,
      g_index=stream.index)
  if hier_group is not None:
    # Hierarchical update exchange (design §20): each compacted
    # row maps through the static interval tables to its owner
    # (slice, hier row); ONE DCN all_to_all per group ships every
    # per-slice sum to its owner cell (same inner device index —
    # pure cross-slice traffic), with non-owned positions at the
    # hier sentinel so the apply drops them.  The receiver
    # flattens slice-major, reproducing the flat all_gather's
    # position order — so per-row segment sums add in the same
    # sequence and the applied updates stay bit-exact vs flat.
    hl = hier_group
    S = num_slices
    cap_h = hl.rows_cap_h
    me_d = jax.lax.axis_index(axis_name)
    cut_lo = jnp.asarray(hl.cut_lo)[me_d]
    cut_sl = jnp.asarray(hl.cut_slice)[me_d]
    cut_h = jnp.asarray(hl.cut_hier)[me_d]
    valid = (uids_s >= 0) & (uids_s < rows_cap)
    safe = jnp.clip(uids_s, 0, rows_cap - 1)
    k2 = jnp.clip(
        jnp.searchsorted(cut_lo, safe, side='right') - 1,
        0, cut_lo.shape[0] - 1)
    owner = cut_sl[k2]
    hrow = safe - cut_lo[k2] + cut_h[k2]
    dest = jax.lax.broadcasted_iota(jnp.int32,
                                    (S,) + uids_s.shape, 0)
    hids = jnp.where(valid[None] & (owner[None] == dest),
                     hrow[None], cap_h).astype(jnp.int32)
    packed = [
        jax.lax.bitcast_convert_type(hids, jnp.float32)[..., None],
        jnp.broadcast_to(sum_g_s[None], (S,) + sum_g_s.shape)
    ]
    if ship_sq:
      packed.append(
          jnp.broadcast_to(sum_sq_s[None], (S,) + sum_sq_s.shape))
    gathered = jax.lax.all_to_all(
        jnp.concatenate(packed, axis=2), dcn_axis, 0, 0)
    gathered = gathered.reshape(-1, gathered.shape[2])
    rows_cap = cap_h
  else:
    # ONE DCN collective per group: ids ride as a bitcast f32
    # column alongside the grad (and square) payload
    packed = [
        jax.lax.bitcast_convert_type(uids_s, jnp.float32)[:, None],
        sum_g_s
    ]
    if ship_sq:
      packed.append(sum_sq_s)
    gathered = jax.lax.all_gather(jnp.concatenate(packed, axis=1),
                                  dcn_axis, axis=0, tiled=True)
  return _Stream(
      jax.lax.bitcast_convert_type(gathered[:, 0], jnp.int32),
      gathered[:, 1:1 + width], rows_cap,
      squares=gathered[:, 1 + width:] if needs_sq else None,
      max_seg=num_slices)


def _split_square_columns(stream: _Stream, width: int) -> _Stream:
  """Stage 2 on one slice, for a hot-cache stream that carries squares:
  the additive squared-grad channel leaves the payload columns.  (No
  ``max_seg``: a row may come from every source device.)"""
  return stream._replace(rows=stream.rows[:, :width],
                         squares=stream.rows[:, width:])


def _group_operand(table, scale, state, stream: _Stream, fetch_g,
                   resident: int):
  """Stage 3, before the apply, for a quantized and/or cold-tier group
  (design §12): the table operand becomes the ``(payload, scale)`` pair
  (``scale`` None: not quantized) that ``_QuantizedTableOptimizer``
  updates, requantizing exactly the touched rows with a refreshed
  scale; a cold-tier group (``fetch_g``: its part of the batch's
  fetch, else None) concatenates the fetched tail rows of payload,
  scale and optimizer state behind the ``resident`` rows and remaps the
  stream's ids into that space, so that the SAME compact/apply runs.
  Returns ``(operand, state, stream)``."""
  if fetch_g is not None:
    with obs_trace.phase('apply/stream'):
      frows = fetch_g['rows'][0]
      cap_f = frows.shape[0]
      # remap tail ids into the concatenated [resident + cap_f] space:
      # resident ids pass through, fetched tail ids land at
      # resident + fetch position, everything else (sentinel; a tail id
      # the pre-pass missed, impossible by contract) drops at the
      # new sentinel resident + cap_f
      flat_ids = stream.ids
      pos = jnp.searchsorted(frows, flat_ids).astype(jnp.int32)
      safe_pos = jnp.minimum(pos, cap_f - 1)
      hit = ((flat_ids >= resident) & (flat_ids < stream.rows_cap)
             & (frows[safe_pos] == flat_ids))
      stream = stream._replace(
          ids=jnp.where(
              flat_ids < resident, flat_ids,
              jnp.where(hit, resident + safe_pos, resident + cap_f)),
          rows_cap=resident + cap_f)
    with obs_trace.phase('apply/read_rows'):
      table = jnp.concatenate([table, fetch_g['payload'][0]])
      if scale is not None:
        scale = jnp.concatenate([scale, fetch_g['scale'][0]])
      state = {
          k: jnp.concatenate([v, fetch_g['opt'][k][0]])
          for k, v in state.items()
      }
  return (table if scale is None else (table, scale)), state, stream


def _tier_writeback(table, scale, state, resident: int):
  """Stage 3, after the apply of a cold-tier group: the updated fetched
  rows leave as the group's WRITEBACK (the host stores them into the
  tier) and the operand is the ``resident`` rows again.  Returns
  ``(table, scale, state, writeback)``."""
  with obs_trace.phase('apply/write_rows'):
    wb = {'payload': table[resident:][None]}
    if scale is not None:
      wb['scale'] = scale[resident:][None]
    wb['opt'] = {k: v[resident:][None] for k, v in state.items()}
    table = table[:resident]
    if scale is not None:
      scale = scale[:resident]
    state = {k: v[:resident] for k, v in state.items()}
  return table, scale, state, wb


def _apply_group(optimizer, table, state, stream: _Stream, lr, group: str,
                 storage_pack: int = 1, cap_rows: Optional[int] = None,
                 n_chunks: int = 1, head_grads=(), adapted: bool = False):
  """Stage 4: one group's update, by the apply ``choose_apply`` names.
  Each kernel is called here and nowhere else in the group loop.  The
  chunked gradient-apply (``n_chunks``, design §11) is the XLA apply's:
  the segwalk kernel is a single-pass streaming apply and consumes the
  full stream, the tied apply is one dense step."""
  choice = choose_apply(
      optimizer, table, stream.rows_cap, stream.rows.shape[1],
      storage_pack=storage_pack, tied=bool(head_grads), adapted=adapted,
      summed_squares=stream.squares is not None, group=group,
      cap=_capacity(optimizer, stream.ids.shape[0], stream.rows_cap,
                    cap_rows))
  if choice.kernel == 'tied':
    return _tied_apply(optimizer, table, state, stream, lr, head_grads)
  if choice.kernel == 'segwalk':
    return _segwalk_apply(optimizer, table, state, stream, lr,
                          storage_pack=storage_pack)
  return _dedup_and_apply(optimizer, table, state, stream, lr,
                          cap_rows=cap_rows, storage_pack=storage_pack,
                          n_chunks=n_chunks)


def _apply_hot_group(optimizer, hot_op, state, hg, width: int, lr,
                     needs_sq: bool, needs_touch: bool, n_chunks: int):
  """Stage 5, after the group loop: ONE dense elementwise step on a hot
  group's replicated buffer, on the mesh-psummed gradient sums — the
  dense add that replaces K random-access scatter rows per hot id
  (design §10).  The grads arrived replicated (the backward psums
  them), so every replica applies identically and the buffers stay in
  sync.  ``hot_op``: the ``[K, w]`` buffer, or its quantized
  ``(payload, scale)`` pair.  ``hg``: ``[K, w (+ w) (+ 1)]``, the sums,
  the squares where ``needs_sq``, the occurrence count where
  ``needs_touch``."""
  hg = hg.astype(jnp.float32)
  sum_g = hg[:, :width]
  sum_sq = hg[:, width:2 * width] if needs_sq else None
  # trailing occurrence-count column (needs_touch optimizers:
  # lazy Adam's dense touched-row mask, design §11)
  cnt_off = 2 * width if needs_sq else width
  count = hg[:, cnt_off:cnt_off + 1] if needs_touch else None
  K = hg.shape[0]
  kch = effective_chunks(n_chunks, K)
  with obs_trace.phase('apply/update'):
    if kch == 1:
      return optimizer.apply_hot(hot_op, state, sum_g, sum_sq, lr,
                                 count=count)
    # chunked dense hot apply (design §11): apply_hot is
    # elementwise per row, so row-range chunks are bit-exact — and
    # chunk k's step can execute while chunk k+1's psummed
    # gradient slice is still in flight (the backward psums the
    # hot grads in the same row chunks).  Quantized buffers chunk
    # identically: the per-row requant is row-local.
    pieces, spieces = [], []
    for lo, hi in chunk_bounds(K, kch):
      hp, hs = optimizer.apply_hot(
          jax.tree.map(lambda x: x[lo:hi], hot_op),
          {kk: vv[lo:hi] for kk, vv in state.items()},
          sum_g[lo:hi],
          None if sum_sq is None else sum_sq[lo:hi], lr,
          count=None if count is None else count[lo:hi])
      pieces.append(hp)
      spieces.append(hs)
    hot_new = jax.tree.map(lambda *p: jnp.concatenate(p, axis=0), *pieces)
    hstate = ({} if not spieces[0] else {
        kk: jnp.concatenate([s[kk] for s in spieces], axis=0)
        for kk in spieces[0]
    })
  return hot_new, hstate


def _build_sparse_apply(dist: DistributedEmbedding, optimizer,
                        global_batch: int, hotness: tuple,
                        fetch_caps: tuple = (), tied: tuple = ()):
  """shard_map'd per-device sparse update over all fusion groups: a loop
  over the groups through the stages above, then the hot groups.

  ``tied``: ``((table id, (group, device, first row, rows)), ...)`` of
  the tables the head also reads (``_tied_layout``): the trailing args
  then carry one replicated ``[rows, width]`` head gradient per entry,
  and their groups update through ``_tied_apply``.

  Hot-cache layers (``dist.hot_enabled``): the per-subgroup streams
  arrive ALREADY deduplicated per (source device, slot) — the same
  compact/apply pipeline runs over far fewer rows — and the trailing
  args carry one replicated ``[hot_rows_cap, w]`` (``2w`` with
  per-occurrence squares) gradient buffer per hot group, applied as a
  DENSE elementwise optimizer step (``apply_hot``) with no scatter.
  Quantized and cold-tier groups (design §12): ``_group_operand``.
  """
  key = ('sparse_apply', optimizer, global_batch, hotness, fetch_caps,
         tied)
  if key in dist._fn_cache:
    return dist._fn_cache[key]
  subs = dist._subgroups(hotness)
  ax = dist.axis_name
  hot_gis = list(getattr(dist.plan, 'hot_groups', []))
  cached = bool(getattr(dist, 'hot_enabled', False))
  needs_sq = bool(getattr(optimizer, 'needs_sq', True))
  needs_touch = cached and bool(getattr(optimizer, 'needs_touch', False))
  n_chunks = getattr(dist.plan, 'overlap_chunks', 1)
  quant = getattr(dist, 'quant', None)
  tiered = set(getattr(dist.plan, 'cold_tier_groups', []))
  opt_q = (_QuantizedTableOptimizer(optimizer, quant)
           if quant is not None else optimizer)
  hier = dist.hier if getattr(dist, 'dcn_sharding', False) else None
  caps = getattr(optimizer, 'capacity_rows', None) or ()

  def local_fn(params, opt_state, lr, fetch, *res_and_g):
    residuals = res_and_g[:len(subs)]
    gs = res_and_g[len(subs):2 * len(subs)]
    hot_gs = res_and_g[2 * len(subs):2 * len(subs) + len(hot_gis)]
    tied_gs = res_and_g[2 * len(subs) + len(hot_gis):]
    new_params = dict(params)
    new_state = dict(opt_state)
    writeback = {}
    fence = lr  # serialisation token threaded through the group applies
    for gi, group in enumerate(dist.plan.groups):
      slots = [(si, group.combiner == 'mean' and not sub.mean_row_sliced
                and not cached)
               for si, sub in enumerate(subs) if sub.gi == gi]
      if not slots:
        continue
      key = f'group_{gi}'
      skey = f'scale_group_{gi}'
      with obs_trace.phase_group(f'g{gi}'):
        with obs_trace.phase('apply/stream'):
          stream, fence = _group_stream(slots, residuals, gs,
                                        group.rows_cap, fence)
          state_g = {k: v[0] for k, v in opt_state[key].items()}
          if dist.num_slices > 1:
            stream = _merge_slices(
                stream, group.width, needs_sq, cached, dist.dcn_axis,
                dist.num_slices,
                hier_group=None if hier is None else hier.groups[gi],
                axis_name=ax)
          elif cached and needs_sq:
            stream = _split_square_columns(stream, group.width)
        head_grads = [
            (first, rows, tied_gs[k], jax.lax.axis_index(ax) == dev)
            for k, (_, (tgi, dev, first, rows)) in enumerate(tied)
            if tgi == gi]
        table, scale = params[key][0], None
        adapted = quant is not None or gi in tiered
        if adapted:
          if quant is not None:
            scale = params[skey][0]
          table, state_g, stream = _group_operand(
              table, scale, state_g, stream,
              fetch[gi] if gi in tiered else None, group.device_rows)
        table, state_g = _apply_group(
            opt_q, table, state_g, stream, lr, key,
            storage_pack=getattr(group, 'storage_pack', 1),
            cap_rows=caps[gi] if gi < len(caps) else None,
            n_chunks=n_chunks, head_grads=head_grads, adapted=adapted)
        if quant is not None:
          table, scale = table
        if gi in tiered:
          table, scale, state_g, writeback[gi] = _tier_writeback(
              table, scale, state_g, group.device_rows)
        # the leading device axis is a reshape, but where a fusion ends in
        # it XLA names the fusion after it: a tied group's whole-shard
        # optimizer step would show under no phase
        with (obs_trace.phase('apply/tied') if head_grads
              else contextlib.nullcontext()):
          new_params[key] = table[None]
          if scale is not None:
            new_params[skey] = scale[None]
          new_state[key] = {k: v[None] for k, v in state_g.items()}
        fence = table[0, 0]

    for k_idx, gi in enumerate(hot_gis):
      hk, hsk = f'hot_group_{gi}', f'hot_scale_group_{gi}'
      hot_op = ((params[hk], params[hsk]) if quant is not None
                else params[hk])
      with obs_trace.phase_group(f'g{gi}'):
        hot_new, new_state[hk] = _apply_hot_group(
            opt_q, hot_op, opt_state[hk], hot_gs[k_idx],
            dist.plan.groups[gi].width, lr, needs_sq, needs_touch,
            n_chunks)
      if quant is not None:
        new_params[hk], new_params[hsk] = hot_new
      else:
        new_params[hk] = hot_new
    return new_params, new_state, writeback

  n_groups = len(dist.plan.groups)
  # hier: table (and scale / optimizer-state) shards live on the
  # (dcn, data) axis PRODUCT (design §20)
  gax = (dist.dcn_axis, ax) if hier is not None else ax
  param_specs = {f'group_{gi}': P(gax, None, None) for gi in range(n_groups)}
  if quant is not None:
    for gi in range(n_groups):
      param_specs[f'scale_group_{gi}'] = P(gax, None, None)
  for gi in hot_gis:
    param_specs[f'hot_group_{gi}'] = P(None, None)
    if quant is not None:
      param_specs[f'hot_scale_group_{gi}'] = P(None, None)

  def _state_spec(opt_state):
    # sharded group leaves are [D, ...] on axis 0; hot-cache leaves are
    # replicated [hot_rows_cap, w] buffers
    out = {}
    for k, leaves in opt_state.items():
      if k.startswith('hot_group_'):
        out[k] = jax.tree.map(
            lambda x: P(*([None] * x.ndim)), leaves)
      else:
        out[k] = jax.tree.map(
            lambda x: P(gax, *([None] * (x.ndim - 1))), leaves)
    return out

  def _fetch_spec(fetch):
    # the cold-tier fetch buffers are per-device data on axis 0
    return jax.tree.map(lambda x: P(ax, *([None] * (x.ndim - 1))),
                        fetch)

  def apply(params, opt_state, lr, fetch, *res_and_g):
    # every sharded optimizer-state leaf is [D, ...] on axis 0 (and,
    # on a two-axis mesh, replicated over the slice axis)
    state_spec = _state_spec(opt_state)
    wb_spec = {
        gi: {
            'payload': P(ax, None, None),
            **({'scale': P(ax, None, None)} if quant is not None else {}),
            'opt': {k: P(ax, None, None)
                    for k in opt_state.get(f'group_{gi}', {})},
        }
        for gi in tiered
    }
    fn = jax.shard_map(
        local_fn,
        mesh=dist.mesh,
        in_specs=(param_specs, state_spec, P(), _fetch_spec(fetch)) +
        tuple(
            P(ax, None, dist.dcn_axis, None)
            for _ in range(2 * len(subs))) + tuple(
                P(None, None) for _ in range(len(hot_gis) + len(tied))),
        out_specs=(param_specs, state_spec, wb_spec),
        check_vma=False)
    return fn(params, opt_state, lr, fetch, *res_and_g)

  dist._fn_cache[key] = apply
  return apply


def sparse_apply_updates(dist: DistributedEmbedding, optimizer, params,
                         opt_state, residuals, gsubs, lr,
                         global_batch: int, hotness: tuple,
                         hot_grads=None, cold_fetch=None, head_grads=None):
  """Apply one sparse optimizer step to the embedding params.

  ``head_grads``: ``{table id: [rows, width]}``, the head's own dense
  gradient of each table it reads (``make_hybrid_train_step(...,
  head_reads_tables=)``); it joins the lookups' row sums before the
  table's one update (``_tied_apply``).

  ``hot_grads``: for hot-cache layers, the ``{group_index: [K, w]}``
  replicated hot-row gradient buffers from ``backward_to_mp``.

  ``cold_fetch``: for cold-tier layers (design §12), the batch's fetch
  pytree (``DistributedEmbedding.build_cold_fetch``) — the SAME buffers
  the forward consumed.  The return value then gains a third element:
  the per-group writeback (updated tail payload/scale/optimizer rows)
  the caller must store with ``dist.cold_write_back``.
  """
  from distributed_embeddings_tpu.parallel.dist_embedding import (
      _fetch_caps_sig)
  tier_on = bool(getattr(dist.plan, 'cold_tier_groups', []))
  if tier_on and cold_fetch is None:
    raise ValueError(
        'sparse_apply_updates on a cold-tier layer requires '
        'cold_fetch= (the batch fetch the forward consumed): the tier '
        'rows it updates live in those buffers (docs/design.md §12)')
  fetch = getattr(cold_fetch, 'device', cold_fetch) if cold_fetch else {}
  tied = ()
  if head_grads:
    tied = tuple(sorted(
        _tied_layout(dist, sorted(head_grads), optimizer).items()))
  fn = _build_sparse_apply(dist, optimizer, global_batch, hotness,
                           fetch_caps=_fetch_caps_sig(fetch), tied=tied)
  hot_list = []
  if hot_grads:
    hot_list = [hot_grads[gi] for gi in dist.plan.hot_groups]
  elif dist.plan.hot_groups:
    raise ValueError(
        'sparse_apply_updates on a hot-cache layer requires hot_grads= '
        '(the {group_index: [K, w]} replicated hot-row gradient buffers '
        'that backward_to_mp returns alongside gsubs)')
  new_params, new_state, writeback = fn(
      params, opt_state, jnp.asarray(lr, jnp.float32), fetch,
      *residuals, *gsubs, *hot_list, *(head_grads[tid] for tid, _ in tied))
  if tier_on:
    return new_params, new_state, writeback
  return new_params, new_state


def make_hybrid_train_step(dist: DistributedEmbedding,
                           head_loss_fn: Callable,
                           dense_optimizer,
                           emb_optimizer,
                           lr_schedule: Optional[Callable] = None,
                           donate: bool = True,
                           jit: bool = True,
                           head_reads_tables: Sequence[int] = ()) -> Callable:
  """Build the full hybrid-parallel sparse train step.

  The TPU-native analog of the reference training loop
  (`examples/dlrm/main.py:201-210` + ``DistributedGradientTape``,
  SURVEY.md §3.2): dense (data-parallel) params update through an optax
  transformation on autodiff grads; embedding tables update through
  row-wise sparse scatters, never materialising a table-shaped gradient.

  Args:
    dist: the model's ``DistributedEmbedding``.
    head_loss_fn: ``(dense_params, emb_outs: tuple, batch) -> scalar`` —
      everything downstream of the embeddings, returning the *global mean*
      loss.  ``dense_params`` is the params dict without its
      ``'embedding'`` entry.
    dense_optimizer: optax ``GradientTransformation`` for dense params.
    emb_optimizer: ``SparseSGD`` / ``SparseAdagrad`` / ``SparseAdam``.
    lr_schedule: optional ``step -> lr`` for the *embedding* optimizer
      (dense schedules live inside the optax chain); defaults to the
      optimizer's fixed ``learning_rate``.
    donate: donate state buffers (in-place update of the tables).
    head_reads_tables: ids of tables the head also multiplies by (a tied
      vocabulary, docs/design.md §25).  ``head_loss_fn`` is then called
      as ``(dense_params, emb_outs, batch, tables)`` with ``tables =
      {table id: [rows, width]}``; the head's dense gradient of each
      joins the lookups' row sums and the table takes one update over
      every row.  On a mesh the owner's shard reaches the data-parallel
      head, and the head's gradient returns, by the mesh's collectives.
      With the default the step is what it was.

  Returns:
    ``step(state, cats, batch) -> (state, loss)`` (jitted).  ``cats`` is
    the embedding input list; ``batch`` is passed through to
    ``head_loss_fn``.
  """

  tier_on = bool(getattr(dist.plan, 'cold_tier_groups', []))
  if tier_on:
    # cold-tier refusal + host-state setup (design §12): the optimizer
    # must expose its per-tail-row state leaves so the tier can carry
    # them (SparseAdam has none and refuses in its init)
    specs_fn = getattr(emb_optimizer, 'tier_leaf_specs', None)
    if specs_fn is None:
      raise ValueError(
          f'{type(emb_optimizer).__name__} does not support cold-tier '
          'layers (no tier_leaf_specs): train tiered tables with '
          'SparseSGD or SparseAdagrad (docs/design.md §12)')
    for leaf, (ldtype, fill) in specs_fn().items():
      dist.cold_tier.ensure_opt(leaf, fill, ldtype)
  tied = (_tied_layout(dist, tuple(head_reads_tables), emb_optimizer)
          if head_reads_tables else {})

  def step(state: TrainState, cats, batch, cold_fetch=None):
    emb_params = state.params['embedding']
    dense_params = {
        k: v for k, v in state.params.items() if k != 'embedding'
    }
    dense_opt_state, emb_opt_state = state.opt_state

    hot_on = bool(getattr(dist, 'hot_enabled', False))
    if hot_on:
      # with_routing: carry the forward's sort-unique inverse
      # permutations (routing products, design §21) so the backward
      # reuses them instead of re-sorting
      emb_outs, residuals, routing, (global_batch, hotness) = (
          dist.forward_with_residuals(emb_params, cats,
                                      cold_fetch=cold_fetch,
                                      with_routing=True))
    else:
      emb_outs, residuals, (global_batch, hotness) = (
          dist.forward_with_residuals(emb_params, cats,
                                      cold_fetch=cold_fetch))

    # the scope opens inside the function vjp differentiates, so the
    # head's backward carries it too (``transpose(jvp(head))``)
    # the owner's rows of each table the head reads, as one array: under
    # the step's jit a static index into the sharded leaf, which the
    # partitioner turns into the mesh's collective.  None read: the head
    # is called, and differentiated, without a fourth argument.
    tables = [{tid: emb_params[f'group_{gi}'][dev, first:first + rows]
               for tid, (gi, dev, first, rows) in tied.items()}] if tied else []
    loss, pull = jax.vjp(
        obs_trace.phase('head')(
            lambda dp, eo, *tb: head_loss_fn(dp, eo, batch, *tb)),
        dense_params, tuple(emb_outs), *tables)
    d_dense, d_emb, *head_grads = pull(jnp.ones((), loss.dtype))
    head_grads = head_grads[0] if tied else None

    with obs_trace.phase('dense_update'):
      updates, dense_opt_state = dense_optimizer.update(
          d_dense, dense_opt_state, dense_params)
      new_dense = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                               dense_params, updates)

    if hot_on:
      # hot-cache layers: the backward consumes the forward's routing
      # products (no re-sort), divides mean cotangents internally, and
      # returns the replicated hot-row grad buffers alongside the
      # deduplicated per-subgroup streams
      cats_dense = [
          x.to_padded_dense(dist._ragged_cap(x))
          if isinstance(x, RaggedBatch) else x for x in cats
      ]
      gsubs, hot_grads = dist.backward_to_mp(
          list(d_emb), global_batch, hotness, cats=cats_dense,
          with_sq=bool(getattr(emb_optimizer, 'needs_sq', False)),
          with_touch=bool(getattr(emb_optimizer, 'needs_touch', False)),
          routing=routing)
      lr = (lr_schedule(state.step) if lr_schedule is not None
            else emb_optimizer.learning_rate)
      if tier_on:
        new_emb, emb_opt_state, writeback = sparse_apply_updates(
            dist, emb_optimizer, emb_params, emb_opt_state, residuals,
            gsubs, lr, global_batch, hotness, hot_grads=hot_grads,
            cold_fetch=cold_fetch)
        params = {**new_dense, 'embedding': new_emb}
        return TrainState(params, (dense_opt_state, emb_opt_state),
                          state.step + 1), loss, writeback
      new_emb, emb_opt_state = sparse_apply_updates(
          dist, emb_optimizer, emb_params, emb_opt_state, residuals,
          gsubs, lr, global_batch, hotness, hot_grads=hot_grads)
      params = {**new_dense, 'embedding': new_emb}
      return TrainState(params, (dense_opt_state, emb_opt_state),
                        state.step + 1), loss

    # row-sliced MEAN inputs: the forward divided the owner-side partial
    # sums by the true per-sample id count; the manual transpose must
    # divide the cotangent the same way (computable here, where the raw
    # ids are available - the shard-local apply cannot know the global
    # count)
    if dist.dp_input:
      cat_pos = {i: i for i in range(len(dist.plan.input_table_map))}
    else:
      # mp inputs arrive in worker order; an input (row-sliced) may appear
      # on several devices with identical ids - any occurrence serves
      cat_pos = {}
      flat = [i for dev in dist.plan.input_ids_list for i in dev]
      for pos, i in enumerate(flat):
        cat_pos.setdefault(i, pos)
    d_emb = list(d_emb)
    for i, tid in enumerate(dist.plan.input_table_map):
      if (dist.plan.row_sliced[tid]
          and dist.table_configs[tid].combiner == 'mean'):
        x = cats[cat_pos[i]]
        if isinstance(x, RaggedBatch):
          x = x.to_padded_dense(dist._ragged_cap(x))
        d_emb[i] = d_emb[i] / _valid_count(
            jnp.asarray(x))[:, None].astype(d_emb[i].dtype)

    gsubs = dist.backward_to_mp(d_emb, global_batch, hotness)
    lr = (lr_schedule(state.step) if lr_schedule is not None
          else emb_optimizer.learning_rate)
    new_emb, emb_opt_state = sparse_apply_updates(
        dist, emb_optimizer, emb_params, emb_opt_state, residuals, gsubs,
        lr, global_batch, hotness, head_grads=head_grads)

    params = {**new_dense, 'embedding': new_emb}
    return TrainState(params, (dense_opt_state, emb_opt_state),
                      state.step + 1), loss

  if not jit:
    return step  # composable form (e.g. as a lax.scan body)
  jitted = jax.jit(step, donate_argnums=(0,) if donate else ())

  def run(state, cats, batch, cold_fetch=None):
    # densify RaggedBatch inputs HERE, outside the jit boundary, where
    # the true max row length is readable — inside jit the lengths are
    # tracers and a batch without a static hot_cap raises (see
    # DistributedEmbedding._ragged_cap)
    cats = [
        x.to_padded_dense(dist._ragged_cap(x))
        if isinstance(x, RaggedBatch) else x for x in cats
    ]
    if not tier_on:
      return jitted(state, cats, batch)
    # cold tier (design §12): the host pre-pass runs OUTSIDE the jit
    # boundary (it reads id values and the host tier), the fetch rides
    # into the step as data, and the step's writeback output lands
    # back in the tier before the loss returns.  ``cold_fetch`` lets a
    # pipeline (coldtier.ColdFetchPipeline) hand in a prefetched one.
    fetch = (cold_fetch if cold_fetch is not None
             else dist.build_cold_fetch(cats))
    state, loss, writeback = jitted(state, cats, batch, fetch.device)
    dist.cold_write_back(fetch, writeback)
    return state, loss

  # introspection surface for the IR-analysis tier (analysis/graphlint,
  # design §18): the raw jitted step (trace/lower/compile without
  # executing) and its donation contract — every state leaf must come
  # back input-output aliased in the compiled executable
  run.jitted = jitted
  run.donate_argnums = (0,) if donate else ()
  return run


def run_pipelined(step, state, feed, batch_fn,
                  on_step: Optional[Callable] = None):
  """Drive a hybrid train step over a pipelined host feed
  (``parallel/csr_feed.CsrFeed``): while the device executes batch N,
  the feed's worker threads build batch N+1's padded static-CSR
  buffers — the host-provisioning overlap of docs/design.md §8.

  Each iteration synchronises on the step's loss: that blocking window
  IS the device time the next batch's build hides behind, and it makes
  the feed's ``stats()['overlap_pct']`` a direct measurement (the
  consumer's blocked time in ``__next__`` is exactly the build time the
  device did NOT hide).  The first batch's build has no prior step to
  hide behind, so the feed's stats reset after it — the reported
  overlap is steady-state.

  Args:
    step: the ``make_hybrid_train_step`` callable.
    state: initial ``TrainState``.
    feed: a ``CsrFeed`` (closed on exit, even on error).
    batch_fn: ``fed -> (cats, batch)`` — the step's inputs from a
      ``FedBatch`` (its ``item`` is the source item; its ``csrs`` are
      the hardware feed buffers).
    on_step: optional ``(i, fed, loss) -> None`` observer (loss is
      already synchronised).

  Returns:
    ``(state, losses, feed_stats)`` — ``feed_stats`` is
    ``CsrFeed.stats()`` at exit (steady-state overlap accounting).
  """
  losses = []
  with feed:
    for i, fed in enumerate(feed):
      cats, batch = batch_fn(fed)
      state, loss = step(state, cats, batch)
      losses.append(float(loss))  # sync: the window the next build hides in
      if i == 0:
        feed.reset_stats()
      if on_step is not None:
        on_step(i, fed, loss)
    stats = feed.stats()
  return state, losses, stats


def _calibration_mirror(dist: DistributedEmbedding, cpus):
  """A CPU flat-mesh twin of ``dist``'s plan plus zero-valued params.

  The plan is deterministic in (configs, world_size, strategy,
  thresholds, input map), so the mirror routes ids identically to the
  real mesh — including for two-axis dists, where the flat mirror over
  the INNER world size sees the full batch exactly like the post-gather
  union stream the apply consumes.  Parameter VALUES don't affect the
  routing, so zeros suffice.
  """
  import numpy as np
  from distributed_embeddings_tpu.parallel.mesh import create_mesh
  mirror = DistributedEmbedding(
      dist.table_configs,
      strategy=dist.plan.strategy,
      column_slice_threshold=dist.plan.column_slice_threshold,
      row_slice=dist.plan.row_slice_threshold,
      dp_input=dist.dp_input,
      input_table_map=dist.plan.input_table_map,
      mesh=create_mesh(cpus[:dist.world_size], axis_name=dist.axis_name),
      axis_name=dist.axis_name,
      param_dtype=dist.param_dtype,
      compute_dtype=dist.compute_dtype,
      packed_storage=dist.plan.packed_storage,
      # mod-sharded (SparseCore) plans route ids through residue
      # windows; the mirror must reproduce them or every calibrated
      # capacity would describe the wrong id->device map
      mod_sharding=dist.plan.mod_sharding,
      num_sc=dist.plan.num_sc,
      # hot-cache plans strip hot ids and dedup the cold exchange; the
      # mirror must reproduce BOTH or the calibrated capacities would
      # describe the un-cached (far larger) streams
      hot_cache=dist.plan.hot_sets or None,
      # chunking never changes the residual streams (bit-exact), but
      # the mirror's plan must carry the same geometry so its physical
      # fingerprint — and the per-chunk buffer sizes the calibrated
      # capacities get split into — describe the real program
      overlap_chunks=dist.plan.overlap_chunks)
  # the mirror's params must match ITS plan's physical layout (packed
  # [param_rows, param_width] for storage-packed groups)
  zeros = {
      f'group_{gi}': np.zeros((dist.world_size, g.param_rows,
                               g.param_width), dist.param_dtype)
      for gi, g in enumerate(mirror.plan.groups)
  }
  for gi in mirror.plan.hot_groups:
    g = mirror.plan.groups[gi]
    zeros[f'hot_group_{gi}'] = np.zeros((g.hot_rows_cap, g.width),
                                        dist.param_dtype)
  return mirror, zeros


def calibrate_capacity_rows(dist: DistributedEmbedding, cats,
                            margin: float = 1.3,
                            params=None,
                            prefer_cpu: bool = True) -> Tuple[int, ...]:
  """Measure per-group unique-update-row counts on a sample batch and
  return calibrated ``capacity_rows`` for the sparse optimizers.

  The compaction capacity sets the STATIC size of every per-group
  scatter/gather in the apply (docs/perf_notes.md: scatter cost is
  linear in static rows, dropped or not), so sizing it from the id
  distribution instead of the worst case shrinks the apply
  proportionally — e.g. synthetic-tiny's big fused group carries 859k
  uniques per 65536-batch against a 1.44M default cap.  Power-law id
  streams are stationary, so one batch plus ``margin`` headroom is
  representative; if a later batch still overflows, the overflow
  correction wave applies the dropped segments (slower, never wrong).

  With ``prefer_cpu`` (the default) and a non-CPU mesh, the measurement
  forward runs on a CPU *mirror* of the plan (same table configs, same
  deterministic plan, zero-valued params — the id routing doesn't depend
  on parameter values): a throwaway full-size forward costs about a
  minute to compile for the chip (72 s AOT for synthetic-tiny at batch
  65536, ROADMAP S6), seconds for the CPU.  Falls back to the active
  backend when fewer CPU devices than ``world_size`` exist — which is
  what a host with several chips and the default single CPU device
  gets.

  The apply runs per device under ``shard_map`` with ONE static capacity
  per group, so the calibration takes the MAX unique count across the
  device axis (each device routes a different id subset to its shard).

  Args:
    dist: the (built) ``DistributedEmbedding``.
    cats: a representative embedding input list, as passed to
      ``forward_with_residuals``.
    margin: multiplicative headroom over the measured unique count.
    params: optional embedding params to reuse (skips a throwaway
      ``dist.init`` — the id streams don't depend on parameter values,
      but the forward needs arrays of the right shape).
    prefer_cpu: run the measurement on a CPU plan mirror when the mesh
      is not CPU (see above).

  Returns:
    One capacity (int rows) per fusion group, ordered by group index —
    pass as ``SparseAdagrad(capacity_rows=...)`` etc.
  """
  import numpy as np
  if (prefer_cpu
      and dist.mesh.devices.ravel()[0].platform != 'cpu'):
    try:
      cpus = jax.devices('cpu')
    except RuntimeError:
      # platform-restricted process (e.g. JAX_PLATFORMS=tpu): no CPU
      # backend to mirror onto — measure on the active backend
      cpus = []
    if len(cpus) < dist.world_size:
      _LOG.warning(
          'calibrate_capacity_rows: %d CPU device(s) < world_size %d, '
          'measuring on the %s backend instead (expect a throwaway '
          'compile).  Set XLA_FLAGS=--xla_force_host_platform_device_'
          'count=%d before JAX initialises to calibrate on CPU.',
          len(cpus), dist.world_size,
          dist.mesh.devices.ravel()[0].platform, dist.world_size)
    else:
      mirror, zeros = _calibration_mirror(dist, cpus)

      def to_host(x):
        if isinstance(x, RaggedBatch):
          return RaggedBatch(np.asarray(x.values), np.asarray(x.row_splits),
                             hot_cap=x.hot_cap)
        return np.asarray(x)

      return calibrate_capacity_rows(mirror, [to_host(x) for x in cats],
                                     margin=margin, params=zeros,
                                     prefer_cpu=False)
  if params is None:
    params = dist.init(0)
  _, residuals, (_, hotness) = dist.forward_with_residuals(params, cats)
  subs = dist._subgroups(hotness)
  per_group = {}
  for si, sub in enumerate(subs):
    ids = np.asarray(residuals[si])        # [D, n_cap, GB, h]
    per_group.setdefault(sub.gi, []).append(ids.reshape(ids.shape[0], -1))
  caps = []
  for gi, group in enumerate(dist.plan.groups):
    streams = per_group.get(gi)
    if not streams:
      caps.append(8)
      continue
    per_dev = np.concatenate(streams, axis=1)  # [D, total_stream]
    uniq = max(
        np.unique(row[row < group.rows_cap]).size for row in per_dev)
    caps.append(max(8, int(uniq * margin)))
  return tuple(caps)


def init_hybrid_train_state(dist: DistributedEmbedding, params,
                            dense_optimizer, emb_optimizer) -> TrainState:
  """Initial ``TrainState`` for ``make_hybrid_train_step``.

  The dense (data-parallel) leaves are committed REPLICATED on the
  layer's mesh: that is the sharding the jitted step returns them
  with, so the first call's input signature equals every later call's
  and the step compiles once.  Left as the uncommitted single-device
  arrays ``model.init`` / ``optimizer.init`` produce, the second call
  sees different input shardings and pays a second full compile
  (measured: one extra backend compile on call 2, on one device and on
  eight)."""
  replicated = NamedSharding(dist.mesh, P())
  dense_params = jax.device_put(
      {k: v for k, v in params.items() if k != 'embedding'}, replicated)
  return TrainState(
      params={**dense_params, 'embedding': params['embedding']},
      opt_state=(jax.device_put(dense_optimizer.init(dense_params),
                                replicated),
                 emb_optimizer.init(dist, params['embedding'])),
      step=jax.device_put(jnp.zeros((), jnp.int32), replicated))
