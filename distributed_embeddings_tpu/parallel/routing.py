"""Shared routing kernels of the lookup pipeline (docs/design.md §21).

Every exchange phase of the plan-driven lookup pipeline — dp→mp id
routing, hot/cold dedup, hierarchical cross-slice fetch, the sparse
backward's dedup-gradient leg — runs on the same four primitives:

- ``gather_slots``          canonical ``[D, n_cap, ...]`` slot buffers
                            as one static gather
- ``route_ids``             raw slot ids → fused-table row space
                            (clip, window, stride, sentinel)
- ``unique_with_inverse``   per-row sort-unique with inverse positions
                            (the dedup of every exchange leg)
- ``dense_segment_sum``     sorted segment totals scattered once per
                            segment (the dedup-gradient reduction)

They used to live as private helpers of ``dist_embedding.py`` and were
re-derived at each call site of the hot forward (1937), the
hierarchical lookup/cold-gather (2222/2251) and the hot backward
(2325); this module is the one definition all of them — and the
backward's residual-reuse path, which consumes the forward's products
instead of re-sorting — now share.  ``dist_embedding`` re-exports them
under the historical underscore names, so existing imports keep
working.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp


def gather_slots(n_dev: int, n_slots: int, key_of, value_of) -> jax.Array:
  """Assemble a ``[n_dev, n_slots, ...]`` canonical slot buffer as ONE
  static gather: ``key_of(dev, slot)`` names each slot's content
  (hashable, Python-time), distinct keys are traced once via
  ``value_of(key)``, and every (device, slot) position selects from the
  stacked distinct values by a Python-time index table.

  The previous per-slot ``jnp.stack`` emitted O(n_dev * n_slots) traced
  ops per subgroup — the bulk of the "very large traced programs" behind
  the 50-634 s compiles (VERDICT round 3 weak 5); this form emits
  O(distinct keys) ops and one gather, with bit-identical results.
  """
  parts, pos = [], {}
  sel = np.empty((n_dev, n_slots), np.int32)
  for dev in range(n_dev):
    for s in range(n_slots):
      k = key_of(dev, s)
      if k not in pos:
        pos[k] = len(parts)
        parts.append(value_of(k))
      sel[dev, s] = pos[k]
  return jnp.stack(parts)[jnp.asarray(sel)]


def valid_count(ids: jax.Array) -> jax.Array:
  """Count of valid (non-``-1``-padding) ids over the trailing hot axis,
  clamped >= 1 — the mean-combiner denominator (out-of-vocab ids count:
  they clip to the last row and ARE looked up, matching
  ``_fused_lookup``'s mask).  Works on ``[..., h]`` or 1-D ids."""
  ids = ids[:, None] if ids.ndim == 1 else ids
  return jnp.maximum(jnp.sum(ids >= 0, axis=-1), 1).astype(jnp.float32)


def route_ids(ids: jax.Array, offsets: jax.Array, vocab: jax.Array,
              rows_cap: int,
              row_lo: Optional[jax.Array] = None,
              row_hi: Optional[jax.Array] = None,
              row_stride: Optional[jax.Array] = None) -> jax.Array:
  """Map raw slot ids into fused-table row space.

  ``ids``: [n_cap, GB, h] with -1 sentinel padding; ``offsets``/``vocab``:
  [n_cap] per-slot fused row offsets and FULL vocabulary sizes.  Ids are
  clipped inside the slot's own table so bad ids can't read a neighbouring
  fused table's rows; padding positions map to ``rows_cap`` (one past the
  fused table), which both the lookup and the sparse scatter drop.

  ``row_lo``/``row_hi`` give each slot's resident row window (row-sliced
  tables: the shard serves only ids in ``[row_lo, row_hi)``; ids owned by
  another shard drop to the sentinel, so shard partial outputs sum to the
  whole).  Clipping runs FIRST against the full vocabulary, so an
  out-of-vocab id lands on the last row and is served by exactly the tail
  shard — identical clip semantics to the unsliced table.  Full tables pass
  ``row_lo=0, row_hi=vocab`` (or None), making the window check a no-op.

  ``row_stride`` (mod-sharded plans, docs/design.md §8): the slot serves
  the residue class ``range(row_lo, row_hi, stride)`` — ids congruent to
  ``row_lo`` modulo ``stride`` — stored densely at local row
  ``(id - row_lo) // stride``.  ``None`` (all slots stride 1) keeps the
  contiguous-window arithmetic with no extra per-id ops.
  """
  mask = ids >= 0
  clipped = jnp.clip(ids, 0, vocab[:, None, None] - 1)
  if row_lo is not None:
    lo = row_lo[:, None, None]
    mask = mask & (clipped >= lo) & (clipped < row_hi[:, None, None])
    clipped = clipped - lo
    if row_stride is not None:
      st = row_stride[:, None, None]
      mask = mask & (clipped % st == 0)
      clipped = clipped // st
  return jnp.where(mask, clipped + offsets[:, None, None], rows_cap)


def sort_with_order(ids: jax.Array, *riders: jax.Array):
  """Stable ascending sort of 1-D ``ids`` that keeps what it ordered:
  ``(ids[order], order, *(r[order] for r in riders))`` with ``order =
  jnp.argsort(ids)``, from the one multi-operand sort ``argsort`` lowers
  to before it drops the sorted keys.  On v5e a two-operand int32 sort
  is 1.4 ns a key and a third operand 0.5 ns more, where fetching
  ``x[order]`` afterwards is 7 ns a row (PERF.md, PR 28)."""
  iota = jnp.arange(ids.shape[0], dtype=jnp.int32)
  return jax.lax.sort((ids, iota) + riders, num_keys=1, is_stable=True)


def _cumulative0(x: jax.Array, reducer, identity) -> jax.Array:
  """The running ``reducer`` of ``x`` along axis 0 as JAX's own lowering
  of a cumulative primitive spells it (``cumred_reduce_window_impl``)."""
  n = x.shape[0]
  if n == 0:
    return x
  rest = x.ndim - 1
  return jax.lax.reduce_window(
      x, identity, reducer, (n,) + (1,) * rest, (1,) * x.ndim,
      ((n - 1, 0),) + ((0, 0),) * rest)


def cumsum0(x: jax.Array) -> jax.Array:
  """``jnp.cumsum(x, axis=0)`` as ONE ``lax.reduce_window`` bound where
  it is called, so that the op carries the caller's phase
  (``obs.trace.phase``) into the device trace.  ``jnp.cumsum`` is not
  used in ``parallel/``: JAX lowers the ``cumsum`` primitive through one
  private function that every call site of a module shares (``@cumsum``),
  and the ops inside it keep no scope, so a trace books them to
  ``unscoped`` whatever phase asked for them (PERF.md section 3).  This
  is that function's body: the same window ``(n, 1, ..)``, strides and
  padding ``(n - 1, 0)``, the same compiled op.  What must stay true:
  the initial value is a NumPy scalar, so JAX binds the monoid primitive
  and the op's name ends in ``reduce_window_sum``, the leaf trace
  reductions class as ``cumsum`` (a ``jnp`` array binds the generic
  ``reduce_window``, which they would class ``other``).  ``x`` is
  int32 or float32; nothing differentiates through it."""
  return _cumulative0(x, jax.lax.add, np.array(0, x.dtype))


def cummax0(x: jax.Array) -> jax.Array:
  """``lax.cummax(x, axis=0)`` bound where it is called: ``cumsum0``'s
  rule for the running maximum (leaf ``reduce_window_max``)."""
  identity = (-np.inf if np.issubdtype(x.dtype, np.floating)
              else np.iinfo(x.dtype).min)
  return _cumulative0(x, jax.lax.max, np.array(identity, x.dtype))


def unique_with_inverse(ids: jax.Array, cap: int):
  """Per-row sort-unique with inverse positions (the cold-id dedup of
  the hot-cache exchange, docs/design.md §10).

  ``ids``: ``[R, n]`` int32, ``< 0`` marks dropped (padding/hot)
  positions.  Returns ``(uniq, inv)``: ``uniq`` ``[R, cap]`` the
  distinct non-negative ids ascending with ``-1`` padding; ``inv``
  ``[R, n]`` the position of each occurrence's id inside ``uniq``
  (``cap`` for dropped occurrences — callers index a zero-extended
  row buffer with it).  ``cap`` must bound the distinct count; callers
  pass ``cap = n``, the guaranteed bound, so nothing can ever drop.
  Pure sort/cumsum/gather — no scatter (compact_segments' rank
  machinery, specialised to ids only).

  The forward's ``inv`` is a ROUTING PRODUCT the backward reuses
  (design §21 residual-reuse rule): re-running this kernel on the same
  ids is bit-identical but prices two argsorts per call site, so the
  hot backward consumes the forward's ``inv`` from the residual aux
  instead of re-sorting.
  """
  n = ids.shape[1]
  big = jnp.int32(np.iinfo(np.int32).max)

  def one(row):
    keyv = jnp.where(row >= 0, row, big)
    sid = jnp.sort(keyv)
    first = jnp.concatenate([jnp.ones((1,), bool), sid[1:] != sid[:-1]])
    real = sid < big
    rank = cumsum0((first & real).astype(jnp.int32)) - 1
    key2 = jnp.where(first & real, rank, n)
    order2 = jnp.argsort(key2)[:cap]
    valid2 = key2[order2] < n
    uvals = sid[order2]
    uniq = jnp.where(valid2, uvals, -1)
    # inverse positions by a searchsorted against the unique buffer
    # (padding mapped past every real id keeps it ascending) — cheaper
    # than a third argsort; dropped occurrences map to ``cap``
    usearch = jnp.where(valid2, uvals, big)
    inv = jnp.searchsorted(usearch, jnp.where(row >= 0, row, big),
                           side='left').astype(jnp.int32)
    inv = jnp.where(row >= 0, jnp.minimum(inv, cap), cap)
    return uniq, inv

  return jax.vmap(one)(ids)


def dense_segment_sum(seg: jax.Array, rows: jax.Array, num: int,
                      row_index: Optional[jax.Array] = None) -> jax.Array:
  """DENSE segment sum: sum ``rows[i]`` (or ``rows[row_index[i]]``)
  into segment ``seg[i]``; segments ``>= num`` drop.  Returns
  ``[num, w]`` f32.

  Sort + cumsum-difference segment totals (the ``compact_segments``
  machinery), then ONE scatter-set of each segment's total at its last
  sorted position — ``n`` static rows with the sorted/unique hints the
  apply path already relies on.  An earlier formulation built the
  dense buffer scatter-free (two searchsorted gathers per OUTPUT row),
  but that prices O(K log n) with K the hot-buffer rows: the hot-cache
  regime is K >> n by construction (K grows with coverage, n is
  batch-bound), measured 1.1 s/step on the CPU harness at K=2.2M vs
  tens of ms for the n-bound scatter.
  """
  n = seg.shape[0]
  s, order = sort_with_order(seg)
  payload = (rows[order] if row_index is None
             else rows[jnp.take(row_index, order)]).astype(jnp.float32)
  payload = jnp.where((s < num)[:, None], payload, 0.0)
  is_last = jnp.concatenate([s[1:] != s[:-1], jnp.ones((1,), bool)])
  csum = cumsum0(payload)
  total = jnp.where(is_last[:, None], csum, 0.0)
  excl = jnp.concatenate(
      [jnp.zeros((1, rows.shape[-1]), jnp.float32), csum[:-1]])
  is_first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
  first_pos = cummax0(
      jnp.where(is_first, jnp.arange(n, dtype=jnp.int32), 0))
  total = total - jnp.where(is_last[:, None], excl[first_pos], 0.0)
  # each in-bounds segment writes exactly once (its last position);
  # every other row scatters out of bounds and drops.  No sorted hint:
  # the dropped rows' sentinel interleaves with the ascending targets.
  dst = jnp.where(is_last & (s < num), s, num)
  return jnp.zeros((num, rows.shape[-1]), jnp.float32).at[dst].set(
      total, mode='drop')
