"""Eligibility report for the fused sparse-apply kernel.

An A/B run that silently measures the XLA fallback (wrong backend, bf16
tables, unsupported widths) reads as "the kernel is no faster" —
`bench.py` embeds this check in its artifact line and the diagnostic
harnesses print it.  What it reports is the dispatch's own answer
(``parallel/sparse.choose_apply``); only the wording lives here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _active_suffix(force_interpret: bool, assume_tpu: bool = False) -> str:
  backend = jax.default_backend()
  if backend == 'tpu':
    return ''
  if force_interpret:
    return ' (interpret mode)'
  if assume_tpu:
    return ' (AOT, assumed TPU)'
  return f', inactive on {backend}'


def _segwalk_groups(dist, param_dtype, accum_dtype: str, active):
  """Per fusion group, whether ``choose_apply`` hands its update to the
  segment-walk kernel: asked as the group loop asks, of the shape the
  group's shard is stored in."""
  from distributed_embeddings_tpu.parallel.sparse import (SparseAdagrad,
                                                          choose_apply)
  asks = SparseAdagrad(use_segwalk_apply=True, accum_dtype=accum_dtype)
  adapted = getattr(dist, 'quant', None) is not None
  tiered = set(getattr(dist.plan, 'cold_tier_groups', []))
  return [
      choose_apply(
          asks, jax.ShapeDtypeStruct((g.param_rows, g.param_width),
                                     jnp.dtype(param_dtype)),
          g.rows_cap, g.width, storage_pack=g.storage_pack,
          adapted=adapted or gi in tiered, active=active).kernel == 'segwalk'
      for gi, g in enumerate(dist.plan.groups)]


def eligibility_line(dist, param_dtype, segwalk_apply: bool,
                     accum_dtype: str = 'float32') -> str:
  """One line saying which fusion groups the requested fused kernel
  would actually serve, and whether it engages on this backend at all
  (empty string when no kernel is requested)."""
  if not segwalk_apply:
    return ''
  from distributed_embeddings_tpu.ops import pallas_segwalk
  served = _segwalk_groups(dist, param_dtype, accum_dtype, active=True)
  return (f'segwalk_apply: {sum(served)}/{len(served)} groups eligible'
          f'{_active_suffix(pallas_segwalk.FORCE_INTERPRET, pallas_segwalk.ASSUME_TPU)}')


def segwalk_serves_all_groups(dist, param_dtype,
                              accum_dtype: str = 'float32') -> bool:
  """True when the segment-walk kernel will handle EVERY fusion group on
  the active backend — in which case compaction capacities are dead
  weight (the kernel has none)."""
  return all(_segwalk_groups(dist, param_dtype, accum_dtype, active=None))


def write_rows_lines(dist, optimizer, stream_rows=None):
  """One line per fusion group saying which scatter emitter its main
  wave's ``apply/write_rows`` takes (``choose_apply``'s ``write``:
  ``'stream'`` over the whole operand or ``'rows'`` at the update rows),
  with the rows written (U), the operand's rows (R) and the share.

  ``stream_rows``: per group, the length of its update stream (batch x
  hotness over its slots on one device); without it the capacity is the
  optimizer's calibrated ``capacity_rows`` bounded by the shard alone,
  and a group with neither says so."""
  from distributed_embeddings_tpu.parallel.sparse import (_capacity,
                                                          choose_apply,
                                                          wave_shape,
                                                          write_rows_line)
  caps = getattr(optimizer, 'capacity_rows', None) or ()
  lines = []
  for gi, g in enumerate(dist.plan.groups):
    cap_rows = caps[gi] if gi < len(caps) else None
    n = stream_rows[gi] if stream_rows is not None else None
    if n is None and cap_rows is None:
      lines.append(f'apply/write_rows: group_{gi} unknown (no stream '
                   'length and no calibrated capacity_rows)')
      continue
    cap = _capacity(optimizer, g.rows_cap + 2 if n is None else n,
                    g.rows_cap, cap_rows)
    choice = choose_apply(
        optimizer, jax.ShapeDtypeStruct((g.param_rows, g.param_width),
                                        jnp.float32),
        g.rows_cap, g.width, storage_pack=g.storage_pack, cap=cap)
    wave, operand = wave_shape(cap, g.rows_cap, choice.pack)
    lines.append(write_rows_line(f'group_{gi}', wave, operand, choice.write))
  return lines
