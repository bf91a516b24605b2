"""Fused TPU attention: causal, document-masked, windowed, grouped-query.

The core of ``models/hybrid_ssm.blocked_attention`` (scores, mask,
soft-max, the product with the values, and their backward) as three
Pallas kernels (forward, dq, dk|dv) in which a tile's scores never leave
VMEM.  One grid step holds one tile of ``BLOCK`` queries against one tile
of ``BLOCK`` keys of one key-value head and walks the ``G`` query heads
that share it, so keys, values and the mask are fetched and built once a
group.  Key tiles wholly above the causal diagonal or behind the window
are not in the grid.  What a step computes follows from the static
shapes alone, never from the documents: a tile pair that no document
spans is computed and masked like any other, so a step's time is the
same for every batch.

Precision (both language-model configurations state "bfloat16 products,
float32 accumulation"): ``q * scale``, ``k``, ``v`` and the cotangent of
the output are rounded to bfloat16 at the kernels' edge, as XLA's
default does to the float32 operands of ``_attend``'s two ``einsum``s
and of their transposes; products accumulate in float32; the running
maximum, the running sum and the soft-max weights are float32 until the
weights are an operand of the second product; the output and the three
gradients leave as float32.  A masked score is a large negative FINITE
number, so no row (a query always sees itself) meets ``inf - inf``.

``blocked_attention`` asks ``takes`` and nothing else: the choice reads
static shapes and the backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Test hook: run the kernels in interpreter mode on any backend, so that
# CPU tests exercise them (tests/test_attention_kernel.py).
FORCE_INTERPRET = False
# AOT hook: compile-only flows (jax.experimental.topologies) trace on a
# CPU default backend while targeting a TPU; setting this makes ``takes``
# choose as it would on the chip (tests/test_tpu_lowering.py,
# tools/aot_tpu.py around benchmarks/dev/aot_hybrid.py).
ASSUME_TPU = False

# Queries a tile and keys a tile, in all three kernels.  The fastest of
# {256, 512, 1024} on a v5e at head widths 128 and 64, windowed and full
# (examples/benchmarks/attention_probe.py; docs/perf_notes.md, "The
# attention kernels").
BLOCK = 512

_LANES = 128
_SUBLANES = 8
_MASKED = -0.7 * float(np.finfo(np.float32).max)
# a tile pair's working set (a group's queries, output and statistics,
# double-buffered) passes the 16 MiB a kernel gets by default
_VMEM_LIMIT = 64 * 2**20
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
# Every product in the kernels accumulates in float32 and STATES its
# precision: the operands are bfloat16 already, and a caller's
# ``jax.default_matmul_precision('highest')`` (a probe that reads what
# the products' precision does to a router's choice) would else ask
# Mosaic for a float32 contraction of bfloat16 tiles, which it refuses
# to compile ("Bad lhs type").
_PRODUCT = dict(preferred_element_type=jnp.float32,
                precision=lax.Precision.DEFAULT)


def takes(q_shape) -> bool:
  """Whether attention on ``q [S, L, Hkv, G, D]`` goes to the kernels:
  the program is compiled for a TPU (or a hook says so) and the static
  shapes fit the tiles (any window does)."""
  _, length, _, _, d = q_shape
  engaged = (jax.default_backend() == 'tpu' or ASSUME_TPU
             or FORCE_INTERPRET)
  return engaged and length % BLOCK == 0 and d in (64, 128)


def _first_key_tile(i, block, window):
  """The first key tile that query tile ``i`` sees."""
  if window is None:
    return jnp.zeros_like(i)
  return jnp.maximum(i * block - (window - 1), 0) // block


def _last_query_tile(j, block, window, tiles):
  """The last query tile that sees key tile ``j``."""
  if window is None:
    return jnp.full_like(j, tiles - 1)
  return jnp.minimum(((j + 1) * block + window - 2) // block, tiles - 1)


def _across(column, block):
  """A ``[block, 128]`` array whose lanes all hold one value a row, as
  ``[block, block]``."""
  return jnp.tile(column, (1, block // _LANES))


def _visible(q_first, k_first, seg_q, seg_k, shape, q_axis, window):
  """The pairs of one tile that attend: ``seg_q`` and ``seg_k`` are the
  tile's document numbers, one of them ``shape`` and the other one row
  of it; queries lie along ``q_axis``."""
  q_pos = q_first + lax.broadcasted_iota(jnp.int32, shape, q_axis)
  k_pos = k_first + lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
  mask = (q_pos >= k_pos) & (seg_q == seg_k)
  if window is not None:
    mask = mask & (q_pos - k_pos < window)
  return mask


def _keys_of_a_query_tile(seg_q_ref, seg_k_ref, block, window):
  """What the forward and dq kernels share, a grid step ``(s, h, i, t)``
  being query tile ``i`` against the ``t``-th key tile it sees: ``(t,
  whether the step has a pair to compute, the pair's mask when called)``."""
  i, t = pl.program_id(2), pl.program_id(3)
  j = _first_key_tile(i, block, window) + t
  runs = j <= i
  visible = lambda: _visible(
      i * block, j * block, _across(seg_q_ref[...], block),
      seg_k_ref[:1, :], (block, block), 0, window)
  return t, runs, visible


def _forward_kernel(q_ref, k_ref, v_ref, seg_q_ref, seg_k_ref, o_ref,
                    lse_ref, m_ref, l_ref, *, block, window, steps):
  group, _, d = q_ref.shape
  t, runs, visible = _keys_of_a_query_tile(seg_q_ref, seg_k_ref, block,
                                           window)

  @pl.when(t == 0)
  def _():
    m_ref[...] = jnp.full_like(m_ref, _MASKED)
    l_ref[...] = jnp.zeros_like(l_ref)
    o_ref[...] = jnp.zeros_like(o_ref)

  @pl.when(runs)
  def _():
    mask = visible()
    k, v = k_ref[...], v_ref[...]

    def head(g, _):
      s = lax.dot_general(q_ref[g], k, _NT, **_PRODUCT)
      s = jnp.where(mask, s, _MASKED)
      m_prev, l_prev = m_ref[g], l_ref[g]
      m_next = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
      p = jnp.exp(s - _across(m_next, block))
      alpha = jnp.exp(m_prev - m_next)
      l_ref[g] = alpha * l_prev + p.sum(axis=-1, keepdims=True)
      m_ref[g] = m_next
      o_ref[g] = alpha[:, :d] * o_ref[g] + jnp.dot(
          p.astype(v.dtype), v, **_PRODUCT)

    lax.fori_loop(0, group, head, None)

  @pl.when(t == steps - 1)
  def _():
    l = l_ref[...]
    o_ref[...] = o_ref[...] / l[..., :d]
    lse_ref[...] = m_ref[...] + jnp.log(l)


def _dq_kernel(q_ref, k_ref, v_ref, seg_q_ref, seg_k_ref, do_ref, lse_ref,
               di_ref, dq_ref, *, block, window):
  group = q_ref.shape[0]
  t, runs, visible = _keys_of_a_query_tile(seg_q_ref, seg_k_ref, block,
                                           window)

  @pl.when(t == 0)
  def _():
    dq_ref[...] = jnp.zeros_like(dq_ref)

  @pl.when(runs)
  def _():
    mask = visible()
    k, v = k_ref[...], v_ref[...]

    def head(g, _):
      s = lax.dot_general(q_ref[g], k, _NT, **_PRODUCT)
      s = jnp.where(mask, s, _MASKED)
      p = jnp.exp(s - _across(lse_ref[g], block))
      dp = lax.dot_general(do_ref[g], v, _NT, **_PRODUCT)
      ds = p * (dp - _across(di_ref[g], block))
      dq_ref[g] += jnp.dot(ds.astype(k.dtype), k, **_PRODUCT)

    lax.fori_loop(0, group, head, None)


def _dkv_kernel(q_ref, k_ref, v_ref, seg_q_ref, seg_k_ref, do_ref, lse_ref,
                di_ref, dk_ref, dv_ref, *, block, window, tiles):
  group = q_ref.shape[0]
  j, t = pl.program_id(2), pl.program_id(3)
  i = j + t

  @pl.when(t == 0)
  def _():
    dk_ref[...] = jnp.zeros_like(dk_ref)
    dv_ref[...] = jnp.zeros_like(dv_ref)

  @pl.when(i <= _last_query_tile(j, block, window, tiles))
  def _():
    # keys along the rows here: the transposes of the forward's tiles
    mask = _visible(i * block, j * block, seg_q_ref[:1, :],
                    _across(seg_k_ref[...], block), (block, block), 1, window)
    k, v = k_ref[...], v_ref[...]

    def head(g, _):
      q, do = q_ref[g], do_ref[g]
      s = lax.dot_general(k, q, _NT, **_PRODUCT)
      s = jnp.where(mask, s, _MASKED)
      p = jnp.exp(s - lse_ref[g][:1, :])
      dv_ref[...] += jnp.dot(p.astype(do.dtype), do, **_PRODUCT)
      dp = lax.dot_general(v, do, _NT, **_PRODUCT)
      ds = p * (dp - di_ref[g][:1, :])
      dk_ref[...] += jnp.dot(ds.astype(q.dtype), q, **_PRODUCT)

    lax.fori_loop(0, group, head, None)


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, interpret,
          name):
  return pl.pallas_call(
      kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
      scratch_shapes=scratch, out_shape=out_shape,
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=('parallel', 'parallel', 'parallel',
                               'arbitrary'),
          vmem_limit_bytes=_VMEM_LIMIT),
      interpret=interpret, name=name)


class _Layout:
  """The grid ``(sequence, key-value head, tile, step)`` of one layer's
  kernels and the block specs of its arrays.  A spec's ``tile_of(tile,
  step)`` names the tile of the array's positions a grid step holds:
  ``own``, the grid's tile; ``key_of``, the key tile that query tile
  ``i`` meets at step ``t``; ``query_of``, the query tile that key tile
  ``j`` meets at step ``t``.  A step past a tile's last pair names that
  pair again, which moves no data."""

  def __init__(self, q_shape, block, window):
    seqs, kv_heads, self.group, length, self.d = q_shape
    self.block, self.window = block, window
    self.tiles = tiles = length // block
    # the key tiles of a query tile at most, and as many query tiles of a
    # key tile
    self.steps = tiles if window is None else min(
        tiles, (window - 1 + block - 1) // block + 1)
    self.grid = (seqs, kv_heads, tiles, self.steps)
    self.statics = dict(block=block, window=window)

  def own(self, i, t):
    return i

  def key_of(self, i, t):
    return jnp.minimum(_first_key_tile(i, self.block, self.window) + t, i)

  def query_of(self, j, t):
    return jnp.minimum(
        j + t, _last_query_tile(j, self.block, self.window, self.tiles))

  def heads(self, tile_of, width=None):
    """``[S, Hkv, G, L, width]``: a group's queries, outputs, statistics."""
    return pl.BlockSpec(
        (None, None, self.group, self.block, width or self.d),
        lambda s, h, i, t: (s, h, 0, tile_of(i, t), 0))

  def keys(self, tile_of):
    """``[S, Hkv, L, D]``."""
    return pl.BlockSpec((None, None, self.block, self.d),
                        lambda s, h, i, t: (s, h, tile_of(i, t), 0))

  def columns(self, tile_of):
    """``[S, L, 128]``: a value a position, along the sublanes."""
    return pl.BlockSpec((None, self.block, _LANES),
                        lambda s, h, i, t: (s, tile_of(i, t), 0))

  def rows(self, tile_of):
    """``[S, 8, L]``: a value a position, along the lanes."""
    return pl.BlockSpec((None, _SUBLANES, self.block),
                        lambda s, h, i, t: (s, 0, tile_of(i, t)))

  def head_rows(self, tile_of):
    """``[S, Hkv, G, 8, L]``: a value a query and head, along the lanes."""
    return pl.BlockSpec(
        (None, None, self.group, _SUBLANES, self.block),
        lambda s, h, i, t: (s, h, 0, 0, tile_of(i, t)))


def _forward(block, window, interpret, q, k, v, seg):
  lay = _Layout(q.shape, block, window)
  own, key_of = lay.own, lay.key_of
  return _call(
      functools.partial(_forward_kernel, steps=lay.steps, **lay.statics),
      lay.grid,
      [lay.heads(own), lay.keys(key_of), lay.keys(key_of), lay.columns(own),
       lay.rows(key_of)],
      [lay.heads(own), lay.heads(own, _LANES)],
      [jax.ShapeDtypeStruct(q.shape, jnp.float32),
       jax.ShapeDtypeStruct(q.shape[:-1] + (_LANES,), jnp.float32)],
      [pltpu.VMEM((lay.group, block, _LANES), jnp.float32)] * 2,
      interpret, 'attention_fwd')(q, k, v, seg['columns'], seg['rows'])


def _backward(block, window, interpret, q, k, v, seg, out, lse, dout):
  lay = _Layout(q.shape, block, window)
  own, key_of, query_of = lay.own, lay.key_of, lay.query_of
  di = jnp.sum(dout * out, axis=-1)                    # [S, Hkv, G, L]
  do = dout.astype(q.dtype)
  along_lanes = lambda x: jnp.broadcast_to(
      x[..., None, :], x.shape[:-1] + (_SUBLANES, x.shape[-1]))
  dq = _call(
      functools.partial(_dq_kernel, **lay.statics), lay.grid,
      [lay.heads(own), lay.keys(key_of), lay.keys(key_of), lay.columns(own),
       lay.rows(key_of), lay.heads(own), lay.heads(own, _LANES),
       lay.heads(own, _LANES)],
      lay.heads(own), jax.ShapeDtypeStruct(q.shape, jnp.float32), [],
      interpret, 'attention_dq')(
          q, k, v, seg['columns'], seg['rows'], do, lse,
          jnp.broadcast_to(di[..., None], di.shape + (_LANES,)))
  dk, dv = _call(
      functools.partial(_dkv_kernel, tiles=lay.tiles, **lay.statics),
      lay.grid,
      [lay.heads(query_of), lay.keys(own), lay.keys(own),
       lay.rows(query_of), lay.columns(own), lay.heads(query_of),
       lay.head_rows(query_of), lay.head_rows(query_of)],
      [lay.keys(own)] * 2,
      [jax.ShapeDtypeStruct(k.shape, jnp.float32)] * 2, [],
      interpret, 'attention_dkv')(
          q, k, v, seg['rows'], seg['columns'], do, along_lanes(lse[..., 0]),
          along_lanes(di))
  return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _core(block, window, interpret, q, k, v, seg):
  """float32 ``q [S, Hkv, G, L, D]`` (scaled), ``k``, ``v`` ``[S, Hkv, L,
  D]`` -> float32 ``[S, Hkv, G, L, D]``.  The operands are float32 on
  this side of the rounding so that their gradients are too."""
  return _core_fwd(block, window, interpret, q, k, v, seg)[0]


def _core_fwd(block, window, interpret, q, k, v, seg):
  q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
  out, lse = _forward(block, window, interpret, q, k, v, seg)
  return out, (q, k, v, seg, out, lse)


def _core_bwd(block, window, interpret, residuals, dout):
  return _backward(block, window, interpret, *residuals, dout) + (None,)


_core.defvjp(_core_fwd, _core_bwd)


def attention(scale, q, k, v, segment_ids, window=None, block=None):
  """``blocked_attention``'s contract on the kernels: ``q [S, L, Hkv, G,
  D]``, ``k``, ``v`` ``[S, L, Hkv, D]``, ``segment_ids [S, L]`` -> float32
  ``[S, L, Hkv, G, D]``; a query sees the keys of its own document at or
  before it and, with a ``window``, fewer than ``window`` positions
  back."""
  block = block or BLOCK
  seqs, length = segment_ids.shape
  ids = segment_ids.astype(jnp.int32)
  # the document numbers twice: a position along the sublanes, and along
  # the lanes, so that no kernel transposes a vector
  seg = dict(
      columns=jnp.broadcast_to(ids[:, :, None], (seqs, length, _LANES)),
      rows=jnp.broadcast_to(ids[:, None, :], (seqs, _SUBLANES, length)))
  out = _core(block, window, FORCE_INTERPRET,
              jnp.transpose(q * scale, (0, 2, 3, 1, 4)),
              jnp.transpose(k, (0, 2, 1, 3)), jnp.transpose(v, (0, 2, 1, 3)),
              seg)
  return jnp.transpose(out, (0, 3, 1, 2, 4))
