"""The chunked state-space-dual scan as two TPU kernels: forward, backward.

``models/hybrid_ssm.ssd_scan``'s recurrence ``h_t = exp(dt_t A) h_{t-1} +
dt_t x_t B_t^T``, ``y_t = h_t C_t``, restarted at every document, in
chunks of ``Q`` positions, as ``_ssd_chunk`` computes it, with every
``[Q, Q]`` array of a chunk (the cumulative decay's masked differences,
their ``exp``, ``C B^T`` and their product) built and spent in VMEM.  The
grid is ``(sequence, head block, chunk)``: the chunk axis is sequential,
and the state ``h [heads, P, N]`` is carried in float32 VMEM scratch from
one chunk to the next (forward), its cotangent from one chunk to the one
before (backward, which walks the chunks in reverse).  The forward kernel
also writes the state entering each chunk, ``[S, chunks, H, P, N]``
float32: the residual from which the backward kernel rebuilds each
chunk's decay, so that no chunk is recomputed through a ``lax.scan``.

The document rules are ``_ssd_chunk``'s: a position reads the incoming
state only if its document ran on from the previous chunk, and the
outgoing state carries the incoming one only if the chunk's last
document ran through the whole chunk.  Every chunk does the same work
whatever its documents are: a step's time does not follow the batch.

Precision (the configuration states "bfloat16 products, float32
accumulation; decay, softplus and the state-space recurrence in
float32"): the operands of the four products ``C B^T``, ``(C B^T decay
dt) x``, ``C h`` and ``(x to_end) B``, and of their transposes in the
backward kernel, are rounded to bfloat16 where they enter the product,
as XLA's default does to ``_ssd_chunk``'s float32 operands; every
product accumulates in float32 and states its precision.  The cumulative
decay (a running sum in float32 adds), ``exp``, ``dt`` and the carried
state and its cotangent stay float32.

The decay's factor ``A`` enters as ``dt A`` per position (the caller's
product): the backward kernel returns that product's cotangent, and
JAX's own rule for the product gives ``A``'s (summed over positions) and
the rest of ``dt``'s.

``ssd_scan`` asks ``takes`` and nothing else: the choice reads static
shapes and the backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Test hook: run the kernels in interpreter mode on any backend, so that
# CPU tests exercise them (tests/test_ssd_kernel.py).
FORCE_INTERPRET = False
# AOT hook: compile-only flows trace on a CPU default backend while
# targeting a TPU; setting this makes ``takes`` choose as it would on the
# chip (tests/test_tpu_lowering.py, tools/aot_tpu.py).
ASSUME_TPU = False

# Heads a grid step holds at most (docs/perf_notes.md, "The state-space
# scan kernels").
HEADS = 16

_LANES = 128
_SUBLANES = 8
_VMEM_LIMIT = 64 * 2**20
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
# Every product accumulates in float32 and states its precision (see
# ops/pallas_attention.py: a caller's default precision must not reach
# a product of bfloat16 tiles).
_PRODUCT = dict(preferred_element_type=jnp.float32,
                precision=lax.Precision.DEFAULT)


def _head_block(heads, d_head):
  """The most heads, at most ``HEADS``, that divide ``heads`` and fill
  whole lane tiles side by side; None where none do."""
  return next((n for n in range(min(heads, HEADS), 0, -1)
               if heads % n == 0 and n * d_head % _LANES == 0), None)


def takes(x_shape, state, chunk) -> bool:
  """Whether the scan of ``x [S, L, H, P]`` with ``state`` columns in
  ``B`` and ``C`` and chunks of ``chunk`` positions goes to the kernels:
  the program is compiled for a TPU (or a hook says so) and the static
  shapes fit the tiles."""
  _, length, heads, d_head = x_shape
  engaged = (jax.default_backend() == 'tpu' or ASSUME_TPU
             or FORCE_INTERPRET)
  return (engaged and chunk % _LANES == 0 and length % chunk == 0
          and state % _LANES == 0 and d_head in (64, 128)
          and _head_block(heads, d_head) is not None)


def _bf16(a):
  return a.astype(jnp.bfloat16)


def _running_sum(v, axis, reverse=False):
  """Inclusive running sum of ``v`` along ``axis`` (from the end with
  ``reverse``), in float32 adds: ``log2`` steps of a rotation and a
  masked add."""
  n = v.shape[axis]
  at = lax.broadcasted_iota(jnp.int32, v.shape, axis)
  step = 1
  while step < n:
    if reverse:
      v = v + jnp.where(at < n - step, pltpu.roll(v, n - step, axis), 0.0)
    else:
      v = v + jnp.where(at >= step, pltpu.roll(v, step, axis), 0.0)
    step *= 2
  return v


class _Chunk:
  """What every head of one grid step shares, from the chunk's document
  numbers (``[Q, 128]`` a position along the sublanes, ``[8, Q]`` along
  the lanes, and the previous chunk's last 8 positions along the
  sublanes) and its ``dt A`` (``[Q, heads]`` and ``[heads, Q]``): the
  mask of the pairs in which ``t`` reads ``s`` (``[t, s]`` with
  ``t_down``, else ``[s, t]``), and for every head at once, as columns
  ``[Q, heads]``, the running sum of ``dt A`` (``cum``; ``cum_row`` the
  same as rows) and what the incoming state and the outgoing one read of
  it."""

  def __init__(self, first, t_down, seg_col_ref, seg_row_ref, seg_prev_ref,
               lac_ref, lar_ref, dtc_ref):
    q = seg_col_ref.shape[0]
    seg_col = seg_col_ref[...]
    seg = seg_col[:, :1]                                    # [Q, 1]
    # the previous chunk's last document (none before the first chunk)
    prev = jnp.where(first, -1, seg_prev_ref[_SUBLANES - 1:, :1])
    last = seg[q - 1:, :]                                   # [1, 1]
    row = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    # position t reads s <= t of its own document
    self.t_down = t_down
    self.mask = (((row >= col) if t_down else (col >= row))
                 & (jnp.tile(seg_col, (1, q // _LANES)) == seg_row_ref[:1, :]))
    ran_on = seg == prev                                    # [Q, 1]
    cum = _running_sum(lac_ref[...], 0)                     # [Q, heads]
    self.cum, self.cum_row = cum, _running_sum(lar_ref[...], 1)
    end = cum[q - 1:, :]                                    # [1, heads]
    self.into = jnp.where(ran_on, jnp.exp(cum), 0.0)
    self.keep = jnp.where(seg == last, jnp.exp(end - cum), 0.0)
    self.dt = dtc_ref[...]
    self.to_end = self.keep * self.dt
    # the last 8 positions: the incoming state's factor in the outgoing
    # one is the last one's ``into``
    self.tail = self.into[q - _SUBLANES:, :]                # [8, heads]
    self.is_last = lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1

  def decay(self, j):
    """Head ``j``'s ``exp(cum_t - cum_s)`` where ``t`` reads ``s``, else
    0, in the orientation of ``mask``."""
    col, row = self.cum[:, j:j + 1], self.cum_row[j:j + 1, :]
    return jnp.where(self.mask,
                     jnp.exp(col - row if self.t_down else row - col), 0.0)

  def carried(self, j, width):
    """Head ``j``'s factor of the incoming state in the outgoing one as a
    row ``[1, width]``: a sum down the sublanes, since Mosaic will not
    broadcast one value along both."""
    last = lax.broadcasted_iota(jnp.int32, (_SUBLANES, 1), 0) == _SUBLANES - 1
    return jnp.sum(jnp.where(last, jnp.broadcast_to(
        self.tail[:, j:j + 1], (_SUBLANES, width)), 0.0), axis=0,
                   keepdims=True)


def _forward_kernel(x_ref, dtc_ref, dtr_ref, lac_ref, lar_ref, b_ref, bt_ref,
                    c_ref, ct_ref, seg_col_ref, seg_row_ref, seg_prev_ref,
                    y_ref, states_ref, h_ref, *, d_head):
  del ct_ref
  heads = h_ref.shape[0]
  first = pl.program_id(2) == 0

  @pl.when(first)
  def _():
    h_ref[...] = jnp.zeros_like(h_ref)

  chunk = _Chunk(first, True, seg_col_ref, seg_row_ref, seg_prev_ref,
                 lac_ref, lar_ref, dtc_ref)
  c = _bf16(c_ref[...])
  bt = _bf16(bt_ref[...])
  cb = lax.dot_general(c, _bf16(b_ref[...]), _NT, **_PRODUCT)   # [t, s]
  dt_row = dtr_ref[...]
  for j in range(heads):
    lanes = slice(j * d_head, (j + 1) * d_head)
    scaled = cb * chunk.decay(j) * dt_row[j:j + 1, :]           # [t, s]
    x = x_ref[:, lanes]                                          # [Q, P]
    h = h_ref[j]                                                 # [N, P]
    states_ref[j] = h
    y = jnp.dot(_bf16(scaled), _bf16(x), **_PRODUCT)
    y = y + jnp.dot(c, _bf16(h), **_PRODUCT) * chunk.into[:, j:j + 1]
    y_ref[:, lanes] = y
    h_ref[j] = (jnp.dot(bt, _bf16(x * chunk.to_end[:, j:j + 1]), **_PRODUCT)
                + h * chunk.carried(j, d_head))


def _backward_kernel(x_ref, dtc_ref, dtr_ref, lac_ref, lar_ref, b_ref, bt_ref,
                     c_ref, ct_ref, seg_col_ref, seg_row_ref, seg_prev_ref,
                     states_ref, dy_ref, dx_ref, db_ref, dct_ref, ddt_ref,
                     dlac_ref, dlar_ref, dh_ref, by_ref, *, d_head):
  """In the orientation ``[s, t]`` (``t`` reads ``s``), so that every
  product is ``a @ b`` or ``a @ b.T``.  The running sum's cotangent has a
  part at ``t`` and one at ``s`` of each pair, both sums of the same
  ``[s, t]`` products, so that they cancel as in XLA's transpose of
  ``_ssd_chunk``; ``by_ref`` collects the columns a lane a head."""
  del dtr_ref
  heads = dh_ref.shape[0]
  q = x_ref.shape[0]

  @pl.when(pl.program_id(2) == 0)
  def _():
    dh_ref[...] = jnp.zeros_like(dh_ref)

  chunk = _Chunk(pl.program_id(2) == pl.num_programs(2) - 1, False,
                 seg_col_ref, seg_row_ref, seg_prev_ref, lac_ref, lar_ref,
                 dtc_ref)
  b, c, ct = _bf16(b_ref[...]), _bf16(c_ref[...]), _bf16(ct_ref[...])
  bc = lax.dot_general(b, c, _NT, **_PRODUCT)                   # [s, t]
  d_bc = jnp.zeros((q, q), jnp.float32)
  db = jnp.zeros(db_ref.shape, jnp.float32)
  dct = jnp.zeros(dct_ref.shape, jnp.float32)
  for j in range(heads):
    lanes = slice(j * d_head, (j + 1) * d_head)
    dt = chunk.dt[:, j:j + 1]
    decay = chunk.decay(j)                                      # [s, t]
    decay_dt = decay * dt
    scaled = bc * decay_dt
    x, dy = x_ref[:, lanes], dy_ref[:, lanes]
    h, dh = states_ref[j], dh_ref[j]     # state in, cotangent of state out
    dyb = _bf16(dy)
    # y_t += sum_s scaled x_s
    d_scaled = lax.dot_general(_bf16(x), dyb, _NT, **_PRODUCT)  # [s, t]
    dx = jnp.dot(_bf16(scaled), dyb, **_PRODUCT)
    # scaled = bc exp(cum_t - cum_s) dt_s
    by_dt = d_scaled * bc * decay
    d_bc = d_bc + d_scaled * decay_dt
    # y += (C h) * into
    d_read = _bf16(dy * chunk.into[:, j:j + 1])
    read = jnp.dot(c, _bf16(h), **_PRODUCT)                     # [Q, P]
    dct = dct + lax.dot_general(_bf16(h), d_read, _NT, **_PRODUCT)
    # h_out = B^T (x to_end) + h carried
    dhb = _bf16(dh)
    d_x_end = jnp.dot(b, dhb, **_PRODUCT)                       # [Q, P]
    dx = dx + d_x_end * chunk.to_end[:, j:j + 1]
    db = db + lax.dot_general(_bf16(x * chunk.to_end[:, j:j + 1]), dhb, _NT,
                              **_PRODUCT)
    carried = chunk.carried(j, d_head)
    dh_ref[j] = carried * dh + jnp.dot(ct, d_read, **_PRODUCT)
    dx_ref[:, lanes] = dx
    by_ref[0, :, j:j + 1] = jnp.sum(by_dt, axis=1, keepdims=True)
    by_ref[1, :, j:j + 1] = jnp.sum(dy * read, axis=1, keepdims=True)
    by_ref[2, :, j:j + 1] = jnp.sum(x * d_x_end, axis=1, keepdims=True)
    by_ref[3, :1, j:j + 1] = jnp.sum(
        jnp.sum(dh * h, axis=0, keepdims=True) * carried, axis=1,
        keepdims=True)
    dlar_ref[j:j + 1, :] = jnp.sum(by_dt * dt, axis=0, keepdims=True)
  d_bc = _bf16(d_bc)
  db_ref[...] = db + jnp.dot(d_bc, c, **_PRODUCT)
  dct_ref[...] = dct + jnp.dot(_bf16(bt_ref[...]), d_bc, **_PRODUCT)
  # the running sum's cotangent: ``scaled`` reads exp(cum_t - cum_s) (the
  # rows' part, t, and the columns', s), ``into`` exp(cum_t), ``to_end``
  # exp(cum_end - cum_s), ``carried`` exp(cum_end); then that of the
  # ``dt A`` it sums
  by_dt, d_into, d_to_end = by_ref[0], by_ref[1], by_ref[2]
  by_end = d_to_end * chunk.to_end
  d_cum = (d_into * chunk.into - by_dt * chunk.dt - by_end
           + jnp.where(chunk.is_last,
                       jnp.sum(by_end, axis=0, keepdims=True)
                       + by_ref[3, :1, :], 0.0))
  dlac_ref[...] = _running_sum(d_cum, 0, reverse=True)
  dlar_ref[...] = _running_sum(dlar_ref[...], 1, reverse=True)
  # dt_s's own: in scaled and in to_end
  ddt_ref[...] = by_dt + d_to_end * chunk.keep


class _Layout:
  """The grid ``(sequence, head block, step)`` of one layer's kernels and
  the block specs of its arrays; ``chunk_of(step)`` is the chunk a step
  holds (the backward kernel walks them from the last)."""

  def __init__(self, x4, state, chunk, reverse):
    seqs, length, heads, self.d_head = x4
    self.heads = _head_block(heads, self.d_head)
    self.chunk, self.state = chunk, state
    self.chunks = length // chunk
    self.grid = (seqs, heads // self.heads, self.chunks)
    last = self.chunks - 1
    self.chunk_of = (lambda i: last - i) if reverse else (lambda i: i)

  def _spec(self, block, index):
    return pl.BlockSpec(block, lambda s, g, i: index(s, g, self.chunk_of(i)))

  def lanes(self):
    """``[S, L, H P]``: ``x``, ``y`` and their cotangents."""
    return self._spec((None, self.chunk, self.heads * self.d_head),
                      lambda s, g, c: (s, c, g))

  def columns(self):
    """``[S, H / heads, L, heads]``: a value a position and head, the
    positions along the sublanes."""
    return self._spec((None, None, self.chunk, self.heads),
                      lambda s, g, c: (s, g, c, 0))

  def rows(self):
    """``[S, H / heads, heads, L]``: the same, along the lanes."""
    return self._spec((None, None, self.heads, self.chunk),
                      lambda s, g, c: (s, g, 0, c))

  def positions(self, width):
    """``[S, L, width]``: ``B``, ``C``, and the document numbers along
    the sublanes."""
    return self._spec((None, self.chunk, width), lambda s, g, c: (s, c, 0))

  def along(self, width):
    """``[S, width, L]``: ``B^T``, ``C^T``, and the document numbers
    along the lanes (8 rows)."""
    return self._spec((None, width, self.chunk), lambda s, g, c: (s, 0, c))

  def before(self):
    """``[S, L, 128]``: the 8 positions before the chunk's first (the
    first 8 for the first chunk, which reads none of them)."""
    per_chunk = self.chunk // _SUBLANES
    return self._spec((None, _SUBLANES, _LANES),
                      lambda s, g, c: (s, jnp.maximum(c * per_chunk - 1, 0),
                                       0))

  def states(self):
    """``[S, chunks, H, N, P]``: the state entering each chunk."""
    return self._spec((None, None, self.heads, self.state, self.d_head),
                      lambda s, g, c: (s, c, g, 0, 0))

  def partials(self, transposed=False):
    """``[S, H / heads, L, N]`` (``[S, H / heads, N, L]``): a head
    block's share of ``dB`` (``dC^T``)."""
    if transposed:
      return self._spec((None, None, self.state, self.chunk),
                        lambda s, g, c: (s, g, 0, c))
    return self._spec((None, None, self.chunk, self.state),
                      lambda s, g, c: (s, g, c, 0))

  def inputs(self):
    return [self.lanes(), self.columns(), self.rows(), self.columns(),
            self.rows(), self.positions(self.state), self.along(self.state),
            self.positions(self.state), self.along(self.state),
            self.positions(_LANES), self.along(_SUBLANES), self.before()]

  def state_scratch(self):
    return pltpu.VMEM((self.heads, self.state, self.d_head), jnp.float32)


def _call(kernel, lay, in_specs, out_specs, out_shape, scratch, interpret,
          name):
  return pl.pallas_call(
      functools.partial(kernel, d_head=lay.d_head), grid=lay.grid,
      in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch,
      out_shape=out_shape,
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=('parallel', 'parallel', 'arbitrary'),
          vmem_limit_bytes=_VMEM_LIMIT),
      interpret=interpret, name=name)


def _by_block(heads, v):
  """``[S, L, H]`` as columns ``[S, H / heads, L, heads]`` and as rows
  ``[S, H / heads, heads, L]``."""
  seqs, length, total = v.shape
  v = v.reshape(seqs, length, total // heads, heads)
  return jnp.transpose(v, (0, 2, 1, 3)), jnp.transpose(v, (0, 2, 3, 1))


def _by_position(columns, rows=None):
  """The inverse of ``_by_block``, the two parts added."""
  seqs, blocks, length, heads = columns.shape
  out = jnp.transpose(columns, (0, 2, 1, 3))
  if rows is not None:
    out = out + jnp.transpose(rows, (0, 3, 1, 2))
  return out.reshape(seqs, length, blocks * heads)


def _operands(lay, x, dt, la, b, c, seg):
  t = lambda a: jnp.swapaxes(a, 1, 2)
  return ((x,) + _by_block(lay.heads, dt) + _by_block(lay.heads, la)
          + (b, t(b), c, t(c), seg['columns'], seg['rows'], seg['columns']))


# Each kernel is one jitted function (as ``hybrid_ssm._attend_window`` is):
# JAX traces and lowers it once for every Mamba layer of a step, where a
# bare ``pallas_call`` is traced and lowered again at each of its 27
# calls, which set-up pays on every run, the compile cache's hits too.
@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _forward(chunk, interpret, x4, x, dt, la, b, c, seg):
  lay = _Layout(x4, b.shape[-1], chunk, reverse=False)
  seqs = x.shape[0]
  return _call(
      _forward_kernel, lay, lay.inputs(), [lay.lanes(), lay.states()],
      [jax.ShapeDtypeStruct(x.shape, jnp.float32),
       jax.ShapeDtypeStruct((seqs, lay.chunks, x4[2], lay.state, lay.d_head),
                            jnp.float32)],
      [lay.state_scratch()], interpret, 'ssd_scan_fwd')(
          *_operands(lay, x, dt, la, b, c, seg))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _backward(chunk, interpret, x4, x, dt, la, b, c, seg, states, dy):
  lay = _Layout(x4, b.shape[-1], chunk, reverse=True)
  seqs, length, _ = x.shape
  blocks = lay.grid[1]
  f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
  columns = f32(seqs, blocks, length, lay.heads)
  dx, db, dct, ddt, dla_c, dla_r = _call(
      _backward_kernel, lay, lay.inputs() + [lay.states(), lay.lanes()],
      [lay.lanes(), lay.partials(), lay.partials(transposed=True),
       lay.columns(), lay.columns(), lay.rows()],
      [f32(*x.shape), f32(seqs, blocks, length, lay.state),
       f32(seqs, blocks, lay.state, length), columns, columns,
       f32(seqs, blocks, lay.heads, length)],
      [lay.state_scratch(),
       pltpu.VMEM((4, lay.chunk, lay.heads), jnp.float32)],
      interpret, 'ssd_scan_bwd')(
          *_operands(lay, x, dt, la, b, c, seg), states, dy)
  return (dx, _by_position(ddt), _by_position(dla_c, dla_r),
          jnp.sum(db, axis=1), jnp.swapaxes(jnp.sum(dct, axis=1), 1, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _core(chunk, interpret, x4, x, dt, la, b, c, seg):
  """float32 ``x [S, L, H P]``, ``dt`` and ``la = dt A`` ``[S, L, H]``,
  ``b``, ``c`` ``[S, L, N]`` -> float32 ``y [S, L, H P]``; ``x4`` is
  ``x``'s shape as ``[S, L, H, P]``."""
  return _core_fwd(chunk, interpret, x4, x, dt, la, b, c, seg)[0]


def _core_fwd(chunk, interpret, x4, x, dt, la, b, c, seg):
  y, states = _forward(chunk, interpret, x4, x, dt, la, b, c, seg)
  return y, (x, dt, la, b, c, seg, states)


def _core_bwd(chunk, interpret, x4, residuals, dy):
  return _backward(chunk, interpret, x4, *residuals, dy) + (None,)


_core.defvjp(_core_fwd, _core_bwd)


def scan(x, dt, a_heads, b, c, segment_ids, chunk):
  """``ssd_scan``'s contract on the kernels: ``x [S, L, H, P]``, ``dt [S,
  L, H]`` (positive), ``a_heads [H]`` (negative), ``b``, ``c`` ``[S, L,
  N]``, ``segment_ids [S, L]`` -> float32 ``y [S, L, H, P]``."""
  seqs, length, heads, d_head = x.shape
  ids = segment_ids.astype(jnp.int32)
  # the document numbers twice: a position along the sublanes, and along
  # the lanes, so that no kernel transposes a vector
  seg = dict(
      columns=jnp.broadcast_to(ids[:, :, None], (seqs, length, _LANES)),
      rows=jnp.broadcast_to(ids[:, None, :], (seqs, _SUBLANES, length)))
  y = _core(chunk, FORCE_INTERPRET, x.shape,
            x.reshape(seqs, length, heads * d_head), dt, dt * a_heads, b, c,
            seg)
  return y.reshape(x.shape)
