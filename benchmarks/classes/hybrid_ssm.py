"""Hybrid state-space / attention language models over a tied vocabulary
(the published ``granitemoehybrid`` family with no experts): a stack of
residual blocks given by ``layer_types``, each a Mamba-2 mixer or causal
grouped-query attention without positional embedding, then a SwiGLU; the
vocabulary is one table, looked up per position and multiplied by again
for the logits.  The five names a configuration of this class gives
(``lib/builders``), and ``scan_work`` for the scan's roofline.

The reference's side (``reference_head``) is the published forward pass
in straightforward ``jax.numpy``, an independent route to the program's
numbers: the state-space recurrence is SEQUENTIAL, ``h_t = a_t h_{t-1} +
dt_t x_t B_t^T`` by ``lax.scan`` over positions (the program computes it
in chunks), attention is the full masked softmax one block of queries
at a time, and every matrix product goes through the ``matmul`` the
reference hands it.  Layers run under ``jax.checkpoint``, and what is
done block by block (queries, the SwiGLU's and the logits' positions) is
a ``lax.map``, so that three checked steps at the published widths fit
one chip and compile in a minute (2.9 GiB of temporaries and 4.6 s a
step on the v5e).  Nothing of the program is imported outside
``builder``.

Equations, keys as in the published ``config.json``::

  x0 = embedding_multiplier * row
  x += residual_multiplier * mixer(rmsnorm(x))
  x += residual_multiplier * swiglu(rmsnorm(x))
  logits = rmsnorm(x) @ table^T / logits_scaling
  loss = mean next-token cross-entropy over targets that are not -1

  mamba:  z | xBC | dt = in_proj(u)
          xBC = silu(causal depthwise conv(xBC) + bias)
          dt = softplus(dt + dt_bias);  A = -exp(A_log)
          h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T;  y_t = h_t C_t + D x_t
          out = out_proj(rmsnorm(y * silu(z)))

Packed documents are independent (``assumed``): state and convolution
window restart at a document's first position, attention stays inside
the document.
"""

import concurrent.futures
import functools
import json

import numpy as np

from benchmarks.lib import builders


def tables(config):
  """The one table: the vocabulary slice, ``hidden_size`` wide."""
  spec = [(int(config['vocab_size']), int(config['hidden_size']))]
  return builders.with_half_range(config['table_init'], spec), [0], [1]


def _sizes(config):
  heads, d_head = int(config['mamba_n_heads']), int(config['mamba_d_head'])
  state = int(config['mamba_d_state']) * int(config['mamba_n_groups'])
  inner = heads * d_head
  hidden = int(config['hidden_size'])
  q_heads = int(config['num_attention_heads'])
  return dict(hidden=hidden, heads=heads, d_head=d_head, inner=inner,
              state=state, conv_dim=inner + 2 * state,
              taps=int(config['mamba_d_conv']),
              ffn=int(config['shared_intermediate_size']),
              q_heads=q_heads, kv_heads=int(config['num_key_value_heads']),
              head_dim=hidden // q_heads)


def _keep_freed_memory():
  """Have glibc keep the memory NumPy frees instead of returning it to the
  system.  At the published widths the dense leaves are 64 to 128 MiB
  each, and every NumPy temporary of that size is a fresh ``mmap``: each
  pass of the harness's row-wise Adam and of its readings over 3 GB
  page-faults its whole output (a 128 MiB copy takes 146 ms into fresh
  pages and 44 ms into touched ones on the chip's host; the reference's
  three steps took 162 s in ``_Optimizer.step`` alone, PERF.md section
  6).  ``M_MMAP_MAX`` 0 serves them from the heap, and an
  ``M_TRIM_THRESHOLD`` of 1 GiB keeps the heap's top until more than that
  is free; both sides of a cell call this (``dense_params``), nothing in
  the timed window allocates on the host, and a C library without
  ``mallopt`` is left as it is."""
  import ctypes
  try:
    mallopt = ctypes.CDLL(None).mallopt
  except (OSError, AttributeError):
    return
  mallopt(-4, 0)                 # M_MMAP_MAX
  mallopt(-1, 1 << 30)           # M_TRIM_THRESHOLD


_DRAWN = {}    # the last draw of ``dense_params``, by configuration and seed


def dense_params(config, seed):
  """The dense parameters of ``_draw``, read-only.  The builder and the
  reference of one run ask with the same configuration and seed and get
  the same host arrays: the draw depends on nothing else, each side
  copies what it changes, and at the published widths a second copy is
  3 GB of a host that the run's readings already fill."""
  key = (json.dumps(config, sort_keys=True), int(seed))
  if key not in _DRAWN:
    _DRAWN.clear()
    _DRAWN[key] = _draw(config, seed)
  return _DRAWN[key]


def _draw(config, seed):
  """``{'layers': [{mixer_norm, mixer: {...}, mlp_norm, mlp_in, mlp_out}],
  'final_norm'}`` as host numpy: kernels ``N(0, 1/fan_in)``, ``A_log =
  log(U[1, 16])``, ``dt_bias`` the inverse softplus of log-uniform
  ``[1e-3, 1e-1]``, ``D`` and gains 1, the convolution's bias 0 (the
  family's convention; ``assumed``).  Every kernel is drawn from a stream
  of its own, ``[seed, 5, its number]``, on a few threads: 746 M normals
  at the published widths, a quarter of a minute on one."""
  import jax
  _keep_freed_memory()
  z = _sizes(config)
  rng = np.random.default_rng([int(seed), 5])
  d, inner, heads = z['hidden'], z['inner'], z['heads']
  kv = z['kv_heads'] * z['head_dim']
  kernels = []

  def kernel(fan_in, fan_out):
    kernels.append(np.empty((fan_in, fan_out), np.float32))
    return kernels[-1]

  def draw(i):
    out = kernels[i]
    np.random.default_rng([int(seed), 5, i]).standard_normal(
        out.shape, np.float32, out=out)
    out /= np.float32(np.sqrt(out.shape[0]))   # in place: 3 GB in all

  ones = lambda n: np.ones(n, np.float32)
  layers = []
  for kind in config['layer_types']:
    if kind == 'mamba':
      dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), heads))
      mixer = {
          'in_proj': kernel(d, inner + z['conv_dim'] + heads),
          'conv_kernel': kernel(z['taps'], z['conv_dim']),
          'conv_bias': np.zeros(z['conv_dim'], np.float32),
          'dt_bias': (dt + np.log(-np.expm1(-dt))).astype(np.float32),
          'A_log': np.log(rng.uniform(1.0, 16.0, heads)).astype(np.float32),
          'D': ones(heads), 'gated_norm': ones(inner),
          'out_proj': kernel(inner, d)}
    elif kind == 'attention':
      mixer = {'q_proj': kernel(d, d), 'k_proj': kernel(d, kv),
               'v_proj': kernel(d, kv), 'o_proj': kernel(d, d)}
    else:
      raise ValueError(f'unknown layer type {kind!r}')
    layers.append({'mixer_norm': ones(d), 'mixer': mixer,
                   'mlp_norm': ones(d), 'mlp_in': kernel(d, 2 * z['ffn']),
                   'mlp_out': kernel(z['ffn'], d)})
  with concurrent.futures.ThreadPoolExecutor(max_workers=8) as pool:
    list(pool.map(draw, range(len(kernels))))
  drawn = {'layers': layers, 'final_norm': ones(d)}
  for leaf in jax.tree.leaves(drawn):
    leaf.flags.writeable = False
  return drawn


def matrix_parameters(config):
  """Parameters that a token multiplies by: per Mamba layer ``in_proj``
  and ``out_proj``, per attention layer its four projections, per layer
  the SwiGLU's two kernels, and once the vocabulary for the logits (the
  lookup multiplies nothing).  Norm gains, ``dt_bias``, ``A_log``, ``D``
  and the convolution are vectors and are left out."""
  z = _sizes(config)
  d, kv = z['hidden'], z['kv_heads'] * z['head_dim']
  mamba = d * (z['inner'] + z['conv_dim'] + z['heads']) + z['inner'] * d
  attention = 2 * d * d + 2 * d * kv
  mlp = d * 2 * z['ffn'] + z['ffn'] * d
  kinds = list(config['layer_types'])
  return (kinds.count('mamba') * (mamba + mlp)
          + kinds.count('attention') * (attention + mlp)
          + int(config['vocab_size']) * d)


def scan_work(config, tokens):
  """Forward+backward FLOPs and least bytes of the state-space scans of
  one step over ``tokens`` positions, all Mamba layers together.

  FLOPs, the sequential recurrence (the cheapest known form): per
  position and head the decay of the state (``P x N`` multiplies), the
  outer product ``dt x B^T`` added to it (``2 P N``) and the read-out ``h
  C`` (``2 P N``): ``5 x H x P x N`` forward; the backward pass computes
  two products for each of the forward's, so three times that in all.
  The chunked form the program runs needs more (``H (Q P + 4 P N) + Q N``
  per position at chunk ``Q``, counting the causal half); the lesser
  count is the required one.

  Bytes, float32: forward reads ``xBC`` (before the convolution, which a
  fused scan would apply on the fly: ``inner + 2N`` wide) and ``dt``
  (``H``) and writes ``y`` (``inner``); backward reads ``xBC``, ``dt`` and
  ``y``'s cotangent and writes the cotangents of ``xBC`` and ``dt``; the
  chunk states (``H x P x N`` per chunk of ``mamba_chunk_size`` positions
  per sequence) are written and read once forward and once backward."""
  z = _sizes(config)
  layers = list(config['layer_types']).count('mamba')
  per_state = z['heads'] * z['d_head'] * z['state']
  flops = 3 * 5 * per_state * tokens * layers
  per_position = (2 * (z['conv_dim'] + z['heads']) + z['inner']      # reads
                  + z['inner'] + z['conv_dim'] + z['heads'])         # writes
  states = 4 * per_state * tokens / int(config['mamba_chunk_size'])
  return {'flops': flops,
          'bytes': 4 * (per_position * tokens + states) * layers}


def work(config, model, global_batch, chips, mix):
  """Forward+backward FLOPs of the head for one chip's share of a step,
  and the bytes it must move beyond the distinct rows.

  FLOPs: three products (forward, input gradient, kernel gradient) of
  ``2 x tokens x parameters`` over ``matrix_parameters``: ``6 x tokens x
  parameters``; plus the scans' own (``scan_work``); plus attention's own
  products, ``Q K^T`` and ``P V`` over the causal half of ``L x L`` per
  sequence, ``2 x 2 x (L^2 / 2) x head_dim x query heads`` forward and
  three times that with the backward pass, per attention layer.  (A
  position attends only within its document, so packed traffic needs
  less still: the count is an upper bound of the required work by under
  1% of the step.)  Recomputation in the backward pass is not counted.

  Bytes: every dense parameter's update reads weight, gradient and
  Adam's two moments and writes weight and moments: 28 bytes a
  parameter (the vocabulary's are the rows' and counted with them)."""
  del model
  z = _sizes(config)
  length = int(mix['seq_len'])
  tokens = global_batch * length
  kinds = list(config['layer_types'])
  attention = (kinds.count('attention') * global_batch
               * 3 * 2 * 2 * (length * length // 2) * z['head_dim']
               * z['q_heads'])
  flops = (6 * tokens * matrix_parameters(config)
           + scan_work(config, tokens)['flops'] + attention)
  dense = matrix_parameters(config) - int(config['vocab_size']) * z['hidden']
  return {'flops': flops / chips, 'bytes': 28 * dense / chips}


# ---- the reference's side ------------------------------------------------


def _rms_norm(x, gain, eps):
  import jax.numpy as jnp
  return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _first_of_document(segment_ids):
  import jax.numpy as jnp
  return jnp.concatenate(
      [jnp.ones_like(segment_ids[:, :1], bool),
       segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1)


def _conv(x, kernel, bias, segment_ids):
  """Causal depthwise convolution whose window stays in the document:
  ``out_t = bias + sum_k kernel[K - 1 - k] x_{t - k}`` over the taps
  ``t - k`` of the same document (slices of the sequence padded in
  front; the padding belongs to no document)."""
  import jax.numpy as jnp
  taps, length = kernel.shape[0], x.shape[1]
  front = ((0, 0), (taps - 1, 0))
  x_padded = jnp.pad(x, front + ((0, 0),))
  seg_padded = jnp.pad(segment_ids, front, constant_values=-1)
  out = bias
  for k in range(taps):
    at = slice(taps - 1 - k, taps - 1 - k + length)
    reach = seg_padded[:, at] == segment_ids
    out = out + kernel[taps - 1 - k] * jnp.where(
        reach[..., None], x_padded[:, at], 0.0)
  return out


def _block(length, most):
  """The largest divisor of ``length`` that is at most ``most``."""
  return next(n for n in range(min(length, most), 0, -1) if length % n == 0)


def _in_blocks(fn, block, *arrays):
  """``fn`` over blocks of ``block`` of the leading axis of ``arrays``,
  one block after the other (``lax.map``, each block recomputed in the
  backward pass): the results stacked on a new leading axis.  A loop and
  not its unrolling, so that one block's interior is alive at a time and
  the compiler sees the block once."""
  import jax
  split = lambda a: a.reshape((a.shape[0] // block, block) + a.shape[1:])
  return jax.lax.map(jax.checkpoint(lambda xs: fn(*xs)),
                     tuple(split(a) for a in arrays))


def _sequential_scan(x, dt, a_heads, b, c, segment_ids, matmul):
  """``y_t = h_t C_t`` with ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``
  and ``h`` zero before a document's first position, position by position.
  ``x [S, L, H, P]``, ``dt [S, L, H]``, ``b``, ``c`` ``[S, L, N]``.  Two
  nested scans, the inner one under ``jax.checkpoint``, so that the
  backward pass keeps a state per block of positions and not per
  position."""
  import jax
  import jax.numpy as jnp
  seqs, length, heads, d_head = x.shape
  state = b.shape[-1]
  keep = jnp.where(_first_of_document(segment_ids)[..., None], 0.0,
                   jnp.exp(dt * a_heads))                    # [S, L, H]
  dtx = (dt[..., None] * x).reshape(seqs, length, heads * d_head)

  def step(h, at):
    keep_t, dtx_t, b_t, c_t = at
    h = (jnp.repeat(keep_t, d_head, axis=-1)[..., None] * h
         + matmul(dtx_t[:, :, None], b_t[:, None, :]))       # [S, H P, N]
    return h, matmul(h, c_t[:, :, None])[..., 0]

  block = _block(length, 64)
  by_block = lambda a: jnp.swapaxes(a, 0, 1).reshape(
      (length // block, block) + a.shape[:1] + a.shape[2:])
  inner = jax.checkpoint(lambda h, xs: jax.lax.scan(step, h, xs))
  _, y = jax.lax.scan(
      inner, jnp.zeros((seqs, heads * d_head, state), jnp.float32),
      tuple(by_block(a) for a in (keep, dtx, b, c)))
  y = y.reshape(length, seqs, heads, d_head)
  return jnp.swapaxes(y, 0, 1)


def _mamba(z, eps, p, u, segment_ids, matmul):
  """The Mamba-2 mixer.  The projection with the convolution, and the
  gated norm with ``out_proj``, are each recomputed in the backward pass
  (``jax.checkpoint``), so that the interiors of the three parts are not
  alive together; the scan keeps its own block states."""
  import jax
  import jax.numpy as jnp
  inner, state = z['inner'], z['state']

  @jax.checkpoint
  def projected(u):
    zxbcdt = matmul(u, p['in_proj'])
    xbc = zxbcdt[..., inner:inner + z['conv_dim']]
    xbc = jax.nn.silu(_conv(xbc, p['conv_kernel'], p['conv_bias'],
                            segment_ids))
    dt = jax.nn.softplus(zxbcdt[..., inner + z['conv_dim']:] + p['dt_bias'])
    return (zxbcdt[..., :inner], xbc[..., :inner],
            xbc[..., inner:inner + state], xbc[..., inner + state:], dt)

  @jax.checkpoint
  def gated(y, x, gate):
    y = (y + p['D'][:, None] * x).reshape(gate.shape)
    return matmul(_rms_norm(y * jax.nn.silu(gate), p['gated_norm'], eps),
                  p['out_proj'])

  gate, x, b, c, dt = projected(u)
  x = x.reshape(u.shape[:2] + (z['heads'], z['d_head']))
  y = _sequential_scan(x, dt, -jnp.exp(p['A_log']), b, c, segment_ids,
                       matmul)
  return gated(y, x, gate)


def _attention(z, scale, block, p, u, segment_ids, matmul):
  """Full masked softmax attention, the key-value heads repeated to the
  query heads, a block of queries at a time (one ``[S, heads, block, L]``
  array of scores at a time)."""
  import jax
  import jax.numpy as jnp
  seqs, length, _ = u.shape
  heads = lambda a, n: jnp.swapaxes(
      a.reshape(seqs, length, n, z['head_dim']), 1, 2)       # [S, n, L, D]
  repeat = z['q_heads'] // z['kv_heads']
  q = heads(matmul(u, p['q_proj']), z['q_heads'])
  k = jnp.repeat(heads(matmul(u, p['k_proj']), z['kv_heads']), repeat, axis=1)
  v = jnp.repeat(heads(matmul(u, p['v_proj']), z['kv_heads']), repeat, axis=1)
  position = jnp.arange(length)

  def attend(qb, seg_q, pos_q):
    """``qb [Bq, S, n, D]``, ``seg_q [Bq, S]``, ``pos_q [Bq]``."""
    scores = matmul(jnp.moveaxis(qb, 0, 2),
                    jnp.swapaxes(k, 2, 3)) * scale            # [S, n, Bq, L]
    mask = ((seg_q.T[:, :, None] == segment_ids[:, None, :])
            & (pos_q[:, None] >= position[None, :]))
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    return jnp.moveaxis(matmul(jax.nn.softmax(scores, axis=-1), v), 2, 0)

  out = _in_blocks(attend, _block(length, block), jnp.moveaxis(q, 2, 0),
                   segment_ids.T, position)              # [L / Bq, Bq, S, n, D]
  out = jnp.moveaxis(out.reshape((length,) + out.shape[2:]), 0, 1)
  return matmul(out.reshape(seqs, length, -1), p['o_proj'])


def _mixer(z, config, kind, matmul, p, x, segment_ids):
  eps = config['rms_norm_eps']
  u = _rms_norm(x, p['mixer_norm'], eps)
  if kind == 'mamba':
    return _mamba(z, eps, p['mixer'], u, segment_ids, matmul)
  return _attention(z, config['attention_multiplier'], 256, p['mixer'], u,
                    segment_ids, matmul)


def _swiglu(config, matmul, p, x):
  import jax
  import jax.numpy as jnp
  h = matmul(_rms_norm(x, p['mlp_norm'], config['rms_norm_eps']),
             p['mlp_in'])
  gate, up = jnp.split(h, 2, axis=-1)
  return matmul(jax.nn.silu(gate) * up, p['mlp_out'])


def _layer(z, config, kind, matmul, p, x, segment_ids):
  """One residual block.  Mixer and SwiGLU are recomputed apart in the
  backward pass (the mixer under ``jax.checkpoint``, the SwiGLU a block
  of positions at a time), so that the gradient of the whole stack at
  the published widths leaves room for three sets of dense leaves on one
  chip: the parameters, their gradients, and the gradients of the step
  before, which the caller still holds."""
  import jax
  import jax.numpy as jnp
  residual = config['residual_multiplier']
  x = x + residual * jax.checkpoint(
      functools.partial(_mixer, z, config, kind, matmul))(p, x, segment_ids)
  length = x.shape[1]
  mlp = _in_blocks(
      lambda xb: _swiglu(config, matmul, p, jnp.swapaxes(xb, 0, 1)),
      _block(length, 1024), jnp.swapaxes(x, 0, 1))        # [L / B, S, B, d]
  return x + residual * jnp.swapaxes(mlp, 0, 1).reshape(x.shape)


def reference_head(config):
  """``loss(dense, emb_outs, batch, matmul, tables)``: the uncut forward
  pass and the mean next-token cross-entropy over the positions that
  have a target, the logits a block of positions at a time."""
  import jax
  import jax.numpy as jnp
  z = _sizes(config)
  tid, = config['head_reads_tables']

  def loss(dense, emb_outs, batch, matmul, tables):
    targets, segment_ids = batch
    x = config['embedding_multiplier'] * emb_outs[0].reshape(
        targets.shape + (z['hidden'],))
    for kind, p in zip(config['layer_types'], dense['layers']):
      x = _layer(z, config, kind, matmul, p, x, segment_ids)
    x = _rms_norm(x, dense['final_norm'], config['rms_norm_eps'])
    table_t = jnp.transpose(tables[tid])

    def block_nll(xb, tb):
      logits = matmul(xb, table_t) / config['logits_scaling']
      logp = jax.nn.log_softmax(logits, axis=-1)
      picked = jnp.take_along_axis(
          logp, jnp.where(tb >= 0, tb, 0)[:, None], axis=-1)[:, 0]
      return -jnp.sum(jnp.where(tb >= 0, picked, 0.0))

    positions = targets.size
    total = jnp.sum(_in_blocks(block_nll, _block(positions, 2048),
                               x.reshape(positions, -1),
                               targets.reshape(positions)))
    return total / jnp.maximum(jnp.sum(targets >= 0), 1)

  return loss


def builder(config, mesh, seed):
  """Through the program's ``models.hybrid_ssm`` head, the vocabulary one
  ``combiner=None`` table of ``DistributedEmbedding`` that the head also
  reads (``head_reads_tables``).  Natural storage, which is the default
  at 128 lanes and wider: the head takes the table as ``[rows, width]``."""
  from distributed_embeddings_tpu.models import hybrid_ssm as prog
  from distributed_embeddings_tpu.parallel import (DistributedEmbedding,
                                                   TableConfig)
  (rows, width, _), = tables(config)[0]
  dist = DistributedEmbedding(
      [TableConfig(input_dim=rows, output_dim=width, combiner=None,
                   name='vocabulary')],
      mesh=mesh, dp_input=True, packed_storage=False)
  cfg = prog.HybridSSMConfig.from_dict(config)
  tid, = config['head_reads_tables']
  return builders.finish(config, seed, dist, prog.make_head_loss_fn(cfg, tid),
                         head_reads_tables=(tid,))
