"""A toy token model, added as new files only: the proof that a model
class of another kind than the benchmark's own (per-position lookups of
one ``combiner: null`` table, a batch of targets and document boundaries,
Adam) needs no edit to the harness.  The five names of
``configs/toy-token.json`` (``lib/builders`` has what each is).

The model: token rows -> ``num_blocks`` residual blocks, each an RMS
norm, a causal cumulative mean over the positions of the same document
(the mixer) and a SwiGLU -> RMS norm -> an untied head over the
vocabulary; the loss is the mean next-token cross-entropy over the
positions that have a target.  The program's side is this file's
``_forward`` in plain ``jax.numpy`` at the backend's default precision,
through ``DistributedEmbedding`` and ``make_hybrid_train_step``; the
reference's side is the same equations with every product through the
``matmul`` the reference hands it.
"""

import numpy as np

from benchmarks.lib import builders

tables = builders.block_tables


def _sizes(config):
  (_, width, _), = tables(config)[0]
  return width, int(config['ffn_width']), int(config['num_blocks'])


def dense_params(config, seed):
  """``{'blocks': [{norm1, mix, norm2, gate, up, down}], 'final_norm',
  'head'}``: kernels ``N(0, 1/fan_in)``, norm gains 1, host numpy."""
  width, ffn, blocks = _sizes(config)
  vocab = tables(config)[0][0][0]
  rng = np.random.default_rng([int(seed), 3])
  kernel = lambda a, b: (rng.standard_normal((a, b)) / np.sqrt(a)).astype(
      np.float32)
  ones = lambda: np.ones(width, np.float32)
  return {'blocks': [{'norm1': ones(), 'mix': kernel(width, width),
                      'norm2': ones(), 'gate': kernel(width, ffn),
                      'up': kernel(width, ffn), 'down': kernel(ffn, width)}
                     for _ in range(blocks)],
          'final_norm': ones(), 'head': kernel(width, vocab)}


def work(config, model, global_batch, chips, mix):
  """Forward+backward FLOPs of the head for one chip's share of a step:
  three products (forward, input gradient, kernel gradient) of ``2 x
  tokens x fan_in x fan_out`` per kernel (per block ``mix``, ``gate``,
  ``up``, ``down``; once the vocabulary head), and per block the mixer's
  ``[L, L] x [L, width]`` product per sequence, forward and for its input
  (its mask takes no gradient): ``2 x 2 x tokens x L x width``.  Beyond
  the distinct rows the head must move nothing off the chip: 0 bytes."""
  del model
  width, ffn, blocks = _sizes(config)
  vocab = tables(config)[0][0][0]
  length = int(mix['seq_len'])
  tokens = global_batch * length
  kernels = blocks * (width * width + 3 * width * ffn) + width * vocab
  flops = (3 * 2 * tokens * kernels
           + blocks * 2 * 2 * tokens * length * width)
  return {'flops': flops / chips, 'bytes': 0}


def _forward(dense, emb_outs, batch, matmul):
  import jax
  import jax.numpy as jnp
  targets, segment_ids = batch
  x = emb_outs[0].reshape(targets.shape + (-1,))           # [S, L, width]
  norm = lambda v, gain: v * jax.lax.rsqrt(
      jnp.mean(v * v, axis=-1, keepdims=True) + 1e-6) * gain
  length = targets.shape[1]
  causal = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]
  same = segment_ids[:, :, None] == segment_ids[:, None, :]
  mask = (causal[None] & same).astype(x.dtype)              # [S, L, L]
  mask = mask / jnp.sum(mask, axis=-1, keepdims=True)
  for block in dense['blocks']:
    mixed = matmul(mask, norm(x, block['norm1']))
    x = x + matmul(mixed, block['mix'])
    h = norm(x, block['norm2'])
    x = x + matmul(jax.nn.silu(matmul(h, block['gate']))
                   * matmul(h, block['up']), block['down'])
  return matmul(norm(x, dense['final_norm']), dense['head'])  # [S, L, vocab]


def masked_xent(logits, targets):
  """Mean next-token cross-entropy over the positions with a target."""
  import jax
  import jax.numpy as jnp
  valid = targets >= 0
  logp = jax.nn.log_softmax(logits, axis=-1)
  picked = jnp.take_along_axis(
      logp, jnp.where(valid, targets, 0)[..., None], axis=-1)[..., 0]
  return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.maximum(
      jnp.sum(valid), 1)


def reference_head(config):
  del config

  def loss(dense, emb_outs, batch, matmul, tables):
    del tables
    return masked_xent(_forward(dense, emb_outs, batch, matmul), batch[0])

  return loss


def builder(config, mesh, seed):
  """One table through the program's ``DistributedEmbedding`` with
  ``combiner=None`` (natural storage: ``SparseAdam`` applies per row)."""
  import sys
  import jax.numpy as jnp
  from distributed_embeddings_tpu.parallel import (DistributedEmbedding,
                                                   TableConfig)
  specs, _, _ = tables(config)
  dist = DistributedEmbedding(
      [TableConfig(input_dim=rows, output_dim=width, combiner=None,
                   name=f'table_{i}') for i, (rows, width, _) in
       enumerate(specs)], mesh=mesh, dp_input=True, packed_storage=False)
  this = sys.modules[__name__]       # so that a test can break the loss

  def head_loss_fn(dense, emb_outs, batch):
    return this.masked_xent(_forward(dense, emb_outs, batch, jnp.matmul),
                            batch[0])

  return builders.finish(config, seed, dist, head_loss_fn)
