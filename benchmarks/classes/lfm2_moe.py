"""Sparse mixture-of-experts language models whose sequence mixer is
mostly a gated short convolution, over a tied vocabulary (the published
``lfm2_moe`` family): a stack of residual blocks given by ``layer_types``,
each an operator (``conv``: the gated short convolution; or
``full_attention``: grouped-query attention with rotary embedding) and
then a feed-forward: a dense SwiGLU in the first ``num_dense_layers``
blocks, and in the others routed experts of which this chip HOLDS
``num_experts``, ``first_expert ..`` of the ``published.num_experts`` the
router scores, with NO shared expert beside them.  The vocabulary is one
table, looked up per position and multiplied by again for the logits.
The five names a configuration of this class gives (``lib/builders``),
and ``expert_work``, ``attention_work`` and ``short_conv_work`` for the
rooflines.

The reference's side (``reference_head``) is the forward pass in
straightforward ``jax.numpy``, an independent route to the program's
numbers: the convolution is a sum of ``conv_L_cache`` shifted products
under a same-document mask (``classes/hybrid_ssm._conv``), the routed
layer a LOOP over the held experts, each a dense SwiGLU over every token
times that token's weight for the expert (0 where the token did not
choose it), attention full scores under a causal-and-document mask, a
block of queries against every key; every matrix product goes through
the ``matmul`` the reference hands it, except the router's, which is
float32 at ``Precision.HIGHEST`` under every ``matmul`` (a selection is
discrete).  Nothing of the program is imported outside ``builder``.

Equations, keys as in the published ``config.json``; every item the
config's keys do not fix is in the configuration file's ``assumed``::

  x0 = row
  h  = x + op(rmsnorm(x));  x' = h + ffn(rmsnorm(h))
  logits = rmsnorm(x_last) @ table^T
  loss = mean next-token cross-entropy over targets that are not -1

  conv(u): [B | C | z] = u W_in;  y = C * causal_conv(B * z);  out = y W_out
  attn(u): q, k, v = u Wq, u Wk, u Wv;  q, k = rmsnorm_D(q), rmsnorm_D(k)
           rotary(q, k) on every attention layer
           p = softmax over j <= i of i's document;  out = (p v) Wo
  ffn, routed: s = sigmoid(float32(u) Wr);  sel = top_k(s + expert_bias)
               w_e = routed_scaling_factor s_e / (sum_{sel} s + 1e-6)
               y = sum_{e in sel, e held} w_e swiglu_e(u)

What the absent experts would add is left out here as in the program (the
chip's share of a stated deployment), and ids, logits and loss are over
the vocabulary slice.  Packed documents are independent.
"""

import concurrent.futures
import functools
import json

import numpy as np

from benchmarks.classes.hybrid_ssm import (_block, _conv, _in_blocks,
                                           _keep_freed_memory, _rms_norm,
                                           tables)
from benchmarks.classes.moe_lm import _rotate, _swiglu
from benchmarks.lib import builders

__all__ = ['tables', 'builder', 'reference_head', 'dense_params', 'work',
           'expert_work', 'attention_work', 'short_conv_work']

_KINDS = ('conv', 'full_attention')


def _sizes(config):
  hidden, q_heads = int(config['hidden_size']), int(config['num_attention_heads'])
  return dict(
      hidden=hidden, vocab=int(config['vocab_size']), q_heads=q_heads,
      kv_heads=int(config['num_key_value_heads']), head_dim=hidden // q_heads,
      ffn=int(config['intermediate_size']),
      expert_ffn=int(config['moe_intermediate_size']),
      held=int(config['num_experts']),
      first=int(config.get('first_expert', 0)),
      # the router scores every expert of the model, held here or not
      router=int(config.get('published', {}).get('num_experts',
                                                 config['num_experts'])),
      per_token=int(config['num_experts_per_tok']),
      dense_layers=int(config['num_dense_layers']),
      taps=int(config['conv_L_cache']))


_DRAWN = {}    # the last draw of ``dense_params``, by configuration and seed


def dense_params(config, seed):
  """The dense parameters of ``_draw``, read-only; the builder and the
  reference of one run get the same host arrays (as
  ``classes/hybrid_ssm.dense_params``: a second copy is 2.5 GB)."""
  key = (json.dumps(config, sort_keys=True), int(seed))
  if key not in _DRAWN:
    _DRAWN.clear()
    _DRAWN[key] = _draw(config, seed)
  return _DRAWN[key]


def _draw(config, seed):
  """``{'layers': [{input_norm, pre_mlp_norm, conv: {in_proj, conv_kernel,
  out_proj} or attention: {q_proj, k_proj, v_proj, o_proj, q_norm,
  k_norm}, and mlp_in, mlp_out (gate | up, down) or moe: {router,
  expert_bias, experts_in, experts_out}}], 'final_norm'}`` as host numpy:
  kernels ``N(0, 1/fan_in)`` (an expert's from its own fan-in, the
  convolution's from its taps), gains 1, ``expert_bias`` 0 (``assumed``).
  Every kernel is drawn from a stream of its own, ``[seed, 5, its
  number]``, on a few threads: 631 M normals at the published widths."""
  import jax
  _keep_freed_memory()
  z = _sizes(config)
  d, heads = z['hidden'], z['q_heads'] * z['head_dim']
  kv = z['kv_heads'] * z['head_dim']
  kernels = []

  def kernel(*shape):
    kernels.append(np.empty(shape, np.float32))
    return kernels[-1]

  def draw(i):
    out = kernels[i]
    np.random.default_rng([int(seed), 5, i]).standard_normal(
        out.shape, np.float32, out=out)
    out /= np.float32(np.sqrt(out.shape[-2]))       # in place

  ones = lambda n: np.ones(n, np.float32)
  layers = []
  for i, kind in enumerate(config['layer_types']):
    p = {'input_norm': ones(d), 'pre_mlp_norm': ones(d)}
    if kind == 'conv':
      p['conv'] = {'in_proj': kernel(d, 3 * d),
                   'conv_kernel': kernel(z['taps'], d),
                   'out_proj': kernel(d, d)}
    elif kind == 'full_attention':
      p['attention'] = {
          'q_proj': kernel(d, heads), 'k_proj': kernel(d, kv),
          'v_proj': kernel(d, kv), 'o_proj': kernel(heads, d),
          'q_norm': ones(z['head_dim']), 'k_norm': ones(z['head_dim'])}
    else:
      raise ValueError(f'unknown layer type {kind!r}')
    if i < z['dense_layers']:
      p['mlp_in'] = kernel(d, 2 * z['ffn'])
      p['mlp_out'] = kernel(z['ffn'], d)
    else:
      p['moe'] = {
          'router': kernel(d, z['router']),
          'expert_bias': np.zeros(z['router'], np.float32),
          'experts_in': kernel(z['held'], d, 2 * z['expert_ffn']),
          'experts_out': kernel(z['held'], z['expert_ffn'], d)}
    layers.append(p)
  drawn = {'layers': layers, 'final_norm': ones(d)}
  with concurrent.futures.ThreadPoolExecutor(max_workers=8) as pool:
    list(pool.map(draw, range(len(kernels))))
  for leaf in jax.tree.leaves(drawn):
    leaf.flags.writeable = False
  return drawn


# ---- counted work ----------------------------------------------------------


def parameters(config):
  """Parameters by part, of the file as it stands (a chip's share where
  it is one): ``short_conv`` and ``attention`` a layer (the convolution's
  taps and the per-head gains included), ``dense_ffn``, ``router`` (with
  its selection bias), ``expert`` (one routed expert) and ``table``."""
  z = _sizes(config)
  d, heads = z['hidden'], z['q_heads'] * z['head_dim']
  kv = z['kv_heads'] * z['head_dim']
  return {'short_conv': 4 * d * d + z['taps'] * d,
          'attention': 2 * d * heads + 2 * d * kv + 2 * z['head_dim'],
          'dense_ffn': 3 * d * z['ffn'], 'router': d * z['router'] + z['router'],
          'expert': 3 * d * z['expert_ffn'], 'table': z['vocab'] * d}


def _layers(config):
  kinds = list(config['layer_types'])
  return (kinds.count('conv'), kinds.count('full_attention'),
          len(kinds) - int(config['num_dense_layers']))


def held_parameters(config):
  """Every parameter this file's stack holds but the layers' norm gains."""
  z, n = _sizes(config), parameters(config)
  convs, attentions, routed = _layers(config)
  return (convs * n['short_conv'] + attentions * n['attention']
          + z['dense_layers'] * n['dense_ffn']
          + routed * (n['router'] + z['held'] * n['expert']) + n['table'])


def matrix_parameters(config):
  """Parameters that EVERY token multiplies by: the operators' and
  attention's projections, the dense layers' SwiGLU, a routed layer's
  router, and the table once for the logits (the lookup multiplies
  nothing; a routed expert is met by its assignments only:
  ``expert_work``).  Taps, gains and biases are vectors and left out."""
  z, n = _sizes(config), parameters(config)
  convs, attentions, routed = _layers(config)
  d = z['hidden']
  return (convs * 4 * d * d + attentions * (n['attention'] - 2 * z['head_dim'])
          + z['dense_layers'] * n['dense_ffn'] + routed * d * z['router']
          + n['table'])


def expected_assignments(config, tokens):
  """Assignments one routed layer holds here if the router spreads its
  ``num_experts_per_tok`` choices evenly: ``tokens x k x held / width``."""
  z = _sizes(config)
  return tokens * z['per_token'] * z['held'] / z['router']


def expert_work(config, tokens):
  """Forward+backward FLOPs and least bytes of the held experts' grouped
  products of one step over ``tokens`` positions, all routed layers
  together, at the EXPECTED count of assignments, counted as
  ``classes/moe_lm.expert_work`` counts them: ``3 x 2 x`` an expert's
  parameters an assignment; the held experts' weights read forward and
  backward and their gradient written, four rows of ``hidden`` an
  assignment.  No recomputation is counted."""
  z, n = _sizes(config), parameters(config)
  layers = _layers(config)[2]
  assignments = expected_assignments(config, tokens)
  return {'flops': 3 * 2 * n['expert'] * assignments * layers,
          'bytes': 4 * (3 * z['held'] * n['expert']
                        + 4 * z['hidden'] * assignments) * layers}


def attention_work(config, global_batch, length):
  """Forward+backward FLOPs of attention's own products (``Q K^T`` and
  ``P V``) over the causal half ``L^2 / 2`` a sequence and attention
  layer: ``2 x 2 x head_dim x query heads`` a pair forward and three
  times that with the backward pass.  (Documents need fewer still: an
  upper bound of the required work.)"""
  z = _sizes(config)
  per_pair = 3 * 2 * 2 * z['head_dim'] * z['q_heads']
  return {'flops': global_batch * per_pair * (length * length // 2)
                   * _layers(config)[1]}


def short_conv_work(config, tokens):
  """Forward+backward FLOPs and least bytes of the gated short
  convolutions of one step over ``tokens`` positions, all ``conv`` layers
  together.

  FLOPs: a position multiplies by ``W_in [hidden, 3 hidden]`` and ``W_out
  [hidden, hidden]``, ``2 x 4 x hidden^2`` forward, and the backward pass
  computes two products for each of the forward's; the gates and the
  taps (``(2 x taps + 2) x hidden`` a position) are under a thousandth
  of that and left out.  Bytes, float32: the two kernels read forward and
  backward and their gradient written; a position's input read and
  output written forward, its input and the output's cotangent read and
  the input's written backward (a fused operator keeps ``B``, ``C``, ``z``
  on the chip).  No recomputation is counted.  FLOPs bind at the
  published sizes."""
  z = _sizes(config)
  kernels = 4 * z['hidden'] * z['hidden']
  layers = _layers(config)[0]
  return {'flops': 3 * 2 * kernels * tokens * layers,
          'bytes': 4 * (3 * kernels + 5 * z['hidden'] * tokens) * layers}


def work(config, model, global_batch, chips, mix):
  """Forward+backward FLOPs of the head for one chip's share of a step,
  and the bytes it must move beyond the distinct rows: ``6 x tokens x
  matrix_parameters`` + the experts' (``expert_work``) + attention's own
  (``attention_work``); every dense parameter's Adam update reads weight,
  gradient and two moments and writes weight and moments, 28 bytes a
  parameter (the table's are the rows' and counted with them)."""
  del model
  length = int(mix['seq_len'])
  tokens = global_batch * length
  flops = (6 * tokens * matrix_parameters(config)
           + expert_work(config, tokens)['flops']
           + attention_work(config, global_batch, length)['flops'])
  dense = held_parameters(config) - parameters(config)['table']
  return {'flops': flops / chips, 'bytes': 28 * dense / chips}


# ---- the reference's side ------------------------------------------------


def _short_conv(p, u, segment_ids, matmul):
  """The gated short convolution: ``B | C | z`` in that order (assumed:
  the published code), the convolution of ``B * z`` as a sum of shifted
  products that stay inside the document, no bias."""
  import jax.numpy as jnp
  b, c, z = jnp.split(matmul(u, p['in_proj']), 3, axis=-1)
  return matmul(c * _conv(b * z, p['conv_kernel'], 0.0, segment_ids),
                p['out_proj'])


def _attention(z, config, p, u, segment_ids, matmul):
  """Attention by full masked softmax, the key-value heads repeated to
  the query heads, a block of queries against EVERY key (one ``[S, heads,
  block, L]`` array of scores at a time)."""
  import jax
  import jax.numpy as jnp
  seqs, length, _ = u.shape
  eps = config['norm_eps']
  theta = float(config['rope_parameters']['rope_theta'])
  heads = lambda a, n: jnp.swapaxes(
      a.reshape(seqs, length, n, z['head_dim']), 1, 2)       # [S, n, L, D]
  # per-head norms with a learned gain, then rotary on every attention
  # layer (assumed: the published code)
  q = _rotate(_rms_norm(heads(matmul(u, p['q_proj']), z['q_heads']),
                        p['q_norm'], eps), theta)
  k = _rotate(_rms_norm(heads(matmul(u, p['k_proj']), z['kv_heads']),
                        p['k_norm'], eps), theta)
  v = heads(matmul(u, p['v_proj']), z['kv_heads'])
  repeat = z['q_heads'] // z['kv_heads']
  k, v = (jnp.repeat(a, repeat, axis=1) for a in (k, v))
  position = jnp.arange(length)
  scale = z['head_dim'] ** -0.5

  def attend(qb, seg_q, pos_q):
    """``qb [Bq, S, n, D]``, ``seg_q [Bq, S]``, ``pos_q [Bq]``."""
    scores = matmul(jnp.moveaxis(qb, 0, 2),
                    jnp.swapaxes(k, 2, 3)) * scale            # [S, n, Bq, L]
    mask = ((seg_q.T[:, :, None] == segment_ids[:, None, :])
            & (pos_q[:, None] >= position[None, :]))
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    return jnp.moveaxis(matmul(jax.nn.softmax(scores, axis=-1), v), 2, 0)

  out = _in_blocks(attend, _block(length, 128), jnp.moveaxis(q, 2, 0),
                   segment_ids.T, position)              # [L / Bq, Bq, S, n, D]
  out = jnp.moveaxis(out.reshape((length,) + out.shape[2:]), 0, 1)
  return matmul(out.reshape(seqs, length, -1), p['o_proj'])


def dense_routing_weights(z, config, p, u):
  """``[T, router width]``: what each token multiplies each expert's
  output by, 0 for an expert it did not choose.  The scores are float32
  at ``Precision.HIGHEST`` whatever ``matmul`` the caller was handed; the
  selection is by score + bias, the weight by the score alone."""
  import jax
  import jax.numpy as jnp
  scores = jax.nn.sigmoid(jnp.matmul(
      u.astype(jnp.float32), p['router'],
      precision=jax.lax.Precision.HIGHEST))
  # the selection bias takes no gradient and stays where it starts
  # (assumed: the trainer's own update is left out)
  _, sel = jax.lax.top_k(scores + jax.lax.stop_gradient(p['expert_bias']),
                         z['per_token'])
  chosen = jnp.sum(jax.nn.one_hot(sel, z['router'], dtype=scores.dtype),
                   axis=1)
  picked = scores * chosen
  # norm_topk_prob: the sum is over ALL the chosen, held here or not
  return float(config['routed_scaling_factor']) * picked / (
      jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)


def _routed(z, config, p, u, matmul):
  """The held experts' part and nothing beside it: one expert after the
  other, each a dense SwiGLU over every token."""
  import jax
  import jax.numpy as jnp
  flat = u.reshape(-1, u.shape[-1])
  weights = dense_routing_weights(z, config, p, flat)
  held = jnp.swapaxes(weights[:, z['first']:z['first'] + z['held']], 0, 1)

  @jax.checkpoint
  def one(flat, kernels_in, kernels_out, w):
    return w[:, None] * _swiglu(matmul, {'mlp_in': kernels_in,
                                         'mlp_out': kernels_out}, flat)

  def step(y, xs):
    return y + one(flat, *xs), None

  y, _ = jax.lax.scan(step, jnp.zeros_like(flat),
                      (p['experts_in'], p['experts_out'], held))
  return y.reshape(u.shape)


def _layer(z, config, kind, matmul, p, x, segment_ids):
  """One residual block: a norm before each sub-layer and none after
  (assumed: the published code).  Operator and feed-forward are
  recomputed apart in the backward pass, the dense SwiGLU a block of
  positions at a time."""
  import jax
  import jax.numpy as jnp
  eps = config['norm_eps']

  @jax.checkpoint
  def operator(p, x):
    u = _rms_norm(x, p['input_norm'], eps)
    if kind == 'conv':
      return _short_conv(p['conv'], u, segment_ids, matmul)
    return _attention(z, config, p['attention'], u, segment_ids, matmul)

  x = x + operator(p, x)
  u = _rms_norm(x, p['pre_mlp_norm'], eps)
  if 'moe' in p:
    return x + _routed(z, config, p['moe'], u, matmul)
  ffn = _in_blocks(
      lambda ub: _swiglu(matmul, p, jnp.swapaxes(ub, 0, 1)),
      _block(x.shape[1], 1024), jnp.swapaxes(u, 0, 1))       # [L / B, S, B, d]
  return x + jnp.swapaxes(ffn, 0, 1).reshape(x.shape)


def reference_head(config):
  """``loss(dense, emb_outs, batch, matmul, tables)``: the forward pass
  and the mean next-token cross-entropy over the positions that have a
  target, the logits against the tied table a block of positions at a
  time."""
  import jax
  import jax.numpy as jnp
  z = _sizes(config)
  tid, = config['head_reads_tables']
  unknown = set(config['layer_types']) - set(_KINDS)
  if unknown:
    raise ValueError(f'unknown layer types {sorted(unknown)}')

  def loss(dense, emb_outs, batch, matmul, tables):
    targets, segment_ids = batch
    x = emb_outs[0].reshape(targets.shape + (z['hidden'],))
    for kind, p in zip(config['layer_types'], dense['layers']):
      x = jax.checkpoint(functools.partial(_layer, z, config, kind, matmul))(
          p, x, segment_ids)
    x = _rms_norm(x, dense['final_norm'], config['norm_eps'])
    table_t = jnp.transpose(tables[tid])

    def block_nll(xb, tb):
      logp = jax.nn.log_softmax(matmul(xb, table_t), axis=-1)
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
  """Through the program's ``models.moe_lm`` head, the vocabulary one
  ``combiner=None`` table of ``DistributedEmbedding`` that the head also
  reads (``head_reads_tables``: the tied apply), natural storage."""
  from distributed_embeddings_tpu.models import moe_lm as prog
  from distributed_embeddings_tpu.parallel import (DistributedEmbedding,
                                                   TableConfig)
  (rows, width, _), = tables(config)[0]
  dist = DistributedEmbedding(
      [TableConfig(input_dim=rows, output_dim=width, combiner=None,
                   name='vocabulary')],
      mesh=mesh, dp_input=True, packed_storage=False)
  cfg = prog.MoELMConfig.from_dict(config)
  tid, = config['head_reads_tables']
  return builders.finish(config, seed, dist, prog.make_head_loss_fn(cfg, tid),
                         head_reads_tables=(tid,))
