"""Sparse mixture-of-experts language models over an untied vocabulary
(the published ``afmoe`` family): a stack of residual blocks given by
``layer_types``, each gated grouped-query attention (``sliding_attention``
with a window and rotary embedding, or ``full_attention`` with neither)
and then a feed-forward: a dense SwiGLU in the first ``num_dense_layers``
blocks, and in the others one shared-expert SwiGLU beside routed experts
of which this chip HOLDS ``num_experts``, ``first_expert ..`` of the
``published.num_experts`` the router scores.  The token table is looked
up per position and read by nothing else; the output head is a dense
leaf.  The five names a configuration of this class gives
(``lib/builders``), and ``expert_work`` for the experts' roofline.

The reference's side (``reference_head``) is the forward pass in
straightforward ``jax.numpy``, an independent route to the program's
numbers: the routed layer is a LOOP over the held experts, each a dense
SwiGLU over every token times that token's weight for the expert (0
where the token did not choose it), where the program sorts, gathers and
runs grouped products; attention is full scores under a band-and-document
mask, a block of queries against every key, where the program computes
only the key blocks a window meets; every matrix product goes through the
``matmul`` the reference hands it, except the router's, which is float32
at ``Precision.HIGHEST`` under every ``matmul`` (as the configuration
states: a selection is discrete).  Layers run under ``jax.checkpoint``
and what is done block by block is a ``lax.map`` or a ``lax.scan``.
Nothing of the program is imported outside ``builder``.

Equations, keys as in the published ``config.json``; every item the
config's keys do not fix is in the configuration file's ``assumed``::

  x0 = row * sqrt(hidden_size)                                (mup_enabled)
  h  = x + rmsnorm(attn(rmsnorm(x)));  x' = h + rmsnorm(ffn(rmsnorm(h)))
  logits = rmsnorm(x_last) @ lm_head
  loss = mean next-token cross-entropy over targets that are not -1

  attn(u): q, k, v, g = u Wq, u Wk, u Wv, u Wg;  q, k = rmsnorm_D(q), rmsnorm_D(k)
           sliding_attention: rotary(q, k);  full_attention: none
           p = softmax over j <= i of i's document (and i - j < window)
           out = ((p v) * sigmoid(g)) Wo
  ffn, routed: s = sigmoid(float32(u) Wr);  sel = top_k(s + expert_bias)
               w_e = route_scale s_e / (sum_{sel} s + 1e-20)
               y = swiglu_shared(u) + sum_{e in sel, e held} w_e swiglu_e(u)

What the absent experts would add is left out here as in the program (the
chip's share of a stated deployment), and ids, logits and loss are over
the vocabulary slice.  Packed documents are independent.
"""

import concurrent.futures
import functools
import json

import numpy as np

from benchmarks.classes.hybrid_ssm import (_block, _in_blocks,
                                           _keep_freed_memory, _rms_norm)
from benchmarks.lib import builders

_KINDS = ('sliding_attention', 'full_attention')


def tables(config):
  """The one table: the vocabulary slice, ``hidden_size`` wide."""
  spec = [(int(config['vocab_size']), int(config['hidden_size']))]
  return builders.with_half_range(config['table_init'], spec), [0], [1]


def _sizes(config):
  return dict(
      hidden=int(config['hidden_size']), vocab=int(config['vocab_size']),
      q_heads=int(config['num_attention_heads']),
      kv_heads=int(config['num_key_value_heads']),
      head_dim=int(config['head_dim']), ffn=int(config['intermediate_size']),
      expert_ffn=int(config['moe_intermediate_size']),
      held=int(config['num_experts']),
      first=int(config.get('first_expert', 0)),
      # the router scores every expert of the model, held here or not
      router=int(config.get('published', {}).get('num_experts',
                                                 config['num_experts'])),
      per_token=int(config['num_experts_per_tok']),
      dense_layers=int(config['num_dense_layers']),
      window=int(config['sliding_window']))


_DRAWN = {}    # the last draw of ``dense_params``, by configuration and seed


def dense_params(config, seed):
  """The dense parameters of ``_draw``, read-only; the builder and the
  reference of one run get the same host arrays (as
  ``classes/hybrid_ssm.dense_params``: a second copy is 2.6 GB)."""
  key = (json.dumps(config, sort_keys=True), int(seed))
  if key not in _DRAWN:
    _DRAWN.clear()
    _DRAWN[key] = _draw(config, seed)
  return _DRAWN[key]


def _draw(config, seed):
  """``{'layers': [{input_norm, post_attn_norm, pre_mlp_norm,
  post_mlp_norm, attention: {q_proj, k_proj, v_proj, gate_proj, o_proj,
  q_norm, k_norm}, and mlp_in, mlp_out (gate | up, down) or moe: {router,
  expert_bias, shared: {mlp_in, mlp_out}, experts_in, experts_out}}],
  'final_norm', 'lm_head'}`` as host numpy: kernels ``N(0, 1/fan_in)``
  (an expert's from its own fan-in), gains 1, ``expert_bias`` 0
  (``assumed``).  Every kernel is drawn from a stream of its own,
  ``[seed, 5, its number]``, on a few threads: 654 M normals at the
  published widths."""
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
    if kind not in _KINDS:
      raise ValueError(f'unknown layer type {kind!r}')
    p = {'input_norm': ones(d), 'post_attn_norm': ones(d),
         'pre_mlp_norm': ones(d), 'post_mlp_norm': ones(d),
         'attention': {
             'q_proj': kernel(d, heads), 'k_proj': kernel(d, kv),
             'v_proj': kernel(d, kv), 'gate_proj': kernel(d, heads),
             'o_proj': kernel(heads, d), 'q_norm': ones(z['head_dim']),
             'k_norm': ones(z['head_dim'])}}
    if i < z['dense_layers']:
      p['mlp_in'] = kernel(d, 2 * z['ffn'])
      p['mlp_out'] = kernel(z['ffn'], d)
    else:
      p['moe'] = {
          'router': kernel(d, z['router']),
          'expert_bias': np.zeros(z['router'], np.float32),
          'shared': {'mlp_in': kernel(d, 2 * z['expert_ffn']),
                     'mlp_out': kernel(z['expert_ffn'], d)},
          'experts_in': kernel(z['held'], d, 2 * z['expert_ffn']),
          'experts_out': kernel(z['held'], z['expert_ffn'], d)}
    layers.append(p)
  drawn = {'layers': layers, 'final_norm': ones(d),
           'lm_head': kernel(d, z['vocab'])}
  with concurrent.futures.ThreadPoolExecutor(max_workers=8) as pool:
    list(pool.map(draw, range(len(kernels))))
  for leaf in jax.tree.leaves(drawn):
    leaf.flags.writeable = False
  return drawn


# ---- counted work ----------------------------------------------------------


def parameters(config):
  """Parameters by part, of the file as it stands (a chip's share where
  it is one): ``attention`` a layer, ``dense_ffn``, ``router``, ``shared``,
  ``expert`` (one routed expert), ``embedding`` and ``head``."""
  z = _sizes(config)
  d, heads = z['hidden'], z['q_heads'] * z['head_dim']
  kv = z['kv_heads'] * z['head_dim']
  return {'attention': 3 * d * heads + 2 * d * kv, 'dense_ffn': 3 * d * z['ffn'],
          'router': d * z['router'], 'shared': 3 * d * z['expert_ffn'],
          'expert': 3 * d * z['expert_ffn'], 'embedding': z['vocab'] * d,
          'head': d * z['vocab']}


def held_parameters(config):
  """Every parameter this file's stack holds: matrix parameters and the
  table; norm gains and ``expert_bias`` are vectors and are left out."""
  z, n = _sizes(config), parameters(config)
  layers = len(config['layer_types'])
  routed = layers - z['dense_layers']
  return (layers * n['attention'] + z['dense_layers'] * n['dense_ffn']
          + routed * (n['router'] + n['shared'] + z['held'] * n['expert'])
          + n['embedding'] + n['head'])


def matrix_parameters(config):
  """Parameters that EVERY token multiplies by: attention's five
  projections a layer, the dense layers' SwiGLU, per routed layer the
  router and the shared expert, and the output head once (the lookup
  multiplies nothing; a routed expert is met by its assignments only:
  ``expert_work``)."""
  z, n = _sizes(config), parameters(config)
  layers = len(config['layer_types'])
  return (layers * n['attention'] + z['dense_layers'] * n['dense_ffn']
          + (layers - z['dense_layers']) * (n['router'] + n['shared'])
          + n['head'])


def expected_assignments(config, tokens):
  """Assignments one routed layer holds here if the router spreads its
  ``num_experts_per_tok`` choices evenly: ``tokens x k x held / width``,
  from the configuration and the mix alone."""
  z = _sizes(config)
  return tokens * z['per_token'] * z['held'] / z['router']


def expert_work(config, tokens):
  """Forward+backward FLOPs and least bytes of the held experts' grouped
  products of one step over ``tokens`` positions, all routed layers
  together, at the EXPECTED count of assignments (so that the work is
  the same whatever implements the layer).

  FLOPs: an assignment meets one expert's three projections, ``3 x 2 x
  hidden x ffn`` forward, and the backward pass computes two products
  for each of the forward's.  Bytes, float32: the held experts' weights
  read once forward and once backward and their gradient written once;
  per assignment the buffer's row read and the output row written
  forward, the output's cotangent read and the row's written backward.
  No recomputation is counted."""
  z, n = _sizes(config), parameters(config)
  layers = len(config['layer_types']) - z['dense_layers']
  assignments = expected_assignments(config, tokens)
  return {'flops': 3 * 2 * n['expert'] * assignments * layers,
          'bytes': 4 * (3 * z['held'] * n['expert']
                        + 4 * z['hidden'] * assignments) * layers}


def attention_work(config, global_batch, length):
  """Forward+backward FLOPs of attention's own products (``Q K^T`` and
  ``P V``) over the pairs a layer really needs: the causal half ``L^2 /
  2`` a sequence on a ``full_attention`` layer, ``W (W + 1) / 2 + (L -
  W) W`` under a window ``W < L``; ``2 x 2 x head_dim x query heads`` a
  pair forward and three times that with the backward pass.  (Documents
  need fewer still: an upper bound of the required work.)"""
  z = _sizes(config)
  w = min(z['window'], length)
  pairs = {'full_attention': length * length // 2,
           'sliding_attention': w * (w + 1) // 2 + (length - w) * w}
  per_pair = 3 * 2 * 2 * z['head_dim'] * z['q_heads']
  return {'flops': global_batch * per_pair * sum(
      pairs[kind] for kind in config['layer_types'])}


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
  dense = held_parameters(config) - parameters(config)['embedding']
  return {'flops': flops / chips, 'bytes': 28 * dense / chips}


# ---- the reference's side ------------------------------------------------


def _swiglu(matmul, p, u):
  import jax
  import jax.numpy as jnp
  gate, up = jnp.split(matmul(u, p['mlp_in']), 2, axis=-1)
  return matmul(jax.nn.silu(gate) * up, p['mlp_out'])


def _rotate(x, theta):
  """Rotary embedding as the family's published code applies it: ``x cos
  + rotate_half(x) sin`` with the angles ``position x theta^(-2i / D)``
  repeated over the head's two halves.  ``x [S, n, L, D]``, positions
  ``0 .. L - 1`` of the sequence (not of the document: ``assumed``)."""
  import jax.numpy as jnp
  length, d = x.shape[-2], x.shape[-1]
  inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
  angle = jnp.outer(jnp.arange(length, dtype=jnp.float32), inv_freq)
  angle = jnp.concatenate([angle, angle], axis=-1)              # [L, D]
  half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
  return x * jnp.cos(angle) + half * jnp.sin(angle)


def _attention(z, config, kind, p, u, segment_ids, matmul):
  """Gated attention by full masked softmax, the key-value heads
  repeated to the query heads, a block of queries against EVERY key (one
  ``[S, heads, block, L]`` array of scores at a time): a
  ``sliding_attention`` layer is the same scores under a band mask."""
  import jax
  import jax.numpy as jnp
  seqs, length, _ = u.shape
  eps = config['rms_norm_eps']
  heads = lambda a, n: jnp.swapaxes(
      a.reshape(seqs, length, n, z['head_dim']), 1, 2)       # [S, n, L, D]
  repeat = z['q_heads'] // z['kv_heads']
  # per-head norms with a learned gain (assumed: the published code)
  q = _rms_norm(heads(matmul(u, p['q_proj']), z['q_heads']), p['q_norm'], eps)
  k = _rms_norm(heads(matmul(u, p['k_proj']), z['kv_heads']), p['k_norm'],
                eps)
  v = heads(matmul(u, p['v_proj']), z['kv_heads'])
  if kind == 'sliding_attention':
    # rotary on the windowed layers only (assumed: the published code)
    q, k = (_rotate(a, float(config['rope_theta'])) for a in (q, k))
  k, v = (jnp.repeat(a, repeat, axis=1) for a in (k, v))
  position = jnp.arange(length)
  scale = z['head_dim'] ** -0.5

  def attend(qb, seg_q, pos_q):
    """``qb [Bq, S, n, D]``, ``seg_q [Bq, S]``, ``pos_q [Bq]``."""
    scores = matmul(jnp.moveaxis(qb, 0, 2),
                    jnp.swapaxes(k, 2, 3)) * scale            # [S, n, Bq, L]
    mask = ((seg_q.T[:, :, None] == segment_ids[:, None, :])
            & (pos_q[:, None] >= position[None, :]))
    if kind == 'sliding_attention':
      mask = mask & (pos_q[:, None] - position[None, :] < z['window'])
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    return jnp.moveaxis(matmul(jax.nn.softmax(scores, axis=-1), v), 2, 0)

  out = _in_blocks(attend, _block(length, 128), jnp.moveaxis(q, 2, 0),
                   segment_ids.T, position)              # [L / Bq, Bq, S, n, D]
  out = jnp.moveaxis(out.reshape((length,) + out.shape[2:]), 0, 1)
  # the output gate (assumed: the published code)
  out = out.reshape(seqs, length, -1) * jax.nn.sigmoid(
      matmul(u, p['gate_proj']))
  return matmul(out, p['o_proj'])


def dense_routing_weights(z, config, p, u):
  """``[T, router width]``: what each token multiplies each expert's
  output by, 0 for an expert it did not choose.  The scores are float32
  at ``Precision.HIGHEST`` whatever ``matmul`` the caller was handed."""
  import jax
  import jax.numpy as jnp
  scores = jax.nn.sigmoid(jnp.matmul(
      u.astype(jnp.float32), p['router'],
      precision=jax.lax.Precision.HIGHEST))
  # the selection bias moves the choice and takes no gradient; it stays
  # at its initial zeros (assumed: the trainer's own update is left out)
  _, sel = jax.lax.top_k(scores + jax.lax.stop_gradient(p['expert_bias']),
                         z['per_token'])
  chosen = jnp.sum(jax.nn.one_hot(sel, z['router'], dtype=scores.dtype),
                   axis=1)
  picked = scores * chosen
  # route_norm: the sum is over ALL the chosen, held here or not
  return float(config['route_scale']) * picked / (
      jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)


def _routed(z, config, p, u, matmul):
  """The shared expert plus the held experts' part: one expert after the
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

  y, _ = jax.lax.scan(step, _swiglu(matmul, p['shared'], flat),
                      (p['experts_in'], p['experts_out'], held))
  return y.reshape(u.shape)


def _layer(z, config, kind, matmul, p, x, segment_ids):
  """One residual block: a norm before and after each sub-layer
  (assumed: the published code).  Attention and feed-forward are
  recomputed apart in the backward pass, the dense SwiGLU a block of
  positions at a time."""
  import jax
  import jax.numpy as jnp
  eps = config['rms_norm_eps']

  @jax.checkpoint
  def attn(p, x):
    out = _attention(z, config, kind, p['attention'],
                     _rms_norm(x, p['input_norm'], eps), segment_ids, matmul)
    return _rms_norm(out, p['post_attn_norm'], eps)

  x = x + attn(p, x)
  u = _rms_norm(x, p['pre_mlp_norm'], eps)
  if 'moe' in p:
    ffn = _routed(z, config, p['moe'], u, matmul)
  else:
    length = x.shape[1]
    ffn = _in_blocks(
        lambda ub: _swiglu(matmul, p, jnp.swapaxes(ub, 0, 1)),
        _block(length, 1024), jnp.swapaxes(u, 0, 1))       # [L / B, S, B, d]
    ffn = jnp.swapaxes(ffn, 0, 1).reshape(x.shape)
  return x + _rms_norm(ffn, p['post_mlp_norm'], eps)


def reference_head(config):
  """``loss(dense, emb_outs, batch, matmul, tables)``: the forward pass
  and the mean next-token cross-entropy over the positions that have a
  target, the logits a block of positions at a time."""
  import jax
  import jax.numpy as jnp
  z = _sizes(config)

  def loss(dense, emb_outs, batch, matmul, tables):
    del tables                         # untied: the head is ``lm_head``
    targets, segment_ids = batch
    x = emb_outs[0].reshape(targets.shape + (z['hidden'],))
    if config['mup_enabled']:
      x = x * z['hidden'] ** 0.5       # (assumed: the factor)
    for kind, p in zip(config['layer_types'], dense['layers']):
      x = jax.checkpoint(functools.partial(_layer, z, config, kind, matmul))(
          p, x, segment_ids)
    x = _rms_norm(x, dense['final_norm'], config['rms_norm_eps'])

    def block_nll(xb, tb):
      logp = jax.nn.log_softmax(matmul(xb, dense['lm_head']), axis=-1)
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
  ``combiner=None`` table of ``DistributedEmbedding`` that only the
  lookup reads (``head_reads_tables`` empty: the sparse apply's default
  path under ``SparseAdam``), natural storage."""
  from distributed_embeddings_tpu.models import moe_lm as prog
  from distributed_embeddings_tpu.parallel import (DistributedEmbedding,
                                                   TableConfig)
  (rows, width, _), = tables(config)[0]
  dist = DistributedEmbedding(
      [TableConfig(input_dim=rows, output_dim=width, combiner=None,
                   name='vocabulary')],
      mesh=mesh, dp_input=True, packed_storage=False)
  cfg = prog.MoELMConfig.from_dict(config)
  return builders.finish(config, seed, dist, prog.make_head_loss_fn(cfg))
