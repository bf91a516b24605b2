"""Hybrid Mamba-2 / mixture-of-experts language models over an untied
vocabulary (the published ``nemotron_h`` family): a stack of residual
blocks of ONE sub-layer each, given by ``hybrid_override_pattern``
(``M`` a Mamba-2 mixer whose B and C come in ``n_groups`` groups, ``*``
grouped-query attention without positional embedding, ``E`` a routed
feed-forward of squared-ReLU experts of which this chip HOLDS
``n_routed_experts``, ``first_expert ..`` of the ``published``
``n_routed_experts`` the router scores, beside one shared squared-ReLU
expert).  The token table is looked up per position and read by nothing
else; the output head is a dense leaf.  The five names a configuration
of this class gives (``lib/builders``), and ``scan_work`` and
``expert_work`` for the rooflines.

The reference's side (``reference_head``) is the forward pass in
straightforward ``jax.numpy``, an independent route to the program's
numbers: the state-space recurrence is SEQUENTIAL, position by position
(``classes/hybrid_ssm._sequential_scan``, one group's heads against that
group's B and C, the groups side by side under ``jax.vmap``), where the
program computes it in chunks; attention is the full masked softmax,
a block of queries against every key (``classes/hybrid_ssm._attention``);
the routed layer is a LOOP over the held experts, each a dense
squared-ReLU expert over every token times that token's weight for the
expert (0 where the token did not choose it), where the program sorts,
gathers and runs grouped products.  Every matrix product goes through
the ``matmul`` the reference hands it, except the router's, which is
float32 at ``Precision.HIGHEST`` under every ``matmul`` (a selection is
discrete).  Layers run under ``jax.checkpoint``.  Nothing of the program
is imported outside ``builder``.

Equations, keys as in the published ``config.json``; every item the
config's keys do not fix is in the configuration file's ``assumed``::

  x0 = row
  x' = x + sub(rmsnorm(x))              one sub-layer a block: M, * or E
  logits = rmsnorm(x_last) @ lm_head
  loss = mean next-token cross-entropy over targets that are not -1

  M: z | xBC | dt = u W_in;  xBC = silu(causal_conv(xBC) + conv_bias)
     x [heads, P], B, C [groups, N]; head h reads group h // (heads / groups)
     dt = softplus(dt + dt_bias);  A = -exp(A_log)
     h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T;  y_t = h_t C_t + D x_t
     out = concat_g rmsnorm((y * silu(z))[group g]) W_out
  *: q, k, v = u Wq, u Wk, u Wv;  softmax(q k / sqrt(head_dim)) over j <= i
     of i's document;  out = (p v) Wo
  E: s = sigmoid(float32(u) Wr);  sel = top_k(s + e_score_correction_bias)
     w_e = routed_scaling_factor s_e / (sum_{sel} s + 1e-20)
     y = relu2_shared(u) + sum_{e in sel, e held} w_e relu2_e(u)
     relu2(u) = relu(u W_up)^2 W_down

What the absent experts would add is left out here as in the program (the
chip's share of a stated deployment), and ids, logits and loss are over
the vocabulary slice.  Packed documents are independent.
"""

import concurrent.futures
import functools
import json

import numpy as np

from benchmarks.classes.hybrid_ssm import (_attention, _block, _conv,
                                           _in_blocks, _keep_freed_memory,
                                           _rms_norm, _sequential_scan,
                                           tables)
from benchmarks.lib import builders

__all__ = ['tables', 'builder', 'reference_head', 'dense_params', 'work',
           'scan_work', 'expert_work']

# a ``hybrid_override_pattern``'s letters
_KINDS = {'M': 'mamba', '*': 'attention', 'E': 'moe'}


def _kinds(config):
  unknown = set(config['hybrid_override_pattern']) - set(_KINDS)
  if unknown:
    raise ValueError(f'unknown layer letters {sorted(unknown)}')
  return [_KINDS[c] for c in config['hybrid_override_pattern']]


def _sizes(config):
  heads, d_head = int(config['mamba_num_heads']), int(config['mamba_head_dim'])
  groups, state = int(config['n_groups']), int(config['ssm_state_size'])
  inner = heads * d_head
  return dict(
      hidden=int(config['hidden_size']), vocab=int(config['vocab_size']),
      heads=heads, d_head=d_head, inner=inner, groups=groups, state=state,
      conv_dim=inner + 2 * groups * state, taps=int(config['conv_kernel']),
      q_heads=int(config['num_attention_heads']),
      kv_heads=int(config['num_key_value_heads']),
      head_dim=int(config['head_dim']),
      expert_ffn=int(config['moe_intermediate_size']),
      shared_ffn=int(config['moe_shared_expert_intermediate_size']),
      held=int(config['n_routed_experts']),
      first=int(config.get('first_expert', 0)),
      # the router scores every expert of the model, held here or not
      router=int(config.get('published', {}).get(
          'n_routed_experts', config['n_routed_experts'])),
      per_token=int(config['num_experts_per_tok']),
      eps=float(config['layer_norm_epsilon']))


_DRAWN = {}    # the last draw of ``dense_params``, by configuration and seed


def dense_params(config, seed):
  """The dense parameters of ``_draw``, read-only; the builder and the
  reference of one run get the same host arrays (as
  ``classes/hybrid_ssm.dense_params``: a second copy is 2.7 GB)."""
  key = (json.dumps(config, sort_keys=True), int(seed))
  if key not in _DRAWN:
    _DRAWN.clear()
    _DRAWN[key] = _draw(config, seed)
  return _DRAWN[key]


def _draw(config, seed):
  """``{'layers': [{input_norm, mamba: {in_proj, conv_kernel, conv_bias,
  dt_bias, A_log, D, gated_norm, out_proj} or attention: {q_proj, k_proj,
  v_proj, o_proj}} or {pre_mlp_norm, moe: {router, expert_bias, shared:
  {mlp_in, mlp_out}, experts_in, experts_out}}], 'final_norm', 'lm_head'}``
  as host numpy: kernels ``N(0, 1/fan_in)`` (an expert's from its own
  fan-in, the convolution's from its taps), gains and ``D`` 1, the
  convolution's bias and ``expert_bias`` 0, ``A_log = log(U[1, 16])`` and
  ``dt_bias`` the inverse softplus of log-uniform ``[time_step_min,
  time_step_max]``, both from the stream ``[seed, 5]`` in turn, a Mamba
  layer after the other (``assumed``).  Every kernel is drawn from a
  stream of its own, ``[seed, 5, its number]``, on a few threads: 664 M
  normals at the published widths."""
  import jax
  _keep_freed_memory()
  z = _sizes(config)
  rng = np.random.default_rng([int(seed), 5])
  d, inner, heads = z['hidden'], z['inner'], z['heads']
  q_width, kv = z['q_heads'] * z['head_dim'], z['kv_heads'] * z['head_dim']
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
  low, high = (float(config[k]) for k in ('time_step_min', 'time_step_max'))
  layers = []
  for kind in _kinds(config):
    if kind == 'mamba':
      dt = np.exp(rng.uniform(np.log(low), np.log(high), heads))
      layers.append({'input_norm': ones(d), 'mamba': {
          'in_proj': kernel(d, inner + z['conv_dim'] + heads),
          'conv_kernel': kernel(z['taps'], z['conv_dim']),
          'conv_bias': np.zeros(z['conv_dim'], np.float32),
          'dt_bias': (dt + np.log(-np.expm1(-dt))).astype(np.float32),
          'A_log': np.log(rng.uniform(1.0, 16.0, heads)).astype(np.float32),
          'D': ones(heads), 'gated_norm': ones(inner),
          'out_proj': kernel(inner, d)}})
    elif kind == 'attention':
      layers.append({'input_norm': ones(d), 'attention': {
          'q_proj': kernel(d, q_width), 'k_proj': kernel(d, kv),
          'v_proj': kernel(d, kv), 'o_proj': kernel(q_width, d)}})
    else:
      layers.append({'pre_mlp_norm': ones(d), 'moe': {
          'router': kernel(d, z['router']),
          'expert_bias': np.zeros(z['router'], np.float32),
          'shared': {'mlp_in': kernel(d, z['shared_ffn']),
                     'mlp_out': kernel(z['shared_ffn'], d)},
          'experts_in': kernel(z['held'], d, z['expert_ffn']),
          'experts_out': kernel(z['held'], z['expert_ffn'], d)}})
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
  it is one): ``mamba`` a mixer (its projections, the convolution's taps
  and bias, ``dt_bias``, ``A_log``, ``D`` and the gated norm's gain),
  ``attention`` a layer, ``router`` (with its selection bias), ``shared``,
  ``expert`` (one routed expert), ``embedding`` and ``head``."""
  z = _sizes(config)
  d, inner, heads = z['hidden'], z['inner'], z['heads']
  q_width, kv = z['q_heads'] * z['head_dim'], z['kv_heads'] * z['head_dim']
  return {
      'mamba': (d * (inner + z['conv_dim'] + heads) + inner * d
                + (z['taps'] + 1) * z['conv_dim'] + 3 * heads + inner),
      'attention': 2 * d * q_width + 2 * d * kv,
      'router': d * z['router'] + z['router'],
      'shared': 2 * d * z['shared_ffn'], 'expert': 2 * d * z['expert_ffn'],
      'embedding': z['vocab'] * d, 'head': d * z['vocab']}


def _counts(config):
  kinds = _kinds(config)
  return tuple(kinds.count(k) for k in ('mamba', 'attention', 'moe'))


def held_parameters(config):
  """Every parameter this file's stack holds but the layers' norm gains."""
  z, n = _sizes(config), parameters(config)
  mambas, attentions, routed = _counts(config)
  return (mambas * n['mamba'] + attentions * n['attention']
          + routed * (n['router'] + n['shared'] + z['held'] * n['expert'])
          + n['embedding'] + n['head'])


def matrix_parameters(config):
  """Parameters that EVERY token multiplies by: a mixer's in- and
  out-projection, attention's four projections, a routed layer's router
  and shared expert, and the output head once (the lookup multiplies
  nothing; a routed expert is met by its assignments only:
  ``expert_work``).  Taps, gains, biases and the state-space vectors are
  left out."""
  z, n = _sizes(config), parameters(config)
  d = z['hidden']
  mambas, attentions, routed = _counts(config)
  projections = d * (z['inner'] + z['conv_dim'] + z['heads']) + z['inner'] * d
  return (mambas * projections + attentions * n['attention']
          + routed * (d * z['router'] + n['shared']) + n['head'])


def expected_assignments(config, tokens):
  """Assignments one routed layer holds here if the router spreads its
  ``num_experts_per_tok`` choices evenly: ``tokens x k x held / width``."""
  z = _sizes(config)
  return tokens * z['per_token'] * z['held'] / z['router']


def expert_work(config, tokens):
  """Forward+backward FLOPs and least bytes of the held experts' grouped
  products of one step over ``tokens`` positions, all routed layers
  together, at the EXPECTED count of assignments, counted as
  ``classes/moe_lm.expert_work`` counts them but for TWO matrices an
  expert (up, down: not gated): ``3 x 2 x`` an expert's parameters an
  assignment; the held experts' weights read forward and backward and
  their gradient written, four rows of ``hidden`` an assignment.  No
  recomputation is counted."""
  z, n = _sizes(config), parameters(config)
  layers = _counts(config)[2]
  assignments = expected_assignments(config, tokens)
  return {'flops': 3 * 2 * n['expert'] * assignments * layers,
          'bytes': 4 * (3 * z['held'] * n['expert']
                        + 4 * z['hidden'] * assignments) * layers}


def attention_work(config, global_batch, length):
  """Forward+backward FLOPs of attention's own products (``Q K^T`` and
  ``P V``) over the causal half ``L^2 / 2`` a sequence and attention
  layer: ``2 x 2 x head_dim x query heads`` a pair forward and three
  times that with the backward pass (documents need fewer: an upper
  bound)."""
  z = _sizes(config)
  per_pair = 3 * 2 * 2 * z['head_dim'] * z['q_heads']
  return {'flops': global_batch * per_pair * (length * length // 2)
                   * _counts(config)[1]}


def scan_work(config, tokens):
  """Forward+backward FLOPs and least bytes of the state-space scans of
  one step over ``tokens`` positions, all Mamba layers together, as
  ``classes/hybrid_ssm.scan_work`` counts them with B and C in groups.

  FLOPs, the sequential recurrence: per position and head ``5 x P x N``
  forward (decay, outer product, read-out), three times that with the
  backward pass; a head's state is ``P x N`` whatever the groups.  Bytes,
  float32: forward reads ``xBC`` (``inner + 2 x groups x N`` wide: each
  group's B and C once, however many heads read them) and ``dt`` and
  writes ``y``; backward reads ``xBC``, ``dt`` and ``y``'s cotangent and
  writes the cotangents of ``xBC`` and ``dt``; the chunk states (``H x P
  x N`` per chunk of ``chunk_size`` positions) are written and read once
  forward and once backward."""
  z = _sizes(config)
  layers = _counts(config)[0]
  per_state = z['heads'] * z['d_head'] * z['state']
  flops = 3 * 5 * per_state * tokens * layers
  per_position = (2 * (z['conv_dim'] + z['heads']) + z['inner']      # reads
                  + z['inner'] + z['conv_dim'] + z['heads'])         # writes
  states = 4 * per_state * tokens / int(config['chunk_size'])
  return {'flops': flops,
          'bytes': 4 * (per_position * tokens + states) * layers}


def work(config, model, global_batch, chips, mix):
  """Forward+backward FLOPs of the head for one chip's share of a step,
  and the bytes it must move beyond the distinct rows: ``6 x tokens x
  matrix_parameters`` + the experts' (``expert_work``) + the scans'
  (``scan_work``) + attention's own (``attention_work``); every dense
  parameter's Adam update reads weight, gradient and two moments and
  writes weight and moments, 28 bytes a parameter (the table's are the
  rows' and counted with them)."""
  del model
  length = int(mix['seq_len'])
  tokens = global_batch * length
  flops = (6 * tokens * matrix_parameters(config)
           + expert_work(config, tokens)['flops']
           + scan_work(config, tokens)['flops']
           + attention_work(config, global_batch, length)['flops'])
  dense = held_parameters(config) - parameters(config)['embedding']
  return {'flops': flops / chips, 'bytes': 28 * dense / chips}


# ---- the reference's side ------------------------------------------------


def _mamba(z, p, u, segment_ids, matmul):
  """The Mamba-2 mixer with B and C in groups.  The projection with the
  convolution, and the gated norm with ``out_proj``, are each recomputed
  in the backward pass (``jax.checkpoint``); the scan keeps its own block
  states."""
  import jax
  import jax.numpy as jnp
  inner, groups, heads = z['inner'], z['groups'], z['heads']
  width = groups * z['state']

  @jax.checkpoint
  def projected(u):
    zxbcdt = matmul(u, p['in_proj'])
    xbc = jax.nn.silu(_conv(zxbcdt[..., inner:inner + z['conv_dim']],
                            p['conv_kernel'], p['conv_bias'], segment_ids))
    dt = jax.nn.softplus(zxbcdt[..., inner + z['conv_dim']:] + p['dt_bias'])
    return (zxbcdt[..., :inner], xbc[..., :inner],
            xbc[..., inner:inner + width], xbc[..., inner + width:], dt)

  @jax.checkpoint
  def gated(y, x, gate):
    # the gate BEFORE the norm, each group's channels normalised apart
    # (assumed: the published code's gated RMSNorm)
    y = (y + p['D'][:, None] * x).reshape(gate.shape) * jax.nn.silu(gate)
    by_group = y.reshape(y.shape[:-1] + (groups, inner // groups))
    normed = _rms_norm(by_group, p['gated_norm'].reshape(groups, -1),
                       z['eps'])
    return matmul(normed.reshape(gate.shape), p['out_proj'])

  gate, x, b, c, dt = projected(u)
  seqs, length = u.shape[:2]
  per = heads // groups
  # head h reads group h // per: the groups side by side
  x = x.reshape(seqs, length, groups, per, z['d_head'])
  dt = dt.reshape(seqs, length, groups, per)
  b, c = (a.reshape(seqs, length, groups, z['state']) for a in (b, c))
  y = jax.vmap(functools.partial(_sequential_scan, matmul=matmul),
               in_axes=(2, 2, 0, 2, 2, None), out_axes=2)(
      x, dt, -jnp.exp(p['A_log']).reshape(groups, per), b, c, segment_ids)
  return gated(y.reshape(seqs, length, heads, z['d_head']),
               x.reshape(seqs, length, heads, z['d_head']), gate)


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
      jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)


def _relu2(matmul, p, u):
  import jax
  import jax.numpy as jnp
  return matmul(jnp.square(jax.nn.relu(matmul(u, p['mlp_in']))),
                p['mlp_out'])


def _routed(z, config, p, u, matmul):
  """The shared expert plus the held experts' part: one expert after the
  other, each a dense squared-ReLU expert over every token."""
  import jax
  import jax.numpy as jnp
  flat = u.reshape(-1, u.shape[-1])
  weights = dense_routing_weights(z, config, p, flat)
  held = jnp.swapaxes(weights[:, z['first']:z['first'] + z['held']], 0, 1)

  @jax.checkpoint
  def one(flat, kernel_in, kernel_out, w):
    return w[:, None] * _relu2(matmul, {'mlp_in': kernel_in,
                                        'mlp_out': kernel_out}, flat)

  def step(y, xs):
    return y + one(flat, *xs), None

  y, _ = jax.lax.scan(step, _relu2(matmul, p['shared'], flat),
                      (p['experts_in'], p['experts_out'], held))
  return y.reshape(u.shape)


def _layer(z, config, kind, matmul, p, x, segment_ids):
  """One residual block of one sub-layer under a norm before it and none
  after (assumed: the published code)."""
  if kind == 'moe':
    u = _rms_norm(x, p['pre_mlp_norm'], z['eps'])
    return x + _routed(z, config, p['moe'], u, matmul)
  u = _rms_norm(x, p['input_norm'], z['eps'])
  if kind == 'mamba':
    return x + _mamba(z, p['mamba'], u, segment_ids, matmul)
  # no positional embedding, scale 1 / sqrt(head_dim) (assumed: the
  # published code)
  return x + _attention(z, z['head_dim'] ** -0.5, 128, p['attention'], u,
                        segment_ids, matmul)


def reference_head(config):
  """``loss(dense, emb_outs, batch, matmul, tables)``: the forward pass
  and the mean next-token cross-entropy over the positions that have a
  target, the logits a block of positions at a time."""
  import jax
  import jax.numpy as jnp
  z = _sizes(config)
  kinds = _kinds(config)

  def loss(dense, emb_outs, batch, matmul, tables):
    del tables                         # untied: the head is ``lm_head``
    targets, segment_ids = batch
    x = emb_outs[0].reshape(targets.shape + (z['hidden'],))
    for kind, p in zip(kinds, dense['layers']):
      x = jax.checkpoint(functools.partial(_layer, z, config, kind, matmul))(
          p, x, segment_ids)
    x = _rms_norm(x, dense['final_norm'], z['eps'])

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
  """Through the program's ``models.moe_lm`` head (the ``nemotron_h``
  family), the vocabulary one ``combiner=None`` table of
  ``DistributedEmbedding`` that only the lookup reads
  (``head_reads_tables`` empty: the sparse apply's default path under
  ``SparseAdam``), natural storage.  The configuration is read before
  anything is drawn: a program without the family refuses it at once."""
  from distributed_embeddings_tpu.models import moe_lm as prog
  from distributed_embeddings_tpu.parallel import (DistributedEmbedding,
                                                   TableConfig)
  cfg = prog.MoELMConfig.from_dict(config)
  (rows, width, _), = tables(config)[0]
  dist = DistributedEmbedding(
      [TableConfig(input_dim=rows, output_dim=width, combiner=None,
                   name='vocabulary')],
      mesh=mesh, dp_input=True, packed_storage=False)
  return builders.finish(config, seed, dist, prog.make_head_loss_fn(cfg))
