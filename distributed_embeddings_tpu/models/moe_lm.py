"""Sparse mixture-of-experts language models: a routed feed-forward layer
of which this chip holds a share, under a sequence mixer that is windowed
or full attention or a gated short convolution, over an untied or a tied
vocabulary.

A stack of residual blocks given by ``layer_types``
(``'sliding_attention'``, ``'full_attention'`` or ``'conv'``): the mixer,
then a feed-forward that is a dense SwiGLU in the first
``num_dense_layers`` blocks and, in the others, the routed experts held
here (``layers/routed_experts.py``) beside ``num_shared_experts`` (1 or
0) shared-expert SwiGLU.  The token table is ONE table behind
``DistributedEmbedding`` (``combiner=None``, one id per position).
Untied, only the lookup reads it: it takes the sparse apply's default
path under ``SparseAdam`` (``make_hybrid_train_step(...,
head_reads_tables=())``) and the output head ``lm_head [hidden,
vocabulary]`` is a dense leaf of its own.  Tied
(``tie_word_embeddings``), the head multiplies by the table again
(``head_reads_tables=(0,)``, docs/design.md §25) and there is no
``lm_head``.

Two published sigmoid-routed families, told apart by ``model_type``
(``MoELMConfig.from_dict``); keys as in their ``config.json``::

  afmoe      x0 = row * sqrt(hidden_size)                 (mup_enabled)
             h  = x + rmsnorm(attn(rmsnorm(x)))           a norm before AND
             x' = h + rmsnorm(ffn(rmsnorm(h)))            after each sub-layer
             logits = rmsnorm(x_last) @ lm_head
  lfm2_moe   x0 = row
             h  = x + op(rmsnorm(x));  x' = h + ffn(rmsnorm(h))
             logits = rmsnorm(x_last) @ table^T           (tied)

  attn(u): q = u Wq [Hq x D]; k = u Wk, v = u Wv [Hkv x D]
           q = rmsnorm_D(q), k = rmsnorm_D(k)             per head, learned gain
           rotary(q, k; rope_theta, the whole head, the position in the
           sequence) on the kinds in ``rotary_layers``: afmoe's
           sliding_attention layers only, every attention layer of lfm2_moe
           p_ij = softmax_j(q_i . k_j / sqrt(D)) over j <= i of i's document,
                  and i - j < sliding_window on a sliding_attention layer
           out  = (p v) Wo;  afmoe: ((p v) * sigmoid(u Wg)) Wo

  conv(u): [B | C | z] = u W_in                           [hidden, 3 x hidden]
           y = C * causal_conv(B * z)      depthwise over positions,
                                           conv_L_cache taps, the last on the
                                           current position, no bias
           out = y W_out

  ffn, dense layers:  swiglu(u) = (silu(u Wgate) * (u Wup)) Wdown
  ffn, routed layers: [swiglu_shared(u) +] the held experts' part of
                      sum_{e in top_k} w_e swiglu_e(u)    (routed_experts.py)

PACKED DOCUMENTS ARE INDEPENDENT (``segment_ids``), as in
``models/hybrid_ssm.py``, whose ``rms_norm``, ``swiglu``,
``blocked_attention``, ``causal_conv`` and ``vocab_loss`` this stack
shares (and its recomputation, here a half-layer at a time: ``layer``):
a ``sliding_attention`` layer computes only the key blocks that meet a
query block's window, and a convolution's tap that would reach into
another document reads zero.  The router's selection bias
(``expert_bias``) is a leaf that stays where it was initialised: the
published trainers move it by a rule outside forward and backward, which
``make_hybrid_train_step`` has no place for, and its gradient is exactly
nought.

Device phases (inside ``head``): ``attention/window``, ``attention/full``
(both under ``attention``), ``mixer/short_conv`` (the WHOLE operator,
projections and gates included, as an attention phase holds its own: a
fusion carries one name, and XLA fuses the gates into the products beside
them), ``mlp`` (the dense SwiGLU), ``moe/route``, ``moe/dispatch``,
``moe/experts``, ``moe/combine``, ``moe/shared``, ``vocab``.  Counters, at
trace time: ``mixer.short_conv_layers``, ``moe.kept_outputs``.  Gauges,
set outside the step by ``record_routing_stats``:
``moe.assignments_held``, ``moe.load_max_over_mean``,
``moe.overflow_rows``.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from distributed_embeddings_tpu.layers import routed_experts as routed
from distributed_embeddings_tpu.models.hybrid_ssm import (
    blocked_attention, causal_conv, count_batch, rms_norm, swiglu, vocab_loss)
from distributed_embeddings_tpu.obs import metrics as obs_metrics
from distributed_embeddings_tpu.obs import trace as obs_trace

__all__ = ['MoELMConfig', 'init_params', 'count_batch', 'forward',
           'make_head_loss_fn', 'selections', 'routing_stats',
           'record_routing_stats']

_KINDS = ('sliding_attention', 'full_attention', 'conv')
# the one array the feed-forward half's checkpoint keeps, where the
# backward pass reads it: a routed sub-layer's output (``layer``)
_ROUTED_OUTPUT = 'routed_ffn'
# What a family's modelling code fixes and no key of its ``config.json``
# states (``fixed``), and the keys it names otherwise (``keys``), by
# ``model_type``; a file without one is ``afmoe``'s.
_FAMILIES = {
    'afmoe': {'fixed': dict(tie_word_embeddings=False), 'keys': {}},
    'lfm2_moe': {
        'fixed': dict(mup_enabled=False, sandwich_norms=False,
                      attention_gate=False, rotary_layers=('full_attention',),
                      route_norm_eps=1e-6, num_shared_experts=0,
                      tie_word_embeddings=True, sliding_window=0),
        'keys': {'norm_eps': 'rms_norm_eps',
                 'routed_scaling_factor': 'route_scale'}},
}


@dataclasses.dataclass(frozen=True)
class MoELMConfig:
  """Sizes of the stack, named as the published configuration names
  them.  ``num_experts`` counts the experts HELD here, ``first_expert ..``
  of the ``router_width`` the router scores; ``vocab_size`` the rows of
  the token table and the columns of ``lm_head``.  ``sandwich_norms``
  (a norm after each sub-layer too), ``attention_gate`` and
  ``rotary_layers`` (the kinds of attention that take rotary) are a
  family's own."""
  hidden_size: int
  vocab_size: int
  layer_types: Tuple[str, ...]
  num_dense_layers: int
  intermediate_size: int
  moe_intermediate_size: int
  num_experts: int
  router_width: int
  num_experts_per_tok: int
  num_attention_heads: int
  num_key_value_heads: int
  head_dim: int
  sliding_window: int
  rope_theta: float = 10000.0
  route_scale: float = 1.0
  first_expert: int = 0
  capacity_factor: float = 1.25
  rms_norm_eps: float = 1e-5
  mup_enabled: bool = True
  attention_block: int = 256
  vocab_block: int = 2048
  logits_scaling: float = 1.0       # ``vocab_loss`` divides by it
  conv_L_cache: int = 3             # taps of a ``conv`` layer
  num_shared_experts: int = 1
  tie_word_embeddings: bool = False
  route_norm_eps: float = 1e-20
  sandwich_norms: bool = True
  attention_gate: bool = True
  rotary_layers: Tuple[str, ...] = ('sliding_attention',)

  @classmethod
  def from_dict(cls, config: Dict[str, Any], **overrides):
    """From a ``config.json`` of the family; what this class does not
    compute is refused by name.  Where the file is a chip's share of a
    deployment, ``num_experts`` is the count held and the router keeps
    the width the file states under ``published``."""
    family = config.get('model_type', 'afmoe')
    if family not in _FAMILIES:
      raise NotImplementedError(f'moe_lm: model_type={family!r}')
    refused = {'score_func': ('sigmoid',), 'route_norm': (True,),
               'n_group': (1,), 'topk_group': (1,),
               'num_shared_experts': (0, 1), 'hidden_act': ('silu',),
               'rope_scaling': (None,), 'conv_bias': (False,),
               'use_expert_bias': (True,), 'norm_topk_prob': (True,)}
    fixed = _FAMILIES[family]['fixed']
    # tied or not is the family's: each was built and tested one way
    refused['tie_word_embeddings'] = (fixed['tie_word_embeddings'],)
    for key, only in refused.items():
      if config.get(key, only[0]) not in only:
        raise NotImplementedError(
            f'moe_lm: {key}={config[key]!r} (only {only!r})')
    unknown = set(config['layer_types']) - set(_KINDS)
    if unknown:
      raise NotImplementedError(f'moe_lm: layer types {sorted(unknown)}')
    renamed = {_FAMILIES[family]['keys'].get(k, k): v
               for k, v in config.items()}
    fields = {f.name for f in dataclasses.fields(cls)}
    picked = {**fixed, **{k: v for k, v in renamed.items() if k in fields}}
    picked['layer_types'] = tuple(config['layer_types'])
    picked['router_width'] = int(config.get('published', {}).get(
        'num_experts', config['num_experts']))
    picked.setdefault('head_dim', config['hidden_size']
                      // config['num_attention_heads'])
    if 'rope_parameters' in config:
      picked['rope_theta'] = float(config['rope_parameters']['rope_theta'])
    return cls(**{**picked, **overrides})

  @property
  def routed(self) -> routed.RoutedExpertsConfig:
    return routed.RoutedExpertsConfig(
        router_width=self.router_width,
        experts_per_token=self.num_experts_per_tok,
        num_held=self.num_experts, first_expert=self.first_expert,
        route_scale=self.route_scale, capacity_factor=self.capacity_factor,
        route_norm_eps=self.route_norm_eps)


def init_params(cfg: MoELMConfig, seed: int):
  """The dense parameters as host numpy: kernels ``N(0, 1/fan_in)`` (an
  expert's from its own fan-in, a convolution's from its taps), norm
  gains 1, ``expert_bias`` 0; no ``lm_head`` under a tied vocabulary.
  Every kernel is drawn from a stream of its own, ``[seed, 5, its
  number]``, on a few threads.  (The user's entry, as
  ``hybrid_ssm.init_params``.  The benchmark draws the same numbers by
  routines of its own, because its references may import nothing of the
  program; ``tests/test_moe_lm.py`` and ``tests/test_lfm2_moe.py`` hold
  the two equal.)"""
  d, heads = cfg.hidden_size, cfg.num_attention_heads * cfg.head_dim
  kv = cfg.num_key_value_heads * cfg.head_dim
  ffn, held = cfg.moe_intermediate_size, cfg.num_experts
  kernels = []

  def kernel(*shape):
    kernels.append(np.empty(shape, np.float32))
    return kernels[-1]

  def draw(i):
    out = kernels[i]
    np.random.default_rng([int(seed), 5, i]).standard_normal(
        out.shape, np.float32, out=out)
    out /= np.float32(np.sqrt(out.shape[-2]))

  ones = lambda n: np.ones(n, np.float32)
  layers = []
  for i, kind in enumerate(cfg.layer_types):
    p = {'input_norm': ones(d), 'pre_mlp_norm': ones(d)}
    if cfg.sandwich_norms:
      p.update(post_attn_norm=ones(d), post_mlp_norm=ones(d))
    if kind == 'conv':
      p['conv'] = {'in_proj': kernel(d, 3 * d),
                   'conv_kernel': kernel(cfg.conv_L_cache, d),
                   'out_proj': kernel(d, d)}
    else:
      p['attention'] = {'q_proj': kernel(d, heads), 'k_proj': kernel(d, kv),
                        'v_proj': kernel(d, kv)}
      if cfg.attention_gate:
        p['attention']['gate_proj'] = kernel(d, heads)
      p['attention'].update(o_proj=kernel(heads, d),
                            q_norm=ones(cfg.head_dim),
                            k_norm=ones(cfg.head_dim))
    if i < cfg.num_dense_layers:
      p['mlp_in'] = kernel(d, 2 * cfg.intermediate_size)
      p['mlp_out'] = kernel(cfg.intermediate_size, d)
    else:
      p['moe'] = {'router': kernel(d, cfg.router_width),
                  'expert_bias': np.zeros(cfg.router_width, np.float32)}
      if cfg.num_shared_experts:
        p['moe']['shared'] = {'mlp_in': kernel(d, 2 * ffn),
                              'mlp_out': kernel(ffn, d)}
      p['moe'].update(experts_in=kernel(held, d, 2 * ffn),
                      experts_out=kernel(held, ffn, d))
    layers.append(p)
  params = {'layers': layers, 'final_norm': ones(d)}
  if not cfg.tie_word_embeddings:
    params['lm_head'] = kernel(d, cfg.vocab_size)
  with concurrent.futures.ThreadPoolExecutor(max_workers=8) as pool:
    list(pool.map(draw, range(len(kernels))))
  return params


def rotary(x, theta: float):
  """Rotary position embedding over the whole head: ``x [S, L, ..., D]``
  at positions ``0 .. L - 1`` of the sequence, the head's two halves
  rotated against each other (``x1 cos - x2 sin | x2 cos + x1 sin``)."""
  length, d = x.shape[1], x.shape[-1]
  inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
  angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq
  shape = (1, length) + (1,) * (x.ndim - 3) + (d // 2,)
  cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
  x1, x2 = jnp.split(x, 2, axis=-1)
  return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(cfg: MoELMConfig, kind: str, p, u, segment_ids):
  """Grouped-query attention on ``u [S, L, hidden]``: per-head norms of
  queries and keys, rotary on the kinds in ``cfg.rotary_layers``, a
  window on a ``sliding_attention`` layer, a sigmoid output gate where
  the family has one."""
  sliding = kind == 'sliding_attention'
  scope = (obs_trace.phase('attention/window') if sliding
           else obs_trace.phase('attention/full'))
  with scope:
    seqs, length, _ = u.shape
    kv_heads, d = cfg.num_key_value_heads, cfg.head_dim
    group = cfg.num_attention_heads // kv_heads
    q = jnp.matmul(u, p['q_proj']).reshape(seqs, length, kv_heads, group, d)
    k = jnp.matmul(u, p['k_proj']).reshape(seqs, length, kv_heads, d)
    v = jnp.matmul(u, p['v_proj']).reshape(seqs, length, kv_heads, d)
    if cfg.attention_gate:
      gate = jnp.matmul(u, p['gate_proj'])
    q = rms_norm(q, p['q_norm'], cfg.rms_norm_eps)
    k = rms_norm(k, p['k_norm'], cfg.rms_norm_eps)
    if kind in cfg.rotary_layers:
      q, k = rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta)
    out = blocked_attention(
        d ** -0.5, q, k, v, segment_ids, cfg.attention_block,
        window=cfg.sliding_window if sliding else None)
    out = out.reshape(seqs, length, -1)
    if cfg.attention_gate:
      out = out * jax.nn.sigmoid(gate)
    return jnp.matmul(out, p['o_proj'])


def short_conv(p, u, segment_ids):
  """The gated short convolution on ``u [S, L, hidden]``: one
  projection to three parts ``B | C | z``, a depthwise causal convolution
  of ``B * z`` that stays inside its document
  (``hybrid_ssm.causal_conv``, no bias), gated by ``C``, projected out.
  One phase holds all of it."""
  obs_metrics.inc('mixer.short_conv_layers')
  with obs_trace.phase('mixer/short_conv'):
    b, c, z = jnp.split(jnp.matmul(u, p['in_proj']), 3, axis=-1)
    y = c * causal_conv(b * z, p['conv_kernel'], 0.0, segment_ids)
    return jnp.matmul(y, p['out_proj'])


def routed_ffn(cfg: MoELMConfig, p, u):
  """``(y, sel)``: the held experts' part (beside the shared expert,
  where the family has one) on ``u [S, L, hidden]``, and the experts
  each token took ``[S * L, k]``."""
  shared = None
  if cfg.num_shared_experts:
    with obs_trace.phase('moe/shared'):
      shared = swiglu(p['shared'], u)
  y, sel = routed.routed_experts(cfg.routed, p, u.reshape(-1, u.shape[-1]))
  y = y.reshape(u.shape)
  return (y if shared is None else shared + y), sel


def layer(cfg: MoELMConfig, kind: str, p, x, segment_ids):
  """One residual block: ``(x', sel)``, ``sel`` the experts each token
  took in a routed block and ``None`` in a dense one.  Mixer and
  feed-forward are rematerialised APART: the backward pass keeps the
  block's input and the state between the two, and recomputes one half
  at a time.  (Under one
  ``jax.checkpoint`` around the whole block, as ``hybrid_ssm.forward``
  has it, the attention blocks and the waves, which rematerialise
  themselves, kept their buffers alive across the block's recomputation:
  0.7 GiB more at the published sizes, compile-only for a v5e.)

  Of a routed block the backward pass keeps the sub-layer's OUTPUT too
  (``[S, L, hidden]`` float32), where a family's norm after it reads it
  (counter ``moe.kept_outputs``).  The waves rematerialise themselves
  and keep only their arguments, so all that recomputing them with the
  half could hand the backward pass is their sum, which that norm's
  gradient reads and nothing else does: for it every wave ran forward a
  third time (docs/design.md §27).  A dense block's recomputation is
  what its own backward reads, and nothing of it is kept.

  The norms before (and, in a family that has them, after) each
  sub-layer and the two adds are the phase ``residual``; the per-head
  norms of queries and keys stay ``attention``'s."""
  eps = cfg.rms_norm_eps
  if 'moe' in p and cfg.sandwich_norms:
    obs_metrics.inc('moe.kept_outputs')

  def norm(u, gain):
    with obs_trace.phase('residual'):
      return rms_norm(u, gain, eps)

  @jax.checkpoint
  def mixer(p, x):
    u = norm(x, p['input_norm'])
    out = (short_conv(p['conv'], u, segment_ids) if kind == 'conv'
           else attention(cfg, kind, p['attention'], u, segment_ids))
    return norm(out, p['post_attn_norm']) if cfg.sandwich_norms else out

  @functools.partial(
      jax.checkpoint,
      policy=jax.checkpoint_policies.save_only_these_names(_ROUTED_OUTPUT))
  def feed_forward(p, x):
    u = norm(x, p['pre_mlp_norm'])
    if 'moe' in p:
      ffn, sel = routed_ffn(cfg, p['moe'], u)
      ffn = checkpoint_name(ffn, _ROUTED_OUTPUT)
    else:
      ffn, sel = swiglu(p, u), None
    return (norm(ffn, p['post_mlp_norm']) if cfg.sandwich_norms
            else ffn), sel

  out = mixer(p, x)
  with obs_trace.phase('residual'):
    x = x + out
  ffn, sel = feed_forward(p, x)
  with obs_trace.phase('residual'):
    return x + ffn, sel


def _embed(cfg: MoELMConfig, rows):
  return rows * (cfg.hidden_size ** 0.5) if cfg.mup_enabled else rows


def _stack(cfg: MoELMConfig, dense, rows, segment_ids):
  """The blocks in turn: the last hidden states and every routed
  block's selection."""
  x, chosen = _embed(cfg, rows), []
  for kind, p in zip(cfg.layer_types, dense['layers']):
    x, sel = layer(cfg, kind, p, x, segment_ids)
    if sel is not None:
      chosen.append(sel)
  return x, chosen


def forward(cfg: MoELMConfig, dense, rows, segment_ids):
  """The stack's last hidden states from the looked-up rows ``[S, L,
  hidden]``, each half of a layer under ``jax.checkpoint`` (``layer``)."""
  return _stack(cfg, dense, rows, segment_ids)[0]


def make_head_loss_fn(cfg: MoELMConfig, table_id: int = 0):
  """``head_loss_fn`` for ``make_hybrid_train_step``: ``emb_outs[0]`` the
  looked-up rows ``[S * L, hidden]``, ``batch = (targets, segment_ids)``
  both ``[S, L]``.  Untied (the default ``head_reads_tables=()``) it is
  called ``(dense, emb_outs, batch)`` and ``dense['lm_head']`` is the
  output head ``[hidden, vocabulary]``; tied
  (``head_reads_tables=(table_id,)``) it is called with ``tables`` as
  well and ``tables[table_id]`` is the vocabulary ``[rows, hidden]``."""

  def head_loss_fn(dense, emb_outs, batch, tables=None):
    targets, segment_ids = batch
    x = forward(cfg, dense, emb_outs[0].reshape(
        targets.shape + (cfg.hidden_size,)), segment_ids)
    table = (tables[table_id] if cfg.tie_word_embeddings
             else dense['lm_head'].T)
    return vocab_loss(cfg, x, dense['final_norm'], table, targets)

  return head_loss_fn


def selections(cfg: MoELMConfig, dense, rows, segment_ids):
  """Forward only: the experts every token takes in each routed layer,
  ``[routed layers, S * L, experts a token]``, as the step's own blocks
  choose them."""
  return jnp.stack(_stack(cfg, dense, rows, segment_ids)[1])


def routing_stats(cfg: MoELMConfig, dense, rows, segment_ids):
  """Forward only: per routed layer (arrays ``[routed layers]``) the
  assignments this chip holds, the largest held expert's load over the
  mean and the assignments past the capacity
  (``routed_experts.routing_stats``).  Jit it and call it every N steps,
  outside the timed step; ``record_routing_stats`` sets the gauges."""
  return jax.vmap(functools.partial(routed.routing_stats, cfg.routed))(
      selections(cfg, dense, rows, segment_ids))


def record_routing_stats(stats):
  """The gauges ``moe.assignments_held`` (summed over the routed
  layers), ``moe.load_max_over_mean`` (the worst layer) and
  ``moe.overflow_rows`` (summed) from ``routing_stats``' arrays."""
  stats = {k: np.asarray(v) for k, v in stats.items()}
  obs_metrics.set_gauge('moe.assignments_held',
                        float(stats['assignments_held'].sum()))
  obs_metrics.set_gauge('moe.load_max_over_mean',
                        float(stats['load_max_over_mean'].max()))
  obs_metrics.set_gauge('moe.overflow_rows',
                        float(stats['overflow_rows'].sum()))
