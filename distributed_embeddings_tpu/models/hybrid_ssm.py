"""Hybrid state-space / attention language model over a tied vocabulary.

A stack of residual blocks given by ``layer_types``: each block is a
mixer, a Mamba-2 selective state-space layer (``'mamba'``) or causal
grouped-query attention without positional embedding (``'attention'``),
followed by a SwiGLU.  The vocabulary is ONE table behind
``DistributedEmbedding`` (``combiner=None``, one id per position) that
the head also multiplies by: ``make_hybrid_train_step(...,
head_reads_tables=(0,))`` hands it to ``head_loss_fn`` and joins the
head's gradient to the lookups' row sums (docs/design.md §25).

Equations (a published Mamba-2 / attention hybrid family; keys as in
its ``config.json``)::

  x0      = embedding_multiplier * row
  x      += residual_multiplier * mixer(rmsnorm(x))
  x      += residual_multiplier * swiglu(rmsnorm(x))
  logits  = rmsnorm(x) @ table^T / logits_scaling

  mamba:  z | xBC | dt = in_proj(u)            (d_inner | d_inner + 2N | H)
          xBC = silu(causal depthwise conv(xBC) + bias)
          dt  = softplus(dt + dt_bias);  A = -exp(A_log)     per head
          h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T         [P, N] per head
          y_t = h_t C_t + D x_t
          out = out_proj(rmsnorm(y * silu(z)))

PACKED DOCUMENTS ARE INDEPENDENT (``segment_ids``): the state and the
convolution's window restart at a document's first position and
attention sees only its own document.

The scan is the chunked state-space-dual form in plain ``jax.numpy``:
per chunk of ``mamba_chunk_size`` positions the cumulative log-decay,
the masked ``C B^T`` product against ``x``, and the chunk's end state;
a ``lax.scan`` carries the state from chunk to chunk, its body under
``jax.checkpoint`` so that no ``[heads, chunk, chunk]`` array outlives
its chunk.  Decay, ``softplus`` and the recurrence are float32; matrix
products take float32 operands at the backend's default precision (on
a TPU: bfloat16 products, float32 accumulation), as the other heads.

Attention's core (``blocked_attention``) is the fused kernels of
``ops/pallas_attention.py`` where the program is compiled for a TPU and
the shapes fit their tiles, and otherwise (a CPU, the tests' 48
positions and heads of 8) a Python loop over query blocks in plain
``jax.numpy``, each under ``jax.checkpoint`` against the keys up to its
own end.  Either way no ``[heads, L, L]`` array exists.

Every layer runs under ``jax.checkpoint``: the backward pass keeps the
layers' inputs and recomputes each layer's interior.

Device phases (``obs.trace.phase``; the step opens ``head`` around all
of it): ``mixer/proj``, ``mixer/conv``, ``mixer/selective_scan`` (a leaf
``scan`` would read as JAX's own ``scan`` to a trace reduction),
``attention``, ``mlp``, ``vocab``.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distributed_embeddings_tpu.obs import metrics as obs_metrics
from distributed_embeddings_tpu.obs import trace as obs_trace
from distributed_embeddings_tpu.ops import pallas_attention

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class HybridSSMConfig:
  """Sizes of the stack, named as the published configuration names
  them.  ``attention_block`` and ``vocab_block`` bound the positions a
  block of attention queries or of logits holds at once."""
  hidden_size: int
  layer_types: Tuple[str, ...]
  intermediate_size: int
  mamba_n_heads: int
  mamba_d_head: int
  mamba_d_state: int
  mamba_d_conv: int
  mamba_chunk_size: int
  num_attention_heads: int
  num_key_value_heads: int
  rms_norm_eps: float = 1e-5
  embedding_multiplier: float = 1.0
  residual_multiplier: float = 1.0
  attention_multiplier: float = 1.0
  logits_scaling: float = 1.0
  attention_block: int = 256
  vocab_block: int = 2048

  @classmethod
  def from_dict(cls, config: Dict[str, Any], **overrides):
    """From a ``config.json`` of the family; what this class does not
    compute is refused by name."""
    refused = {'mamba_n_groups': 1, 'mamba_proj_bias': False,
               'attention_bias': False, 'position_embedding_type': 'nope',
               'num_local_experts': 0, 'mamba_conv_bias': True}
    for key, only in refused.items():
      if config.get(key, only) != only:
        raise NotImplementedError(
            f'hybrid_ssm: {key}={config[key]!r} (only {only!r})')
    unknown = set(config['layer_types']) - {'mamba', 'attention'}
    if unknown:
      raise NotImplementedError(f'hybrid_ssm: layer types {sorted(unknown)}')
    fields = {f.name for f in dataclasses.fields(cls)}
    picked = {k: v for k, v in config.items() if k in fields}
    picked['layer_types'] = tuple(config['layer_types'])
    picked['intermediate_size'] = config.get('shared_intermediate_size',
                                             config['intermediate_size'])
    return cls(**{**picked, **overrides})

  @property
  def d_inner(self):
    return self.mamba_n_heads * self.mamba_d_head

  @property
  def conv_dim(self):
    return self.d_inner + 2 * self.mamba_d_state

  @property
  def head_dim(self):
    return self.hidden_size // self.num_attention_heads


def init_params(cfg: HybridSSMConfig, seed: int):
  """The dense parameters as host numpy: kernels ``N(0, 1/fan_in)``,
  ``A_log = log(U[1, 16])``, ``dt_bias`` the inverse softplus of
  log-uniform ``[1e-3, 1e-1]``, ``D`` and every gain 1, biases 0 (the
  family's convention).  Every kernel is drawn from a stream of its own,
  ``[seed, 5, its number]``, on a few threads (746 M normals at the
  published widths)."""
  rng = np.random.default_rng([int(seed), 5])
  d, inner, heads = cfg.hidden_size, cfg.d_inner, cfg.mamba_n_heads
  kv = cfg.num_key_value_heads * cfg.head_dim
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
  for kind in cfg.layer_types:
    if kind == 'mamba':
      dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), heads))
      mixer = {
          'in_proj': kernel(d, inner + cfg.conv_dim + heads),
          'conv_kernel': kernel(cfg.mamba_d_conv, cfg.conv_dim),
          'conv_bias': np.zeros(cfg.conv_dim, np.float32),
          'dt_bias': (dt + np.log(-np.expm1(-dt))).astype(np.float32),
          'A_log': np.log(rng.uniform(1.0, 16.0, heads)).astype(np.float32),
          'D': ones(heads), 'gated_norm': ones(inner),
          'out_proj': kernel(inner, d)}
    else:
      mixer = {'q_proj': kernel(d, d), 'k_proj': kernel(d, kv),
               'v_proj': kernel(d, kv), 'o_proj': kernel(d, d)}
    layers.append({'mixer_norm': ones(d), 'mixer': mixer,
                   'mlp_norm': ones(d),
                   'mlp_in': kernel(d, 2 * cfg.intermediate_size),
                   'mlp_out': kernel(cfg.intermediate_size, d)})
  with concurrent.futures.ThreadPoolExecutor(max_workers=8) as pool:
    list(pool.map(draw, range(len(kernels))))
  return {'layers': layers, 'final_norm': ones(d)}


def count_batch(batch):
  """Count one step's batch ``(targets, segment_ids)`` (host arrays) into
  the metrics registry: tokens, documents and loss-bearing positions."""
  targets, segment_ids = (np.asarray(a) for a in batch)
  obs_metrics.inc('train.tokens', targets.size)
  obs_metrics.inc('train.documents',
                  int(np.sum(segment_ids.max(axis=-1) + 1)))
  obs_metrics.inc('train.loss_positions', int(np.sum(targets >= 0)))


def _block(n: int, limit: int) -> int:
  """The largest divisor of ``n`` that is at most ``limit``."""
  return next(b for b in range(min(n, limit), 0, -1) if n % b == 0)


def rms_norm(x, gain, eps):
  return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                           + eps) * gain


def causal_conv(x, kernel, bias, segment_ids):
  """Depthwise causal convolution along positions: ``x [S, L, C]``,
  ``kernel [K, C]`` whose LAST tap multiplies the current position; a tap
  that would reach into another document (or before the sequence) reads
  zero."""
  taps = kernel.shape[0]
  out = bias + kernel[taps - 1] * x
  for back in range(1, taps):
    shifted = jnp.pad(x[:, :-back], ((0, 0), (back, 0), (0, 0)))
    before = jnp.pad(segment_ids[:, :-back], ((0, 0), (back, 0)),
                     constant_values=-1)
    same = (before == segment_ids)[..., None]
    out = out + kernel[taps - 1 - back] * jnp.where(same, shifted, 0.0)
  return out


def _ssd_chunk(a_heads, carry, chunk):
  """One chunk of the state-space-dual scan.  ``carry = (h [S, H, P, N],
  the segment id of the previous chunk's last position [S])``; ``chunk =
  (x [S, Q, H, P], dt [S, Q, H], B [S, Q, N], C [S, Q, N], seg [S, Q])``.
  Returns the carry after the chunk and ``y [S, Q, H, P]``."""
  h, prev_seg = carry
  x, dt, b, c, seg = chunk
  q = x.shape[1]
  dt_h = jnp.swapaxes(dt, 1, 2)                            # [S, H, Q]
  # inclusive cumulative log-decay, as a product with a triangle of ones
  # so that it stays float32 and keeps its scope (a cumsum carries none)
  upper = jnp.triu(jnp.ones((q, q), jnp.float32))
  cum = jnp.matmul(dt_h * a_heads[:, None], upper, precision=_HIGHEST)
  # within the chunk: position t reads s <= t of its own document
  mask = ((seg[:, :, None] == seg[:, None, :])
          & jnp.tril(jnp.ones((q, q), bool)))               # [S, t, s]
  decay = jnp.exp(jnp.where(mask[:, None],
                            cum[..., :, None] - cum[..., None, :],
                            -jnp.inf))                      # [S, H, t, s]
  cb = jnp.einsum('stn,sun->stu', c, b)
  y = jnp.einsum('shtu,suhp->sthp',
                 cb[:, None] * decay * dt_h[:, :, None, :], x)
  # the state that came in: read by the positions whose document ran on
  # from the previous chunk
  ran_on = (seg == prev_seg[:, None])[:, None, :]           # [S, 1, Q]
  into = jnp.swapaxes(jnp.exp(cum) * ran_on, 1, 2)          # [S, Q, H]
  y = y + jnp.einsum('stn,shpn->sthp', c, h) * into[..., None]
  # the state that goes out: the last document's positions, decayed to
  # the chunk's end, and the incoming state if that document ran through
  last = seg[:, -1]
  to_end = (jnp.exp(cum[..., -1:] - cum)
            * (seg == last[:, None])[:, None, :] * dt_h)    # [S, H, Q]
  h = (jnp.einsum('suhp,sun->shpn',
                  x * jnp.swapaxes(to_end, 1, 2)[..., None], b)
       + h * (jnp.exp(cum[..., -1])
              * (last == prev_seg)[:, None])[..., None, None])
  return (h, last), y


def ssd_scan(x, dt, a_heads, b, c, segment_ids, chunk_size):
  """``y_t = C_t h_t`` of the selective recurrence ``h_t = exp(dt_t A)
  h_{t-1} + dt_t x_t B_t^T`` restarted at every document, in chunks.
  ``x [S, L, H, P]``, ``dt [S, L, H]`` (positive), ``a_heads [H]``
  (negative), ``b``, ``c`` ``[S, L, N]``, ``segment_ids [S, L]`` counting
  a sequence's documents from 0.  ``L`` must be a multiple of
  ``chunk_size``."""
  seqs, length = x.shape[:2]
  if length % chunk_size:
    raise ValueError(f'ssd_scan: sequence length {length} is no multiple '
                     f'of the chunk size {chunk_size}')
  chunks = length // chunk_size
  split = lambda a: jnp.swapaxes(
      a.reshape((seqs, chunks, chunk_size) + a.shape[2:]), 0, 1)
  h0 = jnp.zeros((seqs,) + x.shape[2:] + (b.shape[-1],), jnp.float32)
  start = (h0, jnp.full((seqs,), -1, segment_ids.dtype))
  _, y = jax.lax.scan(
      jax.checkpoint(functools.partial(_ssd_chunk, a_heads)), start,
      tuple(split(a) for a in (x, dt, b, c, segment_ids)))
  return jnp.swapaxes(y, 0, 1).reshape(x.shape)


def mamba_mixer(cfg: HybridSSMConfig, p, u, segment_ids):
  """The Mamba-2 mixer on ``u [S, L, hidden]``."""
  inner, state = cfg.d_inner, cfg.mamba_d_state
  heads, d_head = cfg.mamba_n_heads, cfg.mamba_d_head
  with obs_trace.phase('mixer/proj'):
    z, xbc, dt = jnp.split(jnp.matmul(u, p['in_proj']),
                           [inner, inner + cfg.conv_dim], axis=-1)
  with obs_trace.phase('mixer/conv'):
    xbc = jax.nn.silu(causal_conv(xbc, p['conv_kernel'], p['conv_bias'],
                                  segment_ids))
  with obs_trace.phase('mixer/selective_scan'):
    x, b, c = jnp.split(xbc, [inner, inner + state], axis=-1)
    x = x.reshape(x.shape[:2] + (heads, d_head))
    dt = jax.nn.softplus(dt + p['dt_bias'])
    y = ssd_scan(x, dt, -jnp.exp(p['A_log']), b, c, segment_ids,
                 cfg.mamba_chunk_size)
    y = (y + p['D'][:, None] * x).reshape(z.shape)
  with obs_trace.phase('mixer/proj'):
    y = rms_norm(y * jax.nn.silu(z), p['gated_norm'], cfg.rms_norm_eps)
    return jnp.matmul(y, p['out_proj'])


def _attend(scale, q, k, v, seg_q, seg_k, first, key_first=None,
            window=None):
  """Queries ``q [S, Bq, Hkv, G, D]`` at positions ``first..`` against
  keys ``k``, ``v`` ``[S, Bk, Hkv, D]`` at positions ``key_first..`` (0
  where none is given):
  causal, within the document, and with a ``window`` only the keys
  fewer than ``window`` positions back."""
  s = jnp.einsum('sqhgd,skhd->shgqk', q, k) * scale
  pos_q = first + jnp.arange(q.shape[1])
  pos_k = jnp.arange(k.shape[1])
  if key_first is not None:
    pos_k = key_first + pos_k
  mask = ((seg_q[:, :, None] == seg_k[:, None, :])
          & (pos_q[:, None] >= pos_k[None, :]))
  if window is not None:
    mask = mask & (pos_q[:, None] - pos_k[None, :] < window)
  s = jnp.where(mask[:, None, None], s, -jnp.inf)
  return jnp.einsum('shgqk,skhd->sqhgd', jax.nn.softmax(s, axis=-1), v)


@functools.partial(jax.jit, static_argnums=(0, 8))
def _attend_window(scale, q, k, v, seg_q, seg_k, first, key_first, window):
  """``_attend`` under ``jax.checkpoint`` with the positions as run-time
  scalars, so that blocks of one shape are ONE traced function: JAX
  traces, differentiates and lowers it once and calls it a block (XLA
  inlines the calls: the compiled step is what unrolled tracing gives).
  A windowed layer's blocks past its first window all have one shape."""
  return jax.checkpoint(_attend, static_argnums=(0, 8))(
      scale, q, k, v, seg_q, seg_k, first, key_first, window)


def blocked_attention(scale, q, k, v, segment_ids, block_limit, window=None):
  """Causal document-masked grouped-query attention: ``q [S, L, Hkv, G,
  D]``, ``k``, ``v`` ``[S, L, Hkv, D]`` -> ``[S, L, Hkv, G, D]``; with a
  ``window`` only the keys fewer than ``window`` positions back.  The one
  place that chooses: the fused kernels (``ops/pallas_attention.py``)
  where the program is compiled for a TPU and the static shapes fit
  their tiles, and one block of ``block_limit`` queries at a time in
  plain ``jax.numpy`` otherwise (a CPU, lengths of tens of positions).
  The counters ``attention.kernel_layers`` and
  ``attention.blocked_layers`` say at trace time which a compiled step
  holds.  Both run under the phase ``attention/core`` (the kernels with
  the transposes they need), so that a trace tells the core from the
  projections, norms, rotary and gate that stand around it in the
  caller's ``attention`` phase."""
  with obs_trace.phase('attention/core'):
    if pallas_attention.takes(q.shape):
      obs_metrics.inc('attention.kernel_layers')
      return pallas_attention.attention(scale, q, k, v, segment_ids, window)
    obs_metrics.inc('attention.blocked_layers')
    return _unrolled_attention(scale, q, k, v, segment_ids, block_limit,
                               window)


def _unrolled_attention(scale, q, k, v, segment_ids, block_limit, window):
  """``blocked_attention`` one block of queries at a time.  Each block
  runs under
  ``jax.checkpoint`` against the keys up to its own end, and with a
  ``window`` from the block boundary at or before its first query's
  oldest key: only the key blocks that meet the window are computed, and
  no ``[heads, L, L]`` array exists.  (The windowed blocks past the
  first window all have one shape.  Three ways of making them one body
  of the compiled step were tried at 8,192 positions under a window of
  2,048, compile-only for a v5e: under ``lax.map``, as a ``lax.scan``
  that writes into the result it carries, and with the later windows
  side by side as a batch.  Each held 1.2 to 1.7 GiB more than the
  unrolled blocks and put the mixture-of-experts cell past the chip's
  memory, for a tenth to a fifth of the compile time.  So they stay
  unrolled in the compiled step, and are one function to JAX.)"""
  length = q.shape[1]
  block = _block(length, block_limit)
  if window is None:
    attend = jax.checkpoint(functools.partial(_attend, scale),
                            static_argnums=(5,))
    return jnp.concatenate(
        [attend(q[:, i:i + block], k[:, :i + block], v[:, :i + block],
                segment_ids[:, i:i + block], segment_ids[:, :i + block], i)
         for i in range(0, length, block)], axis=1)
  out = []
  for i in range(0, length, block):
    lo = max(0, (i - window + 1) // block * block)
    out.append(_attend_window(
        scale, q[:, i:i + block], k[:, lo:i + block], v[:, lo:i + block],
        segment_ids[:, i:i + block], segment_ids[:, lo:i + block],
        jnp.int32(i), jnp.int32(lo), window))
  return jnp.concatenate(out, axis=1)


def attention_mixer(cfg: HybridSSMConfig, p, u, segment_ids):
  """Causal grouped-query attention without positional embedding on
  ``u [S, L, hidden]``, one block of queries at a time."""
  with obs_trace.phase('attention'):
    seqs, length, _ = u.shape
    kv_heads, d = cfg.num_key_value_heads, cfg.head_dim
    group = cfg.num_attention_heads // kv_heads
    q = jnp.matmul(u, p['q_proj']).reshape(seqs, length, kv_heads, group, d)
    k = jnp.matmul(u, p['k_proj']).reshape(seqs, length, kv_heads, d)
    v = jnp.matmul(u, p['v_proj']).reshape(seqs, length, kv_heads, d)
    out = blocked_attention(cfg.attention_multiplier, q, k, v, segment_ids,
                            cfg.attention_block).reshape(seqs, length, -1)
    return jnp.matmul(out, p['o_proj'])


def swiglu(p, u):
  with obs_trace.phase('mlp'):
    gate, up = jnp.split(jnp.matmul(u, p['mlp_in']), 2, axis=-1)
    return jnp.matmul(jax.nn.silu(gate) * up, p['mlp_out'])


def layer(cfg: HybridSSMConfig, kind: str, p, x, segment_ids):
  """One residual block.  The norm before each sub-layer, the multiplier
  and the add are the phase ``residual``: what a block costs beside its
  mixer and its SwiGLU (as far as XLA leaves it unfused: a norm fused
  into the product after it keeps the product's name)."""
  mixer = mamba_mixer if kind == 'mamba' else attention_mixer
  with obs_trace.phase('residual'):
    u = rms_norm(x, p['mixer_norm'], cfg.rms_norm_eps)
  out = mixer(cfg, p['mixer'], u, segment_ids)
  with obs_trace.phase('residual'):
    x = x + cfg.residual_multiplier * out
    u = rms_norm(x, p['mlp_norm'], cfg.rms_norm_eps)
  out = swiglu(p, u)
  with obs_trace.phase('residual'):
    return x + cfg.residual_multiplier * out


def vocab_loss(cfg: HybridSSMConfig, x, final_norm, table, targets):
  """Mean next-token cross-entropy over the positions whose target is
  not -1, the logits ``rmsnorm(x) @ table^T / logits_scaling`` computed
  one block of positions at a time (each under ``jax.checkpoint``: no
  ``[positions, vocabulary]`` array outlives its block)."""
  with obs_trace.phase('vocab'):
    x = rms_norm(x, final_norm, cfg.rms_norm_eps).reshape(-1, x.shape[-1])
    targets = targets.reshape(-1)
    block = _block(x.shape[0], cfg.vocab_block)

    @jax.checkpoint
    def nll(args):
      xb, tb = args
      logits = jnp.matmul(xb, table.T) / cfg.logits_scaling
      valid = tb >= 0
      picked = jnp.take_along_axis(
          logits, jnp.where(valid, tb, 0)[:, None], axis=-1)[:, 0]
      lse = jax.nn.logsumexp(logits, axis=-1)
      return jnp.sum(jnp.where(valid, lse - picked, 0.0))

    total = jnp.sum(jax.lax.map(
        nll, (x.reshape(-1, block, x.shape[-1]),
              targets.reshape(-1, block))))
    return total / jnp.maximum(jnp.sum(targets >= 0), 1)


def forward(cfg: HybridSSMConfig, dense, rows, segment_ids):
  """The stack's last hidden states from the looked-up rows ``[S, L,
  hidden]``, each layer under ``jax.checkpoint``."""
  x = cfg.embedding_multiplier * rows
  for kind, p in zip(cfg.layer_types, dense['layers']):
    x = jax.checkpoint(functools.partial(layer, cfg, kind))(
        p, x, segment_ids)
  return x


def make_head_loss_fn(cfg: HybridSSMConfig, table_id: int = 0):
  """``head_loss_fn(dense, emb_outs, batch, tables)`` for
  ``make_hybrid_train_step(..., head_reads_tables=(table_id,))``:
  ``emb_outs[0]`` the looked-up rows ``[S * L, hidden]``, ``batch =
  (targets, segment_ids)`` both ``[S, L]``, ``tables[table_id]`` the
  vocabulary ``[rows, hidden]``."""

  def head_loss_fn(dense, emb_outs, batch, tables):
    targets, segment_ids = batch
    x = forward(cfg, dense, emb_outs[0].reshape(
        targets.shape + (cfg.hidden_size,)), segment_ids)
    return vocab_loss(cfg, x, dense['final_norm'], tables[table_id],
                      targets)

  return head_loss_fn
