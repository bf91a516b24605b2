"""Plain heads for the reference: straightforward ``jax.numpy``, float32,
nothing of the program imported.  A configuration names its head
(``module:function``); the function returns
``loss(dense, emb_outs, numerical, labels, matmul) -> scalar`` where
``matmul(a, b)`` is the product the caller chose the precision of.
"""

import jax
import jax.numpy as jnp


def _mlp(layers, x, matmul, last_linear):
  for i, layer in enumerate(layers):
    x = matmul(x, layer['kernel']) + layer['bias']
    if not (last_linear and i == len(layers) - 1):
      x = jnp.maximum(x, 0.0)
  return x


def _bce_with_logits(logits, labels):
  """Mean binary cross-entropy from logits, the numerically stable form:
  ``max(z, 0) - z*y + log(1 + exp(-|z|))``."""
  z = logits.reshape(-1)
  y = labels.reshape(-1)
  return jnp.mean(jnp.maximum(z, 0.0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))


def concat_mlp(config):
  """Synthetic models: concatenate the embedding outputs and the dense
  features, MLP down to one logit (``synthetic_models.py:116-175``; the
  average-pool interaction only where the configuration has a stride)."""
  stride = config.get('interact_stride')
  if stride is not None:
    raise NotImplementedError('interact_stride: no cell needs it yet')

  def loss(dense, emb_outs, numerical, labels, matmul):
    x = jnp.concatenate(list(emb_outs) + [numerical], axis=1)
    return _bce_with_logits(_mlp(dense['mlp'], x, matmul, True), labels)

  return loss


def dlrm_dot(config):
  """DLRM: bottom MLP, pairwise dots of the bottom output and the
  embedding outputs (strictly lower triangle, row-major), re-concatenate
  the bottom output, top MLP to one logit (``examples/dlrm/main.py:76-147``,
  ``utils.py:92-113``)."""

  def loss(dense, emb_outs, numerical, labels, matmul):
    bottom = _mlp(dense['bottom_mlp'], numerical, matmul, False)
    feats = jnp.stack([bottom] + list(emb_outs), axis=1)      # [B, n, d]
    n = feats.shape[1]
    pairs = jax.vmap(lambda f: matmul(f, f.T))(feats)           # [B, n, n]
    rows, cols = jnp.tril_indices(n, k=-1)
    x = jnp.concatenate([pairs[:, rows, cols], bottom], axis=1)
    return _bce_with_logits(_mlp(dense['top_mlp'], x, matmul, True), labels)

  return loss
