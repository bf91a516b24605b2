"""What plain reference heads share: straightforward ``jax.numpy``,
float32, nothing of the program imported.

A configuration names its head (``reference_head``, ``module:function``);
the function takes the configuration and returns

  ``loss(dense, emb_outs, batch, matmul, tables) -> scalar``

``dense`` the class's dense pytree, ``emb_outs`` one array per input
(``[B, width]``, or ``[B, hotness, width]`` under ``combiner: null`` with
a hotness over 1), ``batch`` the pytree its traffic generator made,
``matmul(a, b)`` the product whose precision the caller chose, and
``tables`` the whole tables the configuration lists under
``head_reads_tables`` (``{table id: [rows, width]}``, else empty).  The
heads themselves are with their classes (``benchmarks/classes``).
"""

import jax.numpy as jnp


def mlp(layers, x, matmul, last_linear):
  for i, layer in enumerate(layers):
    x = matmul(x, layer['kernel']) + layer['bias']
    if not (last_linear and i == len(layers) - 1):
      x = jnp.maximum(x, 0.0)
  return x


def bce_with_logits(logits, labels):
  """Mean binary cross-entropy from logits, the numerically stable form:
  ``max(z, 0) - z*y + log(1 + exp(-|z|))``."""
  z = logits.reshape(-1)
  y = labels.reshape(-1)
  return jnp.mean(jnp.maximum(z, 0.0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))
