"""Weights from the seed, the same numbers on the device and in the reference.

A table entry is a counter hash of its flat position ``row * width + col``
under two key words: no table is ever held twice, ``lib/program_state``
writes each chip's shards at HBM speed in one jitted call, and the
reference computes any row it needs from the same words without reading
the program.

The arithmetic is exact integer mixing plus ONE float32 multiply of an
integer below 2**23 by a constant, which rounds the same on every backend
(checked bit for bit on the v5e, PERF.md section 6).
"""

import numpy as np

_GOLD = 0x9E3779B9
_M1 = 0x7FEB352D
_M2 = 0x846CA68B


def _mix(x, u32):
  """lowbias32 (Wellons): a full-period 32-bit integer mixer."""
  x = x ^ (x >> u32(16))
  x = x * u32(_M1)
  x = x ^ (x >> u32(15))
  x = x * u32(_M2)
  return x ^ (x >> u32(16))


def value_scale(half_range):
  """The float32 constant that maps a centred 24-bit integer into
  ``[-half_range, half_range)``."""
  return np.float32(half_range * 2.0**-23)


def hashed_values(xp, flat_index, words, scale):
  """float32 values in ``[-half_range, half_range)`` at uint32 flat
  positions.  ``xp`` is ``numpy`` or ``jax.numpy``; ``words`` holds two
  uint32 key words and ``scale`` is ``value_scale(half_range)`` (arrays
  or scalars of ``xp``)."""
  u32 = xp.uint32
  x = flat_index.astype(u32) * u32(_GOLD) + words[0]
  x = _mix(x, u32) ^ words[1]
  x = _mix(x, u32)
  centred = (x >> u32(8)).astype(xp.int32) - xp.int32(1 << 23)
  return centred.astype(xp.float32) * scale


def numpy_rows(words, rows, width, half_range):
  """The float32 rows ``rows`` (int array, any shape) of a ``width``-wide
  table keyed by ``words``: shape ``rows.shape + (width,)``."""
  rows = np.asarray(rows, np.int64)
  flat = rows[..., None] * width + np.arange(width, dtype=np.int64)
  if flat.size and int(flat.max()) >= 2**32:
    raise ValueError('table too large for a 32-bit flat position')
  words = np.asarray(words, np.uint32)
  with np.errstate(over='ignore'):
    return hashed_values(np, flat.astype(np.uint32), words,
                         value_scale(half_range))


def root_key(seed):
  """The key every table's words are folded from: both halves of a seed
  that may not fit 32 signed bits."""
  import jax
  seed = int(seed)
  return jax.random.wrap_key_data(
      np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32))


def table_words(seed, tables):
  """The two key words of each of ``tables`` tables under ``seed``, uint32
  ``[tables, 2]``: table ``t``'s are ``fold_in(root_key(seed), t)`` by
  ``jax.random`` alone, all in one call."""
  import jax
  import jax.numpy as jnp
  root = root_key(seed)
  data = jax.vmap(lambda t: jax.random.key_data(jax.random.fold_in(root, t)))(
      jnp.arange(tables, dtype=jnp.uint32))
  data = np.asarray(data).astype(np.uint32).reshape(tables, -1)
  return np.stack([data[:, 0], data[:, -1]], axis=1)


def dense_layers(seed, stream, dims_in_out):
  """MLP layers for ``[(fan_in, fan_out), ...]``: Glorot-normal kernels
  and ``N(0, 1/fan_out)`` biases (the program's ``models.dlrm.MLP``
  distributions), float32, from ``numpy``'s generator keyed by
  ``(seed, stream)``."""
  rng = np.random.default_rng([int(seed), int(stream)])
  layers = []
  for fan_in, fan_out in dims_in_out:
    std = np.sqrt(2.0 / (fan_in + fan_out))
    layers.append({
        'kernel': (std * rng.standard_normal((fan_in, fan_out))).astype(
            np.float32),
        'bias': (rng.standard_normal(fan_out) / np.sqrt(fan_out)).astype(
            np.float32)})
  return layers
