"""One general traffic generator: a mix is a data file, never code.

``traffic/<mix>.json`` names a ``kind`` (``lib/cell.RUNNERS`` maps it to
the runner that drives it) and the parameters of that kind.  A new mix of a
known kind is a new file.

``train_pool``: a pool of whole training batches, as the reference
benchmark's ``InputGenerator`` makes them (power-law ids with repetition,
``synthetic_models.py:31-45``; copied from the program's
``models/synthetic.py`` so that a later change there cannot move the
yardstick).  Every seed draws the same number of ids of the same shapes:
the seed changes which rows are hit, not how much work a step is.
"""

import numpy as np


def power_law_ids(rng, count, num_rows, alpha):
  """``count`` ids in ``[0, num_rows)`` by the reference's inverse-CDF
  power law with exponent ``alpha`` (not 1: the CDF divides by 1 - alpha;
  at 0 the same formula is the uniform draw)."""
  gamma = 1.0 - alpha
  lo, hi = 1.0, float(num_rows + 1)
  y = (rng.random(count) * (hi**gamma - lo**gamma) + lo**gamma)**(1 / gamma)
  return (y.astype(np.int64) - 1).astype(np.int32)


def train_pool(mix, inputs, num_numerical, seed, batches=None):
  """``batches`` (default ``mix['pool_batches']``) training batches.

  ``inputs`` is ``[(num_rows, hotness), ...]`` in input order.  Returns
  ``[(cats, numerical, labels), ...]`` with ``cats[i]`` int32
  ``[B, hotness_i]``, ``numerical`` float32 ``[B, num_numerical]`` and
  ``labels`` float32 ``[B, 1]``.  Batch ``k`` depends on ``seed`` and
  ``k`` alone, so the reference can draw the first three without the
  rest."""
  batch = int(mix['global_batch'])
  lo, hi = mix['numerical_range']
  out = []
  for k in range(int(mix['pool_batches']) if batches is None else batches):
    rng = np.random.default_rng([int(seed), 7, k])
    cats = [power_law_ids(rng, batch * h, rows, float(mix['alpha']))
            .reshape(batch, h) for rows, h in inputs]
    numerical = rng.uniform(lo, hi, size=(batch, num_numerical)).astype(
        np.float32)
    labels = rng.integers(0, 2, size=(batch, 1)).astype(np.float32)
    out.append((cats, numerical, labels))
  return out

