"""Traffic generators: a mix is a data file, never code.

``traffic/<mix>.json`` names a ``kind`` (the runner that drives it,
``runners/<kind>.py``), a ``generator`` (``module:function``), the
generator's parameters and, where it wants the TPU runtime started
otherwise than by default, ``runtime_env`` (``run.py`` sets those
variables before JAX loads).  A new mix of a known shape is a new file; a new
shape is a new generator, in a module of its own, that the mix names.  A
generator is

  ``(mix, inputs, config, seed, batches=None) -> [(cats, batch), ...]``

``inputs`` being ``[(num_rows, hotness), ...]`` in input order, ``cats[i]``
int32 ``[rows, hotness_i]`` and ``batch`` whatever pytree of arrays the
class's heads take.  Batch ``k`` depends on ``seed`` and ``k`` alone, so
the reference draws the first three without the rest, and every seed
draws arrays of the same shapes: the seed changes which rows are hit,
never how much work a step is.  Here are the two the benchmark has.
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


def _batches(mix, batches):
  return range(int(mix['pool_batches']) if batches is None else batches)


def train_pool(mix, inputs, config, seed, batches=None):
  """Whole click-through batches as the reference benchmark's
  ``InputGenerator`` makes them (power-law ids with repetition,
  ``synthetic_models.py:31-45``; copied from the program's
  ``models/synthetic.py`` so that a later change there cannot move the
  yardstick): ``batch = (numerical, labels)``, ``numerical`` float32
  ``[B, num_numerical_features]`` uniform in ``numerical_range`` and
  ``labels`` float32 ``[B, 1]`` fair coin flips."""
  batch = int(mix['global_batch'])
  lo, hi = mix['numerical_range']
  out = []
  for k in _batches(mix, batches):
    rng = np.random.default_rng([int(seed), 7, k])
    cats = [power_law_ids(rng, batch * h, rows, float(mix['alpha']))
            .reshape(batch, h) for rows, h in inputs]
    numerical = rng.uniform(
        lo, hi, size=(batch, config['num_numerical_features'])).astype(
            np.float32)
    labels = rng.integers(0, 2, size=(batch, 1)).astype(np.float32)
    out.append((cats, (numerical, labels)))
  return out


def train_tokens(mix, inputs, config, seed, batches=None):
  """Packed documents for next-token training: ``global_batch`` sequences
  (a sample is a sequence) of ``seq_len`` positions each.  Token ids are
  unigram draws by ``power_law_ids`` (``alpha``) from the one table the
  class has; documents of log-normal length (``doc_len_median``,
  ``doc_len_sigma``, at least 1 token) are packed end to end through the
  batch's positions, a document running on into the next sequence being
  cut there, so no position is padding.  ``cats`` is the one array of ids
  flattened to ``[global_batch * seq_len, 1]`` (the program's
  ``combiner=None`` takes hotness 1 alone); ``batch = (targets,
  segment_ids)``, both int32 ``[global_batch, seq_len]``: the target is
  the next id within the document and -1 at a document's last position,
  and ``segment_ids`` counts the documents of a sequence from 0."""
  del config
  (rows, hotness), = inputs
  if hotness != 1:
    raise ValueError('train_tokens feeds one id per position')
  seqs, length = int(mix['global_batch']), int(mix['seq_len'])
  mu, sigma = np.log(float(mix['doc_len_median'])), float(mix['doc_len_sigma'])
  out = []
  for k in _batches(mix, batches):
    rng = np.random.default_rng([int(seed), 11, k])
    ids = power_law_ids(rng, seqs * length, rows, float(mix['alpha']))
    # one draw of document lengths covers the batch however they fall:
    # the draw's size does not depend on its values
    lens = np.maximum(1, rng.lognormal(mu, sigma, seqs * length)
                      .astype(np.int64))
    ends = np.cumsum(lens)
    ends = ends[:np.searchsorted(ends, seqs * length)]
    last = np.zeros(seqs * length, bool)
    last[ends - 1] = True                   # a document's last position
    last = last.reshape(seqs, length)
    last[:, -1] = True                      # cut at the sequence's end
    ids2 = ids.reshape(seqs, length)
    targets = np.where(last, -1, np.roll(ids2, -1, axis=1)).astype(np.int32)
    starts = np.roll(last, 1, axis=1)
    starts[:, 0] = False
    segment_ids = np.cumsum(starts, axis=1).astype(np.int32)
    out.append(([ids.reshape(-1, 1)], (targets, segment_ids)))
  return out
