"""Lookup microbenchmark: ragged fused lookup fwd/grad/apply timings.

Port of the reference microbenchmark
(`/root/reference/examples/benchmarks/benchmark.py:23-98`): a 1M x 128
table, random ragged ids with hotness <= 500, timing forward, gradient and
one optimizer apply.  The reference compares its custom CUDA op against
`tf.nn.embedding_lookup_sparse`; here the comparison is the static-CSR
fused path vs the padded-dense path, and the sparse row-wise update vs a
dense-gradient optax update (the sparse path is the one that must win by
orders of magnitude on big tables).

Usage: python examples/benchmarks/lookup_benchmark.py [--rows N] [--width W]
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))


def timeit(fn, *args, iters=10):
  """Per-iteration ms of ``fn(*args)`` with the dispatch cost amortised.

  Run ONE jitted ``lax.scan`` of ``iters`` steps, perturb the input each
  step (roll of the largest integer leaf — the ids the expensive gather
  depends on — falling back to a tiny add on the largest float leaf) so
  nothing hoists out of the loop, give each timed call a distinct
  offset, and force completion with a host transfer of a scalar
  checksum.
  """
  import jax
  import jax.numpy as jnp
  leaves, treedef = jax.tree.flatten(args)
  int_sizes = [
      l.size if jnp.issubdtype(jnp.asarray(l).dtype, jnp.integer) else -1
      for l in leaves
  ]
  if max(int_sizes) > 0:
    tgt, int_tgt = int(np.argmax(int_sizes)), True
  else:
    tgt, int_tgt = int(np.argmax([l.size for l in leaves])), False

  def run(off, *ls):
    def step(c, k):
      ls2 = list(ls)
      x = ls2[tgt]
      if int_tgt:
        ls2[tgt] = jnp.roll(x.reshape(-1), k).reshape(x.shape)
      else:
        ls2[tgt] = x + jnp.float32(1e-30) * k
      out = fn(*jax.tree.unflatten(treedef, ls2))
      s = sum(
          jnp.sum(jnp.asarray(l).astype(jnp.float32))
          for l in jax.tree.leaves(out))
      return c + s, None

    return jax.lax.scan(step, jnp.float32(0), off + jnp.arange(iters))[0]

  jrun = jax.jit(run)
  float(jrun(0, *leaves))  # compile + warm
  times = []
  for off in (1, 1 + iters):
    start = time.perf_counter()
    float(jrun(off, *leaves))
    times.append(time.perf_counter() - start)
  return min(times) / iters * 1000


def main():
  parser = argparse.ArgumentParser()
  parser.add_argument('--rows', type=int, default=1_000_000)
  parser.add_argument('--width', type=int, default=128)
  parser.add_argument('--batch', type=int, default=65536)
  parser.add_argument('--max_hotness', type=int, default=500)
  parser.add_argument('--avg_hotness', type=int, default=31)
  parser.add_argument('--combiner', default='sum', choices=['sum', 'mean'])
  args = parser.parse_args()

  import jax
  if os.environ.get('JAX_PLATFORMS') == 'cpu':
    jax.config.update('jax_platforms', 'cpu')
  import jax.numpy as jnp
  from distributed_embeddings_tpu.ops.embedding_lookup import embedding_lookup
  from distributed_embeddings_tpu.ops.ragged import RaggedBatch
  from distributed_embeddings_tpu.parallel.sparse import dedup_rows

  rng = np.random.default_rng(12)
  table = jnp.asarray(
      rng.normal(size=(args.rows, args.width)).astype(np.float32) * 0.01)

  # random ragged batch: lengths in [1, 2*avg) capped by max_hotness
  lengths = np.minimum(
      rng.integers(1, 2 * args.avg_hotness, size=(args.batch,)),
      args.max_hotness)
  nnz = int(lengths.sum())
  values = rng.integers(0, args.rows, size=(nnz,)).astype(np.int32)
  ragged = RaggedBatch.from_row_lengths(values, lengths)
  print(f'table {args.rows}x{args.width}, batch {args.batch}, '
        f'nnz {nnz} (avg hotness {nnz/args.batch:.1f})')

  # --- forward ------------------------------------------------------------
  fwd = jax.jit(lambda t, r: embedding_lookup(t, r, combiner=args.combiner))
  t_fwd = timeit(fwd, table, ragged)
  print(f'ragged fused forward:        {t_fwd:8.3f} ms')

  hot_cap = int(lengths.max())
  padded = ragged.to_padded_dense(hot_cap)
  mask = np.asarray(padded) >= 0

  def padded_fwd(t, ids):
    m = ids >= 0
    rows = jnp.take(t, jnp.clip(ids, 0, None), axis=0)
    out = jnp.sum(jnp.where(m[..., None], rows, 0), axis=1)
    if args.combiner == 'mean':
      out = out / jnp.maximum(m.sum(1), 1)[:, None]
    return out

  t_pad = timeit(jax.jit(padded_fwd), table, padded)
  print(f'padded dense forward:        {t_pad:8.3f} ms  (hot_cap {hot_cap})')

  # --- gradient (dense autodiff: produces a table-shaped grad) ------------
  def loss(t, r):
    return jnp.sum(embedding_lookup(t, r, combiner=args.combiner))

  t_grad = timeit(jax.jit(jax.grad(loss)), table, ragged)
  print(f'dense-grad backward:         {t_grad:8.3f} ms')

  # --- sparse row-wise update (the training path) -------------------------
  g_out = jnp.ones((args.batch, args.width), jnp.float32)

  def sparse_sgd(t, r, g):
    rowids = r.row_ids()
    pos_g = g[jnp.clip(rowids, 0, args.batch - 1)]
    ids = jnp.where(r.valid_mask(), r.values, args.rows)
    return t.at[ids].add(-0.01 * pos_g, mode='drop')

  t_sparse = timeit(jax.jit(sparse_sgd), table, ragged, g_out)
  print(f'sparse SGD row update:       {t_sparse:8.3f} ms')

  def sparse_sgd_dedup(t, r, g):
    rowids = r.row_ids()
    pos_g = g[jnp.clip(rowids, 0, args.batch - 1)]
    ids = jnp.where(r.valid_mask(), r.values, args.rows)
    uids, tg = dedup_rows(ids, pos_g, sentinel=args.rows)
    return t.at[uids].add(-0.01 * tg, mode='drop')

  t_dedup = timeit(jax.jit(sparse_sgd_dedup), table, ragged, g_out)
  print(f'sparse SGD dedup update:     {t_dedup:8.3f} ms')

  # --- dense optimizer apply (what the sparse path avoids) ----------------
  def dense_sgd(t, g):
    return t - 0.01 * g

  dense_g = jax.jit(jax.grad(loss))(table, ragged)
  t_dense_apply = timeit(jax.jit(dense_sgd), table, dense_g)
  print(f'dense SGD full-table update: {t_dense_apply:8.3f} ms')

  # --- Pallas kernel vs XLA gather across widths (on TPU) -----------------
  from distributed_embeddings_tpu.ops import pallas_lookup
  from distributed_embeddings_tpu.parallel.dist_embedding import _fused_lookup
  if jax.default_backend() == 'tpu':
    print('\npallas dense kernel vs XLA fallback '
          f'(vocab {args.rows}, batch {args.batch}):')
    for w, hot in [(8, 4), (16, 2), (32, 2), (64, 1), (128, 1)]:
      t = jnp.asarray(
          rng.normal(size=(args.rows, w)).astype(np.float32) * 0.01)
      if not pallas_lookup.supported(t, 'sum', hot):
        print(f'  width {w:4d} hot {hot}: unsupported for vocab '
              f'{args.rows} (pack divisibility) — skipped')
        continue
      ids = jnp.asarray(
          rng.integers(0, args.rows, size=(args.batch, hot)).astype(np.int32))
      pl_fn = jax.jit(lambda t, i: pallas_lookup.dense_lookup(
          t, i, 'sum', out_dtype=jnp.float32))
      xla_fn = jax.jit(lambda t, i: _fused_lookup(
          t, i[None], 'sum', jnp.float32)[0])
      t_pl = timeit(pl_fn, t, ids)
      t_xla = timeit(xla_fn, t, ids)
      print(f'  width {w:4d} hot {hot}: pallas {t_pl:8.3f} ms | '
            f'xla {t_xla:8.3f} ms | speedup {t_xla / t_pl:5.2f}x')
  else:
    print('\n(pallas-vs-xla width sweep skipped: no TPU backend)')


if __name__ == '__main__':
  main()
