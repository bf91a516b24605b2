"""Probe TPU primitive costs that drive the sparse-update kernel design:
sort, scatter variants, histogram, one-hot matmul, gather shapes."""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))


def bench(name, fn, *args, iters=10, warmup=3):
  import jax
  for _ in range(warmup):
    out = fn(*args)
  jax.block_until_ready(out)
  start = time.perf_counter()
  for _ in range(iters):
    out = fn(*args)
  jax.block_until_ready(out)
  ms = (time.perf_counter() - start) / iters * 1000
  print(f'{name:44s} {ms:10.3f} ms')
  return ms


def main():
  parser = argparse.ArgumentParser()
  parser.add_argument('--n', type=int, default=1_000_000)
  parser.add_argument('--vocab', type=int, default=1_000_000)
  parser.add_argument('--width', type=int, default=16)
  args = parser.parse_args()

  import jax
  import jax.numpy as jnp

  rng = np.random.default_rng(0)
  n, vocab, w = args.n, args.vocab, args.width
  ids = jnp.asarray(rng.integers(0, vocab, size=(n,)).astype(np.int32))
  g = jnp.asarray(rng.normal(size=(n, w)).astype(np.float32))
  table = jnp.asarray(rng.normal(size=(vocab, w)).astype(np.float32))
  print(f'n={n} vocab={vocab} w={w}')

  bench('gather 1d idx [n] -> [n,w]',
        jax.jit(lambda t, i: jnp.take(t, i, axis=0, mode='clip')), table, ids)
  ids2d = ids.reshape(-1, 8)
  bench('gather 2d idx [n/8,8] -> [n/8,8,w]',
        jax.jit(lambda t, i: jnp.take(t, i, axis=0, mode='clip')), table,
        ids2d)
  bench('sort int32 [n]', jax.jit(jnp.sort), ids)
  bench('argsort int32 [n]', jax.jit(jnp.argsort), ids)
  kv = (ids, jnp.arange(n, dtype=jnp.int32))
  bench('lax.sort pairs (id, idx)',
        jax.jit(lambda a, b: jax.lax.sort((a, b), num_keys=1)), *kv)
  bench('scatter-add [n,w] -> [vocab,w]',
        jax.jit(lambda t, i, v: t.at[i].add(v, mode='drop')), table, ids, g)
  bench('scatter-add unique_indices',
        jax.jit(lambda t, i, v: t.at[i].add(
            v, mode='drop', unique_indices=True)), table, ids, g)
  bench('segment_sum n->vocab',
        jax.jit(lambda i, v: jax.ops.segment_sum(v, i, num_segments=vocab)),
        ids, g)
  sorted_ids = jnp.sort(ids)
  bench('segment_sum sorted indices_are_sorted',
        jax.jit(lambda i, v: jax.ops.segment_sum(
            v, i, num_segments=vocab, indices_are_sorted=True)),
        sorted_ids, g)
  bench('scatter-add 1col [n] -> [vocab]',
        jax.jit(lambda i: jnp.zeros((vocab,), jnp.float32).at[i].add(1.0)),
        ids)
  bench('bincount/histogram to vocab',
        jax.jit(lambda i: jnp.bincount(i, length=vocab)), ids)
  bench('cumsum [n,w] f32', jax.jit(lambda x: jnp.cumsum(x, axis=0)), g)

  # one-hot matmul scatter building block: [RB, C] @ [C, w]
  RB, C = 1024, 2048
  rows_local = jnp.asarray(rng.integers(0, RB, size=(C,)).astype(np.int32))
  gc = jnp.asarray(rng.normal(size=(C, w)).astype(np.float32))

  def onehot_mm(rl, v):
    oh = (rl[None, :] == jax.lax.broadcasted_iota(jnp.int32, (RB, C), 0))
    return jax.lax.dot_general(oh.astype(jnp.float32), v,
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

  t_oh = bench(f'one-hot mm [{RB},{C}]@[{C},{w}] x1',
               jax.jit(onehot_mm), rows_local, gc)
  # how many such matmuls for n ids: n / C
  print(f'  -> {n/C:.0f} blocks for n ids = {t_oh * n / C:.2f} ms if serial')

  def onehot_batched(rl, v):
    # [B, RB, C] @ [B, C, w] batched over blocks
    oh = (rl[:, None, :] == jax.lax.broadcasted_iota(
        jnp.int32, (rl.shape[0], RB, C), 1))
    return jax.lax.dot_general(oh.astype(jnp.float32), v,
                               (((2,), (1,)), ((0,), (0,))),
                               preferred_element_type=jnp.float32)

  nb = n // C
  rl_b = jnp.asarray(rng.integers(0, RB, size=(nb, C)).astype(np.int32))
  g_b = jnp.asarray(rng.normal(size=(nb, C, w)).astype(np.float32))
  bench(f'one-hot mm batched [{nb},{RB},{C}]@[..,{w}]',
        jax.jit(onehot_batched), rl_b, g_b)


if __name__ == '__main__':
  main()
