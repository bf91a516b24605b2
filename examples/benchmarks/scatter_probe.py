"""Measure XLA scatter/gather cost vs index hints on the live backend.

Quantifies the unique_indices / indices_are_sorted effect that
parallel/sparse.py relies on (the apply's scatters dominate the sparse
train step, docs/perf_notes.md).

Usage: python examples/benchmarks/scatter_probe.py [--rows 8000000]
       [--n 1000000] [--width 16]
"""

import argparse
import time


def main():
  p = argparse.ArgumentParser()
  p.add_argument('--rows', type=int, default=8_000_000)
  p.add_argument('--n', type=int, default=1_000_000)
  p.add_argument('--width', type=int, default=16)
  p.add_argument('--iters', type=int, default=10)
  args = p.parse_args()

  import jax
  import jax.numpy as jnp
  import numpy as np

  rows, n, w, iters = args.rows, args.n, args.width, args.iters
  rng = np.random.default_rng(0)
  table = jnp.zeros((rows, w), jnp.float32)
  upd = jnp.asarray(rng.normal(size=(n, w)).astype(np.float32))

  def ids_batch(unique_sorted):
    out = np.empty((iters, n), np.int32)
    for i in range(iters):
      raw = rng.integers(0, rows, size=n).astype(np.int32)
      if unique_sorted:
        u = np.unique(raw)
        pad = np.full(n, rows, np.int32)
        pad[:u.size] = u
        # distinct OOB tail, as _distinct_oob produces
        pad[u.size:] = rows + np.arange(n - u.size, dtype=np.int32)
        out[i] = pad
      else:
        out[i] = raw
    return jnp.asarray(out)

  def scan_of(op):
    def run(tab, ids_stack):
      def body(c, ids):
        return op(c, ids), None
      return jax.lax.scan(body, tab, ids_stack)[0]
    return run

  variants = {
      'scatter-add plain':
          (False, lambda t, i: t.at[i].add(upd, mode='drop')),
      'scatter-add hints':
          (True, lambda t, i: t.at[i].add(upd, mode='drop',
                                          unique_indices=True,
                                          indices_are_sorted=True)),
      'scatter-set hints':
          (True, lambda t, i: t.at[i].set(upd, mode='drop',
                                          unique_indices=True,
                                          indices_are_sorted=True)),
      'gather plain':
          (False, lambda t, i: t.at[jnp.clip(i, 0, rows - 1)].get()),
      'gather sorted':
          (True, lambda t, i: t.at[jnp.clip(i, 0, rows - 1)].get(
              indices_are_sorted=True)),
  }
  print(f'rows={rows} n={n} w={w} backend={jax.default_backend()}')
  for name, (uniq, op) in variants.items():
    stacks = [ids_batch(uniq) for _ in range(3)]
    if 'gather' in name:
      # reduce over ALL gathered rows so no slice-of-gather simplification
      # can shrink the measured gather (review round 2 finding)
      def run(tab, s, op=op):
        def body(c, ids):
          return c + op(tab, ids).sum(axis=0), None
        return jax.lax.scan(body, jnp.zeros((w,)), s)[0]
      f = jax.jit(run)
      float(f(table, stacks[0]).sum())
      times = []
      for s in stacks[1:]:
        t0 = time.perf_counter()
        float(f(table, s).sum())
        times.append(time.perf_counter() - t0)
      ms = min(times) / iters * 1e3
    else:
      run = scan_of(op)
      f = jax.jit(run)
      jax.block_until_ready(f(table, stacks[0]))
      times = []
      for s in stacks[1:]:
        t0 = time.perf_counter()
        r = f(table, s)
        float(r[0, 0])
        times.append(time.perf_counter() - t0)
      ms = min(times) / iters * 1e3
    print(f'{name:22s}: {ms:8.2f} ms  ({ms * 1e6 / n:6.1f} ns/row)')


if __name__ == '__main__':
  main()
