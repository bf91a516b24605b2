"""Measure XLA scatter/gather cost vs index hints on the live backend.

Quantifies what ``parallel/sparse.py``'s ``_write_rows`` chooses between:
XLA:TPU has two scatter emitters, and ``indices_are_sorted`` alone picks
one.  With the hint the scatter STREAMS the whole operand through VMEM
(``t = a * R + b * U``: R operand rows, U update rows); without it the
scatter walks the update ROWS (``t = c * U`` whatever R is, where the
rows lie far apart; cheaper a row where neighbours share a tile).  The
grid below measures both for ``add`` and ``set`` and fits ``a``, ``b``,
``c``; the constants beside ``_write_rows`` are this file's last line on
a v5e (PERF.md section 6, PR 30).

One variant a process (the largest operand is 9.55 GiB and is carried
through the loop donated); each process appends its readings to ``--out``
and prints the fit over everything that file holds, so the last process's
last line is the whole grid's:

  for v in add-both add-unique set-both set-unique; do
    python examples/benchmarks/scatter_probe.py --variant $v; done

Usage: python examples/benchmarks/scatter_probe.py --variant add-both
       [--rows 2500000,8775000,20025088] [--n 92272,1116536,2883584]
       [--width 128] [--iters 10] [--out chiprun_out/scatter_probe.jsonl]
"""

import argparse
import json
import os
import time

# variant -> (ids are sorted, strictly unique, with a distinct
# out-of-bounds tail; what runs).  'both' = unique_indices and
# indices_are_sorted, what every apply site passed before PR 30.
VARIANTS = ('add-both', 'add-unique', 'set-both', 'set-unique',
            'add-plain', 'gather-plain', 'gather-sorted')


def _op(variant, rows):
  import jax.numpy as jnp
  both = dict(mode='drop', unique_indices=True, indices_are_sorted=True)
  uniq = dict(mode='drop', unique_indices=True)
  return {
      'add-both': lambda t, i, upd: t.at[i].add(upd, **both),
      'add-unique': lambda t, i, upd: t.at[i].add(upd, **uniq),
      'set-both': lambda t, i, upd: t.at[i].set(upd, **both),
      'set-unique': lambda t, i, upd: t.at[i].set(upd, **uniq),
      'add-plain': lambda t, i, upd: t.at[i].add(upd, mode='drop'),
      'gather-plain': lambda t, i, upd: t.at[jnp.clip(i, 0, rows - 1)].get(),
      'gather-sorted': lambda t, i, upd: t.at[jnp.clip(i, 0, rows - 1)].get(
          indices_are_sorted=True),
  }[variant]


def _ids(rng, rows, n, iters, unique_sorted):
  """``[iters, n]`` int32 ids.  ``unique_sorted``: ascending, strictly
  unique, spread over the operand, and whatever falls past its end
  replaced by distinct out-of-bounds ids (as ``sparse._distinct_oob``
  makes a compacted buffer's sentinel tail)."""
  import numpy as np
  if not unique_sorted:
    return rng.integers(0, rows, size=(iters, n)).astype(np.int32)
  gap = max(2, 2 * rows // n)  # mean gap rows/n: the ids span the operand
  ids = np.cumsum(rng.integers(1, gap, size=(iters, n)), axis=1) - 1
  tail = rows + np.arange(n)
  return np.where(ids < rows, ids, tail).astype(np.int32)


# the row emitter's ``c * U`` holds where the rows lie far apart; this
# many operand rows to an update row, or more, counts as far (on a v5e
# the per-row cost is flat from 18 apart up and falls below 8 apart,
# where neighbours share a tile: PERF.md section 6, PR 30)
SPARSE_GAP = 16


def fit(readings):
  """Least squares over the grid, ns per row, per op: ``both`` to
  ``a * R + b * U``; ``unique`` to ``c * U`` over the sparse readings
  (``SPARSE_GAP``), the side of the grid where the two emitters' times
  cross, with the range of the denser readings beside it; ``rows_below``
  is the share ``a / (c - b)`` of the operand's rows under which the row
  emitter is the faster."""
  import numpy as np
  out = {}
  for op in ('add', 'set'):
    both = [r for r in readings if r['variant'] == f'{op}-both']
    uniq = [r for r in readings if r['variant'] == f'{op}-unique']
    got = out.setdefault(op, {})
    if len({(r['rows'], r['n']) for r in both}) >= 2:
      x = np.array([[r['rows'], r['n']] for r in both], float)
      y = np.array([r['ms'] * 1e6 for r in both])
      (a, b), *_ = np.linalg.lstsq(x, y, rcond=None)
      got.update(a_ns_per_operand_row=float(a), b_ns_per_update_row=float(b))
    sparse = [r for r in uniq if r['n'] * SPARSE_GAP <= r['rows']]
    if sparse:
      u = np.array([r['n'] for r in sparse], float)
      y = np.array([r['ms'] * 1e6 for r in sparse])
      got['c_ns_per_update_row'] = float(u @ y / (u @ u))
    dense = [r['ns_per_update_row'] for r in uniq if r not in sparse]
    if dense:
      got['c_dense_ns_per_update_row'] = [min(dense), max(dense)]
    if {'a_ns_per_operand_row', 'c_ns_per_update_row'} <= set(got):
      got['rows_below'] = got['a_ns_per_operand_row'] / (
          got['c_ns_per_update_row'] - got['b_ns_per_update_row'])
  return out


def main():
  ints = lambda s: [int(x) for x in s.split(',')]
  p = argparse.ArgumentParser()
  p.add_argument('--variant', choices=VARIANTS, required=True)
  p.add_argument('--rows', type=ints, default=[2_500_000, 8_775_000,
                                               20_025_088])
  p.add_argument('--n', type=ints, default=[92_272, 1_116_536, 2_883_584])
  p.add_argument('--width', type=int, default=128)
  p.add_argument('--iters', type=int, default=10)
  p.add_argument('--out', default='chiprun_out/scatter_probe.jsonl')
  args = p.parse_args()

  import jax
  import jax.numpy as jnp
  import numpy as np

  w, iters, variant = args.width, args.iters, args.variant
  gather = variant.startswith('gather')
  rng = np.random.default_rng(0)
  dev = jax.devices()[0]
  print(f'variant={variant} w={w} backend={jax.default_backend()} '
        f'device={dev.device_kind}')
  os.makedirs(os.path.dirname(args.out) or '.', exist_ok=True)
  for rows in args.rows:
    table = jnp.zeros((rows, w), jnp.float32)
    for n in args.n:
      upd = jnp.asarray(rng.normal(size=(n, w)).astype(np.float32))
      # (an argument of the jitted loop: closed over, the 1.4 GiB of
      # update rows would be compiled into the executable)
      op = _op(variant, rows)
      stacks = [jnp.asarray(_ids(rng, rows, n, iters,
                                 not variant.endswith('plain')))
                for _ in range(3)]
      if gather:
        # reduce over ALL gathered rows so no slice-of-gather
        # simplification can shrink the measured gather
        def run(tab, upd, s, op=op):
          def body(c, ids):
            return c + op(tab, ids, upd).sum(axis=0), None
          return jax.lax.scan(body, jnp.zeros((w,)), s)[0]
        f = jax.jit(run)
        step = lambda s: float(f(table, upd, s).sum())
      else:
        # the operand rides the loop donated: one buffer, written in
        # place, as the train step's table is
        def run(tab, upd, s, op=op):
          return jax.lax.scan(lambda c, ids: (op(c, ids, upd), None), tab,
                              s)[0]
        f = jax.jit(run, donate_argnums=0)

        def step(s):
          nonlocal table
          table = f(table, upd, s)
          return float(table[0, 0])
      step(stacks[0])  # compiles
      times = []
      for s in stacks[1:]:
        t0 = time.perf_counter()
        step(s)
        times.append(time.perf_counter() - t0)
      ms = min(times) / iters * 1e3
      reading = dict(variant=variant, rows=rows, n=n, width=w, ms=ms,
                     ns_per_update_row=ms * 1e6 / n,
                     ns_per_operand_row=ms * 1e6 / rows,
                     platform=dev.platform, device_kind=dev.device_kind)
      print(json.dumps(reading), flush=True)
      with open(args.out, 'a') as fh:
        fh.write(json.dumps(reading) + '\n')
      del upd, stacks, f
    del table
  with open(args.out) as fh:
    readings = [json.loads(l) for l in fh if l.strip()]
  here = [r for r in readings
          if r['width'] == w and r['device_kind'] == dev.device_kind]
  print(json.dumps({'fit_ns': fit(here), 'width': w,
                    'device_kind': dev.device_kind,
                    'readings': len(here)}))


if __name__ == '__main__':
  main()
