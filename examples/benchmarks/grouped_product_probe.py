"""Which grouped matrix product the routed layer takes
(``layers/routed_experts._grouped``), and at which widths, measured on
the chip: ``jax.lax.ragged_dot`` (float32 operands at the default
precision; on a TPU XLA lowers it to a Mosaic kernel of its own) against
``jax.experimental.pallas.ops.tpu.megablox``'s ``gmm`` (float32
operands, and operands cast to bfloat16), forward alone and forward with
both gradients, for ``lhs [rows, k]`` times ``rhs [groups, k, n]`` over
every ``k`` and ``n`` given.  The defaults are the mixture-of-experts
cell's shapes (16 groups of a 20,480-row buffer; gate|up ``(2048,
2048)``, down ``(1024, 2048)``):

  python3 examples/benchmarks/grouped_product_probe.py   # on a TPU

``--transposed`` reads ``(n, k)`` as well as ``(k, n)`` (a layer's down
product).  The nemotron grid (7,680 rows over 8 groups, hidden 2688 and
interior 1856 against their rounded widths; ``ragged_dot`` alone):

  python3 examples/benchmarks/grouped_product_probe.py --rows 7680 \\
      --groups 8 --k 2688,2816,3072 --n 1856,1920,2048 --transposed \\
      --variants ragged_dot_f32

Prints one JSON line a variant and shape: ms a call (forward, and
forward + backward) and the achieved share of the chip's 197 bf16
TFLOP/s.
"""
import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

PEAK = 197e12


def _ragged(lhs, rhs, sizes):
  return jax.lax.ragged_dot(lhs, rhs, sizes)


def _gmm(dtype):
  from jax.experimental.pallas.ops.tpu.megablox import gmm

  def product(lhs, rhs, sizes):
    return gmm(lhs.astype(dtype), rhs.astype(dtype), sizes,
               preferred_element_type=jnp.float32,
               tiling=(512, 512, 512))
  return product


VARIANTS = {'ragged_dot_f32': lambda: _ragged,
            'gmm_f32': lambda: _gmm(jnp.float32),
            'gmm_bf16': lambda: _gmm(jnp.bfloat16)}


def _ms(fn, args, repeats):
  out = jax.block_until_ready(fn(*args))
  started = time.perf_counter()
  for _ in range(repeats):
    out = fn(*args)
  jax.block_until_ready(out)
  return (time.perf_counter() - started) / repeats * 1e3, out


def _ints(text):
  return [int(x) for x in text.split(',')]


def main(argv=None):
  parser = argparse.ArgumentParser()
  parser.add_argument('--rows', type=int, default=20480)
  parser.add_argument('--groups', type=int, default=16)
  parser.add_argument('--k', type=_ints, default=[2048, 1024],
                      help='contracted widths, comma-separated')
  parser.add_argument('--n', type=_ints, default=[2048],
                      help='output widths, comma-separated')
  parser.add_argument('--transposed', action='store_true',
                      help='read (n, k) beside each (k, n)')
  parser.add_argument('--variants', default=','.join(VARIANTS))
  parser.add_argument('--repeats', type=int, default=10)
  args = parser.parse_args(argv)
  if jax.devices()[0].platform != 'tpu':
    raise SystemExit('grouped_product_probe.py measures the chip')
  rows, groups = args.rows, args.groups
  rng = np.random.default_rng(0)
  sizes = jnp.asarray(rng.multinomial(rows, np.ones(groups) / groups),
                      jnp.int32)
  variants = {name: VARIANTS[name]() for name in args.variants.split(',')}
  shapes = [(k, n) for k in args.k for n in args.n]
  if args.transposed:
    shapes += [(n, k) for k, n in shapes if (n, k) not in shapes]
  for k, n in shapes:
    lhs = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((groups, k, n)), jnp.float32)
    cot = jnp.asarray(rng.standard_normal((rows, n)), jnp.float32)
    want = None
    for name, product in variants.items():
      forward = jax.jit(lambda l, r, product=product: product(l, r, sizes))
      step = jax.jit(jax.grad(
          lambda l, r, product=product: jnp.sum(cot * product(l, r, sizes)),
          argnums=(0, 1)))
      try:
        ms_fwd, _ = _ms(forward, (lhs, rhs), args.repeats)
        ms, out = _ms(step, (lhs, rhs), args.repeats)
      except Exception as e:      # a variant the compiler refuses
        print(json.dumps({'k': k, 'n': n, 'variant': name,
                          'refused': repr(e)[:300]}), flush=True)
        continue
      want = out if want is None else want
      gap = max(float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
                for a, b in zip(out, want))
      flops = 2 * rows * k * n
      print(json.dumps({
          'rows': rows, 'groups': groups, 'k': k, 'n': n, 'variant': name,
          'ms_fwd': round(ms_fwd, 3), 'ms_fwd_bwd': round(ms, 3),
          'share_of_peak_fwd': round(flops / (ms_fwd * 1e-3) / PEAK, 4),
          'share_of_peak': round(3 * flops / (ms * 1e-3) / PEAK, 4),
          'gap_to_first_variant': gap}), flush=True)


if __name__ == '__main__':
  sys.exit(main())
