"""Which grouped matrix product the routed layer takes
(``layers/routed_experts._grouped``), measured on the chip at the
mixture-of-experts cell's shapes: ``jax.lax.ragged_dot`` (float32
operands at the default precision; on a TPU XLA lowers it to a Mosaic
kernel of its own) against ``jax.experimental.pallas.ops.tpu.megablox``'s
``gmm`` (float32 operands, and operands cast to bfloat16), forward and
backward (both gradients), over 16 groups of a 20,480-row buffer:

  python3 examples/benchmarks/grouped_product_probe.py   # on a TPU

Prints one JSON line a variant and shape: ms a call (forward + backward)
and the achieved share of the chip's 197 bf16 TFLOP/s.
"""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROWS, GROUPS, PEAK = 20480, 16, 197e12
SHAPES = {'gate_up': (2048, 2048), 'down': (1024, 2048)}


def _ragged(lhs, rhs, sizes):
  return jax.lax.ragged_dot(lhs, rhs, sizes)


def _gmm(dtype):
  from jax.experimental.pallas.ops.tpu.megablox import gmm

  def product(lhs, rhs, sizes):
    return gmm(lhs.astype(dtype), rhs.astype(dtype), sizes,
               preferred_element_type=jnp.float32,
               tiling=(512, 512, 512))
  return product


def main():
  if jax.devices()[0].platform != 'tpu':
    raise SystemExit('grouped_product_probe.py measures the chip')
  rng = np.random.default_rng(0)
  sizes = jnp.asarray(rng.multinomial(ROWS, np.ones(GROUPS) / GROUPS),
                      jnp.int32)
  variants = {'ragged_dot_f32': _ragged, 'gmm_f32': _gmm(jnp.float32),
              'gmm_bf16': _gmm(jnp.bfloat16)}
  for shape, (k, n) in SHAPES.items():
    lhs = jnp.asarray(rng.standard_normal((ROWS, k)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((GROUPS, k, n)), jnp.float32)
    cot = jnp.asarray(rng.standard_normal((ROWS, n)), jnp.float32)
    want = None
    for name, product in variants.items():
      step = jax.jit(jax.grad(
          lambda l, r: jnp.sum(cot * product(l, r, sizes)), argnums=(0, 1)))
      try:
        out = jax.block_until_ready(step(lhs, rhs))
      except Exception as e:      # a variant the compiler refuses
        print(json.dumps({'shape': shape, 'variant': name,
                          'refused': repr(e)[:300]}), flush=True)
        continue
      started = time.perf_counter()
      for _ in range(10):
        out = step(lhs, rhs)
      jax.block_until_ready(out)
      ms = (time.perf_counter() - started) / 10 * 1e3
      want = out if want is None else want
      gap = max(float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
                for a, b in zip(out, want))
      print(json.dumps({
          'shape': shape, 'variant': name, 'ms_fwd_bwd': round(ms, 3),
          'share_of_peak': round(3 * 2 * ROWS * k * n / (ms * 1e-3) / PEAK,
                                 4),
          'gap_to_ragged_dot': gap}), flush=True)


if __name__ == '__main__':
  sys.exit(main())
