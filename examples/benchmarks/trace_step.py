"""Trace ONE steady-state hybrid sparse step (after layout stabilisation).

Usage: python examples/benchmarks/trace_step.py [--trace /tmp/trace_step]
       [--segwalk_apply] [--param_dtype bfloat16] [--model tiny]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))


def main():
  p = argparse.ArgumentParser()
  p.add_argument('--batch', type=int, default=65536)
  p.add_argument('--model', default='tiny')
  p.add_argument('--trace', default='')
  p.add_argument('--param_dtype', default='float32')
  p.add_argument('--segwalk_apply', action='store_true')
  p.add_argument('--capacity_fraction', type=float, default=0.5)
  p.add_argument('--auto_capacity', action='store_true')
  p.add_argument('--calls', type=int, default=3)
  args = p.parse_args()

  import jax
  import jax.numpy as jnp
  import optax
  from distributed_embeddings_tpu.utils import compile_cache
  compile_cache.configure()
  from distributed_embeddings_tpu.models.synthetic import (SYNTHETIC_MODELS,
                                                           InputGenerator,
                                                           SyntheticModel)
  from distributed_embeddings_tpu.models.dlrm import bce_with_logits
  from distributed_embeddings_tpu.parallel import (SparseAdagrad, create_mesh,
                                                   init_hybrid_train_state,
                                                   make_hybrid_train_step)

  mesh = create_mesh(jax.devices())
  config = SYNTHETIC_MODELS[args.model]
  model = SyntheticModel(config, mesh=mesh, dp_input=True,
                         param_dtype=jnp.dtype(args.param_dtype))
  params = model.init(0)
  gen = InputGenerator(config, args.batch, alpha=1.05, num_batches=1, seed=0)
  (num0, cats0), labels0 = gen.pool[0]
  num0 = jnp.asarray(num0)
  cats0 = tuple(jnp.asarray(c) for c in cats0)
  labels0 = jnp.asarray(labels0)
  dist = model.dist_embedding

  def head_loss_fn(dp, eo, batch):
    numerical, labels = batch
    return bce_with_logits(model.head(dp, numerical, eo), labels)

  opt = optax.adagrad(0.01, initial_accumulator_value=0.1, eps=1e-7)
  capacity_rows = None
  if args.auto_capacity:
    from distributed_embeddings_tpu.parallel import calibrate_capacity_rows
    capacity_rows = calibrate_capacity_rows(dist, list(cats0),
                                            params=params['embedding'])
    print('calibrated capacity_rows:', capacity_rows)
  emb_opt = SparseAdagrad(learning_rate=0.01,
                          capacity_fraction=args.capacity_fraction,
                          capacity_rows=capacity_rows,
                          use_segwalk_apply=args.segwalk_apply)
  if args.segwalk_apply:
    from distributed_embeddings_tpu.utils.apply_eligibility import (
        eligibility_line)
    print(eligibility_line(dist, args.param_dtype, args.segwalk_apply))
  step = jax.jit(make_hybrid_train_step(dist, head_loss_fn, opt, emb_opt,
                                        jit=False), donate_argnums=(0,))
  state = init_hybrid_train_state(dist, params, opt, emb_opt)

  for i in range(2):
    t0 = time.perf_counter()
    state, loss = step(state, list(cats0), (num0, labels0))
    loss.block_until_ready()
    print(f'warmup {i}: {time.perf_counter() - t0:.1f}s')

  import contextlib
  times = []
  cm = (jax.profiler.trace(args.trace) if args.trace
        else contextlib.nullcontext())
  with cm:
    for i in range(args.calls):
      t0 = time.perf_counter()
      state, loss = step(state, list(cats0), (num0, labels0))
      loss.block_until_ready()
      times.append(time.perf_counter() - t0)
  print(f'steady-state step: {min(times)*1e3:.1f} ms '
        f'(all: {[round(t*1e3) for t in times]})')


if __name__ == '__main__':
  main()
