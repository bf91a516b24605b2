"""Diagnose the composed sparse train step: memory analysis + xplane trace.

Usage: python examples/benchmarks/diag_full.py [--batch 65536] [--steps 2]
       [--trace /tmp/trace]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))


def main():
  p = argparse.ArgumentParser()
  p.add_argument('--batch', type=int, default=65536)
  p.add_argument('--steps', type=int, default=2)
  p.add_argument('--model', default='tiny')
  p.add_argument('--trace', default='')
  p.add_argument('--param_dtype', default='float32')
  p.add_argument('--segwalk_apply', action='store_true')
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
  K = args.steps

  def head_loss_fn(dense_params, emb_outs, batch):
    numerical, labels = batch
    return bce_with_logits(model.head(dense_params, numerical, emb_outs),
                           labels)

  opt = optax.adagrad(0.01, initial_accumulator_value=0.1, eps=1e-7)
  emb_opt = SparseAdagrad(learning_rate=0.01,
                          use_segwalk_apply=args.segwalk_apply)
  if args.segwalk_apply:
    from distributed_embeddings_tpu.utils.apply_eligibility import (
        eligibility_line)
    print(eligibility_line(dist, args.param_dtype, args.segwalk_apply))
  step = make_hybrid_train_step(dist, head_loss_fn, opt, emb_opt, jit=False)

  def run(st):
    def body(c, k):
      s2, loss = step(c, list(cats0), (num0, labels0))
      return s2, None
    return jax.lax.scan(body, st, jnp.arange(K))[0]

  state = init_hybrid_train_state(dist, params, opt, emb_opt)
  f = jax.jit(run, donate_argnums=(0,))
  t0 = time.perf_counter()
  lowered = f.lower(state)
  compiled = lowered.compile()
  print(f'compile: {time.perf_counter() - t0:.1f}s')
  ma = compiled.memory_analysis()
  if ma is not None:
    for attr in ('temp_size_in_bytes', 'argument_size_in_bytes',
                 'output_size_in_bytes', 'alias_size_in_bytes',
                 'generated_code_size_in_bytes'):
      v = getattr(ma, attr, None)
      if v is not None:
        print(f'{attr}: {v/1e9:.3f} GB')

  # two warmup executions: the AOT compile above does not populate the
  # call-time jit cache, so execution 1 compiles and execution 2 absorbs
  # a second compile if the state came back with other input shardings
  for _ in range(2):
    state = f(state)
    leaf = jax.tree.leaves(state)[0]
    float(jnp.sum(leaf[0].astype(jnp.float32)))
  t0 = time.perf_counter()
  if args.trace:
    with jax.profiler.trace(args.trace):
      state = f(state)
      leaf = jax.tree.leaves(state)[0]
      float(jnp.sum(leaf[0].astype(jnp.float32)))
  else:
    state = f(state)
    leaf = jax.tree.leaves(state)[0]
    float(jnp.sum(leaf[0].astype(jnp.float32)))
  dt = (time.perf_counter() - t0) / K * 1e3
  print(f'full step ({args.model}, batch {args.batch}): {dt:.1f} ms/step')


if __name__ == '__main__':
  main()
