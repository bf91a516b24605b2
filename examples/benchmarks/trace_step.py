"""Capture a few steady-state hybrid sparse steps and print where the
chip's time went, phase by phase: the one capture script.

Wraps its measured steps in ``obs.trace.profile`` (the JAX profiler with
Python's call tracer off, plus the program's host spans on the same
clock) and prints ``tools/trace_report.py --profile`` on what it wrote,
together with the compiled step's memory analysis.  The compile cache's
key takes the phases in (``utils/compile_cache.configure``), so a step
whose phases changed is compiled again, never served with the old ones.

Usage: python examples/benchmarks/trace_step.py [--trace /tmp/trace_step]
       [--segwalk_apply] [--param_dtype bfloat16] [--model tiny]
"""

import argparse
import os
import sys
import tempfile

ROOT = os.path.join(os.path.dirname(__file__), '..', '..')
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, 'tools'))


def main():
  p = argparse.ArgumentParser()
  p.add_argument('--batch', type=int, default=65536)
  p.add_argument('--model', default='tiny')
  p.add_argument('--trace', default='',
                 help='profile directory (default: a temporary one)')
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
  from distributed_embeddings_tpu.obs import trace as obs_trace
  from distributed_embeddings_tpu.parallel import (SparseAdagrad, create_mesh,
                                                   init_hybrid_train_state,
                                                   make_hybrid_train_step)
  import trace_report

  mesh = create_mesh(jax.devices())
  config = SYNTHETIC_MODELS[args.model]
  model = SyntheticModel(config, mesh=mesh, dp_input=True,
                         param_dtype=jnp.dtype(args.param_dtype))
  params = model.init(0)
  gen = InputGenerator(config, args.batch, alpha=1.05, num_batches=1, seed=0)
  (num0, cats0), labels0 = gen.pool[0]
  num0 = jnp.asarray(num0)
  cats0 = [jnp.asarray(c) for c in cats0]
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
  from distributed_embeddings_tpu.utils.apply_eligibility import (
      eligibility_line, write_rows_lines)
  if args.segwalk_apply:
    print(eligibility_line(dist, args.param_dtype, args.segwalk_apply))
  else:
    print('\n'.join(write_rows_lines(dist, emb_opt)))
  step = make_hybrid_train_step(dist, head_loss_fn, opt, emb_opt)
  state = init_hybrid_train_state(dist, params, opt, emb_opt)
  batch = (num0, labels0)

  compiled = step.jitted.lower(state, cats0, batch).compile()
  memory = compiled.memory_analysis()
  if memory is not None:
    print('compiled step: arguments '
          f'{memory.argument_size_in_bytes / 2**30:.2f} GiB + temporaries '
          f'{memory.temp_size_in_bytes / 2**30:.2f} GiB '
          f'(aliased {memory.alias_size_in_bytes / 2**30:.2f} GiB)')
  for _ in range(2):  # the second absorbs a re-layout of the new state
    state, loss = compiled(state, cats0, batch)
  jax.block_until_ready((state, loss))

  directory = args.trace or tempfile.mkdtemp(prefix='trace_step_')
  with obs_trace.profile(directory):
    for i in range(args.calls):
      with obs_trace.span('train/step', step=i + 1):
        state, loss = compiled(state, cats0, batch)
        loss.block_until_ready()
  print(f'profile written under {directory}')
  report = trace_report.profile_report(
      trace_report.load_profile(trace_report.find_profile(directory)),
      program='jit_step')
  print(trace_report.format_profile(report, children=True))


if __name__ == '__main__':
  main()
