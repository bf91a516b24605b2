"""Compile-only sizing of a one-chip training cell's step for a v5e chip
(no chip attached): prints ``memory_analysis()`` of the compiled step at
the cell's own shapes, which must read under the 15.75 GiB the runtime
gives a chip.  Written for the hybrid state-space cell, whose 772 M
parameters at 16 bytes leave the step 4.5 GB; it takes its shapes from
the mix's own generator, so it sizes any ``train_pool`` cell:

  python3 benchmarks/dev/aot_hybrid.py        # granite-4.0-h-micro x train-packed
  python3 benchmarks/dev/aot_hybrid.py --config synthetic-tiny \
      --traffic train-uniform --calibrate     # capacities from the mix's
                                              # calibration batch, as a run
"""
import argparse
import os
import sys
import time

os.environ.setdefault('TPU_LOG_DIR', 'disabled')
sys.path.insert(0, os.getcwd())


def main():
  parser = argparse.ArgumentParser()
  parser.add_argument('--config', default='granite-4.0-h-micro')
  parser.add_argument('--traffic', default='train-packed')
  parser.add_argument('--calibrate', action='store_true')
  parser.add_argument('--hlo', default=None)
  args = parser.parse_args()
  import jax
  import jax.numpy as jnp
  import numpy as np
  from jax.experimental import topologies
  from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
  from benchmarks.lib import names
  from distributed_embeddings_tpu.parallel import (
      TrainState, calibrate_capacity_rows, make_hybrid_train_step)
  jax.config.update('jax_enable_compilation_cache', False)
  config = names.load_json('benchmarks', 'configs', args.config)
  mix = names.load_json('benchmarks', 'traffic', args.traffic)
  topo = topologies.get_topology_desc('v5e:2x2', 'tpu')
  mesh = Mesh(np.asarray(topo.devices).ravel()[:1], ('data',))
  model = names.resolve(config['builder'])(config, mesh, 1)
  dist = model.dist
  print(dist.plan.describe()[:600], flush=True)
  inputs = [(model.tables[t][0], h) for t, h in
            zip(model.input_table_map, model.hotness)]
  (cats, rest), = names.resolve(mix['generator'])(
      mix, inputs, config, mix['calibration_seed'], batches=1)
  caps = None
  if args.calibrate:
    caps = calibrate_capacity_rows(dist, cats)    # on the CPU mirror
    print(f'capacity rows {caps}', flush=True)
  rep = NamedSharding(mesh, P())
  bsh = NamedSharding(mesh, P('data'))
  sds = lambda shape, dt, sh: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
  like = lambda tree, sh: jax.tree.map(
      lambda x: sds(x.shape, x.dtype, sh), tree)
  emb_opt = model.emb_optimizer_cls(capacity_rows=caps,
                                    **model.emb_optimizer_kwargs)
  step = make_hybrid_train_step(dist, model.head_loss_fn,
                                model.dense_optimizer, emb_opt, jit=False,
                                **model.step_kwargs)
  sharded = lambda x: sds(x.shape, x.dtype, NamedSharding(
      mesh, P('data', *([None] * (x.ndim - 1)))))
  emb = {f'group_{gi}': sharded(jax.ShapeDtypeStruct(
      (1, g.param_rows, g.param_width), jnp.float32))
         for gi, g in enumerate(dist.plan.groups)}
  emb_state = jax.tree.map(
      sharded, jax.eval_shape(lambda e: emb_opt.init(dist, e), emb))
  dense = like(model.dense_params, rep)
  dense_state = like(jax.eval_shape(model.dense_optimizer.init,
                                    model.dense_params), rep)
  state = TrainState(params={**dense, 'embedding': emb},
                     opt_state=(dense_state, emb_state),
                     step=sds((), jnp.int32, rep))
  started = time.time()
  compiled = jax.jit(step, donate_argnums=(0,)).lower(
      state, like(cats, bsh), like(rest, bsh)).compile()
  memory = compiled.memory_analysis()
  gib = lambda b: b / 2**30
  print(f'step compiled in {time.time() - started:.0f} s: arguments '
        f'{gib(memory.argument_size_in_bytes):.2f} GiB, temporaries '
        f'{gib(memory.temp_size_in_bytes):.2f} GiB, outputs '
        f'{gib(memory.output_size_in_bytes):.2f} GiB (aliased '
        f'{gib(memory.alias_size_in_bytes):.2f}); arguments + temporaries '
        f'{gib(memory.argument_size_in_bytes + memory.temp_size_in_bytes):.2f}'
        ' of 15.75 GiB', flush=True)
  if args.hlo:
    with open(args.hlo, 'w') as f:
      f.write(compiled.as_text())


if __name__ == '__main__':
  main()
