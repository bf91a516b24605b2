"""What a mixture-of-experts cell's router does over a run's first steps
(by hand, on the chip): the cell's own state, step and pool as
``runners/train_pool`` builds them, driven ``--steps`` steps one at a
time, and before each step the program's own forward-only
``moe_lm.routing_stats`` on the batch it is about to take:

  python3 benchmarks/dev/routing_probe.py --workload trinity-train-packed-8k --seed 11 --steps 16

One JSON line a step: the step's seconds on the host's clock (a drained
device on both edges), the loss, and per routed layer the assignments
this chip holds (the expected count is ``tokens x experts a token x held
/ router width``), the largest held expert's load over the mean and the
assignments past the capacity (what runs every step); before them, how many
tokens' selections the products' precision moves at the first batch (the
program's default against the highest, as the reference computes).  No
reference, no window: a step's time beside what its router did.
"""
import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None):
  parser = argparse.ArgumentParser()
  parser.add_argument('--workload', required=True)
  parser.add_argument('--seed', type=int, required=True)
  parser.add_argument('--steps', type=int, default=16)
  args = parser.parse_args(argv)
  from benchmarks.lib import names
  with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
    cell = {w['name']: w for w in json.load(f)['workloads']}[args.workload]
  root = os.path.join(ROOT, 'benchmarks')
  config = names.load_json(root, 'configs', cell['config'])
  mix = names.load_json(root, 'traffic', cell['traffic'])
  names.set_runtime_env(mix, os.environ)
  import jax
  import jax.numpy as jnp
  import numpy as np
  if jax.devices()[0].platform != 'tpu':
    raise SystemExit('routing_probe.py reads the router at the cell\'s own '
                     'size on the chip')
  from benchmarks.lib import program_state, weights
  from distributed_embeddings_tpu.models import moe_lm
  from distributed_embeddings_tpu.parallel import (
      create_mesh, init_hybrid_train_state, make_hybrid_train_step)
  from distributed_embeddings_tpu.parallel.mesh import make_global_batch
  from distributed_embeddings_tpu.utils import compile_cache
  compile_cache.configure()
  mesh = create_mesh(jax.devices()[:1])
  model = names.resolve(config['builder'])(config, mesh, args.seed)
  dist = model.dist
  inputs = [(model.tables[t][0], h) for t, h in
            zip(model.input_table_map, model.hotness)]
  host_pool = names.resolve(mix['generator'])(mix, inputs, config, args.seed)
  layout = program_state.table_layout(dist)
  emb = program_state.make_tables(
      dist, layout, model.tables, weights.table_words(args.seed, 1))
  emb_opt = model.emb_optimizer_cls(**model.emb_optimizer_kwargs)
  state = init_hybrid_train_state(
      dist, {**jax.tree.map(jnp.asarray, model.dense_params),
             'embedding': emb}, model.dense_optimizer, emb_opt)
  step = make_hybrid_train_step(dist, model.head_loss_fn,
                                model.dense_optimizer, emb_opt)
  cfg = moe_lm.MoELMConfig.from_dict(config)

  def over(fn):
    """``fn(cfg, dense, rows, segment_ids)`` of the state's parameters
    and a placed batch, jitted."""
    def call(params, ids, segment_ids):
      dense = {k: v for k, v in params.items() if k != 'embedding'}
      rows = params['embedding']['group_0'][0][
          ids.reshape(segment_ids.shape)]
      return fn(cfg, dense, rows, segment_ids)
    return jax.jit(call)

  stats = over(moe_lm.routing_stats)

  chosen = lambda: over(lambda *a: jnp.sort(moe_lm.selections(*a), axis=-1))

  # how far the products' precision moves the selection: the program's
  # (float32 operands at the default precision feed the router) against
  # the same weights at the highest, as the reference computes them
  cats, (targets, segment_ids) = host_pool[0]
  placed = make_global_batch(mesh, cats[0], targets, segment_ids)
  mine = np.asarray(chosen()(state.params, placed[0], placed[2]))
  with jax.default_matmul_precision('highest'):
    theirs = np.asarray(chosen()(state.params, placed[0], placed[2]))
  moved = (mine != theirs).any(axis=-1)
  print(json.dumps({'tokens_whose_selection_differs_by_layer':
                    moved.sum(axis=-1).tolist(),
                    'of_tokens': int(moved.shape[-1])}), flush=True)
  tokens = int(mix['global_batch']) * int(mix['seq_len'])
  print(json.dumps({'expected_assignments': tokens * cfg.num_experts_per_tok
                    * cfg.num_experts / cfg.router_width,
                    'capacity': cfg.routed.capacity(tokens),
                    'wave_slots': cfg.routed.wave_slots(tokens),
                    'waves': cfg.routed.waves(tokens)}), flush=True)
  for k in range(args.steps):
    cats, (targets, segment_ids) = host_pool[k % len(host_pool)]
    placed = make_global_batch(mesh, cats[0], targets, segment_ids)
    read = jax.tree.map(np.asarray, stats(state.params, placed[0], placed[2]))
    jax.block_until_ready(state)
    started = time.perf_counter()
    state, loss = step(state, [placed[0]], (placed[1], placed[2]))
    loss = float(loss)
    jax.block_until_ready(state)
    print(json.dumps({
        'step': k + 1, 'seconds': round(time.perf_counter() - started, 4),
        'loss': loss, **{n: [round(float(x), 3) for x in v]
                         for n, v in read.items()}}), flush=True)


if __name__ == '__main__':
  sys.exit(main())
