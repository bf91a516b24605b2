"""Compile the FULL-SIZE synthetic train step for a v5e target — no chip.

The locally installed libtpu runs the entire compile stack against an
abstract topology (`jax.experimental.topologies`), so this validates
that the full-scale program (real table sizes, global batch 65536)
compiles for v5e and reports its REAL memory analysis (does it fit
16 GiB HBM per chip?) without a chip.  Small-shape
variants of the same check run in CI (tests/test_tpu_lowering.py);
this script is the full-size version whose compile takes minutes.

Usage: python examples/benchmarks/compile_check.py [--model tiny]
       [--chips 4] [--batch 65536] [--segwalk_apply]

NOTE: libtpu allows one topology user per host at a time
(/tmp/libtpu_lockfile) — don't run concurrently with the
test_tpu_lowering.py gate.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))


def main():
  p = argparse.ArgumentParser()
  p.add_argument('--model', default='tiny')
  p.add_argument('--chips', type=int, default=4)
  p.add_argument('--batch', type=int, default=65536)
  p.add_argument('--segwalk_apply', action='store_true')
  p.add_argument('--param_dtype', default='float32',
                 choices=['float32', 'bfloat16'],
                 help='table storage dtype: bfloat16 halves the argument '
                 'HBM, the binding resource for models whose state '
                 'approaches chip memory (e.g. small at 8 chips)')
  p.add_argument('--capacity_fraction', type=float, default=0.5,
                 help='compaction capacity fraction (bench.py default '
                 '0.5); temps scale with it')
  p.add_argument('--stream_dtype', default='float32',
                 choices=['float32', 'bfloat16'],
                 help='segwalk update-stream payload dtype: bfloat16 '
                 'halves the comb + sorted-gather temp pair, the '
                 'binding allocation at pod scale')
  p.add_argument('--accum_dtype', default='float32',
                 choices=['float32', 'bfloat16'],
                 help='Adagrad accumulator storage dtype: bfloat16 '
                 'halves the accumulator argument HBM (the jumbo lever)')
  p.add_argument('--compute_dtype', default=None,
                 choices=['float32', 'bfloat16'],
                 help='activation dtype (default: param_dtype, matching '
                 'bench.py): f32 activations on bf16 tables double the '
                 'forward combine temps at jumbo scale')
  p.add_argument('--row_slice', type=int, default=None,
                 help='element threshold for ROW-sharding big tables '
                 '(beyond the reference; spreads a 400M-row table\'s '
                 'rows across chips when column slicing alone cannot)')
  p.add_argument('--column_slice', default=None,
                 help="element threshold for column slicing, or "
                 "'balance' = planner sweep picking the threshold with "
                 "the least per-chip capacity padding (total/chips "
                 "alone is too coarse: it left medium@32 at 16.3 GiB "
                 "of args vs 10.0 at total/256, round 5).  Without "
                 "any threshold a single 100M-row table lands whole "
                 "on one chip and capacity padding bloats every other "
                 "chip to match (medium+ models at multi-chip)")
  p.add_argument('--topology', default='v5e:2x2',
                 help='compile-only topology (chips must divide it)')
  p.add_argument('--compiler_option', action='append', default=[],
                 help='k=v XLA compiler option (repeatable), e.g. '
                 'exec_time_optimization_effort=-1.0 (NO xla_ prefix: '
                 'the effort knobs are ExecutionOptions, not DebugOptions '
                 '— the prefixed names are rejected, probed round 5)')
  p.add_argument('--no_cache', action='store_true',
                 help='skip the persistent compilation cache')
  args = p.parse_args()

  import jax
  # compile-only: never take a chip, even on a machine that has one
  jax.config.update('jax_platforms', 'cpu')
  if not args.no_cache:
    from distributed_embeddings_tpu.utils import compile_cache
    compile_cache.configure()
  import jax.numpy as jnp
  import optax
  from jax.experimental import topologies
  from jax.sharding import NamedSharding, PartitionSpec as P
  from distributed_embeddings_tpu.models.synthetic import (SYNTHETIC_MODELS,
                                                           SyntheticModel,
                                                           expand_tables)
  from distributed_embeddings_tpu.models.dlrm import bce_with_logits
  from distributed_embeddings_tpu.parallel import (SparseAdagrad,
                                                   make_hybrid_train_step)
  from distributed_embeddings_tpu.parallel.grad import TrainState

  if args.segwalk_apply:
    # compile-only flows trace on the CPU backend: without this the
    # backend-sniffing dispatch would silently compile the XLA path
    from distributed_embeddings_tpu.ops import pallas_segwalk
    pallas_segwalk.ASSUME_TPU = True
  topo = topologies.get_topology_desc(args.topology, 'tpu')
  # plain Mesh over the first N topology devices: unlike
  # topologies.make_mesh it permits a SUBSET, so --chips 1 (the exact
  # D=1 bench program) compiles against the 2x2 minimum topology
  import numpy as np
  tdevs = np.asarray(topo.devices).ravel()
  if args.chips > tdevs.size:
    raise SystemExit(f'--chips {args.chips} exceeds topology '
                     f'{args.topology} ({tdevs.size} devices)')
  from jax.sharding import Mesh
  mesh = Mesh(tdevs[:args.chips], ('data',))
  config = SYNTHETIC_MODELS[args.model]
  pdt = jnp.dtype(args.param_dtype)
  cst = args.column_slice
  if cst == 'balance':
    # pure-Python planner sweep (seconds): pick the threshold with the
    # least per-chip padded memory — total/chips alone under-slices
    # (integer table-count imbalance keeps groups ~50% filled)
    from distributed_embeddings_tpu.parallel.planner import ShardingPlan
    tconfigs, titm, _ = expand_tables(config)
    total = sum(c.input_dim * c.output_dim for c in tconfigs)
    best = None
    for div in (args.chips, 2 * args.chips, 4 * args.chips,
                8 * args.chips, 16 * args.chips, 32 * args.chips):
      cand = -(-total // div)
      try:
        # the SAME strategy SyntheticModel builds the compiled model
        # with — a 'basic'-plan sweep would minimise padding for a
        # different placement than the one whose memory is reported
        pe = ShardingPlan(tconfigs, world_size=args.chips,
                          input_table_map=titm,
                          strategy='memory_balanced',
                          column_slice_threshold=cand,
                          row_slice_threshold=args.row_slice
                          ).padded_memory_elements()
      except ValueError:
        continue
      if best is None or pe < best[0]:
        best = (pe, cand)
    if best is None:
      raise SystemExit('balance sweep: every candidate threshold '
                       f'produced an invalid plan for {args.model} at '
                       f'{args.chips} chips — pass an explicit '
                       '--column_slice')
    cst = best[1]
    bpe = jnp.dtype(args.param_dtype).itemsize
    print(f'balance sweep: column_slice_threshold={cst} '
          f'({best[0] * bpe / 2**30:.2f} GiB/chip padded '
          f'{args.param_dtype})', flush=True)
  elif cst is not None:
    cst = int(cst)
  cdt = jnp.dtype(args.compute_dtype or args.param_dtype)
  model = SyntheticModel(config, mesh=mesh, dp_input=True, param_dtype=pdt,
                         compute_dtype=cdt,
                         column_slice_threshold=cst,
                         row_slice=args.row_slice)
  dist = model.dist_embedding
  opt = SparseAdagrad(learning_rate=0.01,
                      capacity_fraction=args.capacity_fraction,
                      use_segwalk_apply=args.segwalk_apply,
                      stream_dtype=args.stream_dtype,
                      accum_dtype=args.accum_dtype)
  dense_opt = optax.adagrad(0.01, initial_accumulator_value=0.1, eps=1e-7)

  def head_loss_fn(dp, eo, b):
    num, labels = b
    return bce_with_logits(model.head(dp, num, eo), labels)

  step = make_hybrid_train_step(dist, head_loss_fn, dense_opt, opt,
                                donate=False, jit=False)
  GB = args.batch
  bsh = NamedSharding(mesh, P('data'))
  rep = NamedSharding(mesh, P())
  tsh = NamedSharding(mesh, P('data', None, None))

  def sds(shape, dt, sh):
    return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

  W = args.chips
  emb = {
      f'group_{gi}': sds((W, g.param_rows, g.param_width), pdt, tsh)
      for gi, g in enumerate(dist.plan.groups)
  }
  adt = jnp.dtype(args.accum_dtype)
  acc = {
      f'group_{gi}': {
          'acc': sds((W, g.param_rows, g.param_width), adt, tsh)
      } for gi, g in enumerate(dist.plan.groups)
  }
  mlp_shapes = jax.eval_shape(
      lambda k: model.mlp.init(k, model._mlp_input_dim), jax.random.key(0))
  mlp = jax.tree.map(lambda x: sds(x.shape, x.dtype, rep), mlp_shapes)
  dense_state_shapes = jax.eval_shape(
      lambda m: dense_opt.init({'mlp': m}), mlp_shapes)
  dense_state = jax.tree.map(lambda x: sds(x.shape, x.dtype, rep),
                             dense_state_shapes)
  state = TrainState(params={'embedding': emb, 'mlp': mlp},
                     opt_state=(dense_state, acc),
                     step=sds((), jnp.int32, rep))
  _, _, hotness = expand_tables(config)
  cats = [sds((GB, h) if h > 1 else (GB,), jnp.int32, bsh) for h in hotness]
  num = sds((GB, config.num_numerical_features), jnp.float32, bsh)
  labels = sds((GB, 1), jnp.float32, bsh)

  copts = {}
  for kv in args.compiler_option:
    k, _, v = kv.partition('=')
    # numeric-typed options (e.g. exec_time_optimization_effort) reject
    # string values outright
    try:
      v = int(v)
    except ValueError:
      try:
        v = float(v)
      except ValueError:
        pass
    copts[k] = v
  t0 = time.time()
  # donate the state like the real bench step (bench.py
  # donate_argnums=(0,)): without it the updated tables appear as
  # full-size HLO-temp copies and D=1 reads as a 6 GiB HBM overshoot
  # the runtime never has
  lowered = jax.jit(step, donate_argnums=(0,)).lower(
      state, cats, (num, labels))
  t_lower = time.time() - t0
  t0 = time.time()
  compiled = lowered.compile(compiler_options=copts or None)
  t_compile = time.time() - t0
  gen = args.topology.split(':')[0]
  print(f'{args.model} {args.chips}-chip {gen} train step compiled in '
        f'{t_lower + t_compile:.0f}s (trace+lower {t_lower:.0f}s, '
        f'XLA {t_compile:.0f}s; '
        f'{"segwalk" if args.segwalk_apply else "xla"} apply)',
        flush=True)
  ma = compiled.memory_analysis()
  if ma is not None:
    for attr in ('temp_size_in_bytes', 'argument_size_in_bytes',
                 'output_size_in_bytes', 'alias_size_in_bytes'):
      v = getattr(ma, attr, None)
      if v is not None:
        print(f'  {attr}: {v / 2**30:.3f} GiB', flush=True)
  try:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):  # older jax wraps in a list
      ca = ca[0] if ca else {}
    if ca:
      for k in ('flops', 'bytes accessed', 'transcendentals'):
        if k in ca:
          print(f'  cost {k}: {ca[k]:.3e}', flush=True)
  except Exception as e:  # cost analysis is best-effort per backend
    print(f'  cost_analysis unavailable: {e}', flush=True)


if __name__ == '__main__':
  main()
