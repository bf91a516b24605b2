"""Compile-only rehearsal of dlrm-train-4chip for v5e:2x2 (no chip)."""
import os, sys, time, json
os.environ.setdefault('TPU_LOG_DIR', 'disabled')
sys.path.insert(0, os.getcwd())
import numpy as np
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding
from benchmarks.lib import names, weights, program_state
from distributed_embeddings_tpu.parallel import make_hybrid_train_step, TrainState

config = names.load_json('benchmarks', 'configs', 'dlrm-mlperf')
topo = topologies.get_topology_desc('v5e:2x2', 'tpu')
tdevs = np.asarray(topo.devices).ravel()
mesh = Mesh(tdevs[:4], ('data',))
model = names.resolve(config['builder'])(config, mesh, 1)
dist = model.dist
print(dist.plan.describe()[:1500], flush=True)
W, GB = 4, 65536
bsh, rep, tsh = NamedSharding(mesh, P('data')), NamedSharding(mesh, P()), NamedSharding(mesh, P('data', None, None))
sds = lambda shape, dt, sh: jax.ShapeDtypeStruct(shape, dt, sharding=sh)

# 1. the tables, written by make_tables (abstract: shapes only)
layout = program_state.table_layout(dist)
words = [np.zeros(2, np.uint32)] * len(model.tables)
import unittest.mock
captured = {}
real_jit = jax.jit
def spy_jit(fn, **kw):
  j = real_jit(fn, **kw)
  class Spy:
    def __call__(self, meta):
      t0 = time.time()
      c = j.lower(jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), meta)).compile()
      ma = c.memory_analysis()
      print(f'make_tables compiled {time.time()-t0:.0f}s: temp {ma.temp_size_in_bytes/2**30:.2f} arg {ma.argument_size_in_bytes/2**30:.2f} out {ma.output_size_in_bytes/2**30:.2f} GiB', flush=True)
      return None
  return Spy()
with unittest.mock.patch.object(jax, 'jit', spy_jit):
  program_state.make_tables(dist, layout, model.tables, words)

# 2. the reader on one device's shard
g = dist.plan.groups[0]
one = SingleDeviceSharding(tdevs[0])
lanes = g.param_width; rows_total = g.param_rows
read = program_state._reader('sgd', 0.003, 0.0, 0.0)
s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
t0 = time.time()
rc = read.lower(s((1, rows_total, lanes), jnp.float32), None, s((), jnp.int32), s((), jnp.int32), s((), jnp.uint32), s((), jnp.uint32), (s((), jnp.uint32), s((), jnp.uint32)), s((), jnp.float32)).compile()
ma = rc.memory_analysis()
print(f'reader compiled {time.time()-t0:.0f}s: temp {ma.temp_size_in_bytes/2**30:.3f} arg {ma.argument_size_in_bytes/2**30:.2f} GiB', flush=True)

# 3. the step, default capacities
emb_opt = model.emb_optimizer_cls(**model.emb_optimizer_kwargs)
step = make_hybrid_train_step(dist, model.head_loss_fn, model.dense_optimizer, emb_opt, donate=False, jit=False, **model.step_kwargs)
emb = {f'group_{gi}': sds((W, gg.param_rows, gg.param_width), jnp.float32, tsh) for gi, gg in enumerate(dist.plan.groups)}
dense = jax.tree.map(lambda x: sds(x.shape, x.dtype, rep), model.dense_params)
dstate = jax.tree.map(lambda x: sds(x.shape, x.dtype, rep), jax.eval_shape(model.dense_optimizer.init, model.dense_params))
state = TrainState(params={**dense, 'embedding': emb}, opt_state=(dstate, {f'group_{gi}': {} for gi in range(len(dist.plan.groups))}), step=sds((), jnp.int32, rep))
cats = [sds((GB, 1), jnp.int32, bsh) for _ in model.hotness]
num, lab = sds((GB, 13), jnp.float32, bsh), sds((GB, 1), jnp.float32, bsh)
t0 = time.time()
c = jax.jit(step, donate_argnums=(0,)).lower(state, cats, (num, lab)).compile()
ma = c.memory_analysis()
print(f'step compiled {time.time()-t0:.0f}s: temp {ma.temp_size_in_bytes/2**30:.2f} arg {ma.argument_size_in_bytes/2**30:.2f} GiB', flush=True)
txt = c.as_text()
print('all-to-all count', txt.count(' all-to-all('), 'all-reduce', txt.count(' all-reduce('))
