"""Record ``v5e_scoped_step.trace.json.gz``: a few steps of a small
hybrid train step on one TPU chip under ``obs.trace.profile``, for
``tests/test_phases.py`` (the per-phase report is pinned on it).

  JAX_COMPILATION_CACHE_DIR=$(mktemp -d) \
      python tests/data/record_v5e_scoped_step.py <out.trace.json.gz>

Re-record when the step's phases change, and re-pin the test's numbers
from ``tools/trace_report.py --profile <out> --json``.  The program has
two table groups (widths 16 and 128), one deliberately unscoped
reduction (the report's ``unscoped`` remainder has something to show)
and a 3 ms host sleep under a ``feed/wait`` span between steps (a named
idle gap).
"""
import glob
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                '..', '..'))


def main(out):
  import jax
  import jax.numpy as jnp
  import numpy as np
  import optax
  from distributed_embeddings_tpu.obs import trace as obs_trace
  from distributed_embeddings_tpu.parallel import (
      DistributedEmbedding, SparseAdagrad, TableConfig, create_mesh,
      init_hybrid_train_state, make_hybrid_train_step)
  assert jax.devices()[0].platform == 'tpu', jax.devices()
  mesh = create_mesh(jax.devices()[:1])
  cfgs = [TableConfig(200_000, 128, 'sum'), TableConfig(100_000, 16, 'sum'),
          TableConfig(50_000, 16, 'mean')]
  dist = DistributedEmbedding(cfgs, mesh=mesh)
  rng = np.random.default_rng(0)
  batch, hot = 8192, 4
  kernel = jnp.asarray(rng.normal(size=(160, 64)).astype(np.float32) * .05)

  def head(dense, emb_outs, y):
    x = jnp.concatenate(list(emb_outs), axis=1)
    return jnp.mean((jnp.tanh(x @ dense['kernel']).sum(1, keepdims=True)
                     - y) ** 2)

  emb_opt = SparseAdagrad(0.05)
  inner = make_hybrid_train_step(dist, head, optax.sgd(0.1), emb_opt,
                                 jit=False)

  def step(state, cats, y):
    state, loss = inner(state, cats, y)
    return state, loss + 0.0 * jnp.sum(jnp.cos(y))   # no phase: unscoped

  step = jax.jit(step, donate_argnums=(0,))
  state = init_hybrid_train_state(
      dist, {'embedding': dist.init(0), 'kernel': kernel}, optax.sgd(0.1),
      emb_opt)
  cats = [jnp.asarray(rng.integers(0, c.input_dim, (batch, hot)), jnp.int32)
          for c in cfgs]
  y = jnp.asarray(rng.normal(size=(batch, 1)).astype(np.float32))
  for _ in range(2):
    state, loss = step(state, cats, y)
  jax.block_until_ready((state, loss))
  directory = tempfile.mkdtemp(prefix='scoped_step_')
  with obs_trace.profile(directory):
    for k in range(3):
      with obs_trace.span('train/step', step=k + 1):
        state, loss = step(state, cats, y)
        loss.block_until_ready()
      with obs_trace.span('feed/wait', after=k):
        time.sleep(0.003)
  (path,) = glob.glob(os.path.join(directory, '**', '*.trace.json.gz'),
                      recursive=True)
  shutil.copy(path, out)
  print(f'{out}: {os.path.getsize(out)} bytes')


if __name__ == '__main__':
  main(sys.argv[1])
