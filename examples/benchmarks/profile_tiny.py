"""Decompose the synthetic-model train step cost on one chip.

Phases isolate the three candidate bottlenecks of the sparse trainer
(scan + donation, synced by a host transfer of a scalar):

  fwd      - distributed forward (lookup + routing) only
  bwd      - forward + head loss + cotangent transpose, NO optimizer
  full     - the exact hybrid sparse step bench.py times
  dense    - autodiff + optax dense-grad step (O(vocab) updates)

Usage: python examples/benchmarks/profile_tiny.py --phase fwd [--model tiny]
       [--segwalk_apply]                   (only --phase full runs the
                                            sparse apply these select)
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))


def main():
  p = argparse.ArgumentParser()
  p.add_argument('--phase', required=True,
                 choices=['fwd', 'bwd', 'full', 'dense'])
  p.add_argument('--model', default='tiny')
  p.add_argument('--batch', type=int, default=65536)
  p.add_argument('--steps', type=int, default=5)
  p.add_argument('--segwalk_apply', action='store_true')
  args = p.parse_args()
  if args.segwalk_apply and args.phase != 'full':
    p.error('--segwalk_apply only affects --phase full '
            '(the other phases never run the sparse apply)')

  import jax
  import jax.numpy as jnp
  import optax
  from distributed_embeddings_tpu.utils import compile_cache
  compile_cache.configure()
  from distributed_embeddings_tpu.models.synthetic import (SYNTHETIC_MODELS,
                                                           InputGenerator,
                                                           SyntheticModel)
  from distributed_embeddings_tpu.models.dlrm import bce_with_logits
  from distributed_embeddings_tpu.parallel import (SparseAdagrad, TrainState,
                                                   create_mesh,
                                                   init_hybrid_train_state,
                                                   init_train_state,
                                                   make_hybrid_train_step)

  mesh = create_mesh(jax.devices())
  config = SYNTHETIC_MODELS[args.model]
  model = SyntheticModel(config, mesh=mesh, dp_input=True)
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
    print(eligibility_line(dist, 'float32', args.segwalk_apply))

  if args.phase == 'fwd':
    def run(ep):
      def body(c, k):
        outs, _, _ = dist.forward_with_residuals(c, list(cats0))
        # fold a checksum back into the params so nothing is dead
        bump = 1e-30 * jnp.sum(outs[0][0].astype(jnp.float32))
        return jax.tree.map(lambda x: x + bump.astype(x.dtype), c), None
      return jax.lax.scan(body, ep, jnp.arange(K))[0]
    state = params['embedding']
  elif args.phase == 'bwd':
    def run(ep):
      def body(c, k):
        outs, residuals, (gb, hot) = dist.forward_with_residuals(
            c, list(cats0))
        dense_params = {kk: v for kk, v in params.items() if kk != 'embedding'}
        loss, pull = jax.vjp(
            lambda eo: head_loss_fn(dense_params, eo, (num0, labels0)),
            tuple(outs))
        (d_emb,) = pull(jnp.ones((), loss.dtype))
        gsubs = dist.backward_to_mp(list(d_emb), gb, hot)
        bump = 1e-30 * (jnp.sum(gsubs[0][0].astype(jnp.float32)) + loss)
        return jax.tree.map(lambda x: x + bump.astype(x.dtype), c), None
      return jax.lax.scan(body, ep, jnp.arange(K))[0]
    state = params['embedding']
  elif args.phase == 'full':
    step = make_hybrid_train_step(dist, head_loss_fn, opt, emb_opt,
                                  jit=False)
    def run(st):
      def body(c, k):
        s2, loss = step(c, list(cats0), (num0, labels0))
        return s2, None
      return jax.lax.scan(body, st, jnp.arange(K))[0]
    state = init_hybrid_train_state(dist, params, opt, emb_opt)
  else:  # dense
    def loss_fn(pp):
      logits = model.apply(pp, num0, list(cats0))
      return bce_with_logits(logits, labels0)
    def run(st):
      def body(c, k):
        loss, grads = jax.value_and_grad(loss_fn)(c.params)
        updates, opt_state = opt.update(grads, c.opt_state, c.params)
        new_params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                                  c.params, updates)
        return TrainState(new_params, opt_state, c.step + 1), None
      return jax.lax.scan(body, st, jnp.arange(K))[0]
    state = init_train_state(params, opt)

  f = jax.jit(run, donate_argnums=(0,))
  # two warmup calls: the second absorbs a second compile if the state
  # came back with other input shardings (see bench.py's warmup note)
  for _ in range(2):
    state = f(state)
    leaf = jax.tree.leaves(state)[0]
    float(jnp.sum(leaf[0].astype(jnp.float32)))
  t0 = time.perf_counter()
  state = f(state)
  leaf = jax.tree.leaves(state)[0]
  float(jnp.sum(leaf[0].astype(jnp.float32)))
  dt = (time.perf_counter() - t0) / K * 1e3
  print(f'PHASE {args.phase} ({args.model}, batch {args.batch}): '
        f'{dt:.1f} ms/step')


if __name__ == '__main__':
  main()
