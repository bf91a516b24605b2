"""The reference's own parts, with no program in sight: a table the head
also reads (``head_reads_tables``) against ``jax.grad`` of the same model
written densely, the three optimizers' read-back of the gradient they
were given, and how a pytree's leaves are named."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import reference, weights

HIGHEST = jax.lax.Precision.HIGHEST
VOCAB, WIDTH = 96, 8
OPTIMIZERS = {
    'sgd': {'kind': 'sgd', 'learning_rate': 0.01},
    'adagrad': {'kind': 'adagrad', 'learning_rate': 0.01,
                'initial_accumulator_value': 0.1, 'epsilon': 1e-7},
    'adam': {'kind': 'adam', 'learning_rate': 0.001, 'b1': 0.9, 'b2': 0.999,
             'epsilon': 1e-8},
}
MIX = {'generator': 'benchmarks.lib.traffic:train_tokens', 'global_batch': 4,
       'seq_len': 16, 'alpha': 1.05, 'doc_len_median': 5,
       'doc_len_sigma': 1.0, 'pool_batches': 1, 'checked_steps': 1}


def _config(kind):
  here = 'benchmarks.tests.test_reference'
  return {
      'tables': 'benchmarks.lib.builders:block_tables',
      'reference_head': f'{here}:tied_head',
      'dense_params': f'{here}:tied_dense',
      'embedding_blocks': [{'num_tables': 1, 'nnz': [1], 'num_rows': VOCAB,
                            'width': WIDTH}],
      'combiner': None, 'head_reads_tables': [0],
      'table_init': {'kind': 'uniform', 'half_range': 0.5},
      'optimizer': OPTIMIZERS[kind],
      'control_precision': {'head_matmul_mantissa_bits': 3}}


def tied_dense(config, seed):
  rng = np.random.default_rng([int(seed), 5])
  return {'mix': (rng.standard_normal((WIDTH, WIDTH)) / np.sqrt(WIDTH)
                  ).astype(np.float32)}


def _xent(logits, targets):
  valid = targets >= 0
  logp = jax.nn.log_softmax(logits, axis=-1)
  picked = jnp.take_along_axis(
      logp, jnp.where(valid, targets, 0)[..., None], axis=-1)[..., 0]
  return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)


def tied_head(config):
  """Rows in, one ``tanh`` layer, logits against the same table."""
  def loss(dense, emb_outs, batch, matmul, tables):
    targets, _ = batch
    hidden = jnp.tanh(matmul(emb_outs[0], dense['mix']))
    logits = matmul(hidden, tables[0].T)
    return _xent(logits.reshape(targets.shape + (-1,)), targets)
  return loss


def _dense_loss(looked_up, multiplied, mix, ids, targets):
  """The same model with the lookup as a one-hot product; the table
  comes in twice, as what is looked up and as what the head multiplies
  by, so that each path's gradient can be had alone."""
  product = lambda a, b: jnp.matmul(a, b, precision=HIGHEST)
  rows = product(jax.nn.one_hot(ids.reshape(-1), VOCAB), looked_up)
  logits = product(jnp.tanh(product(rows, mix)), multiplied.T)
  return _xent(logits.reshape(targets.shape + (-1,)), targets)


@pytest.mark.parametrize('kind', sorted(OPTIMIZERS))
@pytest.mark.parametrize('seed', (3, 2**31 + 41))
def test_a_table_the_head_reads_gets_both_gradients(kind, seed):
  config = _config(kind)
  got = reference.run_reference(config, MIX, seed)
  (cats, (targets, _)), = reference.names.resolve(MIX['generator'])(
      MIX, [(VOCAB, 1)], config, seed, batches=1)
  table = jnp.asarray(weights.numpy_rows(
      weights.table_words(seed, 1)[0], np.arange(VOCAB), WIDTH, 0.5))
  mix = jnp.asarray(tied_dense(config, seed)['mix'])
  loss, (d_lookup, d_head, d_mix) = jax.value_and_grad(
      _dense_loss, argnums=(0, 1, 2))(table, table, mix, cats[0], targets)
  norm = lambda a: float(jnp.linalg.norm(a))
  assert got['loss'][0] == pytest.approx(float(loss), rel=1e-6)
  assert got['grad_norm']['table_0'] == pytest.approx(
      norm(d_lookup + d_head), rel=2e-5)
  assert got['grad_norm']['mix'] == pytest.approx(norm(d_mix), rel=2e-5)
  # neither path alone gives that norm: both are in it
  for alone in (d_lookup, d_head):
    assert abs(norm(alone) - norm(d_lookup + d_head)) > 0.05 * norm(alone)
  # the softmax sends a gradient to every row, asked for or not
  assert got['moved']['table_0'] > 0.9 * VOCAB * WIDTH


@pytest.mark.parametrize('kind', sorted(OPTIMIZERS))
def test_the_gradient_is_read_back_from_the_state(kind):
  opt = reference._Optimizer(OPTIMIZERS[kind], lambda a: a)
  rng = np.random.default_rng(8)
  p0 = rng.standard_normal((50, 6)).astype(np.float32)
  g = (rng.standard_normal((50, 6)) * 1e-2).astype(np.float32)
  g[::7] = 0.0                         # touched rows with no gradient
  p1, state1 = opt.step(p0, opt.init(p0), g)
  back = opt.gradient_from_state(p0, p1, state1)
  # a change read off float32 parameters near 1 carries their rounding
  assert np.allclose(back, g, rtol=2e-4, atol=2e-5)
  change, grad_norm, moved = opt.leaf_readings(p0, p1, state1)
  assert grad_norm == pytest.approx(np.linalg.norm(g.astype(np.float64)),
                                    rel=1e-3)
  assert moved == np.count_nonzero(g) and change > 0


def test_adam_counts_a_rows_own_steps():
  opt = reference._Optimizer(OPTIMIZERS['adam'], lambda a: a)
  p = np.zeros((2, 3), np.float32)
  state = opt.init(p)
  g = np.full((1, 3), 0.5, np.float32)
  # row 0 is touched twice, row 1 once, at the second step
  p[:1], new = opt.step(p[:1], {k: v[:1] for k, v in state.items()}, g)
  for k, v in new.items():
    state[k][:1] = v
  p, state = opt.step(p, state, np.concatenate([g, g]))
  assert state['t'].reshape(-1).tolist() == [2.0, 1.0]
  # a constant gradient moves a row by the learning rate at each of its
  # own steps, whatever the global step is
  assert np.allclose(p[0], -0.002, rtol=1e-4)
  assert np.allclose(p[1], -0.001, rtol=1e-4)


def test_leaves_are_named_by_their_tree_paths():
  tree = {'mlp': [{'kernel': 1, 'bias': 2}, {'kernel': 3, 'bias': 4}],
          'final_norm': 5}
  assert reference.leaf_names(tree) == [
      'final_norm', 'mlp/0/bias', 'mlp/0/kernel', 'mlp/1/bias',
      'mlp/1/kernel']
