"""The hybrid state-space / attention head (models/hybrid_ssm.py) and a
table the head also reads (``make_hybrid_train_step(...,
head_reads_tables=)``, design §25), at a small size: 2 scan heads of 8,
state 16, chunk 8, hidden 32, mamba-mamba-attention-mamba, 64 rows, two
sequences of 48 packed from documents of 1 to 40 tokens.

The oracle is the benchmark's plain reference
(``benchmarks/classes/hybrid_ssm.py``): the recurrence position by
position, full masked attention, nothing of the program imported; the
whole step is held to ``benchmarks.lib.reference.run_reference`` through
the benchmark's own harness.
"""

import argparse
import functools
import json
import os
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

from benchmarks.classes import hybrid_ssm as ref
from benchmarks.lib import cell as cell_lib
from benchmarks.lib import names, traffic
from distributed_embeddings_tpu.models import hybrid_ssm as prog
from distributed_embeddings_tpu.parallel import (
    DistributedEmbedding, SparseAdagrad, SparseAdam, SparseSGD, TableConfig,
    create_mesh, get_optimizer_state, get_weights, init_hybrid_train_state,
    load_train_npz, make_global_batch, make_hybrid_train_step,
    save_train_npz, set_optimizer_state, set_weights)

TOY = os.path.join(cell_lib.BENCH_DIR, 'tests', 'toy_hybrid')
CONFIG = names.load_json(TOY, 'configs', 'toy-hybrid')
MIX = names.load_json(TOY, 'traffic', 'toy-packed')
CFG = prog.HybridSSMConfig.from_dict(CONFIG, attention_block=16,
                                     vocab_block=32)
HIGHEST = jax.lax.Precision.HIGHEST
matmul = functools.partial(jnp.matmul, precision=HIGHEST)

# two sequences of 16 positions, chunk 8: a boundary inside a chunk, at a
# chunk's edge, and a document of one token
SEGMENTS = {
    'inside_a_chunk': [[0] * 5 + [1] * 11, [0] * 3 + [1] * 9 + [2] * 4],
    'at_a_chunks_edge': [[0] * 8 + [1] * 8, [0] * 16],
    'one_token': [[0] * 4 + [1] + [2] * 11, [0] + [1] * 7 + [2] + [3] * 7],
}


def _rel(a, b):
  return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _scan_inputs(seed, seqs=2, length=16):
  rng = np.random.default_rng(seed)
  heads, d_head, state = (CFG.mamba_n_heads, CFG.mamba_d_head,
                          CFG.mamba_d_state)
  f32 = lambda *shape: rng.standard_normal(shape).astype(np.float32)
  return dict(x=f32(seqs, length, heads, d_head),
              dt=np.exp(f32(seqs, length, heads) - 2.0),
              a=-np.exp(f32(heads)), b=f32(seqs, length, state),
              c=f32(seqs, length, state))


@pytest.mark.parametrize('layout', sorted(SEGMENTS))
def test_chunked_scan_matches_the_sequential_recurrence(layout):
  """Forward, and the gradient of every input, to 1e-5 relative."""
  seg = jnp.asarray(SEGMENTS[layout], jnp.int32)
  v = _scan_inputs(3)
  weight = np.random.default_rng(4).standard_normal(
      v['x'].shape).astype(np.float32)

  def chunked(x, dt, a, b, c):   # B and C as one group
    return prog.ssd_scan(x, dt, a, b[:, :, None], c[:, :, None], seg,
                         CFG.mamba_chunk_size)

  def sequential(x, dt, a, b, c):
    return ref._sequential_scan(x, dt, a, b, c, seg, matmul)

  with jax.default_matmul_precision('highest'):
    got, want = chunked(**v), sequential(**v)
    assert _rel(got, want) < 1e-5
    grads = [jax.grad(lambda *args: jnp.sum(f(*args) * weight),
                      argnums=tuple(range(5)))(*v.values())
             for f in (chunked, sequential)]
  for name, g, w in zip(v, *grads):
    assert _rel(g, w) < 1e-5, name


@pytest.mark.parametrize('layout', sorted(SEGMENTS))
def test_mixer_matches_the_reference_in_every_parameter(layout):
  """The whole Mamba-2 mixer (projections, convolution, scan, gated
  norm): output, and ``jax.grad`` of every parameter and of the input."""
  seg = jnp.asarray(SEGMENTS[layout], jnp.int32)
  p = prog.init_params(CFG, 7)['layers'][0]['mixer']
  rng = np.random.default_rng(8)
  p = jax.tree.map(   # off the init's ones and zeros, so every leaf counts
      lambda a: a + 0.1 * rng.standard_normal(a.shape).astype(np.float32), p)
  u = rng.standard_normal((2, 16, CFG.hidden_size)).astype(np.float32)
  weight = rng.standard_normal(u.shape).astype(np.float32)
  sizes = ref._sizes(CONFIG)

  def program(p, u):
    return jnp.sum(prog.mamba_mixer(CFG, p, u, seg) * weight)

  def reference(p, u):
    return jnp.sum(ref._mamba(sizes, CFG.rms_norm_eps, p, u, seg, matmul)
                   * weight)

  with jax.default_matmul_precision('highest'):
    got = jax.value_and_grad(program, argnums=(0, 1))(p, u)
    want = jax.value_and_grad(reference, argnums=(0, 1))(p, u)
  assert abs(got[0] - want[0]) < 1e-5 * abs(want[0])
  flat_got = jax.tree_util.tree_leaves_with_path(got[1])
  for (path, g), w in zip(flat_got, jax.tree.leaves(want[1])):
    assert _rel(g, w) < 1e-5, jax.tree_util.keystr(path)


def _two_documents():
  """Segment ids of one sequence of 24: document 1 is positions 0..9,
  document 2 the rest (the boundary inside a chunk of 8)."""
  return jnp.asarray([[0] * 10 + [1] * 14], jnp.int32)


def _moves_only_its_own_document(fn, x):
  """``fn(x)`` at document 2's positions is the same to the bit after a
  change to one position of document 1, and document 1's does change."""
  before = fn(x)
  after = fn(x.at[:, 4].add(1.0))
  np.testing.assert_array_equal(np.asarray(before)[:, 10:],
                                np.asarray(after)[:, 10:])
  assert np.any(np.asarray(before)[:, 4:10] != np.asarray(after)[:, 4:10])


@pytest.mark.parametrize('part', ['scan', 'conv', 'attention'])
def test_a_document_moves_nothing_of_the_next(part):
  """Each of the three mixers of positions alone (the other two are not
  in the function): a change to a token of document 1 moves nothing at
  document 2's positions."""
  seg = _two_documents()
  rng = np.random.default_rng(11)
  f32 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
  if part == 'scan':
    v = {k: jnp.asarray(a) for k, a in _scan_inputs(5, 1, 24).items()}
    fn = lambda x: prog.ssd_scan(x, v['dt'], v['a'], v['b'][:, :, None],
                                 v['c'][:, :, None], seg,
                                 CFG.mamba_chunk_size)
    x = v['x']
  elif part == 'conv':
    kernel, bias = f32(CFG.mamba_d_conv, 6), f32(6)
    fn = lambda x: prog.causal_conv(x, kernel, bias, seg)
    x = f32(1, 24, 6)
  else:
    p = jax.tree.map(jnp.asarray,
                     prog.init_params(CFG, 2)['layers'][2]['mixer'])
    fn = lambda x: prog.attention_mixer(CFG, p, x, seg)
    x = f32(1, 24, CFG.hidden_size)
  _moves_only_its_own_document(fn, x)


def test_a_document_moves_no_logit_of_the_next():
  """The whole stack: hidden states, so logits, of document 2."""
  seg = _two_documents()
  dense = jax.tree.map(jnp.asarray, prog.init_params(CFG, 2))
  rows = jnp.asarray(np.random.default_rng(1).standard_normal(
      (1, 24, CFG.hidden_size)), jnp.float32)
  _moves_only_its_own_document(
      lambda x: prog.forward(CFG, dense, x, seg), rows)


def test_the_two_sides_draw_the_same_parameters():
  mine, theirs = prog.init_params(CFG, 5), ref.dense_params(CONFIG, 5)
  assert jax.tree.structure(mine) == jax.tree.structure(theirs)
  for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
    np.testing.assert_array_equal(a, b)


def test_one_run_draws_the_dense_parameters_once():
  """The builder and the reference ask ``dense_params`` with the same
  configuration and seed and share one read-only draw; another seed is
  another draw."""
  first, again = ref.dense_params(CONFIG, 5), ref.dense_params(CONFIG, 5)
  for a, b in zip(jax.tree.leaves(first), jax.tree.leaves(again)):
    assert a is b and not a.flags.writeable
  other = ref.dense_params(CONFIG, 6)
  assert not np.array_equal(first['layers'][0]['mlp_in'],
                            other['layers'][0]['mlp_in'])
  np.testing.assert_array_equal(
      ref.dense_params(CONFIG, 5)['layers'][0]['mlp_in'],
      first['layers'][0]['mlp_in'])


def _run_toy_cell(seed, tmp_path):
  with open(os.path.join(TOY, 'manifest.json')) as f:
    manifest = json.load(f)
  args = argparse.Namespace(workload='toy-hybrid-1', seed=seed, seconds=0.2,
                            trace=0)
  return cell_lib.run_cell(manifest, TOY, 'toy-hybrid-1', args,
                           jax.devices()[:1], time.perf_counter(),
                           str(tmp_path))


@pytest.mark.parametrize('seed', [7, 2**31 + 5])
def test_whole_step_follows_the_reference_for_three_steps(seed, tmp_path):
  """``DistributedEmbedding`` + ``make_hybrid_train_step`` + ``SparseAdam``
  + ``optax.adam`` with the tied table, through the benchmark's harness
  against ``run_reference``: the three losses, and every leaf's first
  gradient, change and count of moved elements, within the toy cell's
  limits."""
  result = _run_toy_cell(seed, tmp_path)
  assert result['correct'], result['compared']
  assert set(result['compared']) == {'loss_gap', 'grad_gap', 'change_gap',
                                     'moved_gap'}


def _mesh_layer(world, optimizer=None):
  """The vocabulary beside three narrow tables, so that a mesh of four
  holds whole tables (one table alone would be sliced over it)."""
  mesh = create_mesh(jax.devices()[:world])
  configs = [TableConfig(64, CFG.hidden_size, None, name='vocabulary')] + [
      TableConfig(200 + i, 8, 'sum') for i in range(3)]
  dist = DistributedEmbedding(configs, mesh=mesh, dp_input=True,
                              packed_storage=False)
  opt = optimizer or SparseAdam(learning_rate=3e-4, b1=0.9, b2=0.95)
  return mesh, dist, opt


def _mesh_batches(seed, steps):
  mix = {**MIX, 'global_batch': 4}
  pool = traffic.train_tokens(mix, [(64, 1)], CONFIG, seed, batches=steps)
  rng = np.random.default_rng(seed)
  return [([cats[0]] + [rng.integers(0, 20, (4 * 48, 1)).astype(np.int32)
                        for _ in range(3)], batch) for cats, batch in pool]


def _train(world, weights, batches, optimizer=None):
  mesh, dist, opt = _mesh_layer(world, optimizer)
  dense_opt = optax.adam(3e-4, b1=0.9, b2=0.95)
  state = init_hybrid_train_state(
      dist, {**jax.tree.map(jnp.asarray, prog.init_params(CFG, 3)),
             'embedding': set_weights(dist, weights)}, dense_opt, opt)
  step = make_hybrid_train_step(dist, prog.make_head_loss_fn(CFG), dense_opt,
                                opt, head_reads_tables=(0,), donate=False)
  losses = []
  for cats, batch in batches:
    state, loss = step(state, list(make_global_batch(mesh, *cats)),
                       jax.tree.map(jnp.asarray, batch))
    losses.append(float(loss))
  return dist, state, losses


def _weights(seed):
  rng = np.random.default_rng(seed)
  return [rng.uniform(-0.17, 0.17, shape).astype(np.float32)
          for shape in [(64, CFG.hidden_size), (200, 8), (201, 8), (202, 8)]]


def test_four_devices_train_as_one_does():
  """The owner's shard reaches the data-parallel head and the head's
  gradient returns to the owner: three steps on four devices give the
  losses, tables, moments and dense leaves of the same steps on one."""
  weights, batches = _weights(1), _mesh_batches(9, 3)
  dist1, one, losses1 = _train(1, weights, batches)
  dist4, four, losses4 = _train(4, weights, batches)
  np.testing.assert_allclose(losses4, losses1, rtol=1e-6)
  for a, b in zip(get_weights(dist4, four.params['embedding']),
                  get_weights(dist1, one.params['embedding'])):
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
  for a, b in zip(get_optimizer_state(dist4, four.opt_state[1]),
                  get_optimizer_state(dist1, one.opt_state[1])):
    for leaf in ('m', 'v', 't'):
      np.testing.assert_allclose(a[leaf], b[leaf], rtol=1e-4, atol=1e-9)
  dense = lambda s: {k: v for k, v in s.params.items() if k != 'embedding'}
  for a, b in zip(jax.tree.leaves(dense(four)), jax.tree.leaves(dense(one))):
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
  # every row of the vocabulary took each step; a narrow table's rows
  # only where a batch asked for them
  steps = get_optimizer_state(dist4, four.opt_state[1])
  assert np.all(steps[0]['t'] == 3) and np.any(steps[1]['t'] == 0)


@pytest.mark.parametrize('world', [1, 4])
def test_tied_gradient_is_the_lookups_sums_plus_the_heads(world):
  """One SGD step at learning rate 1 moves the vocabulary by exactly its
  gradient: that equals ``jax.grad`` of the dense formulation, the lookup
  written as a one-hot product so that the table appears in the loss
  twice."""
  weights, ((cats, batch),) = _weights(2), _mesh_batches(5, 1)
  dist, state, _ = _train(world, weights, [(cats, batch)],
                          SparseSGD(learning_rate=1.0))
  moved = weights[0] - get_weights(dist, state.params['embedding'])[0]
  dense = jax.tree.map(jnp.asarray, prog.init_params(CFG, 3))
  head = prog.make_head_loss_fn(CFG)

  def one_hot_loss(table):
    rows = matmul(jax.nn.one_hot(cats[0][:, 0], 64, dtype=jnp.float32),
                  table)
    return head(dense, (rows,), jax.tree.map(jnp.asarray, batch), {0: table})

  with jax.default_matmul_precision('highest'):
    want = jax.grad(one_hot_loss)(jnp.asarray(weights[0]))
  assert _rel(moved, want) < 1e-5
  # and the lookups' share alone is not it
  rows_only = jax.grad(lambda t: head(
      dense, (t[cats[0][:, 0]],), jax.tree.map(jnp.asarray, batch),
      {0: jnp.asarray(weights[0])}))(jnp.asarray(weights[0]))
  assert _rel(rows_only, want) > 0.1


def _plain_step(**kw):
  """A step as ``test_sparse_train.py`` builds it: eight devices, nine
  tables of mixed width, combiner and hotness, Adagrad."""
  import test_sparse_train as sparse_train   # tests/ is on the path
  dist, emb, gen_inputs, kernel, labels, head_loss_fn = sparse_train.build()
  opt = SparseAdagrad(learning_rate=0.5)
  dense_opt = optax.sgd(0.5)
  state = init_hybrid_train_state(
      dist, {'embedding': emb, 'kernel': kernel}, dense_opt, opt)
  step = make_hybrid_train_step(dist, head_loss_fn, dense_opt, opt,
                                donate=False, **kw)
  return step, state, gen_inputs(), labels


def test_default_step_has_nothing_of_a_tied_table():
  """With ``head_reads_tables`` empty the step is what it was: the same
  lowered program as without the argument, the same state to the bit,
  and no trace of the tied apply in it."""
  step, state, cats, labels = _plain_step()
  explicit, _, _, _ = _plain_step(head_reads_tables=())
  text = step.jitted.lower(state, cats, labels).as_text()
  assert text == explicit.jitted.lower(state, cats, labels).as_text()
  assert 'tied' not in text
  a, _ = step(state, cats, labels)
  b, _ = explicit(state, cats, labels)
  for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_a_sliced_table_is_refused_by_name():
  mesh = create_mesh(jax.devices()[:4])
  dist = DistributedEmbedding([TableConfig(64, 32, None)], mesh=mesh,
                              packed_storage=False)
  with pytest.raises(NotImplementedError, match='sliced over the mesh'):
    make_hybrid_train_step(dist, lambda *a: 0.0, optax.sgd(0.1),
                           SparseSGD(0.1), head_reads_tables=(0,))


@pytest.mark.parametrize('world', [1, 4])
@pytest.mark.parametrize('width', [256, 2048, 2688])
def test_wide_rows_through_lookup_apply_and_checkpoint(width, world,
                                                       tmp_path):
  """Rows of 1 KiB, 8 KiB and 10.5 KiB (21 lane tiles, no power of two)
  on the default path at 64 rows: plan,
  ``init``, lookup, an Adam apply that moves exactly the rows asked for,
  and a checkpoint round trip of table and moments."""
  mesh = create_mesh(jax.devices()[:world])
  configs = [TableConfig(64, width, None)] + [
      TableConfig(16, 8, 'sum') for _ in range(world - 1)]
  dist = DistributedEmbedding(configs, mesh=mesh, dp_input=True)
  group = next(g for g in dist.plan.groups if g.width == width)
  assert group.storage_pack == 1 and group.param_width == width
  params = dist.init(0)
  table = get_weights(dist, params)[0]
  assert table.shape == (64, width) and table.std() > 0
  rng = np.random.default_rng(width + world)
  ids = [rng.integers(0, c.input_dim, (32, 1)).astype(np.int32)
         for c in configs]
  cats = list(make_global_batch(mesh, *ids)) if world > 1 else [
      jnp.asarray(ids[0])]
  np.testing.assert_array_equal(np.asarray(dist(params, cats)[0]),
                                table[ids[0][:, 0]])
  opt = SparseAdam(learning_rate=1e-2)
  kernel = jnp.asarray(rng.standard_normal((width, 1)), jnp.float32)

  def head(dense, emb_outs, batch):
    return jnp.mean((emb_outs[0] @ dense['kernel']) ** 2)

  state = init_hybrid_train_state(
      dist, {'kernel': kernel, 'embedding': params}, optax.sgd(0.1), opt)
  step = make_hybrid_train_step(dist, head, optax.sgd(0.1), opt,
                                donate=False)
  state, _ = step(state, cats, None)
  after = get_weights(dist, state.params['embedding'])
  moved = np.flatnonzero(np.any(after[0] != table, axis=1))
  np.testing.assert_array_equal(moved, np.unique(ids[0]))
  moments = get_optimizer_state(dist, state.opt_state[1])
  path = os.path.join(str(tmp_path), 'wide.npz')
  save_train_npz(path, after, moments, plan=dist.plan)
  weights2, moments2, _ = load_train_npz(path)
  emb2 = set_weights(dist, weights2)
  state2 = set_optimizer_state(dist, opt.init(dist, emb2), moments2)
  for a, b in zip(jax.tree.leaves((emb2, state2)),
                  jax.tree.leaves((state.params['embedding'],
                                   state.opt_state[1]))):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_count_batch_counts_tokens_documents_and_loss_positions():
  from distributed_embeddings_tpu.obs import metrics as obs_metrics
  (_, batch), = traffic.train_tokens(MIX, [(64, 1)], CONFIG, 3, batches=1)
  obs_metrics.enable()
  try:
    obs_metrics.reset()
    prog.count_batch(batch)
    counted = obs_metrics.snapshot()
  finally:
    obs_metrics.disable()
  targets, segment_ids = batch
  assert counted['train.tokens'] == 96
  assert counted['train.documents'] == int((segment_ids[:, -1] + 1).sum())
  assert counted['train.loss_positions'] == int((targets >= 0).sum())
