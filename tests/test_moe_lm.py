"""The mixture-of-experts language-model head (models/moe_lm.py) over an
untied token table under ``SparseAdam``, at a small size: hidden 64, 4
query heads of 16 over 2 key-value heads, window 16 in sequences of 48,
sliding-sliding-full with one dense layer, 4 of 16 experts of 32 held, 4
a token, 96 rows, documents of 1 to 40 tokens.

The oracle is the benchmark's plain reference
(``benchmarks/classes/moe_lm.py``): full masked attention under a band,
the routed layer as a loop over the held experts, nothing of the program
imported; the whole step is held to
``benchmarks.lib.reference.run_reference`` through the benchmark's own
harness.
"""

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import re
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

from benchmarks.classes import moe_lm as ref
from benchmarks.lib import cell as cell_lib
from benchmarks.lib import names, traffic
from distributed_embeddings_tpu import obs
from distributed_embeddings_tpu.models import hybrid_ssm
from distributed_embeddings_tpu.models import moe_lm as prog
from distributed_embeddings_tpu.parallel import (
    DistributedEmbedding, SparseAdam, TableConfig, create_mesh,
    get_optimizer_state, get_weights, init_hybrid_train_state,
    make_global_batch, make_hybrid_train_step, set_weights)

TOY = os.path.join(cell_lib.BENCH_DIR, 'tests', 'toy_moe')
CONFIG = names.load_json(TOY, 'configs', 'toy-moe')
MIX = names.load_json(TOY, 'traffic', 'toy-packed-moe')
CFG = prog.MoELMConfig.from_dict(CONFIG)
Z = ref._sizes(CONFIG)
HIGHEST = jax.lax.Precision.HIGHEST
matmul = functools.partial(jnp.matmul, precision=HIGHEST)
KINDS = ('sliding_attention', 'full_attention')

# two sequences of 48, window 16: a document longer than the window, a
# boundary inside a block of 16 queries, a document of one token
SEGMENTS = jnp.asarray(
    [[0] * 30 + [1] * 18, [0] * 5 + [1] + [2] * 20 + [3] * 22], jnp.int32)


def _rel(a, b):
  return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _attention_params(seed):
  """One layer's attention leaves with gains that are not 1, so that the
  per-head norms show."""
  p = jax.tree.map(jnp.asarray,
                   ref.dense_params(CONFIG, seed)['layers'][0]['attention'])
  rng = np.random.default_rng(seed)
  gain = lambda: jnp.asarray(rng.uniform(0.5, 1.5, CFG.head_dim), jnp.float32)
  return {**p, 'q_norm': gain(), 'k_norm': gain()}


def _hidden(seed, seqs=2, length=48):
  return jnp.asarray(np.random.default_rng(seed).standard_normal(
      (seqs, length, CFG.hidden_size)), jnp.float32)


@pytest.mark.parametrize('kind', KINDS)
def test_attention_matches_full_scores_under_a_band_and_document_mask(kind):
  """The blocked attention that computes only the key blocks a window
  meets against the reference's full scores under a mask: rotary on the
  windowed layer only, per-head norms, the output gate; output and the
  gradient of every parameter and of the input."""
  p, u = _attention_params(1), _hidden(2)
  cot = _hidden(3)

  def mine(p, u):
    return jnp.sum(cot * prog.attention(CFG, kind, p, u, SEGMENTS))

  def theirs(p, u):
    return jnp.sum(cot * ref._attention(Z, CONFIG, kind, p, u, SEGMENTS,
                                        matmul))

  with jax.default_matmul_precision('highest'):
    a, (ga, gua) = jax.value_and_grad(mine, argnums=(0, 1))(p, u)
  b, (gb, gub) = jax.value_and_grad(theirs, argnums=(0, 1))(p, u)
  assert a == pytest.approx(float(b), rel=1e-5)
  for leaf in p:
    assert _rel(ga[leaf], gb[leaf]) < 1e-5, leaf
  assert _rel(gua, gub) < 1e-5


def test_the_two_kinds_of_layer_differ_by_window_and_rotary():
  """With every document shorter than the window a ``sliding_attention``
  layer differs from a ``full_attention`` one by the rotary embedding
  alone, and rotary turns a head without changing its length."""
  p, u = _attention_params(4), _hidden(5)
  short = jnp.asarray([[0] * 16 + [1] * 16 + [2] * 16] * 2, jnp.int32)
  with jax.default_matmul_precision('highest'):
    window = prog.attention(CFG, 'sliding_attention', p, u, short)
    full = prog.attention(CFG, 'full_attention', p, u, short)
  assert _rel(window, full) > 1e-2
  # position 0 is never turned: a document that starts the sequence
  # gives its first token the full layer's numbers
  np.testing.assert_allclose(window[:, 0], full[:, 0], atol=1e-6)
  q = _hidden(6).reshape(2, 48, 4, 16)
  np.testing.assert_allclose(jnp.linalg.norm(prog.rotary(q, 1e4), axis=-1),
                             jnp.linalg.norm(q, axis=-1), rtol=1e-5)
  np.testing.assert_allclose(
      jnp.swapaxes(prog.rotary(q, 1e4), 1, 2),
      ref._rotate(jnp.swapaxes(q, 1, 2), 1e4), atol=1e-5)


def test_a_window_over_the_whole_sequence_is_hybrid_ssms_full_attention():
  """``hybrid_ssm.blocked_attention`` is one function for both stacks:
  with no window it is what the hybrid state-space stack computes, a
  window as long as the sequence changes nothing, and a short one equals
  the whole sequence in one block under the band mask."""
  rng = np.random.default_rng(7)
  f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
  q, k, v = f32(2, 48, 2, 2, 16), f32(2, 48, 2, 16), f32(2, 48, 2, 16)
  with jax.default_matmul_precision('highest'):
    full = hybrid_ssm.blocked_attention(0.25, q, k, v, SEGMENTS, 16)
    long = hybrid_ssm.blocked_attention(0.25, q, k, v, SEGMENTS, 16,
                                        window=48)
    band = hybrid_ssm.blocked_attention(0.25, q, k, v, SEGMENTS, 16,
                                        window=16)
    one = hybrid_ssm._attend(0.25, q, k, v, SEGMENTS, SEGMENTS, 0, 0, 16)
    whole = hybrid_ssm._attend(0.25, q, k, v, SEGMENTS, SEGMENTS, 0)
  np.testing.assert_allclose(full, whole, atol=1e-6)
  np.testing.assert_allclose(long, full, atol=1e-6)
  np.testing.assert_allclose(band, one, atol=1e-6)
  assert _rel(band, full) > 1e-2


@pytest.mark.parametrize('length,window,block', [
    (96, 32, 16),      # three windows of two blocks
    (96, 32, 8),       # four blocks a window
    (48, 32, 16),      # no whole windows
    (64, 16, 32),      # a block longer than the window
])
def test_windowed_blocks_equal_one_block_under_the_band(length, window,
                                                        block):
  """Value and gradients of the blocked windowed attention against the
  whole sequence as one block under the band-and-document mask."""
  rng = np.random.default_rng(length + window + block)
  f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
  q, k, v = (f32(2, length, 2, 2, 16), f32(2, length, 2, 16),
             f32(2, length, 2, 16))
  cuts = np.sort(rng.choice(np.arange(1, length), (2, 3)), axis=1)
  seg = jnp.asarray([np.searchsorted(c, np.arange(length), side='right')
                     for c in cuts], jnp.int32)
  cot = f32(2, length, 2, 2, 16)
  with jax.default_matmul_precision('highest'):
    a, ga = jax.value_and_grad(lambda *x: jnp.sum(
        cot * hybrid_ssm.blocked_attention(0.25, *x, seg, block,
                                           window=window)),
                               argnums=(0, 1, 2))(q, k, v)
    b, gb = jax.value_and_grad(lambda *x: jnp.sum(
        cot * hybrid_ssm._attend(0.25, *x, seg, seg, 0, 0, window)),
                               argnums=(0, 1, 2))(q, k, v)
  assert float(a) == pytest.approx(float(b), rel=1e-5)
  for x, y in zip(ga, gb):
    assert _rel(x, y) < 1e-5


def _dense(seed):
  return jax.tree.map(jnp.asarray, ref.dense_params(CONFIG, seed))


def test_a_document_moves_nothing_of_the_next():
  """Perturbing the first document's rows leaves every later document's
  hidden states, and the gradient of the loss in their rows, as they
  were: through both kinds of attention and the routed layer (a token's
  experts are its own)."""
  dense, rows = _dense(8), 0.1 * _hidden(9)
  first = (SEGMENTS == 0)[..., None]
  moved = jnp.where(first, rows + 0.05 * _hidden(10), rows)
  with jax.default_matmul_precision('highest'):
    a = prog.forward(CFG, dense, rows, SEGMENTS)
    b = prog.forward(CFG, dense, moved, SEGMENTS)
  later = ~np.asarray(first[..., 0])
  np.testing.assert_array_equal(np.asarray(a)[later], np.asarray(b)[later])
  assert _rel(np.asarray(a)[~later], np.asarray(b)[~later]) > 1e-3


def test_the_two_sides_draw_the_same_parameters():
  mine, theirs = prog.init_params(CFG, 11), ref.dense_params(CONFIG, 11)
  assert (jax.tree.structure(mine) == jax.tree.structure(theirs))
  for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
    np.testing.assert_array_equal(a, b)
  assert theirs['lm_head'].shape == (64, 96)
  assert theirs['layers'][1]['moe']['experts_in'].shape == (4, 64, 64)
  assert theirs['layers'][1]['moe']['router'].shape == (64, 16)
  assert 'moe' not in theirs['layers'][0]


def test_a_configuration_the_class_does_not_compute_is_refused_by_name():
  for key, value in (('score_func', 'softmax'), ('route_norm', False),
                     ('tie_word_embeddings', True), ('n_group', 2)):
    with pytest.raises(NotImplementedError, match=key):
      prog.MoELMConfig.from_dict({**CONFIG, key: value})
  with pytest.raises(NotImplementedError, match='layer types'):
    prog.MoELMConfig.from_dict({**CONFIG, 'layer_types': ['mamba']})
  # the router keeps the width the file states under ``published``
  assert CFG.router_width == 16 and CFG.num_experts == 4
  assert CFG.routed.first_expert == 4


def _run_toy_cell(seed, tmp_path):
  with open(os.path.join(TOY, 'manifest.json')) as f:
    manifest = json.load(f)
  args = argparse.Namespace(workload='toy-moe-1', seed=seed, seconds=0.2,
                            trace=0)
  return cell_lib.run_cell(manifest, TOY, 'toy-moe-1', args,
                           jax.devices()[:1], time.perf_counter(),
                           str(tmp_path))


def _lone_last_ids(seed):
  """Over the checked batches of ``seed``: the documents whose last
  position holds an id that occurs nowhere else in its batch.  No loss
  and no later position reads such a row: its gradient is exactly
  nought (ROADMAP M10)."""
  pool = traffic.train_tokens(MIX, [(96, 1)], CONFIG, seed,
                              batches=int(MIX['checked_steps']))
  lone = 0
  for cats, (targets, _) in pool:
    ids = cats[0].reshape(-1)
    counts = np.bincount(ids, minlength=96)
    lone += int(np.sum(counts[ids[targets.reshape(-1) < 0]] == 1))
  return lone


@pytest.mark.parametrize('seed', [7, 2**31 + 5])
def test_whole_step_follows_the_reference_for_three_steps(seed, tmp_path):
  """``DistributedEmbedding`` + ``make_hybrid_train_step`` + ``SparseAdam``
  + ``optax.adam`` with the untied table, through the benchmark's harness
  against ``run_reference``: the three losses, and every leaf's first
  gradient, change and count of moved elements, within the toy cell's
  limits; among the batches are rows whose gradient is exactly nought."""
  assert _lone_last_ids(seed) > 0
  result = _run_toy_cell(seed, tmp_path)
  assert result['correct'], result['compared']
  assert set(result['compared']) == {'loss_gap', 'grad_gap', 'change_gap',
                                     'moved_gap'}


def _train(world, weights, batches):
  mesh = create_mesh(jax.devices()[:world])
  configs = [TableConfig(96, CFG.hidden_size, None, name='vocabulary')] + [
      TableConfig(200 + i, 8, 'sum') for i in range(3)]
  dist = DistributedEmbedding(configs, mesh=mesh, dp_input=True,
                              packed_storage=False)
  opt = SparseAdam(learning_rate=3e-4, b1=0.9, b2=0.95)
  dense_opt = optax.adam(3e-4, b1=0.9, b2=0.95)
  state = init_hybrid_train_state(
      dist, {**jax.tree.map(jnp.asarray, prog.init_params(CFG, 3)),
             'embedding': set_weights(dist, weights)}, dense_opt, opt)
  step = make_hybrid_train_step(dist, prog.make_head_loss_fn(CFG), dense_opt,
                                opt, donate=False)
  losses = []
  for cats, batch in batches:
    state, loss = step(state, list(make_global_batch(mesh, *cats)),
                       jax.tree.map(jnp.asarray, batch))
    losses.append(float(loss))
  return dist, state, losses


def test_four_devices_train_as_one_does():
  """Three steps on a mesh of four give the losses, table, moments and
  dense leaves of the same steps on one device (the vocabulary beside
  three narrow tables, so that the mesh holds whole tables)."""
  rng = np.random.default_rng(1)
  weights = [rng.uniform(-0.0346, 0.0346, shape).astype(np.float32)
             for shape in [(96, 64), (200, 8), (201, 8), (202, 8)]]
  pool = traffic.train_tokens({**MIX, 'global_batch': 4}, [(96, 1)], CONFIG,
                              9, batches=3)
  batches = [([cats[0]] + [rng.integers(0, 20, (4 * 48, 1)).astype(np.int32)
                           for _ in range(3)], batch) for cats, batch in pool]
  dist1, one, losses1 = _train(1, weights, batches)
  dist4, four, losses4 = _train(4, weights, batches)
  np.testing.assert_allclose(losses4, losses1, rtol=1e-6)
  np.testing.assert_allclose(get_weights(dist4, four.params['embedding'])[0],
                             get_weights(dist1, one.params['embedding'])[0],
                             rtol=1e-5, atol=1e-7)
  a = get_optimizer_state(dist4, four.opt_state[1])[0]
  b = get_optimizer_state(dist1, one.opt_state[1])[0]
  for leaf in ('m', 'v', 't'):
    # (a moment is a sum over the batch; four devices sum in another order)
    np.testing.assert_allclose(a[leaf], b[leaf], rtol=1e-4, atol=3e-7)
  # untied: only the rows a batch asked for took a step
  assert np.any(b['t'] == 0) and np.any(b['t'] > 0)
  dense = lambda s: {k: v for k, v in s.params.items() if k != 'embedding'}
  # Adam's step is lr * g / (|g| + eps): an element whose gradient is
  # near nought turns another summation order into a share of one step
  # of 3e-4, so a leaf is held as a whole, and no element by a step
  for x, y in zip(jax.tree.leaves(dense(four)), jax.tree.leaves(dense(one))):
    x, y = np.asarray(x), np.asarray(y)
    assert _rel(x, y) < 1e-5
    np.testing.assert_allclose(x, y, atol=1e-4)


def _routed_block(seed):
  """A routed block's leaves with norm gains that are not 1 (so that the
  norm after the sub-layer shows), its input and a cotangent."""
  p = _dense(seed)['layers'][1]
  rng = np.random.default_rng(seed)
  for leaf in ('input_norm', 'pre_mlp_norm', 'post_attn_norm',
               'post_mlp_norm'):
    p[leaf] = jnp.asarray(rng.uniform(0.5, 1.5, CFG.hidden_size),
                          jnp.float32)
  return p, _hidden(seed + 1), _hidden(seed + 2)


def _block_grad(cfg, p, x, cot):
  """The gradient of one ``sliding_attention`` block in its parameters
  and its input, as a jitted function of them.  (The loss is not linear
  in the block's output: the backward pass reads the forward's, as under
  a stack it always does.)"""
  def loss(p, x):
    return jnp.sum(
        cot * prog.layer(cfg, 'sliding_attention', p, x, SEGMENTS)[0] ** 2)
  return jax.jit(jax.grad(loss, argnums=(0, 1)))


@contextlib.contextmanager
def _nothing_kept(monkeypatch):
  """The block as it stood until PR 34: with no array named, the half's
  checkpoint keeps nothing, and the backward of the norm after a routed
  sub-layer reads the recomputed waves' sum."""
  with monkeypatch.context() as patch:
    patch.setattr(prog, 'checkpoint_name', lambda x, name: x)
    yield


# at the toy sizes a wave is 128 slots of the 384 there can be: at 1.25
# one wave always runs and two lie under the ``cond`` and its ``scan``,
# at 8.0 all three always run
CAPACITIES = (1.25, 8.0)


@pytest.mark.parametrize('capacity', CAPACITIES)
def test_a_routed_blocks_gradient_is_the_unrematerialised_blocks(
    capacity, monkeypatch):
  """With a routed sub-layer's output KEPT for the norm after it, the
  block's gradient (every parameter, and the input) is bit for bit what
  it was when the norm read the recomputed waves' sum, and to rounding
  what the same block gives with no ``jax.checkpoint`` anywhere (the
  halves', the waves', the attention blocks')."""
  cfg = dataclasses.replace(CFG, capacity_factor=capacity)
  assert cfg.sandwich_norms and cfg.routed.waves(96) == 3
  p, x, cot = _routed_block(20)
  mine = _block_grad(cfg, p, x, cot)(p, x)
  with _nothing_kept(monkeypatch):
    before = _block_grad(cfg, p, x, cot)(p, x)
  for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(before)):
    np.testing.assert_array_equal(a, b)
  monkeypatch.setattr(jax, 'checkpoint', lambda f, **_: f)
  plain = _block_grad(cfg, p, x, cot)
  assert 'checkpoint' not in str(plain.trace(p, x).jaxpr)
  plain = plain(p, x)
  assert (jax.tree.structure(mine) == jax.tree.structure(plain))
  for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(plain)):
    assert _rel(a, b) < 1e-5
  # (the selection bias moves the selection only)
  assert not np.any(mine[0]['moe']['expert_bias'])
  assert float(jnp.linalg.norm(mine[0]['post_mlp_norm'])) > 0


@pytest.mark.parametrize('capacity', CAPACITIES)
def test_the_norm_after_a_routed_sub_layer_costs_no_wave_a_pass(
    capacity, monkeypatch):
  """The mechanism, by count: the optimized program of a routed block's
  gradient holds no more products and no more scatters under a family
  with a norm after the sub-layer than under one without (each wave
  forward twice, the forward pass's and its own checkpoint's, and
  backward once).  With nothing kept the half's recomputation ran every
  wave a third time to hand the norm their sum: more of both.  (The
  mixer is taken out of the count: the norm after IT stays inside its
  checkpoint and changes what its own recomputation keeps.)"""
  monkeypatch.setattr(prog, 'attention', lambda cfg, kind, p, u, seg: u)
  p, x, cot = _routed_block(21)

  def count(sandwich):
    cfg = dataclasses.replace(CFG, capacity_factor=capacity,
                              sandwich_norms=sandwich)
    text = _block_grad(cfg, p, x, cot).lower(p, x).compile().as_text()
    return {op: len(re.findall(rf' {op}\(', text))
            for op in ('dot', 'scatter')}

  with_norm, without = count(True), count(False)
  assert without['dot'] > 0 and without['scatter'] > 0
  assert with_norm['dot'] <= without['dot'], (with_norm, without)
  assert with_norm['scatter'] <= without['scatter'], (with_norm, without)
  with _nothing_kept(monkeypatch):
    before = count(True)
  assert before['dot'] > with_norm['dot'], (before, with_norm)
  assert before['scatter'] > with_norm['scatter'], (before, with_norm)


def _kept_outputs(cfg, dense):
  obs.reset()
  obs.metrics.enable()
  try:
    jax.eval_shape(functools.partial(prog.forward, cfg), dense,
                   0.1 * _hidden(15), SEGMENTS)
    return obs.metrics.snapshot().get('moe.kept_outputs', 0)
  finally:
    obs.metrics.disable()
    obs.reset()


def test_kept_outputs_counts_the_routed_blocks_under_a_norm():
  """``moe.kept_outputs`` counts one a traced routed block whose output
  the backward pass keeps: both routed blocks of the toy ``afmoe``
  stack (not its dense block), none of an ``lfm2_moe`` stack, which has
  no norm after a sub-layer."""
  assert _kept_outputs(CFG, _dense(14)) == 2
  lfm2 = os.path.join(cell_lib.BENCH_DIR, 'tests', 'toy_lfm2')
  config = names.load_json(lfm2, 'configs', 'toy-lfm2')
  cfg = prog.MoELMConfig.from_dict(config)
  assert len(cfg.layer_types) - cfg.num_dense_layers == 2
  dense = jax.eval_shape(lambda: jax.tree.map(
      jnp.asarray, prog.init_params(cfg, 14)))
  assert _kept_outputs(cfg, dense) == 0


def test_routing_stats_set_the_gauges_outside_the_step():
  dense, rows = _dense(12), 0.1 * _hidden(13)
  stats = jax.jit(functools.partial(prog.routing_stats, CFG))(
      dense, rows, SEGMENTS)
  assert stats['assignments_held'].shape == (2,)      # two routed layers
  held = np.asarray(stats['assignments_held'])
  assert np.all(held > 0) and np.all(held < 96 * 4)
  assert np.all(np.asarray(stats['overflow_rows']) == 0)
  obs.reset()
  obs.metrics.enable()
  try:
    prog.record_routing_stats(stats)
    prog.count_batch((np.asarray([[1, -1, 2, -1]]), np.asarray([[0, 0, 1, 1]])))
    snap = obs.metrics.snapshot()
  finally:
    obs.metrics.disable()
    obs.reset()
  flat = json.dumps(snap)
  for name in ('moe.assignments_held', 'moe.load_max_over_mean',
               'moe.overflow_rows', 'train.tokens'):
    assert name in flat, name
