"""The short-convolution mixture-of-experts stack (models/moe_lm.py read
from an ``lfm2_moe`` configuration) over a TIED token table under
``SparseAdam``, at a small size: hidden 64, 4 query heads of 16 over 2
key-value heads, sequences of 48, conv-attention-conv with one dense
layer, 4 of 16 experts of 32 held, 2 a token, no shared expert, 96 rows,
documents of 1 to 40 tokens.

The oracle is the benchmark's plain reference
(``benchmarks/classes/lfm2_moe.py``): the convolution as shifted products
under a same-document mask, full masked attention, the routed layer as a
loop over the held experts, nothing of the program imported; the whole
step is held to ``benchmarks.lib.reference.run_reference`` through the
benchmark's own harness.
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

from benchmarks.classes import lfm2_moe as ref
from benchmarks.lib import cell as cell_lib
from benchmarks.lib import names, traffic
from distributed_embeddings_tpu import obs
from distributed_embeddings_tpu.layers import routed_experts as routed
from distributed_embeddings_tpu.models import moe_lm as prog
from distributed_embeddings_tpu.parallel import (
    DistributedEmbedding, SparseAdam, TableConfig, create_mesh,
    get_optimizer_state, get_weights, init_hybrid_train_state,
    make_global_batch, make_hybrid_train_step, set_weights)

TOY = os.path.join(cell_lib.BENCH_DIR, 'tests', 'toy_lfm2')
CONFIG = names.load_json(TOY, 'configs', 'toy-lfm2')
MIX = names.load_json(TOY, 'traffic', 'toy-packed-lfm2')
CFG = prog.MoELMConfig.from_dict(CONFIG)
Z = ref._sizes(CONFIG)
HIGHEST = jax.lax.Precision.HIGHEST
matmul = functools.partial(jnp.matmul, precision=HIGHEST)

# two sequences of 48: a document of one token, a boundary inside a
# block of 16 queries, a document that ends the sequence
SEGMENTS = jnp.asarray(
    [[0] * 30 + [1] * 18, [0] * 5 + [1] + [2] * 20 + [3] * 22], jnp.int32)
LAYOUTS = {
    'one_document': [[0] * 48] * 2,
    # documents of 1, 2 and 3 tokens: a boundary one, two and three
    # positions back, at every distance the three taps reach
    'a_boundary_at_every_tap_distance': [
        [0] + [1] * 2 + [2] * 3 + [3] * 42,
        [0] * 3 + [1] * 2 + [2] + [3] * 20 + [4] * 22],
    'documents_of_one_token': [list(range(48)),
                               [0] * 24 + list(range(1, 25))],
}


def _rel(a, b):
  return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _hidden(seed, seqs=2, length=48):
  return jnp.asarray(np.random.default_rng(seed).standard_normal(
      (seqs, length, CFG.hidden_size)), jnp.float32)


def _dense(seed, config=CONFIG):
  return jax.tree.map(jnp.asarray, ref.dense_params(config, seed))


def _same_value_and_gradients(mine, theirs, p, u):
  with jax.default_matmul_precision('highest'):
    a, (ga, gua) = jax.value_and_grad(mine, argnums=(0, 1))(p, u)
  b, (gb, gub) = jax.value_and_grad(theirs, argnums=(0, 1))(p, u)
  assert float(a) == pytest.approx(float(b), rel=1e-5)
  for leaf in p:
    assert _rel(ga[leaf], gb[leaf]) < 1e-5, leaf
  assert _rel(gua, gub) < 1e-5


@pytest.mark.parametrize('layout', sorted(LAYOUTS))
def test_short_conv_matches_shifted_products_under_a_document_mask(layout):
  """The operator against the reference's sum of three shifted products:
  output and the gradient of both projections, of the taps and of the
  input, whatever the documents."""
  seg = jnp.asarray(LAYOUTS[layout], jnp.int32)
  p, u, cot = _dense(1)['layers'][0]['conv'], _hidden(2), _hidden(3)
  assert p['in_proj'].shape == (64, 192) and p['conv_kernel'].shape == (3, 64)
  _same_value_and_gradients(
      lambda p, u: jnp.sum(cot * prog.short_conv(p, u, seg)),
      lambda p, u: jnp.sum(cot * ref._short_conv(p, u, seg, matmul)), p, u)


def test_the_convolution_reads_this_and_the_two_positions_before():
  """``y_t = C_t * sum_k taps[2 - k] (B z)_{t - k}``, the LAST tap on the
  current position: against the sum written out by hand."""
  p, u = _dense(4)['layers'][0]['conv'], _hidden(5, seqs=1, length=6)
  seg = jnp.zeros((1, 6), jnp.int32)
  b, c, z = jnp.split(matmul(u, p['in_proj']), 3, axis=-1)
  bz, taps = np.asarray(b * z)[0], np.asarray(p['conv_kernel'])
  conv = np.stack([sum(taps[2 - k] * bz[t - k] for k in range(3) if t >= k)
                   for t in range(6)])
  with jax.default_matmul_precision('highest'):
    got = prog.short_conv(p, u, seg)
  np.testing.assert_allclose(
      got[0], matmul(np.asarray(c)[0] * conv, p['out_proj']), atol=1e-5)


def test_a_document_moves_nothing_of_the_next():
  """Perturbing the first document's rows leaves every later document's
  hidden states as they were, to the bit: through the convolution, whose
  taps reach two positions back, through attention and through the
  routed layer (a token's experts are its own)."""
  dense, rows = _dense(8), 0.1 * _hidden(9)
  first = (SEGMENTS == 0)[..., None]
  moved = jnp.where(first, rows + 0.05 * _hidden(10), rows)
  later = ~np.asarray(first[..., 0])
  conv = dense['layers'][0]['conv']
  with jax.default_matmul_precision('highest'):
    for fn in (lambda r: prog.short_conv(conv, r, SEGMENTS),
               lambda r: prog.forward(CFG, dense, r, SEGMENTS)):
      a, b = np.asarray(fn(rows)), np.asarray(fn(moved))
      np.testing.assert_array_equal(a[later], b[later])
      assert _rel(a[~later], b[~later]) > 1e-3


def _biased(seed):
  """A routed layer's leaves with a selection bias that is NOT nought."""
  p = _dense(seed)['layers'][1]['moe']
  bias = np.random.default_rng(seed).normal(0, 0.2, CFG.router_width)
  return {**p, 'expert_bias': jnp.asarray(bias, jnp.float32)}


def test_selection_is_by_score_plus_bias_and_weights_by_score_alone():
  """With a seeded non-zero bias: the experts a token takes are the top
  two of score + bias (not of the score), each weighted by its own score
  over the two scores' sum + 1e-6, held here or not; and the layer's
  output and gradients are the reference's loop over the held experts
  (the bias takes no gradient, and nothing stands beside the experts)."""
  p, u = _biased(14), _hidden(15)
  flat = u.reshape(-1, CFG.hidden_size)
  with jax.default_matmul_precision('highest'):
    sel, weights = routed.route(CFG.routed, flat, p['router'],
                                p['expert_bias'])
  scores = np.asarray(jax.nn.sigmoid(matmul(flat, p['router'])))
  by_bias = np.argsort(-(scores + np.asarray(p['expert_bias'])), axis=-1)[:, :2]
  np.testing.assert_array_equal(np.sort(sel, axis=-1),
                                np.sort(by_bias, axis=-1))
  assert np.any(np.sort(by_bias, -1)
                != np.sort(np.argsort(-scores, -1)[:, :2], -1))
  picked = np.take_along_axis(scores, np.asarray(sel), axis=-1)
  np.testing.assert_allclose(
      weights, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
  assert CFG.routed.route_norm_eps == 1e-6 and CFG.route_scale == 1
  assert 'shared' not in p and CFG.num_shared_experts == 0
  cot = _hidden(16)
  leaves = {k: v for k, v in p.items() if k != 'expert_bias'}
  _same_value_and_gradients(
      lambda q, u: jnp.sum(cot * prog.routed_ffn(
          CFG, {**q, 'expert_bias': p['expert_bias']}, u)[0]),
      lambda q, u: jnp.sum(cot * ref._routed(
          Z, CONFIG, {**q, 'expert_bias': p['expert_bias']}, u, matmul)),
      leaves, u)


def test_the_shares_add_up_to_the_uncut_layer():
  """With no shared expert the routed layer IS the sum of its shares:
  the parts that the four shares of the toy's router give (experts 0-3,
  4-7, 8-11, 12-15, each through the program) sum to the reference's
  layer with all sixteen experts held."""
  whole = {**CONFIG, 'num_experts': 16, 'first_expert': 0}
  p, u = _dense(17, whole)['layers'][1]['moe'], _hidden(18)
  assert p['experts_in'].shape == (16, 64, 64)
  uncut = ref._routed(ref._sizes(whole), whole, p, u, matmul)
  total = 0
  with jax.default_matmul_precision('highest'):
    for first in (0, 4, 8, 12):
      cfg = prog.MoELMConfig.from_dict(CONFIG, first_expert=first)
      share = {**p, 'experts_in': p['experts_in'][first:first + 4],
               'experts_out': p['experts_out'][first:first + 4]}
      total = total + prog.routed_ffn(cfg, share, u)[0]
  assert _rel(total, uncut) < 1e-5


def test_attention_takes_rotary_on_every_layer_and_has_no_gate():
  """The stack's one kind of attention against the reference's full
  scores: per-head norms with gains that are not 1, rotary at theta 1e6
  on a ``full_attention`` layer, no output gate."""
  p = dict(_dense(19)['layers'][1]['attention'])
  rng = np.random.default_rng(19)
  for gain in ('q_norm', 'k_norm'):
    p[gain] = jnp.asarray(rng.uniform(0.5, 1.5, CFG.head_dim), jnp.float32)
  assert 'gate_proj' not in p and CFG.rotary_layers == ('full_attention',)
  assert CFG.rope_theta == 1e6 and CFG.head_dim == 16
  u, cot = _hidden(20), _hidden(21)
  _same_value_and_gradients(
      lambda p, u: jnp.sum(cot * prog.attention(CFG, 'full_attention', p, u,
                                                SEGMENTS)),
      lambda p, u: jnp.sum(cot * ref._attention(Z, CONFIG, p, u, SEGMENTS,
                                                matmul)), p, u)
  # rotary shows: without it the layer gives other numbers
  bare = prog.MoELMConfig.from_dict(CONFIG, rotary_layers=())
  with jax.default_matmul_precision('highest'):
    assert _rel(prog.attention(bare, 'full_attention', p, u, SEGMENTS),
                prog.attention(CFG, 'full_attention', p, u, SEGMENTS)) > 1e-2


def test_the_two_sides_draw_the_same_parameters():
  mine, theirs = prog.init_params(CFG, 11), ref.dense_params(CONFIG, 11)
  assert jax.tree.structure(mine) == jax.tree.structure(theirs)
  for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
    np.testing.assert_array_equal(a, b)
  assert 'lm_head' not in theirs                  # tied
  first, second = theirs['layers'][:2]
  assert set(first) == {'input_norm', 'pre_mlp_norm', 'conv', 'mlp_in',
                        'mlp_out'}
  assert set(second) == {'input_norm', 'pre_mlp_norm', 'attention', 'moe'}
  assert set(second['moe']) == {'router', 'expert_bias', 'experts_in',
                                'experts_out'}
  assert second['moe']['router'].shape == (64, 16)
  # the taps are N(0, 1/3)
  taps = ref.dense_params({**CONFIG, 'hidden_size': 4096}, 1)[
      'layers'][0]['conv']['conv_kernel']
  assert np.var(taps) == pytest.approx(1 / 3, rel=0.05)


@pytest.mark.parametrize('key,value', [
    ('conv_bias', True), ('use_expert_bias', False),
    ('norm_topk_prob', False), ('tie_word_embeddings', False),
    ('layer_types', ['conv', 'mamba']), ('model_type', 'lfm3')])
def test_a_configuration_the_class_does_not_compute_is_refused_by_name(
    key, value):
  match = 'layer types' if key == 'layer_types' else key
  with pytest.raises(NotImplementedError, match=match):
    prog.MoELMConfig.from_dict({**CONFIG, key: value})
  # what the family fixes, and the share the file states
  assert not (CFG.mup_enabled or CFG.sandwich_norms or CFG.attention_gate)
  assert CFG.tie_word_embeddings and CFG.conv_L_cache == 3
  assert (CFG.router_width, CFG.num_experts, CFG.routed.first_expert) == (
      16, 4, 4)


def _run_toy_cell(seed, tmp_path):
  with open(os.path.join(TOY, 'manifest.json')) as f:
    manifest = json.load(f)
  args = argparse.Namespace(workload='toy-lfm2-1', seed=seed, seconds=0.2,
                            trace=0)
  return cell_lib.run_cell(manifest, TOY, 'toy-lfm2-1', args,
                           jax.devices()[:1], time.perf_counter(),
                           str(tmp_path))


@pytest.mark.parametrize('seed', [7, 2**31 + 5])
def test_whole_step_follows_the_reference_for_three_steps(seed, tmp_path):
  """``DistributedEmbedding`` + ``make_hybrid_train_step(...,
  head_reads_tables=(0,))`` + ``SparseAdam`` + ``optax.adam``: the first
  step in which a table the head reads sits under a ROUTED stack, through
  the benchmark's harness against ``run_reference``: the three losses,
  and every leaf's first gradient, change and count of moved elements,
  within the toy cell's limits."""
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
                                opt, head_reads_tables=(0,), donate=False)
  losses = []
  for cats, batch in batches:
    state, loss = step(state, list(make_global_batch(mesh, *cats)),
                       jax.tree.map(jnp.asarray, batch))
    losses.append(float(loss))
  return dist, state, losses


def test_four_devices_train_as_one_does():
  """Three steps on a mesh of four give the losses, table, moments and
  dense leaves of the same steps on one device (the vocabulary beside
  three narrow tables, so that the mesh holds whole tables): the owner's
  shard reaches the data-parallel head under the routed stack, and the
  head's gradient returns."""
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
    np.testing.assert_allclose(a[leaf], b[leaf], rtol=1e-4, atol=3e-7)
  # tied: every row of the vocabulary took each step
  assert np.all(b['t'] == 3)
  dense = lambda s: {k: v for k, v in s.params.items() if k != 'embedding'}
  # (Adam's step is lr * g / (|g| + eps): an element whose gradient is
  # near nought turns another summation order into a share of one step,
  # so a leaf is held as a whole: tests/test_moe_lm.py)
  for x, y in zip(jax.tree.leaves(dense(four)), jax.tree.leaves(dense(one))):
    x, y = np.asarray(x), np.asarray(y)
    assert _rel(x, y) < 1e-5
    np.testing.assert_allclose(x, y, atol=1e-4)


def test_the_counter_counts_the_operators_and_the_gauges_work():
  """``mixer.short_conv_layers`` counts one a traced operator (two in
  the toy stack), and ``record_routing_stats``' three gauges work for
  this stack."""
  dense, rows = _dense(12), 0.1 * _hidden(13)
  obs.reset()
  obs.metrics.enable()
  try:
    stats = jax.jit(functools.partial(prog.routing_stats, CFG))(
        dense, rows, SEGMENTS)
    prog.record_routing_stats(stats)
    snap = obs.metrics.snapshot()
  finally:
    obs.metrics.disable()
    obs.reset()
  assert stats['assignments_held'].shape == (2,)      # two routed layers
  held = np.asarray(stats['assignments_held'])
  assert np.all(held > 0) and np.all(held < 96 * 2)
  flat = json.dumps(snap)
  for name in ('moe.assignments_held', 'moe.load_max_over_mean',
               'moe.overflow_rows', 'mixer.short_conv_layers'):
    assert name in flat, name
  assert snap['mixer.short_conv_layers'] == 2, snap
