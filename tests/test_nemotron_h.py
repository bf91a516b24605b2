"""The hybrid Mamba-2 mixture-of-experts stack (models/moe_lm.py read from
a ``nemotron_h`` configuration) over an untied token table under
``SparseAdam``, at a small size: hidden 64, blocks of ONE sub-layer in
the pattern ``MEM*E``, Mamba mixers of 8 heads of 16 whose B and C come
in 2 groups of 16, attention of 4 query heads of 16 over 2 key-value
heads, 4 of 16 squared-ReLU experts of 32 held, 3 a token, beside a
shared one of 48; 96 rows, sequences of 48.

The oracle is the benchmark's plain reference
(``benchmarks/classes/nemotron_h.py``): the recurrence position by
position, one group's heads at a time, full masked attention, the routed
layer as a loop over the held experts, nothing of the program imported;
the whole step is held to ``benchmarks.lib.reference.run_reference``
through the benchmark's own harness.  The grouped scan is also held,
through both of ``ssd_scan``'s paths (the ``lax.scan`` and the kernels in
interpreter mode), to ``classes/hybrid_ssm._sequential_scan`` run on each
group's heads against that group's B and C.
"""

import argparse
import dataclasses
import functools
import json
import os
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from benchmarks.classes import hybrid_ssm as ssm_ref
from benchmarks.classes import nemotron_h as ref
from benchmarks.lib import cell as cell_lib
from benchmarks.lib import names
from distributed_embeddings_tpu import obs
from distributed_embeddings_tpu.layers import routed_experts as routed
from distributed_embeddings_tpu.models import hybrid_ssm
from distributed_embeddings_tpu.models import moe_lm as prog
from distributed_embeddings_tpu.ops import pallas_ssd

TOY = os.path.join(cell_lib.BENCH_DIR, 'tests', 'toy_nemotron')
CONFIG = names.load_json(TOY, 'configs', 'toy-nemotron')
CFG = prog.MoELMConfig.from_dict(CONFIG)
Z = ref._sizes(CONFIG)
HIGHEST = jax.lax.Precision.HIGHEST
matmul = functools.partial(jnp.matmul, precision=HIGHEST)

# two sequences of 48: a document of one token, boundaries inside a chunk
# of 16 and at a chunk's edge, a document that ends the sequence
SEGMENTS = jnp.asarray(
    [[0] * 30 + [1] * 18, [0] * 5 + [1] + [2] * 10 + [3] * 32], jnp.int32)


def _rel(a, b):
  return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _hidden(seed, seqs=2, length=48, width=64):
  return jnp.asarray(np.random.default_rng(seed).standard_normal(
      (seqs, length, width)), jnp.float32)


def _dense(seed, config=CONFIG):
  return jax.tree.map(jnp.asarray, ref.dense_params(config, seed))


def _same_value_and_gradients(mine, theirs, p, u, tol=1e-5):
  with jax.default_matmul_precision('highest'):
    a, (ga, gua) = jax.value_and_grad(mine, argnums=(0, 1))(p, u)
  b, (gb, gub) = jax.value_and_grad(theirs, argnums=(0, 1))(p, u)
  assert float(a) == pytest.approx(float(b), rel=tol)
  for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(ga),
                          jax.tree.leaves(gb)):
    assert _rel(x, y) < tol, path
  assert _rel(gua, gub) < tol


# ---- the grouped scan --------------------------------------------------------

CHUNK, LENGTH, D_HEAD, STATE = 128, 384, 64, 128


def _scan_inputs(groups, heads, seed=3):
  rng = np.random.default_rng(seed)
  f32 = lambda *shape: rng.standard_normal(shape).astype(np.float32)
  return dict(x=f32(2, LENGTH, heads, D_HEAD),
              dt=np.asarray(jax.nn.softplus(f32(2, LENGTH, heads) - 4.0)),
              a=-rng.uniform(1.0, 16.0, heads).astype(np.float32),
              b=f32(2, LENGTH, groups, STATE), c=f32(2, LENGTH, groups, STATE))


def _sequential_by_group(x, dt, a, b, c, seg):
  """The benchmark's recurrence on each group's heads against that
  group's B and C, the groups concatenated along the heads."""
  groups = b.shape[2]
  per = x.shape[2] // groups
  return jnp.concatenate(
      [ssm_ref._sequential_scan(x[:, :, g * per:(g + 1) * per],
                                dt[:, :, g * per:(g + 1) * per],
                                a[g * per:(g + 1) * per], b[:, :, g],
                                c[:, :, g], seg, matmul)
       for g in range(groups)], axis=2)


def _readings(scan, v, weight):
  out = scan(*v.values())
  grads = jax.grad(lambda *args: jnp.sum(scan(*args) * weight),
                   argnums=tuple(range(5)))(*v.values())
  return (out,) + grads


# one document across the chunks, one boundary inside a chunk, one at a
# chunk's edge, a document of one token
_SCAN_SEGMENTS = np.stack([
    np.repeat([0, 1, 2], [40, 88, 256]),
    np.repeat([0, 1, 2], [1, 127, 256])]).astype(np.int32)


@pytest.mark.parametrize('path', ['lax_scan', 'kernels'])
@pytest.mark.parametrize('groups,heads', [(1, 4), (2, 4), (8, 16)])
def test_grouped_scan_is_the_recurrence_on_each_groups_heads(
    monkeypatch, path, groups, heads):
  """Output and the gradients of x, dt, A, B and C, with B and C ``[S, L,
  G, N]`` (head h reads group h // (H / G)): ``dB`` and ``dC`` are a
  group's heads' sum.  The kernels (interpreter mode) round their
  products' operands to bfloat16, so they are held to the rounding."""
  monkeypatch.setattr(pallas_ssd, 'FORCE_INTERPRET', path == 'kernels')
  v = _scan_inputs(groups, heads)
  seg = jnp.asarray(_SCAN_SEGMENTS)
  weight = np.random.default_rng(4).standard_normal(
      v['x'].shape).astype(np.float32)
  assert pallas_ssd.takes(v['x'].shape, STATE, CHUNK, groups) == (
      path == 'kernels')
  scan = lambda *a: hybrid_ssm.ssd_scan(*a, seg, CHUNK)
  with jax.default_matmul_precision('highest'):
    got = _readings(scan, v, weight)
  want = _readings(lambda *a: _sequential_by_group(*a, seg), v, weight)
  tol = 1e-2 if path == 'kernels' else 1e-4
  for name, g, w in zip(('y',) + tuple(v), got, want):
    assert g.shape == w.shape and bool(jnp.all(jnp.isfinite(g))), name
    assert _rel(g, w) < tol, (name, _rel(g, w))


def test_a_head_block_lies_inside_one_group(monkeypatch):
  """The kernels take a block of heads only from one group: 16 heads of
  64 in 8 groups go 2 to a block (where one group takes 16 to a block),
  and a group of one head of 64 fills no lane tile."""
  assert pallas_ssd._head_block(64, 64) == 16
  assert pallas_ssd._head_block(64, 64, groups=8) == 8   # the cell's
  assert pallas_ssd._head_block(16, 64, groups=8) == 2
  assert pallas_ssd._head_block(8, 64, groups=8) is None
  monkeypatch.setattr(pallas_ssd, 'ASSUME_TPU', True)
  assert pallas_ssd.takes((2, 8192, 64, 64), 128, 128, 8)
  assert not pallas_ssd.takes((2, 8192, 64, 64), 128, 128, 3)
  assert not pallas_ssd.takes((2, 384, 8, 64), 128, 128, 8)


def test_grouped_gated_norm_normalises_each_group_apart():
  """``rmsnorm(y * silu(z))`` over each group's channels apart, against
  a NumPy norm a group at a time."""
  rng = np.random.default_rng(5)
  y, z = (rng.standard_normal((2, 3, 64)).astype(np.float32)
          for _ in range(2))
  gain = rng.uniform(0.5, 1.5, 64).astype(np.float32)
  assert CFG.mamba.mamba_n_groups == 2 and CFG.mamba.d_inner == 128
  cfg = dataclasses.replace(CFG.mamba, mamba_n_heads=4)
  gated = y * z / (1 + np.exp(-z))
  want = np.concatenate(
      [part / np.sqrt(np.mean(part * part, axis=-1, keepdims=True) + 1e-5)
       for part in np.split(gated, 2, axis=-1)], axis=-1) * gain
  got = hybrid_ssm.gated_norm(cfg, y, z, gain)
  np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
  # one group: the norm over all the channels, as before groups
  one = dataclasses.replace(cfg, mamba_n_groups=1)
  whole = gated / np.sqrt(np.mean(gated * gated, -1, keepdims=True) + 1e-5)
  np.testing.assert_allclose(hybrid_ssm.gated_norm(one, y, z, gain),
                             whole * gain, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize('form', ['swiglu', 'relu2'])
def test_an_experts_interior_in_each_form(form):
  """What lies between an expert's two products, routed or shared:
  ``silu(gate) * up`` of the first product's two halves, or
  ``relu(h)^2``, against NumPy."""
  h = np.random.default_rng(9).standard_normal((5, 12)).astype(np.float32)
  if form == 'swiglu':
    gate, up = np.split(h, 2, axis=-1)
    want = gate / (1 + np.exp(-gate)) * up
  else:
    want = np.maximum(h, 0) ** 2
  np.testing.assert_allclose(routed.interior(form, jnp.asarray(h)), want,
                             rtol=1e-6, atol=1e-6)


# ---- the three kinds of block ---------------------------------------------


def test_mamba_mixer_matches_the_reference_in_every_parameter():
  """The grouped mixer against the reference's: output and the gradient
  of every leaf (projections, taps and bias, dt_bias, A_log, D, the
  gated norm's gain) and of the input, across document boundaries."""
  p = dict(_dense(1)['layers'][0]['mamba'])
  rng = np.random.default_rng(1)
  p['gated_norm'] = jnp.asarray(rng.uniform(0.5, 1.5, 128), jnp.float32)
  p['conv_bias'] = jnp.asarray(rng.normal(0, 0.1, 192), jnp.float32)
  assert p['in_proj'].shape == (64, 2 * 128 + 2 * 2 * 16 + 8)
  u, cot = 0.5 * _hidden(2), _hidden(3)
  _same_value_and_gradients(
      lambda p, u: jnp.sum(cot * hybrid_ssm.mamba_mixer(CFG.mamba, p, u,
                                                        SEGMENTS)),
      lambda p, u: jnp.sum(cot * ref._mamba(Z, p, u, SEGMENTS, matmul)),
      p, u, tol=1e-4)


def test_attention_has_no_positional_embedding_and_no_norms():
  """The stack's attention against the reference's full scores: 4 query
  heads of head_dim 16 over 2 key-value heads, scale 1 / sqrt(16), no
  rotary, no per-head norms, no gate."""
  p = _dense(4)['layers'][3]['attention']
  assert set(p) == {'q_proj', 'k_proj', 'v_proj', 'o_proj'}
  assert not (CFG.qk_norms or CFG.attention_gate or CFG.rotary_layers)
  u, cot = _hidden(5), _hidden(6)
  _same_value_and_gradients(
      lambda p, u: jnp.sum(cot * prog.attention(CFG, 'attention', p, u,
                                                SEGMENTS)),
      lambda p, u: jnp.sum(cot * ssm_ref._attention(
          Z, 16 ** -0.5, 16, p, u, SEGMENTS, matmul)), p, u)


def _biased(seed):
  """A routed layer's leaves with a selection bias that is NOT nought."""
  p = _dense(seed)['layers'][1]['moe']
  bias = np.random.default_rng(seed).normal(0, 0.2, CFG.router_width)
  return {**p, 'expert_bias': jnp.asarray(bias, jnp.float32)}


def test_routed_layer_is_the_shared_expert_plus_the_held_experts_loop():
  """With a seeded non-zero bias: the routed sub-layer (squared-ReLU
  waves beside a squared-ReLU shared expert of its own width) against
  the reference's loop over the held experts, output and gradients."""
  p, u, cot = _biased(7), _hidden(8), _hidden(9)
  assert p['experts_in'].shape == (4, 64, 32)
  assert p['shared']['mlp_in'].shape == (64, 48)
  leaves = {k: v for k, v in p.items() if k != 'expert_bias'}
  _same_value_and_gradients(
      lambda q, u: jnp.sum(cot * prog.routed_ffn(
          CFG, {**q, 'expert_bias': p['expert_bias']}, u)[0]),
      lambda q, u: jnp.sum(cot * ref._routed(
          Z, CONFIG, {**q, 'expert_bias': p['expert_bias']}, u, matmul)),
      leaves, u)


@pytest.mark.parametrize('capacity', [0.25, 1.25, 8.0])
def test_ungated_waves_match_a_dense_loop_over_the_experts(capacity):
  """``routed_experts`` with ``'relu2'`` experts at capacities whose
  waves overflow or pad, against a dense loop over the held experts
  ``w_e relu(u W_up)^2 W_down``: output and every gradient."""
  cfg = routed.RoutedExpertsConfig(
      router_width=16, experts_per_token=3, num_held=4, first_expert=4,
      route_scale=2.5, capacity_factor=capacity, expert_form='relu2')
  p = {k: v for k, v in _biased(10).items() if k != 'shared'}
  u = _hidden(11).reshape(-1, 64)
  cot = _hidden(12).reshape(-1, 64)

  def loop(p, u):
    w = ref.dense_routing_weights(Z, CONFIG, p, u)
    y = jnp.zeros_like(u)
    for e in range(4):
      y = y + w[:, 4 + e, None] * matmul(
          jnp.square(jax.nn.relu(matmul(u, p['experts_in'][e]))),
          p['experts_out'][e])
    return jnp.sum(cot * y)

  _same_value_and_gradients(
      lambda p, u: jnp.sum(cot * routed.routed_experts(cfg, p, u)[0]),
      loop, p, u)


def test_the_shares_add_up_to_the_uncut_layer():
  """The parts that the four shares of the toy's router give (experts
  0-3, 4-7, 8-11, 12-15, each through the program), with the shared
  expert, which every chip computes alike, counted ONCE, sum to the
  reference's layer with all sixteen experts held."""
  whole = {**CONFIG, 'n_routed_experts': 16, 'first_expert': 0}
  p, u = _dense(17, whole)['layers'][1]['moe'], _hidden(18)
  assert p['experts_in'].shape == (16, 64, 32)
  uncut = ref._routed(ref._sizes(whole), whole, p, u, matmul)
  total = 0
  with jax.default_matmul_precision('highest'):
    for first in (0, 4, 8, 12):
      cfg = prog.MoELMConfig.from_dict(CONFIG, first_expert=first,
                                       num_shared_experts=int(first == 0))
      share = {k: v for k, v in p.items() if k != 'shared' or first == 0}
      share.update(experts_in=p['experts_in'][first:first + 4],
                   experts_out=p['experts_out'][first:first + 4])
      total = total + prog.routed_ffn(cfg, share, u)[0]
  assert _rel(total, uncut) < 1e-5


# ---- the stack -------------------------------------------------------------


def test_the_pattern_builds_blocks_of_one_sub_layer():
  """``hybrid_override_pattern`` is read into the kinds of block, each of
  ONE sub-layer under one norm; the groups, the expert form and the
  shared expert's width are read from the file."""
  assert CFG.layer_types == ('mamba', 'moe', 'mamba', 'attention', 'moe')
  assert CFG.mamba.mamba_n_groups == 2 and CFG.mamba.conv_dim == 192
  assert CFG.expert_form == CFG.routed.expert_form == 'relu2'
  assert CFG.shared_intermediate_size == 48 and CFG.num_shared_experts == 1
  assert (CFG.router_width, CFG.num_experts, CFG.routed.first_expert) == (
      16, 4, 4)
  assert CFG.route_scale == 2.5 and CFG.routed.route_norm_eps == 1e-20
  layers = prog.init_params(CFG, 1)['layers']
  assert [sorted(p) for p in layers] == [
      ['input_norm', 'mamba'], ['moe', 'pre_mlp_norm'],
      ['input_norm', 'mamba'], ['attention', 'input_norm'],
      ['moe', 'pre_mlp_norm']]


def test_the_two_sides_draw_the_same_parameters():
  mine, theirs = prog.init_params(CFG, 11), ref.dense_params(CONFIG, 11)
  assert jax.tree.structure(mine) == jax.tree.structure(theirs)
  for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
    np.testing.assert_array_equal(a, b)
  assert theirs['lm_head'].shape == (64, 96)                 # untied
  # dt in [time_step_min, time_step_max] through the inverse softplus
  dt = np.log1p(np.exp(theirs['layers'][0]['mamba']['dt_bias']))
  assert np.all((dt >= 1e-3) & (dt <= 1e-1))


@pytest.mark.parametrize('key,value', [
    ('mamba_proj_bias', True), ('use_conv_bias', False), ('use_bias', True),
    ('attention_bias', True), ('mlp_bias', True),
    ('mlp_hidden_act', 'silu'), ('mamba_hidden_act', 'gelu'),
    ('sliding_window', 1024), ('n_group', 2), ('topk_group', 2),
    ('norm_topk_prob', False), ('n_shared_experts', 2),
    ('tie_word_embeddings', True),
    ('hybrid_override_pattern', 'MEM-E'), ('model_type', 'nemotron_x')])
def test_a_configuration_the_family_does_not_compute_is_refused_by_name(
    key, value):
  match = 'layer types' if key == 'hybrid_override_pattern' else key
  with pytest.raises(NotImplementedError, match=match):
    prog.MoELMConfig.from_dict({**CONFIG, key: value})


def test_two_half_kinds_are_not_this_familys():
  """A ``layer_types`` of the other families' blocks is refused for this
  family, and this family's blocks for theirs."""
  with pytest.raises(NotImplementedError, match='layer types'):
    prog.MoELMConfig.from_dict({**CONFIG, 'layer_types': ['conv']})
  lfm2 = names.load_json(os.path.join(cell_lib.BENCH_DIR, 'tests',
                                      'toy_lfm2'), 'configs', 'toy-lfm2')
  with pytest.raises(NotImplementedError, match='layer types'):
    prog.MoELMConfig.from_dict({**lfm2, 'layer_types': ['conv', 'moe']})


def test_a_document_moves_nothing_of_the_next():
  """Perturbing the first document's rows leaves every later document's
  hidden states as they were, through the grouped scan (its state
  restarts), the convolution, attention and the routed layer: to
  float32's rounding and not to the bit, because the chunked scan's
  decay is a difference of running sums over the whole chunk, so a
  document that starts inside a chunk reads ``(P + S_t) - (P + S_s)``
  with ``P`` the earlier document's part (the routed layer and attention
  alone are exact to the bit here)."""
  dense, rows = _dense(8), 0.1 * _hidden(9)
  first = (SEGMENTS == 0)[..., None]
  moved = jnp.where(first, rows + 0.05 * _hidden(10), rows)
  later = ~np.asarray(first[..., 0])
  with jax.default_matmul_precision('highest'):
    a = np.asarray(prog.forward(CFG, dense, rows, SEGMENTS))
    b = np.asarray(prog.forward(CFG, dense, moved, SEGMENTS))
  np.testing.assert_allclose(a[later], b[later], rtol=1e-5, atol=1e-5)
  assert _rel(a[~later], b[~later]) > 1e-3


def _counters(cfg, length):
  dense = prog.init_params(cfg, 1)
  rows = jnp.zeros((1, length, cfg.hidden_size))
  seg = jnp.zeros((1, length), jnp.int32)
  obs.reset()
  obs.metrics.enable()
  try:
    jax.eval_shape(lambda d, r: prog.forward(cfg, d, r, seg), dense, rows)
    return obs.metrics.snapshot()
  finally:
    obs.metrics.disable()
    obs.reset()


def test_the_counters_count_grouped_scans_kernels_and_ungated_layers(
    monkeypatch):
  """At trace time: ``mixer.grouped_scan_layers`` a Mamba layer whose B
  and C come in groups, ``moe.ungated_expert_layers`` a routed layer of
  squared-ReLU experts, and ``mixer.scan_kernel_layers`` the scans the
  kernels take: none on a CPU, and every one where a TPU is assumed, at
  the cell's head shapes (heads of 64, 8 groups of 128, chunks of 128)."""
  snap = _counters(CFG, 48)
  assert snap['mixer.grouped_scan_layers'] == 2
  assert snap['moe.ungated_expert_layers'] == 2
  assert 'mixer.scan_kernel_layers' not in snap
  wide = prog.MoELMConfig.from_dict({
      **CONFIG, 'mamba_num_heads': 16, 'mamba_head_dim': 64,
      'ssm_state_size': 128, 'n_groups': 8, 'chunk_size': 128})
  monkeypatch.setattr(pallas_ssd, 'ASSUME_TPU', True)
  snap = _counters(wide, 256)
  assert snap['mixer.scan_kernel_layers'] == 2
  assert snap['mixer.grouped_scan_layers'] == 2


def _run_toy_cell(seed, tmp_path):
  with open(os.path.join(TOY, 'manifest.json')) as f:
    manifest = json.load(f)
  args = argparse.Namespace(workload='toy-nemotron-1', seed=seed,
                            seconds=0.2, trace=0)
  return cell_lib.run_cell(manifest, TOY, 'toy-nemotron-1', args,
                           jax.devices()[:1], time.perf_counter(),
                           str(tmp_path))


@pytest.mark.parametrize('seed', [7, 2**31 + 5])
def test_whole_step_follows_the_reference_for_three_steps(seed, tmp_path):
  """``DistributedEmbedding`` + ``make_hybrid_train_step`` +
  ``SparseAdam`` + ``optax.adam``: blocks of one sub-layer, a grouped
  scan, squared-ReLU waves and an untied head, through the benchmark's
  harness against ``run_reference``: the three losses, and every leaf's
  first gradient, change after the Adam steps and count of moved
  elements, within the toy cell's limits."""
  result = _run_toy_cell(seed, tmp_path)
  assert result['correct'], result['compared']
  assert set(result['compared']) == {'loss_gap', 'grad_gap', 'change_gap',
                                     'moved_gap'}
