"""Packed-native storage (GroupSpec.storage_pack) equivalence suite.

Narrow fusion groups store their parameter shard physically lane-packed
as ``[rows_cap/pack, 128]`` — TPU HBM moves 512 B bursts, and the
(8,128) tiling makes narrow minor dims hostile to the memory system, so
the packed layout is the native one and the natural ``[rows_cap, w]``
shape never exists on device (killing the lane-padded relayout that
barred the fused apply kernels from huge narrow groups,
docs/perf_notes.md round 3).  These tests pin the contract: every
observable behavior (forward, gradients, sparse train steps, every
optimizer, checkpoint round-trips) is IDENTICAL between
``packed_storage=True`` and ``False``.

Reference analog: none — the reference's CUDA kernels address rows at
natural width (`embedding_lookup_kernels.cu`); packing is a TPU-layout
concern.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from distributed_embeddings_tpu.layers.embedding import TableConfig
from distributed_embeddings_tpu.parallel import (DistributedEmbedding,
                                                 SparseAdagrad, SparseAdam,
                                                 SparseSGD,
                                                 make_hybrid_train_step)
from distributed_embeddings_tpu.parallel.checkpoint import (get_weights,
                                                            set_weights)
from distributed_embeddings_tpu.parallel.mesh import create_mesh
from distributed_embeddings_tpu.parallel.sparse import init_hybrid_train_state

WORLD = 4

CONFIGS = [
    TableConfig(412, 16, 'sum'),
    TableConfig(300, 16, 'sum'),
    TableConfig(200, 128, 'sum'),
    TableConfig(150, 16, 'mean'),
    TableConfig(90, 8, 'sum'),
]


def _mesh():
  return create_mesh(jax.devices()[:WORLD])


def _pair(**kw):
  """The same layer with packed and natural storage."""
  mesh = _mesh()
  return (DistributedEmbedding(CONFIGS, mesh=mesh, packed_storage=True, **kw),
          DistributedEmbedding(CONFIGS, mesh=mesh, packed_storage=False, **kw))


def _inputs(rng, batch=32, hot=3):
  return [rng.integers(0, c.input_dim, size=(batch, hot)).astype(np.int32)
          for c in CONFIGS]


def test_plan_marks_qualifying_groups():
  packed, natural = _pair()
  packs = {g.key: g.storage_pack for g in packed.plan.groups}
  # every narrow (8..64, divides 128) group packs; width-128 groups don't
  for g in packed.plan.groups:
    if 8 <= g.width < 128 and 128 % g.width == 0:
      assert g.storage_pack == 128 // g.width, g.key
      assert g.param_width == 128
      assert g.param_rows * g.storage_pack == g.rows_cap
    else:
      assert g.storage_pack == 1, g.key
  assert any(p > 1 for p in packs.values()), 'no packed group in fixture'
  assert all(g.storage_pack == 1 for g in natural.plan.groups)


def test_init_and_forward_equivalent():
  packed, natural = _pair()
  pp, pn = packed.init(7), natural.init(7)
  # identical bytes, different physical grouping
  for gi, g in enumerate(packed.plan.groups):
    a = np.asarray(pp[f'group_{gi}'])
    b = np.asarray(pn[f'group_{gi}'])
    assert a.shape == (WORLD, g.param_rows, g.param_width)
    np.testing.assert_array_equal(
        a.reshape(WORLD, g.rows_cap, g.width), b)
  rng = np.random.default_rng(1)
  inputs = _inputs(rng)
  outs_p = packed.apply(pp, inputs)
  outs_n = natural.apply(pn, inputs)
  for a, b in zip(outs_p, outs_n):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-6, atol=1e-6)


def test_forward_oob_ids_clip_not_crash():
  packed, _ = _pair()
  params = packed.init(3)
  rng = np.random.default_rng(2)
  inputs = _inputs(rng)
  inputs[0][:, 0] = 10**9  # way out of vocab
  outs = packed.apply(params, inputs)
  assert all(np.isfinite(np.asarray(o)).all() for o in outs)


@pytest.mark.parametrize('opt_name', ['sgd', 'adagrad', 'adagrad_sq', 'adam'])
def test_sparse_train_step_equivalent(opt_name):
  """One full hybrid sparse step: identical new params under both
  layouts — including SparseAdam, which exercises the unpack fallback
  (supports_lane_packing=False)."""
  opts = {
      'sgd': lambda: SparseSGD(learning_rate=0.05),
      'adagrad': lambda: SparseAdagrad(learning_rate=0.05),
      'adagrad_sq': lambda: SparseAdagrad(learning_rate=0.05, dedup=False),
      'adam': lambda: SparseAdam(learning_rate=0.05),
  }
  packed, natural = _pair()
  dense_opt = optax.sgd(0.1)
  wsum = sum(c.output_dim for c in CONFIGS)

  def head(dense_params, emb_outs, labels):
    h = jnp.concatenate(list(emb_outs), axis=-1)
    return jnp.mean((h @ dense_params['kernel'] - labels)**2)

  rng = np.random.default_rng(3)
  inputs = _inputs(rng, batch=WORLD * 8)
  labels = rng.normal(size=(WORLD * 8, 1)).astype(np.float32)
  kernel = rng.normal(size=(wsum, 1)).astype(np.float32) * 0.1

  results = {}
  for name, dist in (('packed', packed), ('natural', natural)):
    opt = opts[opt_name]()
    emb = dist.init(11)
    state = init_hybrid_train_state(
        dist, {'embedding': emb, 'kernel': jnp.asarray(kernel)},
        dense_opt, opt)
    step = make_hybrid_train_step(dist, head, dense_opt, opt, donate=False)
    new_state, loss = step(state, inputs, jnp.asarray(labels))
    results[name] = (new_state, float(loss))

  (sp, lp), (sn, ln) = results['packed'], results['natural']
  assert np.isclose(lp, ln, rtol=1e-6), (lp, ln)
  for gi, g in enumerate(packed.plan.groups):
    a = np.asarray(sp.params['embedding'][f'group_{gi}'])
    b = np.asarray(sn.params['embedding'][f'group_{gi}'])
    np.testing.assert_allclose(
        a.reshape(WORLD, g.rows_cap, g.width), b, rtol=2e-5, atol=2e-6,
        err_msg=f'group {gi} ({opt_name})')


def test_checkpoint_roundtrip_packed():
  """set_weights -> get_weights is the identity under packed storage,
  and a checkpoint written natural loads packed (and vice versa)."""
  packed, natural = _pair()
  rng = np.random.default_rng(5)
  tables = [rng.normal(size=(c.input_dim, c.output_dim)).astype(np.float32)
            for c in CONFIGS]
  params_p = set_weights(packed, tables)
  for gi, g in enumerate(packed.plan.groups):
    assert params_p[f'group_{gi}'].shape == (WORLD, g.param_rows,
                                             g.param_width)
  back = get_weights(packed, params_p)
  for t, b in zip(tables, back):
    np.testing.assert_array_equal(t, b)
  # cross-layout: natural layer's weights reload into the packed layer
  params_n = natural.init(9)
  mid = get_weights(natural, params_n)
  params_p2 = set_weights(packed, mid)
  again = get_weights(packed, params_p2)
  for t, b in zip(mid, again):
    np.testing.assert_array_equal(t, b)


def test_optimizer_state_roundtrip_packed():
  from distributed_embeddings_tpu.parallel.checkpoint import (
      get_optimizer_state, set_optimizer_state)
  packed, _ = _pair()
  params = packed.init(13)
  opt = SparseAdagrad(learning_rate=0.05)
  state = opt.init(packed, params)
  tstates = get_optimizer_state(packed, state)
  for entry, cfg in zip(tstates, CONFIGS):
    assert entry['acc'].shape == (cfg.input_dim, cfg.output_dim)
  # the checkpoint contract is the GLOBAL canonical layout (padding rows
  # and empty-device shards legitimately zero-fill on rebuild): a second
  # gather of the rebuilt state must reproduce the canonical exactly
  rebuilt = set_optimizer_state(packed, state, tstates)
  again = get_optimizer_state(packed, rebuilt)
  for e1, e2 in zip(tstates, again):
    assert e1.keys() == e2.keys()
    for k in e1:
      np.testing.assert_array_equal(e1[k], e2[k])


def test_adam_state_shapes_with_packed_storage():
  """SparseAdam's per-row step counter stays NATURAL under packing."""
  packed, _ = _pair()
  params = packed.init(17)
  state = SparseAdam().init(packed, params)
  for gi, g in enumerate(packed.plan.groups):
    leaves = state[f'group_{gi}']
    assert leaves['m'].shape == (WORLD, g.param_rows, g.param_width)
    assert leaves['t'].shape == (WORLD, g.rows_cap)


def test_pallas_lookup_prepacked_interpret():
  """The lookup kernel's prepacked operand path (logical_width) matches
  both its natural-table path and the XLA oracle, interpreter mode."""
  from distributed_embeddings_tpu.ops import pallas_lookup
  rng = np.random.default_rng(21)
  vocab, w = 256, 16
  pack = 128 // w
  table = rng.normal(size=(vocab, w)).astype(np.float32)
  ids = rng.integers(-1, vocab, size=(64, 4)).astype(np.int32)
  nat = pallas_lookup.dense_lookup(jnp.asarray(table), jnp.asarray(ids),
                                   'sum', interpret=True)
  pre = pallas_lookup.dense_lookup(
      jnp.asarray(table.reshape(vocab // pack, 128)), jnp.asarray(ids),
      'sum', interpret=True, logical_width=w)
  np.testing.assert_allclose(np.asarray(nat), np.asarray(pre),
                             rtol=1e-6, atol=1e-6)
  # backward: cotangent lands in the packed layout, bytes equal natural
  def loss_nat(t):
    return jnp.sum(pallas_lookup.dense_lookup(t, jnp.asarray(ids), 'sum',
                                              interpret=True)**2)
  def loss_pre(t):
    return jnp.sum(pallas_lookup.dense_lookup(t, jnp.asarray(ids), 'sum',
                                              interpret=True,
                                              logical_width=w)**2)
  g_nat = jax.grad(loss_nat)(jnp.asarray(table))
  g_pre = jax.grad(loss_pre)(jnp.asarray(table.reshape(vocab // pack, 128)))
  np.testing.assert_allclose(np.asarray(g_pre).reshape(vocab, w),
                             np.asarray(g_nat), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize('op', ['sgd', 'adagrad_dedup', 'adagrad_sq'])
def test_segwalk_prepacked_interpret(op):
  """segwalk_apply(logical_width=...) on the physical packed operand
  matches the natural-table call exactly (interpreter mode)."""
  from distributed_embeddings_tpu.ops import pallas_segwalk
  rng = np.random.default_rng(33)
  rows, w = 512, 16
  pack = 128 // w
  n = 1024
  table = rng.normal(size=(rows, w)).astype(np.float32)
  acc = np.abs(rng.normal(size=(rows, w))).astype(np.float32)
  ids = np.sort(rng.integers(0, rows, size=(n,))).astype(np.int32)
  g = rng.normal(size=(n, w)).astype(np.float32)
  kw = dict(op=op, eps=1e-7, interpret=True)
  a = (None if op == 'sgd' else jnp.asarray(acc))
  out_nat = pallas_segwalk.segwalk_apply(
      jnp.asarray(table), a, jnp.asarray(ids), jnp.asarray(g), 0.05, **kw)
  a_p = (None if op == 'sgd'
         else jnp.asarray(acc.reshape(rows // pack, 128)))
  out_pre = pallas_segwalk.segwalk_apply(
      jnp.asarray(table.reshape(rows // pack, 128)), a_p,
      jnp.asarray(ids), jnp.asarray(g), 0.05, logical_width=w, **kw)
  if op == 'sgd':
    out_nat, out_pre = (out_nat,), (out_pre,)
  for x, y in zip(out_nat, out_pre):
    np.testing.assert_allclose(np.asarray(y).reshape(rows, w),
                               np.asarray(x), rtol=1e-6, atol=1e-6)


def test_eligibility_reports_packed_groups_served():
  """The huge-narrow-group exclusion (packed_dispatch_ok) disappears
  under packed storage: a group far over PACKED_PARAM_BYTES_LIMIT is
  reported (and dispatched) kernel-eligible because no reshape exists."""
  from distributed_embeddings_tpu.parallel import sparse
  from distributed_embeddings_tpu.utils.apply_eligibility import (
      eligibility_line)
  mesh = _mesh()
  big_rows = (sparse.PACKED_PARAM_BYTES_LIMIT // (128 * 4)) * WORLD * 8
  # enough tables that the auto threshold never column-slices the big
  # one below pack-eligible width (one table per device suffices)
  cfgs = [TableConfig(big_rows, 16, 'sum')] + [
      TableConfig(64, 16, 'sum') for _ in range(WORLD - 1)
  ]
  packed = DistributedEmbedding(cfgs, mesh=mesh, packed_storage=True)
  natural = DistributedEmbedding(cfgs, mesh=mesh, packed_storage=False)
  (gp,), (gn,) = packed.plan.groups, natural.plan.groups
  assert gp.param_width == 128 and gn.param_width == 16
  assert '1/1 groups' in eligibility_line(packed, 'float32', True), \
      'packed big group must serve'
  assert '0/1 groups' in eligibility_line(natural, 'float32', True), \
      'natural big group barred'


def test_eligibility_line_renders_every_branch():
  """The artifact-label helper must RENDER for each requested kernel —
  a crash here happens after bench's timed loop and loses the whole
  artifact line (a deleted-variable regression in the round-6 rowwise
  removal got exactly this far before review caught it)."""
  from distributed_embeddings_tpu.utils.apply_eligibility import (
      eligibility_line)
  mesh = _mesh()
  dist = DistributedEmbedding([TableConfig(64, 16, 'sum')] * WORLD,
                              mesh=mesh)
  assert eligibility_line(dist, 'float32', False) == ''
  for accum in ('float32', 'bfloat16'):
    line = eligibility_line(dist, 'float32', True, accum_dtype=accum)
    assert 'segwalk_apply:' in line, (accum, line)


def test_calibration_mirror_matches_packed_layout():
  """The CPU calibration mirror's zero params must match its plan's
  PHYSICAL (packed) layout, and its measurement forward must run —
  the bug class where natural-shaped zeros hit the packed lookup
  (caught in round-4 review) stays fixed."""
  from distributed_embeddings_tpu.parallel.sparse import _calibration_mirror
  mesh = _mesh()
  dist = DistributedEmbedding(CONFIGS, mesh=mesh, packed_storage=True)
  mirror, zeros = _calibration_mirror(dist, jax.devices()[:WORLD])
  for gi, g in enumerate(mirror.plan.groups):
    assert g.storage_pack == dist.plan.groups[gi].storage_pack
    assert zeros[f'group_{gi}'].shape == (WORLD, g.param_rows,
                                          g.param_width)
  rng = np.random.default_rng(41)
  cats = _inputs(rng, batch=WORLD * 4)
  _, residuals, _ = mirror.forward_with_residuals(zeros, cats)
  assert len(residuals) > 0


def test_adam_packed_over_limit_fails_fast():
  """SparseAdam + packed storage on a group whose natural-space apply
  reshape could provoke the lane-padded relayout must fail at INIT with
  an actionable message, not OOM mid-step."""
  from distributed_embeddings_tpu.parallel import sparse
  mesh = _mesh()
  big_rows = (sparse.PACKED_PARAM_BYTES_LIMIT // (128 * 4)) * WORLD * 8
  cfgs = [TableConfig(big_rows, 16, 'sum')] + [
      TableConfig(64, 16, 'sum') for _ in range(WORLD - 1)
  ]
  dist = DistributedEmbedding(cfgs, mesh=mesh, packed_storage=True)
  fake_params = {
      f'group_{gi}': jnp.zeros((WORLD, 8, g.param_width))
      for gi, g in enumerate(dist.plan.groups)
  }
  with pytest.raises(ValueError, match='packed_storage=False'):
    SparseAdam().init(dist, fake_params)
  # the escape hatch works (init accepts the same huge group natural),
  # and small packed groups stay fine
  nat = DistributedEmbedding(cfgs, mesh=mesh, packed_storage=False)
  nat_params = {
      f'group_{gi}': jnp.zeros((WORLD, 8, g.param_width))
      for gi, g in enumerate(nat.plan.groups)
  }
  SparseAdam().init(nat, nat_params)
  small = DistributedEmbedding(CONFIGS, mesh=mesh, packed_storage=True)
  SparseAdam().init(small, small.init(0))
