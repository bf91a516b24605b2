"""SparseCore path (docs/design.md §8): static-CSR transform, emulation
backend, mod-sharded planner/checkpoint, and the hardware-gated adapter.

The equivalence bar is BIT-exactness where the design promises it: the
emulated forward shares the TensorCore path's combine tail, so outputs
(and therefore losses) must be *identical* f32, not merely close; the
emulated grad apply reuses the audited compact_segments + apply_unique
pair, so a full train step matches the dense-gradient oracle to the same
tolerance the TensorCore sparse path does.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_embeddings_tpu.parallel import (DistributedEmbedding,
                                                 SparseAdagrad, SparseSGD,
                                                 TableConfig, create_mesh,
                                                 get_optimizer_state,
                                                 get_weights,
                                                 init_hybrid_train_state,
                                                 make_hybrid_train_step,
                                                 set_optimizer_state,
                                                 set_weights)
from distributed_embeddings_tpu.parallel import sparsecore
from distributed_embeddings_tpu.parallel.dist_embedding import _fused_lookup
from distributed_embeddings_tpu.parallel.planner import (ShardingPlan,
                                                         mod_slice_rows)


# ---------------------------------------------------------------- planner


def test_mod_plan_windows_and_padding():
  plan = ShardingPlan([TableConfig(100, 12, 'sum'),
                       TableConfig(16, 12, 'sum')],
                      world_size=4, strategy='basic',
                      row_slice_threshold=300, mod_sharding=True)
  assert plan.row_sliced == [True, False]
  shards = plan.shard_layout()[0]
  # four residue classes, stride 4, spanning the full table
  assert sorted(s[5] for s in shards) == [0, 1, 2, 3]
  assert all(s[6] == 100 and s[7] == 4 for s in shards)
  for g in plan.groups:
    # SC padding: rows_cap multiple of 8 (not the 128-lane pack gran),
    # natural storage always
    assert g.rows_cap % 8 == 0
    assert g.storage_pack == 1
    assert g.sc_padded_width == 16  # width 12 pads to the SC lane gran 8


def test_mod_slice_rows_counts():
  cfg = TableConfig(10, 4, 'sum')  # 40 elements; threshold 10 -> 4 shards
  assert mod_slice_rows(cfg, 10, 4) == [3, 3, 2, 2]
  assert sum(mod_slice_rows(cfg, 10, 4)) == 10
  assert mod_slice_rows(cfg, None, 4) == [10]


def test_mod_plan_forces_natural_storage():
  plan = ShardingPlan([TableConfig(64, 16, 'sum')] * 4, world_size=4,
                      mod_sharding=True, packed_storage=True)
  assert not plan.packed_storage
  assert all(g.storage_pack == 1 for g in plan.groups)


# ------------------------------------------------------------- transform


@pytest.mark.parametrize('seed', range(4))
def test_csr_builders_agree(seed):
  """The NumPy host builder (padded hardware layout) and the traced XLA
  builder (flat exact layout) must produce identical logical sections —
  same ids, same samples, same gains, partition by partition."""
  rng = np.random.default_rng(3000 + seed)
  rows_cap = int(rng.integers(8, 200))
  num_sc = int(rng.choice([1, 2, 4, 8]))
  n_cap, gb, h = (int(rng.integers(1, 4)), int(rng.integers(1, 12)),
                  int(rng.integers(1, 5)))
  combiner = str(rng.choice(['sum', 'mean']))
  routed = rng.integers(0, rows_cap + 4, size=(n_cap, gb, h)).astype(
      np.int32)  # includes sentinel-range values (>= rows_cap)
  host = sparsecore.build_csr_host(routed, rows_cap, num_sc, combiner)
  tr = sparsecore.csr_from_routed(jnp.asarray(routed), rows_cap, num_sc,
                                  combiner)
  ends = np.asarray(tr.row_pointers)
  starts = np.concatenate([[0], ends[:-1]])
  cap = host.max_ids_per_partition
  assert cap % 8 == 0
  assert host.dropped == 0
  for p in range(num_sc):
    n_p = ends[p] - starts[p]
    h0 = p * cap
    assert host.row_pointers[p] - h0 == n_p
    np.testing.assert_array_equal(
        host.embedding_ids[h0:h0 + n_p],
        np.asarray(tr.embedding_ids)[starts[p]:ends[p]])
    np.testing.assert_array_equal(
        host.sample_ids[h0:h0 + n_p],
        np.asarray(tr.sample_ids)[starts[p]:ends[p]])
    np.testing.assert_array_equal(
        host.gains[h0:h0 + n_p],
        np.asarray(tr.gains)[starts[p]:ends[p]])
    # padding tail of the section: sentinel ids, one-past samples, 0 gain
    assert (host.gains[h0 + n_p:h0 + cap] == 0).all()
  # an under-sized capacity truncates and REPORTS, never silently
  capped = sparsecore.build_csr_host(routed, rows_cap, num_sc, combiner,
                                     max_ids_per_partition=8)
  total_valid = int((routed < rows_cap).sum())
  kept = sum(
      int(capped.row_pointers[p] - p * capped.max_ids_per_partition)
      for p in range(num_sc))
  assert kept + capped.dropped == total_valid


@pytest.mark.parametrize('num_sc', [1, 2, 4])
def test_emulated_lookup_bit_exact_unit(num_sc):
  rng = np.random.default_rng(7)
  rows_cap, w = 40, 12  # width not a multiple of 8: storage stays natural
  routed = rng.integers(0, rows_cap + 2, size=(2, 6, 3)).astype(np.int32)
  table = rng.normal(size=(rows_cap, w)).astype(np.float32)
  for combiner in ('sum', 'mean'):
    got = sparsecore.emulated_lookup(jnp.asarray(table), jnp.asarray(routed),
                                     combiner, jnp.float32, num_sc)
    want = _fused_lookup(jnp.asarray(table), jnp.asarray(routed), combiner,
                         jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------- forward / train fuzz


def _random_setup(rng, world):
  configs = []
  n_tables = world + int(rng.integers(0, 3))
  for _ in range(n_tables):
    rows = int(rng.integers(8, 200))
    width = int(rng.choice([4, 8, 12, 16, 32]))
    configs.append(TableConfig(rows, width, str(rng.choice(['sum', 'mean']))))
  weights = [
      rng.normal(size=(c.input_dim, c.output_dim)).astype(np.float32)
      for c in configs
  ]
  sizes = [c.size for c in configs]
  row_thr = (int(rng.integers(min(sizes), max(sizes) + 1))
             if rng.random() < 0.7 else None)
  return configs, weights, row_thr


@pytest.mark.parametrize('seed', range(5))
def test_fuzz_forward_bit_exact_and_checkpoint(seed):
  """Fuzzed mod-sharded layouts: the sparsecore emulation forward must
  equal the TensorCore XLA forward on the SAME plan bit-exactly, and the
  mod-sharded checkpoint must round-trip into a contiguous plan and back."""
  rng = np.random.default_rng(4000 + seed)
  world = int(rng.choice([2, 4, 8]))
  mesh = create_mesh(jax.devices()[:world])
  configs, weights, row_thr = _random_setup(rng, world)
  num_sc = int(rng.choice([1, 2, 4]))
  kw = dict(mesh=mesh, row_slice=row_thr,
            strategy=str(rng.choice(['basic', 'memory_balanced'])))
  d_sc = DistributedEmbedding(configs, lookup_impl='sparsecore',
                              num_sc=num_sc, **kw)
  d_tc = DistributedEmbedding(configs, lookup_impl='xla',
                              mod_sharding=True, **kw)
  p_sc = set_weights(d_sc, weights)
  p_tc = set_weights(d_tc, weights)
  batch = world * int(rng.integers(1, 3))
  ids = []
  for c in configs:
    h = int(rng.integers(1, 5))
    x = rng.integers(0, c.input_dim, size=(batch, h)).astype(np.int32)
    if h > 1:
      x[rng.integers(0, batch), rng.integers(1, h)] = -1  # padding
    x[rng.integers(0, batch), 0] = c.input_dim + 1  # out-of-vocab
    ids.append(jnp.asarray(x))
  out_sc = d_sc.apply(p_sc, ids)
  out_tc = d_tc.apply(p_tc, ids)
  for i, (a, b) in enumerate(zip(out_sc, out_tc)):
    np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b),
        err_msg=f'seed {seed} input {i} (world {world}, num_sc {num_sc}, '
        f'row_thr {row_thr})')
  # mod-sharded save -> contiguous restore, and back
  globals_sc = get_weights(d_sc, p_sc)
  for w, b in zip(weights, globals_sc):
    np.testing.assert_array_equal(w, b)
  d_cont = DistributedEmbedding(configs, lookup_impl='auto', **kw)
  p_cont = set_weights(d_cont, globals_sc)
  for w, b in zip(weights, get_weights(d_cont, p_cont)):
    np.testing.assert_array_equal(w, b)
  p_back = set_weights(d_sc, get_weights(d_cont, p_cont))
  for a, b in zip(jax.tree.leaves(p_sc), jax.tree.leaves(p_back)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize('seed', range(4))
def test_fuzz_sparsecore_train_step(seed):
  """Full hybrid sparse train step with lookup_impl='sparsecore', on
  the faked 8-device mesh: a layer built with the SparseCore lookup
  trains through the sparse apply every layer takes, so the loss and the
  updated tables equal the XLA lookup's on the same plan bit for bit
  (shared combine tail, one apply), and one SGD step reproduces the
  dense-gradient oracle (SGD is linear) to the tolerance the TensorCore
  sparse path holds."""
  import optax
  rng = np.random.default_rng(5000 + seed)
  world = int(rng.choice([2, 4, 8]))
  mesh = create_mesh(jax.devices()[:world])
  configs, weights, row_thr = _random_setup(rng, world)
  adagrad = bool(rng.random() < 0.5)
  batch = world * 2
  ids = []
  for c in configs:
    x = rng.integers(0, c.input_dim, size=(batch, 3)).astype(np.int32)
    x[rng.integers(0, batch), rng.integers(1, 3)] = -1
    if rng.random() < 0.5:
      x[rng.integers(0, batch), 0] = c.input_dim + 2
    ids.append(x)
  total_w = sum(c.output_dim for c in configs)
  kernel = jnp.asarray(
      rng.standard_normal((total_w, 1)).astype(np.float32) * 0.1)
  labels = jnp.asarray(rng.integers(0, 2, (batch, 1)).astype(np.float32))
  lr = 0.3

  def head_loss_fn(dense_params, emb_outs, b):
    h = jnp.concatenate(list(emb_outs), axis=-1)
    return jnp.mean((h @ dense_params['kernel'] - b)**2)

  def run(lookup, opt, **extra):
    dist = DistributedEmbedding(configs, mesh=mesh, row_slice=row_thr,
                                lookup_impl=lookup, **extra)
    step = make_hybrid_train_step(dist, head_loss_fn, optax.sgd(lr), opt,
                                  donate=False)
    state = init_hybrid_train_state(dist, {
        'embedding': set_weights(dist, weights),
        'kernel': kernel
    }, optax.sgd(lr), opt)
    state, loss = step(state, [jnp.asarray(x) for x in ids], labels)
    return float(loss), get_weights(dist, state.params['embedding']), dist, \
        state

  opt = (SparseAdagrad if adagrad else SparseSGD)(learning_rate=lr)
  loss_sc, w_sc, dist_sc, state_sc = run('sparsecore', opt)
  loss_tc, w_tc, _, _ = run('xla', opt, mod_sharding=True)
  # identical plan + bit-exact forward => bit-equal loss, and the same
  # apply on the same residuals and cotangents => bit-equal tables
  assert loss_sc == loss_tc, (loss_sc, loss_tc)
  for t, (a, b) in enumerate(zip(w_sc, w_tc)):
    np.testing.assert_array_equal(
        a, b, err_msg=f'seed {seed} table {t} (world {world}, '
        f'adagrad {adagrad}, row_thr {row_thr})')
  if adagrad:
    return
  # SGD: dense-gradient oracle (as in test_fuzz_equivalence)
  def loss_fn(ws):
    outs = []
    for t, c in enumerate(configs):
      x = jnp.asarray(ids[t])
      valid = x >= 0
      safe = jnp.clip(x, 0, c.input_dim - 1)
      out = jnp.zeros((batch, c.output_dim))
      for h in range(3):
        out = out + jnp.where(valid[:, h, None], ws[t][safe[:, h]], 0)
      if c.combiner == 'mean':
        out = out / jnp.maximum(jnp.sum(valid, axis=1), 1)[:, None]
      outs.append(out)
    h = jnp.concatenate(outs, axis=-1)
    return jnp.mean((h @ kernel - labels)**2)

  g = jax.grad(loss_fn)([jnp.asarray(w) for w in weights])
  for t in range(len(configs)):
    want = weights[t] - lr * np.asarray(g[t])
    np.testing.assert_allclose(w_sc[t], want, rtol=3e-5, atol=3e-6,
                               err_msg=f'seed {seed} table {t}')


def test_mod_checkpoint_roundtrip_with_optimizer_state():
  """Sparse-optimizer state saved from a mod-sharded plan restores into
  a contiguous plan (and back) through the global canonical layout."""
  import optax
  rng = np.random.default_rng(11)
  world = 4
  mesh = create_mesh(jax.devices()[:world])
  configs = [TableConfig(50, 8, 'sum'), TableConfig(40, 8, 'sum')]
  weights = [
      rng.normal(size=(c.input_dim, c.output_dim)).astype(np.float32)
      for c in configs
  ]
  ids = [
      jnp.asarray(rng.integers(0, c.input_dim, size=(world * 2, 2)).astype(
          np.int32)) for c in configs
  ]
  labels = jnp.asarray(np.ones((world * 2, 1), np.float32))
  lr = 0.1

  def head_loss_fn(dense_params, emb_outs, b):
    h = jnp.concatenate(list(emb_outs), axis=-1)
    return jnp.mean((h @ dense_params['kernel'] - b)**2)

  kernel = jnp.asarray(
      rng.standard_normal((16, 1)).astype(np.float32) * 0.1)

  def one_step(dist):
    opt = SparseAdagrad(learning_rate=lr)
    step = make_hybrid_train_step(dist, head_loss_fn, optax.sgd(lr), opt,
                                  donate=False)
    state = init_hybrid_train_state(dist, {
        'embedding': set_weights(dist, weights),
        'kernel': kernel
    }, optax.sgd(lr), opt)
    state, _ = step(state, ids, labels)
    return state

  d_mod = DistributedEmbedding(configs, mesh=mesh, row_slice=100,
                               mod_sharding=True)
  d_cont = DistributedEmbedding(configs, mesh=mesh, row_slice=100)
  s_mod = one_step(d_mod)
  s_cont = one_step(d_cont)
  # identical global views from both layouts
  w_mod = get_weights(d_mod, s_mod.params['embedding'])
  w_cont = get_weights(d_cont, s_cont.params['embedding'])
  for a, b in zip(w_mod, w_cont):
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
  st_mod = get_optimizer_state(d_mod, s_mod.opt_state[1])
  st_cont = get_optimizer_state(d_cont, s_cont.opt_state[1])
  for a, b in zip(st_mod, st_cont):
    assert a.keys() == b.keys()
    for k in a:
      np.testing.assert_allclose(a[k], b[k], rtol=1e-6, atol=1e-7)
  # restore mod-saved state into the contiguous layer and back
  restored = set_optimizer_state(d_cont, s_cont.opt_state[1], st_mod)
  rt = get_optimizer_state(d_cont, restored)
  for a, b in zip(rt, st_mod):
    for k in a:
      np.testing.assert_array_equal(a[k], b[k])
  restored_mod = set_optimizer_state(d_mod, s_mod.opt_state[1], st_cont)
  rt2 = get_optimizer_state(d_mod, restored_mod)
  for a, b in zip(rt2, st_cont):
    for k in a:
      np.testing.assert_array_equal(a[k], b[k])


# ------------------------------------------- host preprocessing + capacity


def test_host_preprocess_and_calibration():
  world = 4
  mesh = create_mesh(jax.devices()[:world])
  rng = np.random.default_rng(13)
  configs = [TableConfig(120, 16, 'sum'), TableConfig(60, 16, 'mean'),
             TableConfig(40, 8, 'sum')]
  dist = DistributedEmbedding(configs, mesh=mesh, lookup_impl='sparsecore',
                              row_slice=500)
  cats = [
      rng.integers(0, c.input_dim, size=(world * 4, 3)).astype(np.int32)
      for c in configs
  ]
  caps = sparsecore.calibrate_max_ids_per_partition(
      dist, [jnp.asarray(c) for c in cats])
  assert len(caps) == len(dist.plan.groups)
  assert all(c % 8 == 0 and c >= 8 for c in caps)
  # calibrated caps must hold the calibrating batch without drops
  csrs = sparsecore.preprocess_batch_host(dist, cats,
                                          max_ids_per_partition=caps)
  assert sum(c.dropped for lst in csrs.values() for c in lst) == 0
  # every valid id of every stream lands in some section
  stats = sparsecore.measure_preprocess_ms(dist, cats, repeats=2)
  assert stats['csr_preprocess_ms'] >= 0
  assert stats['csr_dropped'] == 0
  assert stats['csr_preprocess_ids'] == sum(c.size for c in cats)


def test_host_preprocess_matches_traced_routing():
  """The NumPy routing twin must agree with the traced routing: feeding
  the host CSR's per-device totals against the distributed forward's
  residual ids."""
  world = 2
  mesh = create_mesh(jax.devices()[:world])
  rng = np.random.default_rng(17)
  configs = [TableConfig(30, 8, 'sum'), TableConfig(20, 8, 'sum')]
  dist = DistributedEmbedding(configs, mesh=mesh, lookup_impl='sparsecore',
                              row_slice=100)
  cats = [
      rng.integers(0, c.input_dim, size=(world * 3, 2)).astype(np.int32)
      for c in configs
  ]
  params = dist.init(0)
  _, residuals, (_, hotness) = dist.forward_with_residuals(
      params, [jnp.asarray(c) for c in cats])
  subs = dist._subgroups(hotness)
  csrs = sparsecore.preprocess_batch_host(dist, cats)
  num_sc = dist.plan.num_sc
  for si, sub in enumerate(subs):
    res = np.asarray(residuals[si])  # [D, n_cap, GB, h]
    for dev in range(world):
      g = dist.plan.groups[sub.gi]
      valid = res[dev][res[dev] < g.rows_cap]
      host = csrs[(sub.gi, sub.hotness)][dev]
      kept = sum(
          int(host.row_pointers[p] - p * host.max_ids_per_partition)
          for p in range(num_sc))
      assert kept == valid.size
      # same multiset of fused rows
      rows_host = []
      for p in range(num_sc):
        h0 = p * host.max_ids_per_partition
        n_p = host.row_pointers[p] - h0
        rows_host.append(host.embedding_ids[h0:h0 + n_p] * num_sc + p)
      np.testing.assert_array_equal(
          np.sort(np.concatenate(rows_host)), np.sort(valid))


def test_group_supported_gates():
  f32 = jax.ShapeDtypeStruct((64, 32), jnp.float32)
  assert sparsecore.group_supported(f32, 'sum', 4)
  assert sparsecore.group_supported(f32, 'mean', 1)
  assert not sparsecore.group_supported(f32, None, 1)  # pass-through
  wide = jax.ShapeDtypeStruct((64, 384), jnp.float32)
  assert not sparsecore.group_supported(wide, 'sum', 4)
  bf16 = jax.ShapeDtypeStruct((64, 32), jnp.bfloat16)
  assert not sparsecore.group_supported(bf16, 'sum', 4)


def test_combiner_none_falls_back_and_matches():
  """A combiner=None group under lookup_impl='sparsecore' takes the
  TensorCore path per the §8 contract and still produces exact results."""
  world = 2
  mesh = create_mesh(jax.devices()[:world])
  rng = np.random.default_rng(23)
  configs = [TableConfig(40, 16, None), TableConfig(40, 16, 'sum')]
  weights = [
      rng.normal(size=(c.input_dim, c.output_dim)).astype(np.float32)
      for c in configs
  ]
  dist = DistributedEmbedding(configs, mesh=mesh, lookup_impl='sparsecore')
  params = set_weights(dist, weights)
  ids = [
      jnp.asarray(rng.integers(0, 40, size=(world * 2,)).astype(np.int32)),
      jnp.asarray(rng.integers(0, 40, size=(world * 2, 3)).astype(np.int32)),
  ]
  outs = dist.apply(params, ids)
  np.testing.assert_allclose(
      np.asarray(outs[0]), weights[0][np.asarray(ids[0])], rtol=1e-6)
