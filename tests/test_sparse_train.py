"""Sparse (O(nnz)) embedding training path vs dense-autodiff oracles.

The reference validates gradients by comparing weights after one optimizer
step between a distributed and a single-process model
(`/root/reference/tests/dist_model_parallel_test.py:162-171`).  Here the
oracle is the *dense autodiff* path over the same DistributedEmbedding: the
sparse scatter updates (parallel/sparse.py) must land on exactly the same
weights.
"""

import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

from distributed_embeddings_tpu.parallel import (DistributedEmbedding,
                                                 SparseAdagrad, SparseAdam,
                                                 SparseSGD, TableConfig,
                                                 TrainState, create_mesh,
                                                 dedup_rows,
                                                 init_hybrid_train_state,
                                                 make_hybrid_train_step)

WORLD = 8
GLOBAL_BATCH = 16
LR = 0.5

SPECS = [
    # (rows, width, combiner, hotness): mixed widths/combiners so fusion,
    # hotness classes and mean scaling are all exercised
    (40, 4, None, 1),
    (30, 4, 'sum', 3),
    (50, 8, 'mean', 3),
    (25, 4, 'sum', 1),
    (60, 8, 'sum', 2),
    (35, 4, None, 1),
    (45, 8, 'mean', 2),
    (55, 4, 'sum', 3),
    (20, 4, 'sum', 2),
]


def build(dp_input=True, column_slice_threshold=None, unique_ids=False,
          seed=0):
  mesh = create_mesh(jax.devices()[:WORLD])
  specs = SPECS
  if unique_ids:
    # grow vocabularies so a whole batch of distinct ids fits
    specs = [(max(r, GLOBAL_BATCH * h), w, c, h) for r, w, c, h in SPECS]
  configs = [TableConfig(r, w, c) for r, w, c, _ in specs]
  dist = DistributedEmbedding(configs,
                              strategy='memory_balanced',
                              column_slice_threshold=column_slice_threshold,
                              dp_input=dp_input,
                              mesh=mesh)
  rng = np.random.default_rng(seed)
  params_emb = dist.init(0)

  def gen_inputs():
    inputs = []
    for rows, width, combiner, hot in specs:
      if unique_ids:
        # distinct ids per batch: scatter and dedup semantics coincide
        ids = rng.choice(rows, size=GLOBAL_BATCH * hot,
                         replace=False).astype(np.int32)
        ids = ids.reshape(GLOBAL_BATCH, hot)
      else:
        ids = rng.integers(0, rows,
                           size=(GLOBAL_BATCH, hot)).astype(np.int32)
      if combiner is not None and hot > 1 and not unique_ids:
        lengths = rng.integers(1, hot + 1, size=(GLOBAL_BATCH,))
        ids = np.where(
            np.arange(hot)[None, :] < lengths[:, None], ids, -1)
      inputs.append(jnp.asarray(ids))
    return inputs

  total_width = sum(w for _, w, _, _ in specs)
  kernel = jnp.asarray(
      rng.normal(size=(total_width, 1)).astype(np.float32))
  labels = jnp.asarray(
      rng.normal(size=(GLOBAL_BATCH, 1)).astype(np.float32))

  def head_loss_fn(dense_params, emb_outs, batch):
    labels = batch
    x = jnp.concatenate(list(emb_outs), axis=1)
    pred = x @ dense_params['kernel']
    return jnp.mean((pred - labels)**2)

  return dist, params_emb, gen_inputs, kernel, labels, head_loss_fn


def dense_grads(dist, params, kernel, cats, labels, head_loss_fn):
  """Oracle: dense autodiff grads for tables and head."""

  def loss(p):
    outs = dist.apply(p['embedding'], cats)
    return head_loss_fn({'kernel': p['kernel']}, tuple(outs), labels)

  return jax.grad(loss)({'embedding': params, 'kernel': kernel})


def test_forward_with_residuals_matches_apply():
  dist, params, gen_inputs, *_ = build()
  cats = gen_inputs()
  ref = dist.apply(params, cats)
  outs, residuals, (batch, hotness) = dist.forward_with_residuals(params, cats)
  assert len(outs) == len(ref)
  for a, b in zip(ref, outs):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  assert len(residuals) > 0
  for res in residuals:
    assert res.shape[0] == WORLD and res.ndim == 4


def test_forward_with_residuals_matches_apply_mp_input():
  dist, params, gen_inputs, *_ = build(dp_input=False)
  # worker-order inputs at global batch
  rng = np.random.default_rng(3)
  flat_ids = [i for dev in dist.plan.input_ids_list for i in dev]
  cats = []
  for i in flat_ids:
    rows, width, combiner, hot = SPECS[i]
    cats.append(
        jnp.asarray(
            rng.integers(0, rows, size=(GLOBAL_BATCH, hot)).astype(
                np.int32)))
  ref = dist.apply(params, cats)
  outs, residuals, (batch, hotness) = dist.forward_with_residuals(params, cats)
  for a, b in zip(ref, outs):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize('column_slice_threshold', [None, 50 * 8 // 2])
def test_sparse_sgd_matches_dense(column_slice_threshold):
  dist, params_emb, gen_inputs, kernel, labels, head_loss_fn = build(
      column_slice_threshold=column_slice_threshold)
  cats = gen_inputs()

  grads = dense_grads(dist, params_emb, kernel, cats, labels, head_loss_fn)
  expected_tables = jax.tree.map(lambda p, g: p - LR * g, params_emb,
                                 grads['embedding'])
  expected_kernel = kernel - LR * grads['kernel']

  step = make_hybrid_train_step(dist, head_loss_fn, optax.sgd(LR),
                                SparseSGD(LR), donate=False)
  state = init_hybrid_train_state(dist, {
      'embedding': params_emb,
      'kernel': kernel
  }, optax.sgd(LR), SparseSGD(LR))
  state, loss = step(state, cats, labels)

  assert np.isfinite(float(loss))
  np.testing.assert_allclose(np.asarray(state.params['kernel']),
                             np.asarray(expected_kernel), rtol=2e-5,
                             atol=2e-6)
  for k in params_emb:
    np.testing.assert_allclose(np.asarray(state.params['embedding'][k]),
                               np.asarray(expected_tables[k]), rtol=2e-5,
                               atol=2e-6)


def _keras_adagrad_dense(params, grads, acc, lr, eps=1e-7):
  new_acc = jax.tree.map(lambda a, g: a + g * g, acc, grads)
  new_p = jax.tree.map(lambda p, g, a: p - lr * g / jnp.sqrt(a + eps),
                       params, grads, new_acc)
  return new_p, new_acc


def test_sparse_adagrad_dedup_matches_dense():
  dist, params_emb, gen_inputs, kernel, labels, head_loss_fn = build()
  cats = gen_inputs()
  opt = SparseAdagrad(learning_rate=LR, initial_accumulator_value=0.1,
                      dedup=True)

  # oracle: two keras-adagrad steps on dense grads
  p = params_emb
  acc = jax.tree.map(lambda x: jnp.full_like(x, 0.1), params_emb)
  for _ in range(2):
    g = dense_grads(dist, p, kernel, cats, labels,
                    head_loss_fn)['embedding']
    p, acc = _keras_adagrad_dense(p, g, acc, LR)

  step = make_hybrid_train_step(dist, head_loss_fn, optax.sgd(LR), opt,
                                donate=False)
  state = init_hybrid_train_state(dist, {
      'embedding': params_emb,
      'kernel': kernel
  }, optax.sgd(LR), opt)
  # freeze the head so table grads stay identical across the two steps'
  # oracles (the oracle above reuses the same kernel each step)
  state = TrainState({'embedding': state.params['embedding'],
                      'kernel': kernel}, state.opt_state, state.step)
  for _ in range(2):
    new_state, _ = step(state, cats, labels)
    state = TrainState({'embedding': new_state.params['embedding'],
                        'kernel': kernel}, new_state.opt_state,
                       new_state.step)

  for k in params_emb:
    np.testing.assert_allclose(np.asarray(state.params['embedding'][k]),
                               np.asarray(p[k]), rtol=3e-5, atol=3e-6)


def test_sparse_adagrad_scatter_matches_dedup_on_unique_ids():
  # with no duplicate ids in the batch the fast scatter path must agree
  # with the exact dedup path
  results = []
  for dedup in (False, True):
    dist, params_emb, gen_inputs, kernel, labels, head_loss_fn = build(
        unique_ids=True, seed=11)
    cats = gen_inputs()
    opt = SparseAdagrad(learning_rate=LR, dedup=dedup)
    step = make_hybrid_train_step(dist, head_loss_fn, optax.sgd(LR), opt,
                                  donate=False)
    state = init_hybrid_train_state(dist, {
        'embedding': params_emb,
        'kernel': kernel
    }, optax.sgd(LR), opt)
    state, _ = step(state, cats, labels)
    results.append(jax.tree.map(np.asarray, state.params['embedding']))
  for k in results[0]:
    np.testing.assert_allclose(results[0][k], results[1][k], rtol=1e-5,
                               atol=1e-6)


def test_sparse_adam_runs_and_is_lazy():
  dist, params_emb, gen_inputs, kernel, labels, head_loss_fn = build()
  cats = gen_inputs()
  opt = SparseAdam(learning_rate=0.1)
  step = make_hybrid_train_step(dist, head_loss_fn, optax.sgd(LR), opt,
                                donate=False)
  state = init_hybrid_train_state(dist, {
      'embedding': params_emb,
      'kernel': kernel
  }, optax.sgd(LR), opt)
  new_state, loss = step(state, cats, labels)
  assert np.isfinite(float(loss))

  # laziness: rows never looked up keep zero moments and unchanged weights
  grads = dense_grads(dist, params_emb, kernel, cats, labels, head_loss_fn)
  for k in params_emb:
    untouched = np.asarray(jnp.all(grads['embedding'][k] == 0, axis=-1))
    m = np.asarray(new_state.opt_state[1][k]['m'])
    assert np.all(m[untouched] == 0)
    before = np.asarray(params_emb[k])
    after = np.asarray(new_state.params['embedding'][k])
    np.testing.assert_array_equal(after[untouched], before[untouched])
    # and at least something moved
    assert not np.array_equal(before, after)


def test_dedup_rows_unit():
  rng = np.random.default_rng(0)
  n, w, vocab = 64, 5, 10
  ids = rng.integers(0, vocab, size=(n,)).astype(np.int32)
  g = rng.normal(size=(n, w)).astype(np.float32)
  uids, tg = jax.jit(lambda i, x: dedup_rows(i, x, sentinel=vocab))(ids, g)
  uids, tg = np.asarray(uids), np.asarray(tg)
  dense = np.zeros((vocab, w), np.float32)
  np.add.at(dense, ids, g)
  seen = uids[uids < vocab]
  assert sorted(seen.tolist()) == sorted(set(ids.tolist()))
  out = np.zeros((vocab, w), np.float32)
  out[seen] = tg[uids < vocab]
  np.testing.assert_allclose(out, dense, rtol=1e-5, atol=1e-6)


def test_compact_segments_unit():
  from distributed_embeddings_tpu.parallel.sparse import compact_segments
  rng = np.random.default_rng(3)
  n, w, vocab = 256, 4, 23
  ids = rng.integers(0, vocab, size=(n,)).astype(np.int32)
  ids[5:9] = vocab  # sentinel padding rows
  g = rng.normal(size=(n, w)).astype(np.float32)
  cap = vocab + 2
  uids, sum_g, sum_sq, nuniq = jax.jit(
      lambda i, x: compact_segments(i, x, cap, sentinel=vocab,
                                    with_sq=True))(ids, g)
  uids, sum_g, sum_sq = map(np.asarray, (uids, sum_g, sum_sq))
  dense = np.zeros((vocab, w), np.float32)
  np.add.at(dense, ids[ids < vocab], g[ids < vocab])
  dense_sq = np.zeros((vocab, w), np.float32)
  np.add.at(dense_sq, ids[ids < vocab], g[ids < vocab]**2)
  keep = uids < vocab
  assert sorted(uids[keep].tolist()) == sorted(set(ids[ids < vocab].tolist()))
  out = np.zeros((vocab, w), np.float32)
  out[uids[keep]] = sum_g[keep]
  np.testing.assert_allclose(out, dense, rtol=1e-4, atol=1e-5)
  out_sq = np.zeros((vocab, w), np.float32)
  out_sq[uids[keep]] = sum_sq[keep]
  np.testing.assert_allclose(out_sq, dense_sq, rtol=1e-4, atol=1e-5)
  # the sentinel occupies one segment; all real uniques must fit
  assert int(nuniq) == len(set(ids[ids < vocab].tolist())) + 1


# --- the dedup as it stood before ISSUE 28, frozen: its sorts dropped what
# they ordered and every per-slot value came back through an index gather
# (``ids[order]``, ``key[order2]``, ``sid[order2]``, ``first_pos[order2]``,
# ``csum[fp - 1]``; ``_lane_pack`` gathered through ``arange``).  The
# program's functions must return the same bits by their shorter road.


def _frozen_compact_segments(ids, grads, cap, sentinel, with_sq=False,
                             order=None, g_index=None, max_seg=None):
  from distributed_embeddings_tpu.parallel.sparse import (_seg_fold_bounded,
                                                          _sorted_segments)
  n = ids.shape[0]
  if order is None:
    order = jnp.argsort(ids)
  sid = ids[order]
  sg = (grads[order] if g_index is None else
        grads[jnp.take(g_index, order)]).astype(jnp.float32)
  is_first, is_last, first_pos, _ = _sorted_segments(sid)
  rank = jnp.cumsum(is_first.astype(jnp.int32)) - 1
  num_unique = rank[-1] + 1
  key = jnp.where(is_last, rank, n)
  order2 = jnp.argsort(key)[:cap]
  valid = key[order2] < n
  uids = jnp.where(valid, sid[order2], sentinel)
  fp = first_pos[order2]
  if max_seg is not None:
    sum_g = jnp.where(valid[:, None],
                      _seg_fold_bounded(sg, first_pos, max_seg)[order2], 0.0)
    sum_sq = (jnp.where(
        valid[:, None],
        _seg_fold_bounded(sg * sg, first_pos, max_seg)[order2], 0.0)
              if with_sq else None)
    return uids, sum_g, sum_sq, num_unique

  def seg_tot(csum):
    hi = csum[order2]
    lo = jnp.where((fp > 0)[:, None], csum[jnp.maximum(fp - 1, 0)], 0.0)
    return jnp.where(valid[:, None], hi - lo, 0.0)

  sum_g = seg_tot(jnp.cumsum(sg, axis=0))
  sum_sq = seg_tot(jnp.cumsum(sg * sg, axis=0)) if with_sq else None
  return uids, sum_g, sum_sq, num_unique


def _frozen_lane_pack(uids, sum_g, sum_sq, pack, rows_cap, exact=False):
  from distributed_embeddings_tpu.ops.pallas_segwalk import (lane_expand,
                                                             packed_ids)
  c, w = sum_g.shape
  lanes = pack * w
  psent = rows_cap // pack
  pids, slot = packed_ids(uids, pack, rows_cap)
  g_lanes = lane_expand(sum_g, slot, pack)
  payload = (g_lanes if sum_sq is None else jnp.concatenate(
      [g_lanes, lane_expand(sum_sq, slot, pack)], axis=1))
  pids_c, pay_c, _, _ = _frozen_compact_segments(
      pids, payload, min(c, psent + 2), psent,
      order=jnp.arange(c, dtype=jnp.int32),
      max_seg=pack if exact else None)
  return (pids_c, pay_c[:, :lanes],
          pay_c[:, lanes:] if sum_sq is not None else None)


def _dedup_stream(seed, n, w, vocab, sentinels=0, presorted=False,
                  max_seg=None):
  """An update stream: ``n`` ids under ``vocab`` (each at most ``max_seg``
  times where that is given), ``sentinels`` of them padding."""
  rng = np.random.default_rng(seed)
  if max_seg is None:
    ids = rng.integers(0, vocab, size=(n,))
  else:
    ids = rng.permutation(np.repeat(np.arange(vocab), max_seg))[:n]
  ids = ids.astype(np.int32)
  ids[rng.choice(n, size=sentinels, replace=False)] = vocab
  if presorted:
    ids = np.sort(ids)
  return ids, rng.normal(size=(n, w)).astype(np.float32)


def _assert_same_outputs(got, want):
  for a, b in zip(got, want):
    assert (a is None) == (b is None)
    if a is not None:
      a, b = np.asarray(a), np.asarray(b)
      assert a.dtype == b.dtype and np.array_equal(a, b)


# (n, w, vocab, cap or its distance from the unique count, keywords)
_COMPACT_CASES = {
    'sentinels-w8': (512, 8, 40, 42, dict(sentinels=9)),
    'cap-below-w16': (512, 16, 90, 'uniq-7', dict(sentinels=3)),
    'cap-at-w16': (512, 16, 90, 'uniq+0', dict(sentinels=3)),
    'cap-above-w16': (512, 16, 90, 'uniq+5', dict()),
    'cap-over-n-w8': (64, 8, 500, 600, dict(sentinels=2)),
    'sq-w8': (512, 8, 40, 42, dict(sentinels=9, with_sq=True)),
    'sq-cap-below-w128': (384, 128, 70, 'uniq-20',
                          dict(sentinels=5, with_sq=True)),
    'g-index-w16': (512, 16, 40, 42, dict(sentinels=4, g_index=True,
                                          with_sq=True)),
    'max-seg-w8': (256, 8, 100, 102, dict(sentinels=6, max_seg=3)),
    'max-seg-sq-w128': (256, 128, 100, 'uniq-4',
                        dict(sentinels=6, max_seg=3, with_sq=True)),
    'presorted-w16': (512, 16, 40, 42, dict(sentinels=9, presorted=True,
                                            with_sq=True)),
    'w128': (384, 128, 70, 72, dict(sentinels=5)),
    'one-segment-w8': (128, 8, 1, 3, dict(with_sq=True)),
}


@pytest.mark.parametrize('case', sorted(_COMPACT_CASES))
def test_compact_segments_keeps_the_frozen_formulations_bits(case):
  """ISSUE 28 changes how the dedup moves its values and none of them:
  every output equal to the bit, ``np.array_equal`` and no tolerance."""
  from distributed_embeddings_tpu.parallel.sparse import compact_segments
  n, w, vocab, cap, kw = _COMPACT_CASES[case]
  kw = dict(kw)
  ids, g = _dedup_stream(
      sum(map(ord, case)), n, w, vocab, sentinels=kw.pop('sentinels', 0),
      presorted=kw.pop('presorted', False), max_seg=kw.get('max_seg'))
  if isinstance(cap, str):
    cap = len(set(ids.tolist())) + int(cap[4:])
  if kw.pop('g_index', False):
    # compact payload rows, each stream position naming one of them
    g_index = np.random.default_rng(1).integers(0, n // 4, size=(n,))
    kw['g_index'] = jnp.asarray(g_index.astype(np.int32))
    g = g[:n // 4]
  got = jax.jit(lambda i, x: compact_segments(i, x, cap, vocab, **kw))(ids, g)
  want = jax.jit(
      lambda i, x: _frozen_compact_segments(i, x, cap, vocab, **kw))(ids, g)
  assert got[0].shape == (min(cap, n),)
  _assert_same_outputs(got, want)


@pytest.mark.parametrize('exact', [False, True])
@pytest.mark.parametrize('with_sq', [False, True])
@pytest.mark.parametrize('w,rows_cap,n', [(8, 1600, 2048), (16, 4096, 300),
                                          (64, 512, 1024)])
def test_lane_pack_keeps_the_frozen_formulations_bits(w, rows_cap, n,
                                                      with_sq, exact):
  """``_lane_pack`` on what the outer compaction hands it (ascending
  ids, sentinels last), whether the packed capacity shrinks the buffer
  (``rows_cap // pack + 2 < c``) or not."""
  from distributed_embeddings_tpu.parallel.sparse import (_lane_pack,
                                                          compact_segments)
  pack = 128 // w
  ids, g = _dedup_stream(w + n, n, w, rows_cap, sentinels=7)
  uids, sum_g, sum_sq, _ = jax.jit(lambda i, x: compact_segments(
      i, x, min(n, rows_cap + 2), rows_cap, with_sq=with_sq))(ids, g)
  got = jax.jit(lambda *a: _lane_pack(*a, pack, rows_cap, exact=exact))(
      uids, sum_g, sum_sq)
  want = jax.jit(
      lambda *a: _frozen_lane_pack(*a, pack, rows_cap, exact=exact))(
          uids, sum_g, sum_sq)
  assert got[0].shape == (min(uids.shape[0], rows_cap // pack + 2),)
  _assert_same_outputs(got, want)


def _gathers(jaxpr, from_iota=()):
  """``(gathers, gathers indexed by an iota alone, which outputs are an
  iota alone)`` of a jaxpr, nested calls included.  A value is "an iota
  alone" when nothing but ``iota``s and literals feeds it: a gather
  through one is a copy, or a shift, written as a permutation."""
  from jax.extend import core as jex_core
  iota = {v for v, f in zip(jaxpr.invars, from_iota) if f}
  known = lambda v: isinstance(v, jex_core.Literal) or v in iota
  total = by_iota = 0
  for eqn in jaxpr.eqns:
    flags = [not isinstance(v, jex_core.Literal) and v in iota
             for v in eqn.invars]
    inner = [p for p in eqn.params.values() if hasattr(p, 'jaxpr')]
    if eqn.primitive.name == 'gather':
      total += 1
      by_iota += flags[1]
    if len(inner) == 1 and len(inner[0].jaxpr.invars) == len(eqn.invars):
      t, b, outs = _gathers(inner[0].jaxpr, flags)
      total, by_iota = total + t, by_iota + b
    else:
      outs = [eqn.primitive.name == 'iota' or
              (any(flags) and all(map(known, eqn.invars)))] * len(eqn.outvars)
    iota |= {v for v, f in zip(eqn.outvars, outs) if f}
  return total, by_iota, [known(v) and not isinstance(v, jex_core.Literal)
                          for v in jaxpr.outvars]


@pytest.mark.parametrize('with_sq,frozen_count', [(False, 7), (True, 9)])
def test_compact_segments_gathers_only_payloads(with_sq, frozen_count):
  """The structural counter of ISSUE 28: the traced compaction holds one
  gather for the payload in sorted order and one per running sum for
  ``hi``.  Whoever writes ``x[order]`` after an ``argsort`` here again
  (20 ms a step for an int32 stream of 2.9 M on v5e) fails this."""
  from distributed_embeddings_tpu.parallel.sparse import compact_segments
  ids, g = _dedup_stream(0, 512, 16, 40, sentinels=9)
  trace = lambda f: jax.make_jaxpr(
      lambda i, x: f(i, x, 42, 40, with_sq=with_sq))(ids, g)
  assert _gathers(trace(_frozen_compact_segments).jaxpr)[0] == frozen_count
  assert _gathers(trace(compact_segments).jaxpr)[0] == 2 + with_sq
  # ``hi`` and ``lo`` are windows of one gather that both start past its
  # row 0: a window from row 0 is a bitcast of the buffer, and beside it
  # the v5e compiler wrote ``hi - lo`` over ``hi`` while still reading
  # it one slot behind (PERF.md, PR 28)
  starts = re.findall(r'f32\[\d+,16\] = slice\[[^\]]*start_indices=\((\d+), 0\)',
                      str(trace(compact_segments)))
  assert sorted(starts) == sorted(['1', '2'] * (1 + with_sq))
  text = jax.jit(lambda i, x: compact_segments(
      i, x, 42, 40, with_sq=with_sq)).lower(ids, g).as_text()
  assert text.count('"stablehlo.gather"(') == 2 + with_sq


def test_lane_pack_gathers_through_no_iota():
  """``_lane_pack``'s stream arrives sorted: it enters the compaction
  past the sort, and nothing is gathered through ``arange`` (the frozen
  formulation did, twice, and once more for ``lo``)."""
  from distributed_embeddings_tpu.parallel.sparse import _lane_pack
  uids = jnp.arange(300, dtype=jnp.int32)
  g = jnp.ones((300, 16), jnp.float32)
  trace = lambda f: _gathers(jax.make_jaxpr(
      lambda u, x: f(u, x, x, 8, 512))(uids, g).jaxpr)[:2]
  assert trace(_frozen_lane_pack) == (7, 2)
  assert trace(_lane_pack) == (1, 0)


def _while_as_cond(cond_fun, body_fun, init):
  """The overflow correction as it stood before ISSUE 25: the SAME body
  under a two-branch ``lax.cond`` whose other branch is the identity.
  Only valid for the zero-or-one-trip loop ``_dedup_and_apply`` builds."""
  return jax.lax.cond(cond_fun(init), body_fun, lambda c: c, init)


def _overflow_step(monkeypatch, form, opt_fn, seed, donate=False, steps=1):
  """``steps`` hybrid steps on a fresh ``build(seed)`` (a fresh layer, so
  no traced function is shared between forms) with the correction in its
  ``form``.  Returns ``(state, jaxpr text of the step)``."""
  dist, params_emb, gen_inputs, kernel, labels, head_loss_fn = build(
      seed=seed)
  cats = gen_inputs()
  opt = opt_fn(dist, cats)
  with monkeypatch.context() as m:
    if form == 'cond':
      m.setattr(jax.lax, 'while_loop', _while_as_cond)
    step = make_hybrid_train_step(dist, head_loss_fn, optax.sgd(LR), opt,
                                  donate=donate)
    state = init_hybrid_train_state(
        dist, {'embedding': jax.tree.map(jnp.copy, params_emb),
               'kernel': jnp.copy(kernel)}, optax.sgd(LR), opt)
    text = str(step.jitted.trace(state, cats, labels).jaxpr)
    for _ in range(steps):
      state, loss = step(state, cats, labels)
    assert np.isfinite(float(loss))
  return state, text


def _assert_same_bits(a, b):
  """Tables AND sparse optimizer state, bit for bit."""
  for x, y in zip(jax.tree.leaves((a.params['embedding'], a.opt_state[1])),
                  jax.tree.leaves((b.params['embedding'], b.opt_state[1])),
                  strict=True):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _check_overflow_forms(monkeypatch, check, opt_fn, seed):
  """``check='cond_form'``: the zero-or-one-trip loop gives the bits of
  the two-branch cond it replaced.  ``check='donated'``: two steps on a
  donated state (the loop updates the shards in place) give the bits of
  two steps on a kept one."""
  got, text = _overflow_step(monkeypatch, 'loop', opt_fn, seed,
                             donate=check == 'donated', steps=2)
  # the construct under test is in the program, and in its new form
  assert ' while[' in text and ' cond[' not in text
  want, wtext = _overflow_step(
      monkeypatch, 'cond' if check == 'cond_form' else 'loop', opt_fn,
      seed, steps=2)
  if check == 'cond_form':
    assert ' cond[' in wtext and ' while[' not in wtext
  _assert_same_bits(got, want)


def _check_overflow_oracle(opt_fn, seed):
  """One step against the dense keras-adagrad oracle (dedup=True -> the
  oracle's sum-then-square semantics)."""
  dist, params_emb, gen_inputs, kernel, labels, head_loss_fn = build(seed=seed)
  cats = gen_inputs()
  opt = opt_fn(dist, cats)
  g = dense_grads(dist, params_emb, kernel, cats, labels,
                  head_loss_fn)['embedding']
  acc0 = jax.tree.map(lambda x: jnp.full_like(x, 0.1), params_emb)
  want, _ = _keras_adagrad_dense(params_emb, g, acc0, LR)

  step = make_hybrid_train_step(dist, head_loss_fn, optax.sgd(LR), opt,
                                donate=False)
  state = init_hybrid_train_state(dist, {
      'embedding': params_emb,
      'kernel': kernel
  }, optax.sgd(LR), opt)
  state, loss = step(state, cats, labels)
  assert np.isfinite(float(loss))
  for k in params_emb:
    np.testing.assert_allclose(np.asarray(state.params['embedding'][k]),
                               np.asarray(want[k]), rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize('frac,check', [(0.02, 'oracle'), (1.0, 'oracle'),
                                        (0.02, 'cond_form'),
                                        (0.02, 'donated')])
def test_capacity_fraction_overflow_fallback(frac, check, monkeypatch):
  # frac=0.02 forces the traced unique count over the compaction capacity,
  # exercising the overflow correction (taken on every device); frac=1.0
  # never overflows.  Both must match the dense keras-adagrad oracle
  # exactly (dedup=True -> the oracle's sum-then-square semantics).
  opt_fn = lambda dist, cats: SparseAdagrad(
      learning_rate=LR, dedup=True, initial_accumulator_value=0.1,
      capacity_fraction=frac)
  if check == 'oracle':
    return _check_overflow_oracle(opt_fn, seed=5)
  _check_overflow_forms(monkeypatch, check, opt_fn, seed=5)


def _calibration_caps(mode, dist, cats):
  from distributed_embeddings_tpu.parallel import calibrate_capacity_rows
  if mode == 'calibrated':
    caps = calibrate_capacity_rows(dist, cats, margin=1.3)
    assert len(caps) == len(dist.plan.groups)
    assert all(isinstance(c, int) and c >= 8 for c in caps)
    return caps
  return tuple(8 for _ in dist.plan.groups)


@pytest.mark.parametrize('mode,check', [('calibrated', 'oracle'),
                                        ('too_small', 'oracle'),
                                        ('calibrated', 'cond_form'),
                                        ('too_small', 'cond_form'),
                                        ('calibrated', 'donated'),
                                        ('too_small', 'donated')])
def test_capacity_rows_calibration(mode, check, monkeypatch):
  # calibrated per-group capacities must reproduce the dense oracle (the
  # correction is in the program and NOT taken); a deliberately
  # under-sized capacity_rows must stay correct through the overflow
  # correction wave (taken)
  opt_fn = lambda dist, cats: SparseAdagrad(
      learning_rate=LR, dedup=True, initial_accumulator_value=0.1,
      capacity_rows=_calibration_caps(mode, dist, cats))
  if check == 'oracle':
    return _check_overflow_oracle(opt_fn, seed=7)
  _check_overflow_forms(monkeypatch, check, opt_fn, seed=7)


@pytest.mark.parametrize('taken', [True, False])
@pytest.mark.parametrize('max_seg', [None, 2])
@pytest.mark.parametrize('storage', ['wide', 'packed_view', 'packed'])
@pytest.mark.parametrize('opt_name', ['sgd', 'adagrad'])
def test_overflow_loop_gives_the_cond_forms_bits(monkeypatch, opt_name,
                                                 storage, max_seg, taken):
  """ISSUE 25: the table and state leaves ride a zero-or-one-trip
  ``lax.while_loop`` instead of a two-branch ``lax.cond`` (XLA copied
  the whole shard for each branch).  Same segments, same sums, same
  ``apply_unique`` call: the bits of the cond form, with the correction
  taken (301 segments against a capacity of 128) and not (101), for the
  natural width-128 operand, the packed VIEW of a natural width-16 one,
  the physically packed one, and the bounded exact fold (``max_seg``,
  with pre-summed squares for Adagrad as the cross-slice merge sends)."""
  from distributed_embeddings_tpu.parallel import sparse as sparse_mod
  rows_cap, n, cap_rows = 512, 1024, 128
  w = 128 if storage == 'wide' else 16
  pack = 8 if storage == 'packed' else 1
  rng = np.random.default_rng(3)
  k = 300 if taken else 100
  rows = rng.permutation(rows_cap)[:k]
  ids = np.full(n, rows_cap, np.int32)   # the rest is padding (sentinel)
  ids[:2 * k] = np.concatenate([rows, rows])  # each row twice: max_seg 2
  ids = rng.permutation(ids)
  g = rng.normal(size=(n, w)).astype(np.float32)
  table = rng.normal(size=(rows_cap // pack, w * pack)).astype(np.float32)
  if opt_name == 'sgd':
    opt, state = SparseSGD(learning_rate=LR), {}
  else:
    opt = SparseAdagrad(learning_rate=LR, dedup=False)
    state = {'acc': np.full_like(table, 0.1)}
  sq = g * g * 0.5 if (max_seg and opt_name == 'adagrad') else None

  def run(form):
    def fn(table, state, ids, g, sq):
      return sparse_mod._dedup_and_apply(
          opt, table, state,
          sparse_mod._Stream(ids, g, rows_cap, None, sq, max_seg), LR,
          cap_rows=cap_rows, storage_pack=pack)

    with monkeypatch.context() as m:
      if form == 'cond':
        m.setattr(jax.lax, 'while_loop', _while_as_cond)
      text = str(jax.make_jaxpr(fn)(table, state, ids, g, sq))
      return jax.jit(fn)(table, state, ids, g, sq), text

  (t_loop, s_loop), text = run('loop')
  assert ' while[' in text and ' cond[' not in text
  (t_cond, s_cond), text = run('cond')
  assert ' cond[' in text and ' while[' not in text
  np.testing.assert_array_equal(np.asarray(t_loop), np.asarray(t_cond))
  assert set(s_loop) == set(s_cond)
  for name in s_loop:
    np.testing.assert_array_equal(np.asarray(s_loop[name]),
                                  np.asarray(s_cond[name]))
  # every one of the k rows moved: by the main wave alone where they fit
  # the capacity, by the correction for the segments past it
  moved = (np.asarray(t_loop) != table).reshape(rows_cap, -1).any(axis=1)
  assert moved.sum() == k


def test_hybrid_step_with_lr_schedule():
  dist, params_emb, gen_inputs, kernel, labels, head_loss_fn = build()
  cats = gen_inputs()
  sched = lambda step: 0.1 / (1.0 + step.astype(jnp.float32))
  step = make_hybrid_train_step(dist, head_loss_fn, optax.sgd(LR),
                                SparseSGD(), lr_schedule=sched,
                                donate=False)
  state = init_hybrid_train_state(dist, {
      'embedding': params_emb,
      'kernel': kernel
  }, optax.sgd(LR), SparseSGD())
  state, l1 = step(state, cats, labels)
  state, l2 = step(state, cats, labels)
  assert np.isfinite(float(l1)) and np.isfinite(float(l2))
  assert int(state.step) == 2


def _run_steps_with_accum_dtype(adt, n_steps=3, lr=LR, fixed_batch=False):
  dist, params_emb, gen_inputs, kernel, labels, head_loss_fn = build()
  opt = SparseAdagrad(learning_rate=lr, initial_accumulator_value=0.1,
                      accum_dtype=adt)
  step = make_hybrid_train_step(dist, head_loss_fn, optax.sgd(lr), opt,
                                donate=False)
  state = init_hybrid_train_state(dist, {
      'embedding': params_emb,
      'kernel': kernel
  }, optax.sgd(lr), opt)
  cats = gen_inputs() if fixed_batch else None
  losses = []
  for _ in range(n_steps):
    state, loss = step(state, cats if fixed_batch else gen_inputs(),
                       labels)
    losses.append(float(loss))
  return state, losses


def test_bf16_accumulator_matches_f32_within_tolerance():
  """accum_dtype='bfloat16' (VERDICT r4 item 5): accumulator storage
  halves; the trained tables must track the f32-accumulator path within
  bf16 rounding of the monotone accumulator (arithmetic stays f32 —
  identical batches via identical build(seed) rng streams)."""
  st32, _ = _run_steps_with_accum_dtype('float32')
  st16, _ = _run_steps_with_accum_dtype('bfloat16')
  acc16 = st16.opt_state[1]
  assert all(v['acc'].dtype == jnp.bfloat16 for v in acc16.values())
  acc32 = st32.opt_state[1]
  for k in acc32:
    np.testing.assert_allclose(np.asarray(acc16[k]['acc'],
                                          dtype=np.float32),
                               np.asarray(acc32[k]['acc']), rtol=8e-3,
                               atol=8e-3)
  for k in st32.params['embedding']:
    np.testing.assert_allclose(
        np.asarray(st16.params['embedding'][k]),
        np.asarray(st32.params['embedding'][k]), rtol=1e-2, atol=5e-3)


@pytest.mark.slow  # ~22 s of 50-step loops; the bf16-accumulator
# CORRECTNESS gate (test_bf16_accumulator_matches_f32_within_tolerance)
# stays tier-1 — this is the accuracy-delta characterization on top,
# moved off the 870 s tier-1 budget (run via -m slow)
def test_bf16_accumulator_convergence_delta():
  """Measured accuracy impact of bf16 accumulators (the documented
  jumbo trade-off): after 50 steps on the same stream, the loss path
  must end within 5% relative of the f32-accumulator run."""
  _, l32 = _run_steps_with_accum_dtype('float32', n_steps=50, lr=0.05,
                                       fixed_batch=True)
  _, l16 = _run_steps_with_accum_dtype('bfloat16', n_steps=50, lr=0.05,
                                       fixed_batch=True)
  assert l32[-1] < l32[0]  # the task actually trains
  # both runs overfit the fixed batch toward 0 — compare the AREA under
  # the loss path, which stays sensitive to accumulator rounding even
  # after the endpoint saturates
  area32, area16 = sum(l32), sum(l16)
  delta = abs(area16 - area32) / max(area32, 1e-9)
  print(f'\nbf16-accumulator loss-path delta over 50 steps: '
        f'{delta * 100:.3f}% (f32 area {area32:.6f} vs bf16 '
        f'{area16:.6f}; endpoints {l32[-1]:.2e} / {l16[-1]:.2e})')
  assert delta < 0.05


def test_bf16_accumulator_segwalk_gate():
  """bf16 accumulators ride segwalk ONLY on bf16 tables (pair-fetch);
  on f32 tables the dispatch and the eligibility probe must BOTH
  report the XLA fallback (single-source gate, advisor r3)."""
  from distributed_embeddings_tpu.ops import pallas_segwalk
  from distributed_embeddings_tpu.parallel.sparse import choose_apply
  from distributed_embeddings_tpu.utils.apply_eligibility import (
      segwalk_serves_all_groups)
  dist, params_emb, *_ = build()
  opt = SparseAdagrad(use_segwalk_apply=True, accum_dtype='bfloat16')
  kernel = lambda table: choose_apply(opt, table, 1024, 128).kernel
  assert kernel(jnp.zeros((1024, 128), jnp.float32)) == 'xla'
  assert not segwalk_serves_all_groups(dist, 'float32',
                                       accum_dtype='bfloat16')
  # positive case: bf16 table + bf16 accumulator engages the kernel
  # (backend-gated; FORCE_INTERPRET stands in for the chip here)
  pallas_segwalk.FORCE_INTERPRET = True
  try:
    assert kernel(jnp.zeros((1024, 128), jnp.bfloat16)) == 'segwalk'
    # serves-all needs a plan whose row granularity satisfies the bf16
    # pair divisibility — the planner grants that when params ARE bf16.
    # Large-ish unsliced tables: auto column slicing would split widths
    # below the kernel's 8-lane minimum at this world size.
    bdist = DistributedEmbedding(
        [TableConfig(256 + 32 * i, 16, 'sum') for i in range(WORLD)],
        mesh=create_mesh(jax.devices()[:WORLD]),
        column_slice_threshold=1 << 30,
        param_dtype=jnp.bfloat16)
    assert segwalk_serves_all_groups(bdist, 'bfloat16',
                                     accum_dtype='bfloat16')
    assert not segwalk_serves_all_groups(bdist, 'bfloat16',
                                         accum_dtype='float16')
  finally:
    pallas_segwalk.FORCE_INTERPRET = False


def test_bf16_accumulator_checkpoint_roundtrip():
  """bf16 accumulators cross the global-canonical checkpoint exactly:
  np.savez writes ml_dtypes arrays as raw void bytes (dtype lost), so
  the canonical file stores them as f32 (exact superset) and the load
  path casts back to the live template dtype."""
  from distributed_embeddings_tpu.parallel import (get_optimizer_state,
                                                   set_optimizer_state)
  from distributed_embeddings_tpu.parallel.checkpoint import (
      get_weights, load_train_npz, save_train_npz)
  import tempfile, os
  dist, params_emb, *_ = build()
  opt = SparseAdagrad(accum_dtype='bfloat16')
  st = opt.init(dist, params_emb)
  st = jax.tree.map(
      lambda x: x + (jnp.arange(x.size, dtype=jnp.float32).reshape(
          x.shape) % 3).astype(x.dtype), st)
  ts = get_optimizer_state(dist, st)
  with tempfile.TemporaryDirectory() as d:
    path = os.path.join(d, 'bf16acc.npz')
    save_train_npz(path, get_weights(dist, params_emb), ts)
    _, ts2, _ = load_train_npz(path)
    assert all(np.asarray(t['acc']).dtype == np.float32 for t in ts2)
    st2 = set_optimizer_state(dist, st, ts2)
  assert all(v['acc'].dtype == jnp.bfloat16 for v in st2.values())
  ts_rt = get_optimizer_state(dist, st2)
  for a, b in zip(ts, ts_rt):
    for k in a:
      np.testing.assert_array_equal(np.asarray(a[k], dtype=np.float32),
                                    np.asarray(b[k], dtype=np.float32))


def test_dispatch_says_which_path_each_group_takes_on_tpu(monkeypatch,
                                                          caplog):
  """`use_segwalk_apply=True` is a request: on a TPU a group the kernel
  cannot serve says so (and why) instead of taking XLA in silence, and a
  served group says that too; off the chip nothing is said."""
  import logging
  import jax
  import jax.numpy as jnp
  from distributed_embeddings_tpu.parallel import SparseAdagrad, sparse
  opt = SparseAdagrad(use_segwalk_apply=True)
  good = jax.ShapeDtypeStruct((1024, 128), jnp.float32)
  odd = jax.ShapeDtypeStruct((1024, 24), jnp.float32)
  kernel = lambda o, t, group: sparse.choose_apply(
      o, t, *t.shape, group=group).kernel
  with caplog.at_level(logging.INFO, logger=sparse.__name__):
    assert kernel(opt, good, 'group_0') == 'xla'
    assert not caplog.records  # CPU backend: not asked here, not said
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    assert kernel(opt, good, 'group_0') == 'segwalk'
    assert kernel(opt, odd, 'group_1') == 'xla'
    assert kernel(SparseAdagrad(), odd, 'group_1') == 'xla'
  said = [(r.levelname, r.getMessage()) for r in caplog.records]
  assert said[0][0] == 'INFO' and 'group_0 takes the segment-walk' in said[0][1]
  assert said[1][0] == 'WARNING' and 'group_1 takes the XLA apply' in said[1][1]
  assert 'not a kernel shape' in said[1][1]
  assert len(said) == 2  # the kernel was not asked for: nothing to say
