"""The sparse apply's stages, each alone (docs/design.md §26): the one
function that says which apply serves a group (``choose_apply``) and the
report that repeats it, the *stream* stage, and the *merge* stage's two
forms on the faked (2, 4) mesh.  Whole steps are compared elsewhere
(test_sparse_train, test_hierarchical_exchange, test_fuzz_equivalence).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from distributed_embeddings_tpu.analysis import graphlint
from distributed_embeddings_tpu.ops import pallas_segwalk
from distributed_embeddings_tpu.parallel import (DistributedEmbedding,
                                                 SparseAdagrad, SparseAdam,
                                                 SparseSGD, TableConfig,
                                                 create_mesh, sparse)
from distributed_embeddings_tpu.utils.apply_eligibility import (
    eligibility_line, segwalk_serves_all_groups)

WORLD = 4
BIG = (sparse.PACKED_PARAM_BYTES_LIMIT // (128 * 4)) * WORLD * 8

# name -> (rows, width, layer kwargs, accum dtype, what choose_apply must
# say of the layer's one group: kernel, view, pack, part of the reason)
LAYERS = {
    'wide_f32': (256, 128, {}, 'float32', 'segwalk', 'natural', 1, ''),
    'narrow_stored_packed': (256, 16, {}, 'float32',
                             'segwalk', 'stored_packed', 8, ''),
    'bf16_acc_on_f32_table': (256, 128, {}, 'bfloat16', 'xla', 'natural',
                              1, 'bfloat16 accumulators on a float32'),
    'bf16_acc_on_bf16_table': (256, 128, {'param_dtype': jnp.bfloat16},
                               'bfloat16', 'segwalk', 'natural', 1, ''),
    'width_24': (256, 24, {}, 'float32', 'xla', 'natural', 1,
                 'not a kernel shape'),
    'huge_narrow_natural': (BIG, 16, {'packed_storage': False}, 'float32',
                            'xla', 'natural', 1, 'lane-padded relayout'),
    'huge_narrow_stored_packed': (BIG, 16, {}, 'float32',
                                  'segwalk', 'stored_packed', 8, ''),
    'quantized': (256, 128, {'table_dtype': 'int8'}, 'float32', 'xla',
                  'natural', 1, 'quantized or cold-tier'),
}


@pytest.mark.parametrize('name', list(LAYERS))
def test_choose_apply_and_the_report_agree(name, monkeypatch):
  """One group a layer, asked as the group loop asks: ``choose_apply``
  names the kernel, the view and, where the segment-walk kernel was
  asked for and declines, why — every reason there is — and
  ``eligibility_line`` / ``segwalk_serves_all_groups`` repeat it."""
  rows, width, kw, accum, kernel, view, pack, why = LAYERS[name]
  mesh = create_mesh(jax.devices()[:WORLD])
  cfgs = [TableConfig(rows, width, 'sum')] + [
      TableConfig(64, width, 'sum') for _ in range(WORLD - 1)]
  dist = DistributedEmbedding(cfgs, mesh=mesh,
                              column_slice_threshold=1 << 40, **kw)
  (g,) = dist.plan.groups
  dtype = jnp.dtype(kw.get('param_dtype', jnp.float32))
  table = jax.ShapeDtypeStruct((g.param_rows, g.param_width), dtype)
  opt = SparseAdagrad(use_segwalk_apply=True, accum_dtype=accum)
  ask = lambda **more: sparse.choose_apply(
      opt, table, g.rows_cap, g.width, storage_pack=g.storage_pack,
      adapted=dist.quant is not None, **more)
  got = ask(active=True)
  assert got[:3] == (kernel, view, pack), got
  assert (why in got.declined) if why else got.declined == '', got
  line = eligibility_line(dist, dtype, True, accum_dtype=accum)
  assert f'{int(kernel == "segwalk")}/1 groups eligible' in line, line
  # on this CPU the kernel runs only where a test stands in for the chip
  assert ask().kernel == 'xla'
  assert ('cpu' in ask().declined) == (kernel == 'segwalk')
  assert not segwalk_serves_all_groups(dist, dtype, accum_dtype=accum)
  monkeypatch.setattr(pallas_segwalk, 'FORCE_INTERPRET', True)
  assert ask().kernel == kernel
  assert segwalk_serves_all_groups(
      dist, dtype, accum_dtype=accum) == (kernel == 'segwalk')


@pytest.mark.parametrize('case, opt, kw, expect', [
    # the head reads the group: its dense step, whatever else was asked
    ('tied', SparseAdagrad(use_segwalk_apply=True), dict(tied=True),
     ('tied', 'natural', 1, '')),
    ('not_asked', SparseSGD(), {}, ('xla', 'natural', 1, '')),
    ('summed_squares', SparseAdagrad(dedup=False, use_segwalk_apply=True),
     dict(summed_squares=True, active=True),
     ('xla', 'natural', 1, 'the stream carries squares')),
    # a natural narrow group takes the packed VIEW exactly where it
    # shrinks the scatters: rows_cap // pack + 2 < cap (512 // 8 + 2)
    ('packed_view', SparseSGD(), dict(width=16, cap=67),
     ('xla', 'packed_view', 8, '')),
    ('packed_view_boundary', SparseSGD(), dict(width=16, cap=66),
     ('xla', 'natural', 1, '')),
    ('packed_view_cap_unknown', SparseSGD(), dict(width=16),
     ('xla', 'packed_view', 8, '')),
    ('adam_never_lane_packs', SparseAdam(), dict(width=16, cap=400),
     ('xla', 'natural', 1, '')),
    ('adam_on_packed_storage', SparseAdam(), dict(width=16, storage_pack=8),
     ('xla', 'unpacked', 1, '')),
    ('sgd_on_packed_storage', SparseSGD(), dict(width=16, storage_pack=8),
     ('xla', 'stored_packed', 8, '')),
])
def test_choose_apply_view_and_kernel_order(case, opt, kw, expect):
  kw = dict(kw)
  width, pack = kw.pop('width', 128), kw.get('storage_pack', 1)
  table = jax.ShapeDtypeStruct((512 // pack, width * pack), jnp.float32)
  got = sparse.choose_apply(opt, table, 512, width, **kw)
  assert got[:3] == expect[:3], (case, got)
  assert (expect[3] in got.declined) if expect[3] else not got.declined


# ---- stage 1: the stream ---------------------------------------------------

ROWS_CAP = 40


def _slot(rng, n_cap, gb, h, w, pad=0.3):
  ids = rng.integers(0, ROWS_CAP, (1, n_cap, gb, h)).astype(np.int32)
  ids[rng.random(ids.shape) < pad] = ROWS_CAP   # padding: the sentinel
  g = rng.normal(size=(1, n_cap, gb, w)).astype(np.float32)
  return jnp.asarray(ids), jnp.asarray(g)


def test_stream_indexed_and_broadcast_forms_hold_the_same_rows():
  """``n >= 2m``: the bag's one cotangent row stays compact behind an
  index; below, it is broadcast.  Position for position the same rows,
  and the same ids; the fence comes back through the ids' barrier."""
  rng = np.random.default_rng(0)
  a_ids, a_g = _slot(rng, 2, 8, 4, 8)        # n = 64, m = 16
  b_ids, b_g = _slot(rng, 8, 8, 1, 8)        # n = m = 64
  fence = jnp.float32(0.25)
  alone, f1 = sparse._group_stream([(0, False)], [a_ids], [a_g], ROWS_CAP,
                                   fence)
  both, f2 = sparse._group_stream([(0, False), (1, False)], [a_ids, b_ids],
                                  [a_g, b_g], ROWS_CAP, fence)
  assert alone.index is not None and alone.rows.shape == (16, 8)
  assert both.index is None and both.rows.shape == (128, 8)   # 128 < 2 * 80
  assert float(f1) == float(f2) == 0.25
  n = alone.ids.shape[0]
  np.testing.assert_array_equal(alone.ids, both.ids[:n])
  np.testing.assert_array_equal(np.asarray(alone.rows)[alone.index],
                                both.rows[:n])
  np.testing.assert_array_equal(
      both.rows[:n], np.repeat(np.asarray(a_g).reshape(16, 8), 4, axis=0))
  np.testing.assert_array_equal(both.rows[n:], np.asarray(b_g).reshape(64, 8))
  assert (alone.squares, alone.max_seg, alone.rows_cap) == (
      None, None, ROWS_CAP)


@pytest.mark.parametrize('h', [1, 3])
def test_stream_divides_a_mean_bag_by_its_window_count_only_when_asked(h):
  """A 'mean' slot divides by the ids its window holds (at least one); a
  ``mean_row_sliced`` or hot-cache slot's cotangent arrives divided and
  passes as it is."""
  rng = np.random.default_rng(1)
  ids, g = _slot(rng, 2, 4, h, 8, pad=0.4)
  asked, _ = sparse._group_stream([(0, True)], [ids], [g], ROWS_CAP, 0.0)
  plain, _ = sparse._group_stream([(0, False)], [ids], [g], ROWS_CAP, 0.0)
  count = np.maximum((np.asarray(ids)[0] < ROWS_CAP).sum(axis=2), 1)
  want = np.asarray(g)[0] / count[..., None].astype(np.float32)
  rows = lambda s: np.asarray(s.rows if s.index is None
                              else s.rows[s.index])
  np.testing.assert_array_equal(
      rows(asked), np.repeat(want.reshape(8, 8), h, axis=0))
  np.testing.assert_array_equal(
      rows(plain), np.repeat(np.asarray(g).reshape(8, 8), h, axis=0))


def test_split_square_columns_bounds_no_multiplicity():
  """One slice, hot-cache stream with squares in its trailing columns: a
  row may come from every source device, so the split stream carries no
  ``max_seg`` and the apply adds every occurrence's squares (a bound of
  one slice dropped all but a row's last occurrence)."""
  w, lr = 8, 0.5
  ids = jnp.asarray([3, 5, 3, ROWS_CAP], jnp.int32)
  rows = jnp.asarray(np.random.default_rng(2).normal(
      size=(4, 2 * w)).astype(np.float32))
  stream = sparse._split_square_columns(
      sparse._Stream(ids, rows, ROWS_CAP), w)
  assert stream.max_seg is None and stream.squares.shape == (4, w)
  table = jnp.zeros((ROWS_CAP, w), jnp.float32)
  acc = jnp.full((ROWS_CAP, w), 0.1, jnp.float32)
  opt = SparseAdagrad(learning_rate=lr, dedup=False)
  _, state = sparse._dedup_and_apply(opt, table, {'acc': acc}, stream, lr)
  got = np.asarray(state['acc'])
  r = np.asarray(rows)
  np.testing.assert_allclose(got[3], 0.1 + r[0, w:] + r[2, w:], rtol=1e-6)
  np.testing.assert_allclose(got[5], 0.1 + r[1, w:], rtol=1e-6)


# ---- stage 2: the merge across slices --------------------------------------


@pytest.mark.parametrize('needs_sq', [False, True])
def test_flat_and_hierarchical_merges_give_bit_equal_row_totals(needs_sq):
  """On the (2, 4) mesh each device's stream goes through the flat
  ``all_gather`` merge and through the hierarchical owner-routed
  ``all_to_all``; summed as the apply sums them (the bounded exact fold),
  every row's total — gradient and squares — is the same bits at its
  owner, and the flat merge's two slices agree."""
  S, D, w, n = 2, 4, 8, 48
  mesh = create_mesh((S, D))
  cfgs = [TableConfig(60 + 7 * i, w, 'sum') for i in range(6)]
  dist = DistributedEmbedding(cfgs, mesh=mesh, dcn_sharding=True)
  (g,), (hl,) = dist.plan.groups, dist.hier.groups
  rng = np.random.default_rng(4)
  ids = np.stack([
      np.where(rng.random(n) < 0.2, g.rows_cap,
               rng.integers(0, max(g.rows[d], 1), n))
      for _ in range(S) for d in range(D)]).astype(np.int32)
  ids[:, 1] = ids[:, 0]                       # a duplicate inside a slice
  rows = rng.normal(size=(S * D, n, w)).astype(np.float32)

  def totals(hier_group):
    def local(ids, rows):
      merged = sparse._merge_slices(
          sparse._Stream(ids[0], rows[0], g.rows_cap), w, needs_sq,
          False, dist.dcn_axis, S, hier_group=hier_group,
          axis_name=dist.axis_name)
      assert merged.max_seg == S and merged.index is None
      assert (merged.squares is not None) == needs_sq
      cap = merged.rows_cap
      uids, sum_g, sum_sq, _ = sparse.compact_segments(
          merged.ids, merged.rows if not needs_sq else jnp.concatenate(
              [merged.rows, merged.squares], axis=1),
          sparse._guaranteed_cap(merged.ids.shape[0], cap), cap,
          max_seg=S)
      dense = jnp.zeros((cap, sum_g.shape[1]), jnp.float32).at[uids].add(
          sum_g, mode='drop')
      return dense[None]

    both = (dist.dcn_axis, dist.axis_name)
    return np.asarray(jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(both), P(both)), out_specs=P(both),
        check_vma=False))(jnp.asarray(ids), jnp.asarray(rows)))

  flat, hier = totals(None), totals(hl)
  assert flat.shape[1] == g.rows_cap and hier.shape[1] == hl.rows_cap_h
  np.testing.assert_array_equal(flat[:D], flat[D:])
  checked = 0
  for d in range(D):
    real = np.arange(g.rows[d])
    owner, hrow = hl.map_rows(d, real)
    np.testing.assert_array_equal(hier[owner * D + d, hrow], flat[d, real])
    checked += int(np.any(flat[d, real] != 0, axis=1).sum())
  assert checked > 20, 'the streams touched next to no row'


# ---- stage 4: the write (ISSUE 30) ------------------------------------------
# One function scatters compacted unique rows (``sparse._write_rows``) and
# picks XLA:TPU's scatter emitter from the wave's static shapes
# (``sparse.write_algorithm``): 'stream' sets ``indices_are_sorted``, 'rows'
# leaves it off.

SDS = jax.ShapeDtypeStruct
SCATTERS = {'scatter-add': 'add', 'scatter': 'set'}


def _scatters(fn, *args):
  """``[(op, update rows, operand rows, scope, params)]`` of every scatter
  ``fn`` traces to, sub-jaxprs (jit, while, shard_map) included."""
  return [(SCATTERS[e.primitive.name], e.invars[2].aval.shape[0],
           e.invars[0].aval.shape[0], str(e.source_info.name_stack),
           e.params)
          for e, _ in graphlint._walk_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
          if e.primitive.name in SCATTERS]


def _boundary(operand_rows, op):
  """The largest wave that takes the row emitter into ``operand_rows``,
  from the module's constants: ``U * (c - b) <= a * R``."""
  a = sparse._STREAM_NS_PER_OPERAND_ROW
  b = sparse._STREAM_NS_PER_UPDATE_ROW[op]
  c = sparse._ROWS_NS_PER_UPDATE_ROW[op]
  return int(a * operand_rows / (c - b))


@pytest.mark.parametrize('op', ['add', 'set'])
@pytest.mark.parametrize('cell, wave, operand, takes', [
    ('dlrm-train-4chip', 92_272, 20_025_088, 'rows'),
    ('tiny-train-zipf', 1_116_536, 8_775_000, 'stream'),
    ('tiny-train-uniform', 2_883_584, 8_775_000, 'stream'),
    ('just_under_the_boundary', None, 20_025_088, 'rows'),
    ('just_over_the_boundary', None, 20_025_088, 'stream'),
])
def test_write_rows_takes_the_emitter_the_static_share_says(
    cell, wave, operand, takes, op):
  """At the cells' own shapes (abstract: no memory) the one scatter
  ``_write_rows`` traces carries ``indices_are_sorted`` exactly where
  ``write_algorithm`` says 'stream', and the answer flips between two
  neighbouring wave sizes at the module constants' boundary."""
  if wave is None:
    wave = _boundary(operand, op) + (takes == 'stream')
  assert sparse.write_algorithm(wave, operand, op) == takes
  (got,) = _scatters(
      lambda t, u, r: sparse._write_rows(t, u, r, op),
      SDS((operand, 128), jnp.float32), SDS((wave,), jnp.int32),
      SDS((wave, 128), jnp.float32))
  assert got[:3] == (op, wave, operand) and 'apply/write_rows' in got[3]
  assert got[4]['indices_are_sorted'] == (takes == 'stream')
  assert got[4]['unique_indices']
  assert got[4]['mode'] == jax.lax.GatherScatterMode.FILL_OR_DROP


def _quantized(inner):
  from distributed_embeddings_tpu.parallel import quantization
  return sparse._QuantizedTableOptimizer(
      inner, quantization.resolve_table_dtype('int8'))


WRITERS = {
    'sgd': lambda: SparseSGD(learning_rate=0.5),
    'adagrad_dedup': lambda: SparseAdagrad(learning_rate=0.5),
    'adagrad_per_occurrence': lambda: SparseAdagrad(learning_rate=0.5,
                                                    dedup=False),
    'adagrad_bf16_acc': lambda: SparseAdagrad(learning_rate=0.5,
                                              accum_dtype='bfloat16'),
    'adam': lambda: SparseAdam(learning_rate=0.5),
    'quantized_adagrad': lambda: _quantized(SparseAdagrad(learning_rate=0.5)),
}


def _toy_wave(name, rng, rows=96, wave=40, real=29, w=16):
  """One optimizer's operand, state and a compacted wave: ``real``
  ascending unique rows, then the sentinel repeated."""
  opt = WRITERS[name]()
  table = jnp.asarray(rng.normal(size=(rows, w)).astype(np.float32))
  uids = np.full(wave, rows, np.int32)
  uids[:real] = np.sort(rng.choice(rows, real, replace=False))
  sum_g = rng.normal(size=(wave, w)).astype(np.float32)
  sum_g[real:] = 0.0
  inner = getattr(opt, 'inner', opt)
  if isinstance(inner, SparseAdagrad):
    state = {'acc': jnp.full((rows, w), 0.1, jnp.dtype(inner.accum_dtype))}
  elif isinstance(inner, SparseAdam):
    state = {'m': jnp.zeros((rows, w)), 'v': jnp.zeros((rows, w)),
             't': jnp.zeros((rows,), jnp.int32)}
  else:
    state = {}
  if inner is not opt:
    from distributed_embeddings_tpu.parallel import quantization
    table = quantization.quantize_jnp(table, opt.spec)
  return opt, table, state, jnp.asarray(uids), jnp.asarray(sum_g)


@pytest.mark.parametrize('name', list(WRITERS))
def test_both_emitters_write_the_same_bits(name, monkeypatch):
  """Rows are unique and each is written once, so the hint changes the
  emitter and nothing else: table, scale and every state leaf are the
  same bits either way, and the sentinel tail wrote nothing."""
  opt, table, state, uids, sum_g = _toy_wave(name, np.random.default_rng(7))
  sum_sq = sum_g * sum_g if getattr(opt, 'needs_sq', False) else None

  def run(takes):
    monkeypatch.setattr(sparse, 'write_algorithm', lambda *a: takes)
    seen = _scatters(opt.apply_unique, table, state, uids, sum_g, sum_sq,
                     0.5)
    assert seen and all(
        s[4]['indices_are_sorted'] == (takes == 'stream') for s in seen)
    return jax.tree.leaves(jax.jit(opt.apply_unique)(
        table, state, uids, sum_g, sum_sq, 0.5))

  stream, rows = run('stream'), run('rows')
  assert len(stream) == len(jax.tree.leaves((table, state)))
  for a, b in zip(stream, rows):
    assert a.dtype == b.dtype
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  before = np.asarray(jax.tree.leaves(table)[0])
  touched = np.any(np.asarray(stream[0]) != before, axis=1)
  np.testing.assert_array_equal(np.flatnonzero(touched),
                                np.asarray(uids)[:29])


@pytest.mark.parametrize('rows_win', [False, True])
@pytest.mark.parametrize('name', ['sgd', 'adagrad_dedup', 'adam',
                                  'quantized_adagrad'])
def test_every_apply_scatter_of_a_train_step_comes_through_write_rows(
    name, rows_win, monkeypatch):
  """A jaxpr walk over one whole train step: every scatter under an
  ``apply/`` phase is ``_write_rows``'s (scope ``apply/write_rows``,
  ``unique_indices``, mode drop, the hint ``write_algorithm`` gives for
  its own shapes).  ``rows_win`` turns the constants so that the row
  emitter wins at every toy shape: a site that hard-codes the hint
  would then stand out."""
  import optax
  from distributed_embeddings_tpu.parallel import (init_hybrid_train_state,
                                                   make_hybrid_train_step)
  if rows_win:
    monkeypatch.setattr(sparse, '_ROWS_NS_PER_UPDATE_ROW',
                        {'add': 0.0, 'set': 0.0})
  opt = getattr(WRITERS[name](), 'inner', WRITERS[name]())
  kw = {'table_dtype': 'int8'} if name.startswith('quantized') else {}
  mesh = create_mesh(jax.devices()[:WORLD])
  cfgs = [TableConfig(200, 16, 'sum'), TableConfig(90, 16, 'sum'),
          TableConfig(300, 128, 'sum'), TableConfig(64, 128, 'mean')]
  dist = DistributedEmbedding(cfgs, mesh=mesh, **kw)
  dense = {'kernel': jnp.zeros((sum(c.output_dim for c in cfgs), 1))}

  def head(dp, eo, b):
    return jnp.mean((jnp.concatenate(list(eo), axis=-1) @ dp['kernel'] - b)
                    **2)

  step = make_hybrid_train_step(dist, head, optax.sgd(0.1), opt, jit=False)
  state = init_hybrid_train_state(dist, {'embedding': dist.init(0), **dense},
                                  optax.sgd(0.1), opt)
  rng = np.random.default_rng(3)
  cats = [jnp.asarray(rng.integers(0, c.input_dim, (32, 2)).astype(np.int32))
          for c in cfgs]
  seen = [s for s in _scatters(step, state, cats, jnp.zeros((32, 1)))
          if 'apply/' in s[3]]
  writes = {'sgd': 1, 'adagrad_dedup': 2, 'adam': 4, 'quantized_adagrad': 3}
  assert len(seen) >= writes[name] * len(dist.plan.groups), seen
  for op, wave, operand, scope, params in seen:
    assert 'apply/write_rows' in scope, scope
    assert params['unique_indices'], scope
    assert params['mode'] == jax.lax.GatherScatterMode.FILL_OR_DROP
    takes = sparse.write_algorithm(wave, operand, op)
    assert takes == ('rows' if rows_win else takes)
    assert params['indices_are_sorted'] == (takes == 'stream'), (
        scope, op, wave, operand)


@pytest.mark.parametrize('name', list(LAYERS))
def test_choose_apply_names_the_emitter_the_main_wave_takes(name):
  """``ApplyChoice.write`` against the program: the table scatter that
  ``_dedup_and_apply`` traces for the group (abstract operands: the two
  huge layers cost no memory) carries the hint ``choose_apply`` named,
  and the per-group report prints the same word with the wave, the
  operand and the share."""
  from distributed_embeddings_tpu.utils.apply_eligibility import (
      write_rows_lines)
  rows, width, kw, accum, *_ = LAYERS[name]
  mesh = create_mesh(jax.devices()[:WORLD])
  cfgs = [TableConfig(rows, width, 'sum')] + [
      TableConfig(64, width, 'sum') for _ in range(WORLD - 1)]
  dist = DistributedEmbedding(cfgs, mesh=mesh,
                              column_slice_threshold=1 << 40, **kw)
  (g,) = dist.plan.groups
  n = 4096
  opt = SparseAdagrad(accum_dtype=accum, capacity_fraction=1.0)
  dtype = jnp.dtype(kw.get('param_dtype', jnp.float32))
  shape = (g.param_rows, g.param_width)
  table, op, apply_opt = SDS(shape, dtype), 'add', opt
  if dist.quant is not None:
    table = (SDS(shape, jnp.int8), SDS((shape[0], 1), jnp.float32))
    op, apply_opt = 'set', sparse._QuantizedTableOptimizer(opt, dist.quant)
  cap = sparse._capacity(opt, n, g.rows_cap, None)
  choice = sparse.choose_apply(apply_opt, table, g.rows_cap, g.width,
                               storage_pack=g.storage_pack, cap=cap,
                               adapted=dist.quant is not None)
  wave, operand = sparse.wave_shape(cap, g.rows_cap, choice.pack)
  assert choice.write == sparse.write_algorithm(wave, operand, op)
  assert choice.write == ('rows' if rows == BIG else 'stream')

  def run(table, acc, ids, grads):
    return sparse._dedup_and_apply(
        apply_opt, table, {'acc': acc},
        sparse._Stream(ids, grads, g.rows_cap), 0.1,
        storage_pack=g.storage_pack)

  seen = _scatters(run, table, SDS(shape, jnp.dtype(accum)),
                   SDS((n,), jnp.int32), SDS((n, g.width), jnp.float32))
  main = [s for s in seen if s[0] == op and s[2] == operand
          and s[1] == wave]
  assert main, seen
  assert all(s[4]['indices_are_sorted'] == (choice.write == 'stream')
             for s in main)
  if dist.quant is None:
    (line,) = write_rows_lines(dist, opt, stream_rows=[n])
    assert line == (f'apply/write_rows: group_0 writes {wave:,} rows into '
                    f'{operand:,} ({100.0 * wave / operand:.2f}%): '
                    f'{choice.write}'), line
    assert 'unknown' in write_rows_lines(dist, opt)[0]
