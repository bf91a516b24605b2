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
