"""Native C++ static-CSR builder vs the NumPy oracle.

Oracle pattern (SURVEY.md §4, same as test_fastloader.py): the
optimized native path must return BIT-identical buffers to
``build_csr_host`` / ``_route_ids_np`` across fuzzed shapes, partition
counts, capacities, and overflow/drop cases — and the parallel
(group, device) fan-out must be invariant in the worker count.  Skips
(visibly) when no C++ toolchain can build ``cc/libdetcsr.so``; never
fails for that reason.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_embeddings_tpu.parallel import (DistributedEmbedding,
                                                 TableConfig, create_mesh)
from distributed_embeddings_tpu.parallel import csr_native, sparsecore
from distributed_embeddings_tpu.utils import nativebuild


@pytest.fixture(scope='module')
def built():
  if not csr_native.available():
    pytest.skip(f'native CSR builder unavailable: '
                f'{nativebuild.toolchain_note()}')
  return True


def _assert_host_csr_equal(a, b, msg=''):
  assert a.max_ids_per_partition == b.max_ids_per_partition, msg
  assert a.dropped == b.dropped, msg
  for name, x, y in zip(('row_pointers', 'embedding_ids', 'sample_ids',
                         'gains'), a[:4], b[:4]):
    np.testing.assert_array_equal(x, y, err_msg=f'{msg} field {name}')


@pytest.mark.parametrize('seed', range(8))
def test_fuzz_build_parity(built, seed):
  """Fuzzed shapes x num_sc x caps x combiners, including sentinel-range
  ids and deliberately undersized capacities (overflow/drop accounting
  must match exactly, not just the happy path)."""
  rng = np.random.default_rng(6000 + seed)
  for case in range(25):
    rows_cap = int(rng.integers(1, 300))
    num_sc = int(rng.choice([1, 2, 4, 8, 16]))
    n_cap, gb, h = (int(rng.integers(1, 5)), int(rng.integers(1, 40)),
                    int(rng.integers(1, 6)))
    combiner = [None, 'sum', 'mean'][int(rng.integers(0, 3))]
    # id range reaches past rows_cap (sentinel/padding territory) AND
    # below 0: the oracle's `flat < rows_cap` classifies negative ids
    # as in-range with floor-mod partitions, and the native twin must
    # match that bit-exactly rather than corrupt memory on a
    # truncating C %/ / (review finding, round 6)
    lo_id = -int(rng.integers(0, 6))
    routed = rng.integers(lo_id, rows_cap + int(rng.integers(1, 8)),
                          size=(n_cap, gb, h)).astype(np.int32)
    if rng.random() < 0.3:
      routed[rng.integers(0, n_cap)] = rows_cap  # an all-padding slot
    # None = size-to-batch; small explicit caps force drops
    cap = (None if rng.random() < 0.4
           else int(rng.integers(1, max(2, (n_cap * gb * h) // num_sc))))
    want = sparsecore.build_csr_host(routed, rows_cap, num_sc, combiner,
                                     max_ids_per_partition=cap)
    got = csr_native.build_csr(routed, rows_cap, num_sc, combiner,
                               max_ids_per_partition=cap)
    _assert_host_csr_equal(want, got,
                           f'seed {seed} case {case} (rows_cap {rows_cap}, '
                           f'num_sc {num_sc}, cap {cap}, {combiner})')


@pytest.mark.parametrize('seed', range(4))
def test_fuzz_route_parity(built, seed):
  """The native routing twin must equal ``_route_ids_np`` bit-exactly —
  including negative ids, out-of-vocab clipping, and mod-sharding
  (lo/hi/stride) residue windows."""
  rng = np.random.default_rng(6500 + seed)
  for _ in range(25):
    n_cap, gb, h = (int(rng.integers(1, 6)), int(rng.integers(1, 30)),
                    int(rng.integers(1, 5)))
    ids = rng.integers(-3, 80, size=(n_cap, gb, h)).astype(np.int32)
    vocab = rng.integers(1, 75, size=n_cap).astype(np.int32)
    offs = rng.integers(0, 500, size=n_cap).astype(np.int32)
    lo = rng.integers(0, 20, size=n_cap).astype(np.int32)
    hi = lo + rng.integers(1, 60, size=n_cap).astype(np.int32)
    stride = rng.integers(1, 5, size=n_cap).astype(np.int32)
    rows_cap = int(rng.integers(100, 2000))
    want = sparsecore._route_ids_np(ids, offs, vocab, rows_cap, lo, hi,
                                    stride)
    got = csr_native.route_ids(ids, offs, vocab, rows_cap, lo, hi, stride)
    np.testing.assert_array_equal(want, got)


def _mesh_dist_cats(world=4, seed=13):
  mesh = create_mesh(jax.devices()[:world])
  rng = np.random.default_rng(seed)
  configs = [TableConfig(120, 16, 'sum'), TableConfig(60, 16, 'mean'),
             TableConfig(40, 8, 'sum')]
  dist = DistributedEmbedding(configs, mesh=mesh, lookup_impl='sparsecore',
                              row_slice=500)
  cats = [
      rng.integers(0, c.input_dim, size=(world * 4, 3)).astype(np.int32)
      for c in configs
  ]
  return dist, cats


def test_preprocess_native_matches_numpy_end_to_end(built):
  """Whole-batch parity through ``preprocess_batch_host`` on a real
  mod-sharded plan: every (group, device) pair's buffers bit-equal."""
  dist, cats = _mesh_dist_cats()
  caps = sparsecore.calibrate_max_ids_per_partition(
      dist, [jnp.asarray(c) for c in cats])
  want = sparsecore.preprocess_batch_host(dist, cats,
                                          max_ids_per_partition=caps,
                                          native='numpy', num_workers=1)
  got = sparsecore.preprocess_batch_host(dist, cats,
                                         max_ids_per_partition=caps,
                                         native='native', num_workers=1)
  assert want.keys() == got.keys()
  for k in want:
    for dev, (a, b) in enumerate(zip(want[k], got[k])):
      _assert_host_csr_equal(a, b, f'group/hotness {k} device {dev}')


@pytest.mark.parametrize('native', ['numpy', 'native'])
def test_preprocess_thread_count_invariance(built, native):
  """The parallel (group, device) fan-out is deterministic: ANY worker
  count (inline, explicit pools, the shared pool) produces identical
  buffers in identical device order."""
  dist, cats = _mesh_dist_cats(seed=29)
  ref = sparsecore.preprocess_batch_host(dist, cats, native=native,
                                         num_workers=1)
  for nw in (2, 3, 8, None):
    got = sparsecore.preprocess_batch_host(dist, cats, native=native,
                                           num_workers=nw)
    assert ref.keys() == got.keys(), nw
    for k in ref:
      for dev, (a, b) in enumerate(zip(ref[k], got[k])):
        _assert_host_csr_equal(a, b, f'workers {nw} key {k} device {dev}')


def test_measure_preprocess_reports_native_and_parity(built):
  dist, cats = _mesh_dist_cats(seed=31)
  stats = sparsecore.measure_preprocess_ms(dist, cats, repeats=2)
  assert stats['csr_native_parity'] is True
  assert stats['csr_native_ns_per_id'] > 0
  assert stats['csr_numpy_ns_per_id'] > 0
  assert stats['csr_preprocess_builder'].startswith('native')
  assert stats['csr_dropped'] == 0


def test_resolve_builder_modes(built):
  assert sparsecore.resolve_builder('auto') == 'native'
  assert sparsecore.resolve_builder('native') == 'native'
  assert sparsecore.resolve_builder('numpy') == 'numpy'
  with pytest.raises(ValueError):
    sparsecore.resolve_builder('cuda')


def test_resolve_builder_numpy_fallback_without_native(monkeypatch):
  """'auto' quietly falls back to NumPy when the library is absent;
  'native' must raise, never silently measure NumPy under that label."""
  monkeypatch.setattr(sparsecore, 'native_available', lambda: False)
  assert sparsecore.resolve_builder('auto') == 'numpy'
  with pytest.raises(RuntimeError, match='native CSR builder'):
    sparsecore.resolve_builder('native')


def test_staleness_is_decided_by_content_not_by_modification_time(built):
  """A copied tree does not keep modification times meaningful: the
  build records its sources' digest beside the binary, and only a
  digest that matches the present sources makes the binary current."""
  import os
  so, srcs = csr_native._SO_NAME, csr_native._SRC_NAMES
  stamp = nativebuild.so_path(so) + '.srcsum'
  assert not nativebuild.stale(so, srcs)
  with open(stamp, encoding='ascii') as f:
    digest = f.read()
  try:
    # sources "older" than the binary by mtime, but not the ones it was
    # built from: stale
    with open(stamp, 'w', encoding='ascii') as f:
      f.write('0' * 64)
    assert nativebuild.stale(so, srcs)
    os.remove(stamp)  # a binary of unknown provenance: stale
    assert nativebuild.stale(so, srcs)
  finally:
    with open(stamp, 'w', encoding='ascii') as f:
      f.write(digest)
  assert not nativebuild.stale(so, srcs)
