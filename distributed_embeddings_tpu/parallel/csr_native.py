"""ctypes binding for the native static-CSR builder (cc/csr_builder.cc).

The native library is the production twin of the NumPy host builder in
``parallel/sparsecore.py`` (``_route_ids_np`` + ``build_csr_host``):
same routing, same partition-stable order, same padded section layout,
same capacity/overflow accounting — bit-exact by construction and by
fuzz (tests/test_csr_native.py).  The NumPy builder remains the oracle
and the automatic fallback; ``sparsecore.build_csr`` /
``preprocess_batch_host`` pick this path when the library is built
(``make -C distributed_embeddings_tpu/cc``, auto-built on first use via
the shared ``utils/nativebuild`` lifecycle).

Each C call releases the GIL, so Python worker threads over
(group, device) pairs parallelise the per-batch transform for real —
the lever ``docs/perf_notes.md`` ("Static-CSR host preprocessing cost")
names for keeping a SparseCore chip fed.
"""

from __future__ import annotations

import ctypes
from typing import Optional

import numpy as np

from distributed_embeddings_tpu.utils import nativebuild

_SO_NAME = 'libdetcsr.so'
_SRC_NAMES = ('csr_builder.cc',)

_lib = None
_load_failed = False  # sticky: the feed resolves per batch, and every
#                       failed attempt would otherwise respawn `make`

_I32P = ctypes.POINTER(ctypes.c_int32)
_F32P = ctypes.POINTER(ctypes.c_float)


class NativeBuilderError(RuntimeError):
  """The native builder was unavailable or rejected a call at runtime.
  ``sparsecore._route_and_build`` catches this (and any other native
  failure) and falls back to the bit-exact NumPy oracle for that job,
  journaling the degradation — a broken .so must degrade a run's
  throughput, never its correctness or its life."""


def build(quiet: bool = True) -> bool:
  """Builds the shared library with make; returns success."""
  global _load_failed
  ok = nativebuild.build(_SO_NAME, _SRC_NAMES, quiet=quiet)
  if ok:
    _load_failed = False  # a later explicit build may succeed: retry load
  return ok


def _load():
  global _lib, _load_failed
  if _lib is not None:
    return _lib
  if _load_failed:
    return None
  lib = nativebuild.load(_SO_NAME, _SRC_NAMES)
  if lib is None:
    _load_failed = True
    return None
  lib.det_csr_route.restype = None
  lib.det_csr_route.argtypes = [
      _I32P, ctypes.c_int64, ctypes.c_int64, _I32P, _I32P, _I32P, _I32P,
      _I32P, ctypes.c_int32, _I32P
  ]
  lib.det_csr_counts.restype = ctypes.c_int64
  lib.det_csr_counts.argtypes = [
      _I32P, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32, _I32P
  ]
  lib.det_csr_build.restype = ctypes.c_int64
  lib.det_csr_build.argtypes = [
      _I32P, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
      ctypes.c_int32, ctypes.c_int32, ctypes.c_int, ctypes.c_int32,
      _I32P, _I32P, _I32P, _F32P
  ]
  _lib = lib
  return lib


def available() -> bool:
  return _load() is not None


def _i32(x) -> np.ndarray:
  return np.ascontiguousarray(x, dtype=np.int32)


def _ptr(a: np.ndarray):
  return a.ctypes.data_as(_F32P if a.dtype == np.float32 else _I32P)


def route_ids(ids: np.ndarray, offs, vocab, rows_cap: int, lo, hi,
              stride) -> np.ndarray:
  """Native twin of ``sparsecore._route_ids_np`` (same contract: ids
  ``[n_cap, GB, h]``, per-slot routing constants ``[n_cap]``)."""
  lib = _load()
  if lib is None:
    raise NativeBuilderError('native CSR builder not built')
  ids = _i32(ids)
  n_cap = ids.shape[0]
  gbh = int(ids.size // max(n_cap, 1))
  out = np.empty_like(ids)
  offs, vocab, lo, hi, stride = (_i32(offs), _i32(vocab), _i32(lo),
                                 _i32(hi), _i32(stride))
  lib.det_csr_route(_ptr(ids), n_cap, gbh, _ptr(offs), _ptr(vocab),
                    _ptr(lo), _ptr(hi), _ptr(stride), rows_cap, _ptr(out))
  return out


def partition_counts(routed: np.ndarray, rows_cap: int,
                     num_sc: int) -> np.ndarray:
  """Per-partition valid-id counts (the capacity-sizing pass)."""
  lib = _load()
  if lib is None:
    raise NativeBuilderError('native CSR builder not built')
  routed = _i32(routed)
  counts = np.zeros((num_sc,), np.int32)
  lib.det_csr_counts(_ptr(routed.reshape(-1)), routed.size, rows_cap,
                     num_sc, _ptr(counts))
  return counts


def build_csr(routed: np.ndarray, rows_cap: int, num_sc: int,
              combiner: Optional[str] = 'sum',
              max_ids_per_partition: Optional[int] = None):
  """Native ``build_csr_host`` twin returning the same ``HostCsr``
  (bit-exact: identical buffers, cap, and dropped count)."""
  from distributed_embeddings_tpu.parallel.sparsecore import (HostCsr,
                                                              _round_up8)
  lib = _load()
  if lib is None:
    raise NativeBuilderError('native CSR builder not built')
  routed = _i32(routed)
  n_cap, gb, h = routed.shape
  flat = routed.reshape(-1)
  if max_ids_per_partition is not None:
    cap = _round_up8(max_ids_per_partition)
  else:
    counts = partition_counts(flat, rows_cap, num_sc)
    cap = _round_up8(max(int(counts.max(initial=0)), 1))
  rp = np.empty((num_sc,), np.int32)
  eids = np.empty((num_sc * cap,), np.int32)
  sids = np.empty((num_sc * cap,), np.int32)
  gains = np.empty((num_sc * cap,), np.float32)
  dropped = lib.det_csr_build(_ptr(flat), n_cap, gb, h, rows_cap, num_sc,
                              1 if combiner == 'mean' else 0, cap,
                              _ptr(rp), _ptr(eids), _ptr(sids),
                              _ptr(gains))
  if dropped < 0:
    raise NativeBuilderError(
        f'det_csr_build rejected arguments (num_sc={num_sc}, '
        f'cap={cap}, h={h})')
  return HostCsr(row_pointers=rp, embedding_ids=eids, sample_ids=sids,
                 gains=gains, max_ids_per_partition=cap,
                 dropped=int(dropped))
