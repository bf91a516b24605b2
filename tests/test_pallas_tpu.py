"""Pallas lookup kernel on REAL TPU hardware: compiled correctness +
microbenchmark vs the XLA fallback.

The interpreter tests (test_pallas_lookup.py) validate semantics; DMA and
semaphore behaviour only exist on the chip, so these run compiled
(``interpret=False``).  Skipped on the CPU mesh — run on the chip with::

    DET_TESTS_REAL_TPU=1 python -m pytest tests/test_pallas_tpu.py -v -s

(DET_TESTS_REAL_TPU stops conftest.py from forcing the CPU backend.)  To
ask for the chip and not get one is an error, not 28 green skips.
"""

import os

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_embeddings_tpu.ops import pallas_lookup
from distributed_embeddings_tpu.parallel.dist_embedding import _fused_lookup

if (os.environ.get('DET_TESTS_REAL_TPU') == '1'
    and jax.default_backend() != 'tpu'):
  raise RuntimeError(
      'DET_TESTS_REAL_TPU=1 but JAX found no TPU (backend '
      f'{jax.default_backend()!r}): the hardware-gated suite was asked '
      'for and cannot run here')

requires_tpu = pytest.mark.skipif(
    jax.default_backend() != 'tpu',
    reason='needs a real TPU (DET_TESTS_REAL_TPU=1)')


def _bench(fn, table, stacks, iters):
  """Per-step ms of ``fn(table, ids)`` via one jitted scan per stack.

  Distinct ids per scan step so nothing hoists out of the loop, a
  full-output checksum against DCE, completion forced by the host
  transfer of that scalar, a fresh stack per timed call.
  """

  def run(tab, s):
    def body(c, ids):
      return c + jnp.sum(fn(tab, ids)), None
    return jax.lax.scan(body, jnp.float32(0), s)[0]

  f = jax.jit(run)
  float(f(table, stacks[0]))  # compile + warm
  times = []
  for s in stacks[1:]:
    start = time.perf_counter()
    float(f(table, s))
    times.append(time.perf_counter() - start)
  return min(times) / iters * 1e3


@requires_tpu
@pytest.mark.parametrize('w', [8, 16, 32, 64, 128, 256])
@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
def test_compiled_matches_oracle(w, dtype):
  if dtype == jnp.bfloat16 and w > 128:
    pytest.skip('wide bf16 takes the XLA fallback (pallas_lookup.supported)')
  rng = np.random.default_rng(0)
  vocab, m, h = 4096, 512, 4
  table = jnp.asarray(rng.normal(size=(vocab, w))).astype(dtype)
  ids = rng.integers(0, vocab, size=(m, h)).astype(np.int32)
  ids[::3, 2:] = vocab  # padding sentinel
  ids = jnp.asarray(ids)
  got = pallas_lookup.dense_lookup(table, ids, 'sum',
                                   out_dtype=jnp.float32)
  want = _fused_lookup(table, ids[None], 'sum', jnp.float32)[0]
  tol = 1e-5 if dtype == jnp.float32 else 2e-2
  np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                             rtol=tol, atol=tol)


@requires_tpu
@pytest.mark.parametrize('w,hot', [(8, 4), (32, 2), (64, 1), (128, 1)])
def test_microbench_vs_xla_fallback(w, hot):
  """Record kernel-vs-XLA timings; the measured outcome (XLA's gather
  wins at every shape on v5e — docs/perf_notes.md) is why 'auto'
  dispatches to XLA.  The assert only flags pathological regression."""
  rng = np.random.default_rng(1)
  vocab, m, iters = 1_000_000, 16384, 20
  table = jnp.asarray(rng.normal(size=(vocab, w)).astype(np.float32))
  stacks = [
      jnp.asarray(
          rng.integers(0, vocab, size=(iters, m, hot)).astype(np.int32))
      for _ in range(3)
  ]

  pl_fn = lambda t, i: pallas_lookup.dense_lookup(t, i, 'sum',
                                                  out_dtype=jnp.float32)
  xla_fn = lambda t, i: _fused_lookup(t, i[None], 'sum', jnp.float32)[0]
  t_pl = _bench(pl_fn, table, stacks, iters)
  t_xla = _bench(xla_fn, table, stacks, iters)
  ids = stacks[0][0]
  np.testing.assert_allclose(np.asarray(jax.jit(pl_fn)(table, ids)),
                             np.asarray(jax.jit(xla_fn)(table, ids)),
                             rtol=1e-5, atol=1e-5)
  print(f'\nwidth {w} hot {hot}: pallas {t_pl:.3f} ms, '
        f'xla {t_xla:.3f} ms ({t_xla / t_pl:.2f}x)')
  # soft bound: the kernel must never be pathologically slower
  assert t_pl < 5 * t_xla


@requires_tpu
@pytest.mark.parametrize('op', ['sgd', 'adagrad_dedup', 'adagrad_sq'])
@pytest.mark.parametrize('w', [16, 128])
def test_segwalk_apply_compiled_matches_oracle(op, w):
  """Fused segment-walk apply (ops/pallas_segwalk.py) compiled on the
  chip: the per-row SMEM walk, carry threading, and RMW DMA bursts only
  exist on hardware."""
  from test_pallas_segwalk import oracle, LR, EPS
  from distributed_embeddings_tpu.ops import pallas_segwalk
  rng = np.random.default_rng(4)
  rows, n = 50_000, 20_000
  table = rng.normal(size=(rows, w)).astype(np.float32)
  acc = None if op == 'sgd' else rng.uniform(
      0.05, 0.5, size=(rows, w)).astype(np.float32)
  ids = rng.integers(0, rows, n).astype(np.int32)
  ids[rng.random(n) < 0.1] = rows  # sentinel tail after sort
  # power-law-ish duplicates: fold a chunk onto few hot rows
  ids[:2000] = rng.integers(0, 50, 2000)
  grads = rng.normal(size=(n, w)).astype(np.float32)
  want_t, want_a = oracle(op, table, acc, ids, grads)
  # compiled (interpret=False): bypass run_kernel's interpret=True
  order = np.argsort(ids, kind='stable')
  sid = jnp.asarray(ids[order], jnp.int32)
  sg = jnp.asarray(grads[order], jnp.float32)
  if op == 'sgd':
    got_t = np.asarray(pallas_segwalk.segwalk_apply(
        jnp.asarray(table), None, sid, sg, LR, op=op, eps=EPS))
    got_a = None
  else:
    t2, a2 = pallas_segwalk.segwalk_apply(
        jnp.asarray(table), jnp.asarray(acc), sid, sg, LR, op=op,
        eps=EPS)
    got_t, got_a = np.asarray(t2), np.asarray(a2)
  np.testing.assert_allclose(got_t, want_t, rtol=1e-4, atol=1e-4)
  if got_a is not None:
    np.testing.assert_allclose(got_a, want_a, rtol=1e-4, atol=1e-4)


@requires_tpu
@pytest.mark.parametrize('w,n', [(16, 1 << 21), (128, 1 << 18)])
def test_segwalk_apply_microbench(w, n):
  """Segment-walk (sorted raw stream in, no compaction) vs the XLA
  compact-then-apply pipeline at synthetic-tiny-like scale: this is the
  round-3 perf bet — the ~300 ms compaction pipeline should collapse
  into the stream read (docs/perf_notes.md, multi-chip model)."""
  from distributed_embeddings_tpu.ops import pallas_segwalk
  from distributed_embeddings_tpu.parallel.sparse import (SparseAdagrad,
                                                          _Stream,
                                                          _dedup_and_apply)
  rng = np.random.default_rng(5)
  rows = 8_000_000 if w == 16 else 1_000_000
  iters = 3
  table = jnp.zeros((rows, w), jnp.float32) + 0.5
  acc = jnp.ones((rows, w), jnp.float32)
  opt = SparseAdagrad(learning_rate=0.01, dedup=True)
  stacks = []
  for _ in range(3):
    s = np.empty((iters, n), np.int32)
    for i in range(iters):
      # zipf-ish duplicates like the power-law generator
      raw = (rng.pareto(1.05, n) * 1000).astype(np.int64) % rows
      s[i] = raw.astype(np.int32)
    stacks.append(jnp.asarray(s))
  g = jnp.asarray(rng.normal(size=(n, w)).astype(np.float32))

  def segwalk_fn(tab, ac, ids):
    order = jnp.argsort(ids)
    return pallas_segwalk.segwalk_apply(
        tab, ac, ids[order].astype(jnp.int32), g[order], 0.01,
        op='adagrad_dedup', eps=1e-7)

  def xla_fn(tab, ac, ids):
    t2, s2 = _dedup_and_apply(opt, tab, {'acc': ac},
                              _Stream(ids, g, rows), 0.01)
    return t2, s2['acc']

  def bench(fn):
    def run(tab, ac, s):
      def body(carry, ids):
        t2, a2 = fn(*carry, ids)
        return (t2, a2), None
      (t2, a2), _ = jax.lax.scan(body, (tab, ac), s)
      return jnp.sum(t2[:8]) + jnp.sum(a2[:8])
    f = jax.jit(run)
    float(f(table, acc, stacks[0]))
    times = []
    for s in stacks[1:]:
      start = time.perf_counter()
      float(f(table, acc, s))
      times.append(time.perf_counter() - start)
    return min(times) / iters * 1e3

  t_sw = bench(segwalk_fn)
  t_xla = bench(xla_fn)
  print(f'\nsegwalk apply w={w} n={n}: segwalk {t_sw:.1f} ms, '
        f'xla pipeline {t_xla:.1f} ms ({t_xla / t_sw:.2f}x)')
  assert t_sw < 5 * t_xla


# Raw int32 bit patterns the f32 id sideband must carry unscathed
# (advisor r4, pallas_segwalk.py:573): every practical id (< 2^23) is a
# DENORMAL f32, and synthetic patterns cover NaN/inf/sign-bit encodings —
# FTZ or NaN canonicalization anywhere in the select -> DMA -> bitcast
# chain would silently scatter updates to wrong rows.
_SIDEBAND_PATTERNS = np.array(
    [
        0, 1, 2, 3, 7, 255, 65535, 123456,      # denormal patterns
        (1 << 23) - 1,                          # largest denormal
        1 << 23,                                # smallest normal
        0x7F800000,                             # +inf pattern
        0x7F800001, 0x7FC00000, 0x7FFFFFFF,     # sNaN / qNaN / max-NaN
        -0x80000000, -1,                        # -0.0 / -NaN patterns
        0x00400001, 0x007FFFFF,                 # mid/top denormals
    ],
    dtype=np.int64).astype(np.int32)


@requires_tpu
@pytest.mark.parametrize('stream_dtype', ['float32', 'bfloat16'])
def test_sideband_bit_roundtrip_compiled(stream_dtype):
  """Round-trip the EXACT host sideband encoding through a compiled
  kernel using the EXACT in-kernel decoding (pallas_segwalk.py:233-246):
  lane-iota select into the padded gradient block, DMA to VMEM, bitcast
  back.  Bit-exact or the segwalk path is unsafe on this hardware."""
  from jax.experimental import pallas as pl
  from jax.experimental.pallas import tpu as pltpu
  gw, n = 16, 256
  ids = jnp.asarray(np.resize(_SIDEBAND_PATTERNS, n))
  sdt = jnp.dtype(stream_dtype)

  def kernel(g_ref, out_ref):
    blk = g_ref[:]
    if sdt == jnp.bfloat16:
      lo = jax.lax.bitcast_convert_type(blk[:, gw:gw + 1],
                                        jnp.uint16).astype(jnp.int32)
      hi = jax.lax.bitcast_convert_type(blk[:, gw + 1:gw + 2],
                                        jnp.uint16).astype(jnp.int32)
      oid = jnp.left_shift(hi, 16) | lo
    else:
      oid = jax.lax.bitcast_convert_type(blk[:, gw:gw + 1], jnp.int32)
    out_ref[:] = jnp.broadcast_to(oid, (n, 128))

  @jax.jit
  def roundtrip(ids):
    grads = jnp.full((n, gw), 0.25, sdt)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, 128), 1)
    gpad = jnp.pad(grads, ((0, 0), (0, 128 - gw)))
    if sdt == jnp.bfloat16:
      ids_bf = jax.lax.bitcast_convert_type(ids, jnp.bfloat16)
      comb = jnp.where(
          lane == gw, ids_bf[:, 0:1],
          jnp.where(lane == gw + 1, ids_bf[:, 1:2], gpad))
    else:
      comb = jnp.where(
          lane == gw,
          jax.lax.bitcast_convert_type(ids, jnp.float32)[:, None], gpad)
    return pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((n, 128), lambda i: (0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((n, 128), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, 128), jnp.int32))(comb)

  got = np.asarray(roundtrip(ids))
  np.testing.assert_array_equal(got[:, 0], np.asarray(ids))
  np.testing.assert_array_equal(got[:, 77], np.asarray(ids))


@requires_tpu
@pytest.mark.parametrize('stream_dtype', ['float32', 'bfloat16'])
def test_segwalk_sideband_denormal_ids_end_to_end(stream_dtype):
  """Drive the REAL segwalk apply with id-coded gradients: if any
  denormal id pattern is flushed, its update lands on row 0 instead of
  its own row and the comparison fails loudly."""
  from test_pallas_segwalk import oracle, LR, EPS
  from distributed_embeddings_tpu.ops import pallas_segwalk
  w, rows, n = 16, 4096, 2048
  rng = np.random.default_rng(7)
  ids = np.sort(rng.integers(0, rows, n)).astype(np.int32)
  grads = ((ids[:, None] % 97 + 1) / 97.0 *
           np.ones((n, w))).astype(np.float32)
  if stream_dtype == 'bfloat16':
    # the bf16 stream is bit-identical on PRE-QUANTIZED gradients
    # (ROUND4_NOTES): quantize both kernel input and oracle input
    grads = np.asarray(jnp.asarray(grads, jnp.bfloat16).astype(jnp.float32))
  table = rng.normal(size=(rows, w)).astype(np.float32)
  want_t, _ = oracle('sgd', table, None, ids, grads)
  got_t = np.asarray(
      pallas_segwalk.segwalk_apply(jnp.asarray(table), None,
                                   jnp.asarray(ids), jnp.asarray(grads),
                                   LR, op='sgd', eps=EPS,
                                   stream_dtype=stream_dtype))
  np.testing.assert_allclose(got_t, want_t, rtol=1e-5, atol=1e-5)
