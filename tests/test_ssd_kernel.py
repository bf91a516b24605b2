"""The state-space scan kernels (ops/pallas_ssd.py) against the
``lax.scan`` path of ``hybrid_ssm.ssd_scan`` and the benchmark's
sequential recurrence, in interpreter mode on the CPU, and the rule by
which ``ssd_scan`` chooses between the kernels and the ``lax.scan``.

The documents are ``tests/test_hybrid_ssm.py``'s ``SEGMENTS`` cases at
the kernels' chunk of 128 positions, three chunks a sequence: a boundary
inside a chunk, a document that starts at a chunk's first position, a
document of one token (one of them at a chunk's first position), one
that runs across all three chunks, and many in one chunk.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.classes import hybrid_ssm as ref
from distributed_embeddings_tpu.models import hybrid_ssm
from distributed_embeddings_tpu.obs import metrics as obs_metrics
from distributed_embeddings_tpu.ops import pallas_ssd

CHUNK = 128
LENGTH = 3 * CHUNK
HEADS, D_HEAD, STATE = 4, 64, 128
matmul = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


def _packed(*lengths):
  """One sequence's document numbers from its documents' lengths."""
  ids = np.repeat(np.arange(len(lengths)), lengths)
  assert ids.size == LENGTH
  return ids


SEGMENTS = {
    'inside_a_chunk': [_packed(40, 200, 144), _packed(100, 284)],
    'at_a_chunks_edge': [_packed(CHUNK, 2 * CHUNK), _packed(LENGTH)],
    'one_token': [_packed(64, 1, 319), _packed(1, 127, 1, 255)],
    'many_in_one_chunk': [
        _packed(*([1, 2, 3, 5, 8, 13] * 4), 2 * CHUNK),
        _packed(*([7, 1] * 16), 2 * CHUNK)],
}


def _rel(a, b):
  return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _inputs(seed=3):
  rng = np.random.default_rng(seed)
  f32 = lambda *shape: rng.standard_normal(shape).astype(np.float32)
  # dt = softplus(projection + dt_bias), A = -exp(A_log) in the model's
  # own ranges (hybrid_ssm.init_params)
  return dict(x=f32(2, LENGTH, HEADS, D_HEAD),
              dt=np.asarray(jax.nn.softplus(f32(2, LENGTH, HEADS) - 4.0)),
              a=-rng.uniform(1.0, 16.0, HEADS).astype(np.float32),
              b=f32(2, LENGTH, STATE), c=f32(2, LENGTH, STATE))


def _readings(scan, v, weight):
  """``scan``'s output and the gradient of every input."""
  out = scan(*v.values())
  grads = jax.grad(lambda *args: jnp.sum(scan(*args) * weight),
                   argnums=tuple(range(5)))(*v.values())
  return (out,) + grads


@pytest.fixture
def interpreted(monkeypatch):
  monkeypatch.setattr(pallas_ssd, 'FORCE_INTERPRET', True)


def _one_group(scan):
  """``scan`` on ``b``, ``c`` ``[S, L, N]``, the reference's form: B and
  C as ``[S, L, 1, N]``, one group that every head reads."""
  return lambda x, dt, a, b, c, *rest: scan(x, dt, a, b[:, :, None],
                                            c[:, :, None], *rest)


def _three_paths(layout):
  seg = jnp.asarray(np.stack(SEGMENTS[layout]), jnp.int32)
  kernel = _one_group(lambda *v: pallas_ssd.scan(*v, seg, CHUNK))

  @_one_group
  def chunked(*v):
    with pytest.MonkeyPatch.context() as mp:
      mp.setattr(pallas_ssd, 'FORCE_INTERPRET', False)
      assert not pallas_ssd.takes(v[0].shape, STATE, CHUNK)
      return hybrid_ssm.ssd_scan(*v, seg, CHUNK)

  sequential = lambda *v: ref._sequential_scan(*v, seg, matmul)
  return kernel, chunked, sequential


@pytest.mark.parametrize('layout', sorted(SEGMENTS))
def test_kernels_are_the_chunked_scan_with_bfloat16_operands(interpreted,
                                                             layout):
  """Output and the gradients of x, dt, A, B and C: the kernels round
  every product's operands to bfloat16, the other two paths compute in
  float32 here, so they differ by that rounding and no more."""
  v = _inputs()
  weight = np.random.default_rng(4).standard_normal(
      v['x'].shape).astype(np.float32)
  kernel, chunked, sequential = _three_paths(layout)
  assert pallas_ssd.takes(v['x'].shape, STATE, CHUNK)
  with jax.default_matmul_precision('highest'):
    got = _readings(kernel, v, weight)
    for other in (chunked, sequential):
      want = _readings(other, v, weight)
      for name, g, w in zip(('y',) + tuple(v), got, want):
        assert g.dtype == jnp.float32 and g.shape == w.shape
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert _rel(g, w) < 1e-2, (name, _rel(g, w))


@pytest.fixture
def float32_operands(monkeypatch):
  """The kernels with no rounding of their products' operands.  They are
  jitted, so JAX's caches are emptied around the test: before, lest a
  kernel traced with the rounding be reused, and after, lest this one be."""
  monkeypatch.setattr(pallas_ssd, '_bf16', lambda a: a)
  jax.clear_caches()
  yield
  jax.clear_caches()


@pytest.mark.parametrize('layout', ['one_token', 'many_in_one_chunk'])
def test_kernels_math_is_the_recurrence_with_float32_operands(
    interpreted, float32_operands, layout):
  """With the operands' rounding taken out the kernels compute the
  recurrence to float32's precision: every document rule, the running
  sum, and every gradient's formula."""
  v = _inputs(5)
  weight = np.random.default_rng(6).standard_normal(
      v['x'].shape).astype(np.float32)
  kernel, _, sequential = _three_paths(layout)
  with jax.default_matmul_precision('highest'):
    got = _readings(kernel, v, weight)
    want = _readings(sequential, v, weight)
  for name, g, w in zip(('y',) + tuple(v), got, want):
    assert _rel(g, w) < 1e-5, (name, _rel(g, w))


def test_a_document_moves_nothing_of_the_next(interpreted):
  """A change to one token of a document that ends mid-chunk leaves the
  next document's output the same to the bit."""
  seg = jnp.asarray(np.stack(SEGMENTS['inside_a_chunk']), jnp.int32)
  v = _inputs(7)
  scan = lambda x: _one_group(pallas_ssd.scan)(x, v['dt'], v['a'], v['b'],
                                               v['c'], seg, CHUNK)
  before = np.asarray(scan(v['x']))
  after = np.asarray(scan(jnp.asarray(v['x']).at[0, 20].add(1.0)))
  np.testing.assert_array_equal(before[0, 40:], after[0, 40:])
  np.testing.assert_array_equal(before[1], after[1])
  assert np.any(before[0, 20:40] != after[0, 20:40])


def test_takes_on_a_tpu_at_shapes_that_fit(monkeypatch):
  shape = (2, LENGTH, HEADS, D_HEAD)
  # a CPU backend and no hook: the lax.scan, whatever the shapes
  assert not pallas_ssd.takes(shape, STATE, CHUNK)
  monkeypatch.setattr(pallas_ssd, 'ASSUME_TPU', True)
  assert pallas_ssd.takes(shape, STATE, CHUNK)
  assert pallas_ssd.takes((2, 4096, 64, 64), 128, 256)   # the cell's
  # compiled for a TPU, and a chunk, a state or a head block that fills
  # no lane tile
  assert not pallas_ssd.takes(shape, STATE, 64)
  assert not pallas_ssd.takes(shape, 16, CHUNK)
  assert not pallas_ssd.takes((2, LENGTH, 1, 64), STATE, CHUNK)
  assert not pallas_ssd.takes((2, LENGTH, 3, 8), STATE, CHUNK)


def test_a_counter_says_which_mamba_layers_take_the_kernels(monkeypatch):
  cfg = hybrid_ssm.HybridSSMConfig(
      hidden_size=64, layer_types=('mamba', 'attention', 'mamba', 'mamba'),
      intermediate_size=64, mamba_n_heads=2, mamba_d_head=64,
      mamba_d_state=STATE, mamba_d_conv=4, mamba_chunk_size=CHUNK,
      num_attention_heads=4, num_key_value_heads=2)
  dense = hybrid_ssm.init_params(cfg, 1)
  rows = jnp.zeros((1, 2 * CHUNK, cfg.hidden_size))
  seg = jnp.zeros((1, 2 * CHUNK), jnp.int32)

  def count():
    obs_metrics.reset()
    obs_metrics.enable()
    try:
      jax.eval_shape(lambda d, r: hybrid_ssm.forward(cfg, d, r, seg),
                     dense, rows)
      return obs_metrics.snapshot().get('mixer.scan_kernel_layers', 0)
    finally:
      obs_metrics.disable()
      obs_metrics.reset()

  assert count() == 0
  monkeypatch.setattr(pallas_ssd, 'ASSUME_TPU', True)
  assert count() == 3
