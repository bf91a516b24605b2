"""The fused attention kernels (ops/pallas_attention.py) against
``hybrid_ssm._attend`` on the whole sequence, in interpreter mode on the
CPU, and the rule by which ``blocked_attention`` chooses between them and
the unrolled ``jax.numpy`` blocks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_embeddings_tpu.models import hybrid_ssm
from distributed_embeddings_tpu.obs import metrics as obs_metrics
from distributed_embeddings_tpu.ops import pallas_attention

BLOCK = 128      # the tile these tests give the kernels: four to a sequence
LENGTH = 4 * BLOCK


def _documents():
  """``[2, LENGTH]`` document numbers: one that ends inside a block, a
  one-token document, one that ends at a block's edge, one that spans
  two edges; and a sequence that is one document."""
  seg = np.zeros((2, LENGTH), np.int32)
  ends = [100, 101, 2 * BLOCK, 2 * BLOCK + 1, 3 * BLOCK + 77, LENGTH]
  first = 0
  for doc, end in enumerate(ends):
    seg[0, first:end] = doc
    first = end
  return jnp.asarray(seg)


def _bf16(x):
  return x.astype(jnp.bfloat16).astype(jnp.float32)


@pytest.fixture
def interpreted(monkeypatch):
  monkeypatch.setattr(pallas_attention, 'FORCE_INTERPRET', True)
  monkeypatch.setattr(pallas_attention, 'BLOCK', BLOCK)


@pytest.mark.parametrize('group', [8, 4])
@pytest.mark.parametrize('d', [128, 64])
@pytest.mark.parametrize('window', [None, 200, 1])
def test_kernel_is_attend_on_the_whole_sequence(interpreted, window, d, group):
  rng = np.random.default_rng(7)
  kv_heads = 2
  draw = lambda *shape: jnp.asarray(
      rng.standard_normal(shape, dtype=np.float32))
  # operands and a cotangent that bfloat16 holds exactly, so that what is
  # left between the two paths is the order of float32 sums and the
  # rounding of the soft-max weights and of their gradient as operands of
  # the later products: a key too many or too few in a window of 200
  # would move a row by a two-hundredth
  scale = 0.125
  q = _bf16(draw(2, LENGTH, kv_heads, group, d))
  k = _bf16(draw(2, LENGTH, kv_heads, d))
  v = _bf16(draw(2, LENGTH, kv_heads, d))
  weights = _bf16(draw(2, LENGTH, kv_heads, group, d))
  seg = _documents()

  def readings(core):
    out = core(q, k, v)
    return (out,) + jax.grad(
        lambda q, k, v: jnp.sum(core(q, k, v) * weights), (0, 1, 2))(q, k, v)

  assert pallas_attention.takes(q.shape)
  want = readings(lambda q, k, v: hybrid_ssm._attend(
      scale, q, k, v, seg, seg, 0, None, window))
  got = readings(lambda q, k, v: hybrid_ssm.blocked_attention(
      scale, q, k, v, seg, 64, window))
  for name, a, b in zip(('out', 'dq', 'dk', 'dv'), got, want):
    assert a.dtype == jnp.float32 and a.shape == b.shape
    assert bool(jnp.all(jnp.isfinite(a))), name
    # (under a window of one position the gradients of q and k are nought)
    gap = float(jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(b)), 1))
    assert gap < 4e-3, (name, gap)


def _count(core, *operands):
  """The registry after ``core`` is traced once (through a function of
  its own: JAX traces one function on one set of shapes once)."""
  obs_metrics.reset()
  obs_metrics.enable()
  try:
    jax.eval_shape(lambda *args: core(*args), *operands)
    return obs_metrics.snapshot()
  finally:
    obs_metrics.disable()
    obs_metrics.reset()


def test_blocked_path_on_a_cpu_and_where_no_tile_fits(monkeypatch):
  q = jnp.zeros((1, LENGTH, 2, 4, 64))
  k = v = jnp.zeros((1, LENGTH, 2, 64))
  seg = jnp.zeros((1, LENGTH), jnp.int32)
  core = lambda q, k, v, seg: hybrid_ssm.blocked_attention(
      0.125, q, k, v, seg, 64, None)
  monkeypatch.setattr(pallas_attention, 'BLOCK', BLOCK)
  # a CPU backend and no hook: the unrolled blocks, whatever the shapes
  assert not pallas_attention.takes(q.shape)
  assert _count(core, q, k, v, seg) == {'attention.blocked_layers': 1.0}
  monkeypatch.setattr(pallas_attention, 'ASSUME_TPU', True)
  assert pallas_attention.takes(q.shape)
  assert _count(core, q, k, v, seg) == {'attention.kernel_layers': 1.0}
  # compiled for a TPU, and a length that is no multiple of the tile, or
  # a head width that fills no lane tile
  short = LENGTH - 48
  assert not pallas_attention.takes((1, short, 2, 4, 64))
  assert not pallas_attention.takes((1, LENGTH, 2, 4, 8))
  assert _count(core, q[:, :short], k[:, :short], v[:, :short],
                seg[:, :short]) == {'attention.blocked_layers': 1.0}
