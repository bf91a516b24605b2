"""The routed layer (layers/routed_experts.py) at a small size: 16 experts
of 32 over hidden 64, 4 a token, 96 tokens; a share of the experts held,
the rest another chip's.

The oracle is a dense loop over the held experts, each a SwiGLU over
every token times the token's weight for it, with the weights from the
benchmark's plain reference (``benchmarks/classes/moe_lm.py``: nothing
of the program imported there).
"""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from benchmarks.classes import moe_lm as ref
from distributed_embeddings_tpu.layers import routed_experts as routed

HIDDEN, FFN, WIDTH, PER_TOKEN, TOKENS = 64, 32, 16, 4, 96
HIGHEST = jax.lax.Precision.HIGHEST
matmul = functools.partial(jnp.matmul, precision=HIGHEST)

SHARES = {'all': (0, 16), 'first_quarter': (0, 4), 'last_quarter': (12, 4)}


def _cfg(first, held, **kw):
  return routed.RoutedExpertsConfig(
      router_width=WIDTH, experts_per_token=PER_TOKEN, num_held=held,
      first_expert=first, route_scale=2.826, **kw)


def _params(seed, first, held, bias=None):
  """The whole layer's experts drawn once; a share takes its slice."""
  rng = np.random.default_rng(seed)
  f32 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
  every_in = f32(WIDTH, HIDDEN, 2 * FFN) / np.sqrt(HIDDEN)
  every_out = f32(WIDTH, FFN, HIDDEN) / np.sqrt(FFN)
  return {'router': f32(HIDDEN, WIDTH) / np.sqrt(HIDDEN),
          'expert_bias': (jnp.zeros(WIDTH) if bias is None
                          else jnp.asarray(bias, jnp.float32)),
          'experts_in': every_in[first:first + held],
          'experts_out': every_out[first:first + held]}


def _tokens(seed):
  return jnp.asarray(np.random.default_rng(seed).standard_normal(
      (TOKENS, HIDDEN)), jnp.float32)


def dense_loop(first, held, p, u):
  """The held experts' part, an expert at a time over every token."""
  z = dict(router=WIDTH, per_token=PER_TOKEN)
  weights = ref.dense_routing_weights(z, {'route_scale': 2.826}, p, u)
  y = jnp.zeros_like(u)
  for e in range(held):
    gate, up = jnp.split(matmul(u, p['experts_in'][e]), 2, axis=-1)
    y = y + weights[:, first + e, None] * matmul(
        jax.nn.silu(gate) * up, p['experts_out'][e])
  return y


def _both(first, held, p, u, seed=5):
  """Value and gradients (every parameter, and the tokens) of the layer
  and of the loop under one random cotangent."""
  cot = jnp.asarray(np.random.default_rng(seed).standard_normal(u.shape),
                    jnp.float32)
  cfg = _cfg(first, held)
  with jax.default_matmul_precision('highest'):
    got = jax.value_and_grad(
        lambda p, u: jnp.sum(cot * routed.routed_experts(cfg, p, u)[0]),
        argnums=(0, 1))(p, u)
  want = jax.value_and_grad(
      lambda p, u: jnp.sum(cot * dense_loop(first, held, p, u)),
      argnums=(0, 1))(p, u)
  return got, want


def _assert_close(got, want):
  (a, ga), (b, gb) = got, want
  np.testing.assert_allclose(a, b, rtol=2e-5)
  flat_a, _ = jax.tree_util.tree_flatten_with_path(ga)
  for (path, x), y in zip(flat_a, jax.tree.leaves(gb)):
    scale = float(jnp.max(jnp.abs(y))) + 1e-30
    np.testing.assert_allclose(x / scale, y / scale, atol=2e-5,
                               err_msg=str(path))


@pytest.mark.parametrize('share', sorted(SHARES))
def test_layer_matches_a_dense_loop_over_the_held_experts(share):
  """Output, and the gradient in every parameter (the router's too) and
  in the tokens, whatever share of the experts is held."""
  first, held = SHARES[share]
  p, u = _params(1, first, held), _tokens(2)
  with jax.default_matmul_precision('highest'):
    y, _ = routed.routed_experts(_cfg(first, held), p, u)
  np.testing.assert_allclose(y, dense_loop(first, held, p, u), atol=2e-5)
  got, want = _both(first, held, p, u)
  _assert_close(got, want)
  # the selection bias moves the choice alone: no gradient reaches it
  assert not np.any(np.asarray(got[1][0]['expert_bias']))
  assert np.any(np.asarray(got[1][0]['router']))


def test_the_shares_add_up_to_the_whole_layer():
  """Four shares of four experts: the parts sum to what the uncut layer
  gives (the guide's tie of a chip's share to the model; what every chip
  computes alike, the shared expert, is outside this layer and counted
  once by ``models/moe_lm.routed_ffn``)."""
  u = _tokens(3)
  whole = dense_loop(0, WIDTH, _params(4, 0, WIDTH), u)
  with jax.default_matmul_precision('highest'):
    parts = sum(routed.routed_experts(_cfg(first, 4),
                                      _params(4, first, 4), u)[0]
                for first in (0, 4, 8, 12))
  np.testing.assert_allclose(parts, whole, atol=3e-5)
  assert float(jnp.max(jnp.abs(whole))) > 0.1


@pytest.mark.parametrize('factor, capacity',
                         [(1.25, 128), (2.5, 256), (4.0, 384)])
def test_nothing_is_dropped_whatever_the_router_does(factor, capacity):
  """A router forced so that every token chooses held experts only (four
  times the expected load: every wave runs), one forced so that none
  does (no load), and a random one: each equals the loop in value and in
  every gradient, through ONE compiled function; whether the capacity
  (what runs every step) is one wave of the three, two, or all of them."""
  first, held = 4, 4
  cfg = _cfg(first, held, capacity_factor=factor)
  assert (cfg.wave_slots(TOKENS), cfg.waves(TOKENS)) == (128, 3)
  assert cfg.capacity(TOKENS) == capacity
  here = np.zeros(WIDTH, np.float32)
  here[first:first + held] = 10.0
  u = _tokens(6)
  cot = jnp.asarray(np.random.default_rng(7).standard_normal(u.shape),
                    jnp.float32)

  @jax.jit
  def layer(p, u):
    with jax.default_matmul_precision('highest'):
      return jax.value_and_grad(
          lambda p, u: jnp.sum(cot * routed.routed_experts(cfg, p, u)[0]),
          argnums=(0, 1))(p, u)

  loads = {}
  for name, bias in (('all_here', here), ('none_here', -here),
                     ('random', None)):
    p = _params(8, first, held, bias)
    sel, _ = routed.route(cfg, u, p['router'], p['expert_bias'])
    loads[name] = int(routed.routing_stats(cfg, sel)['assignments_held'])
    want = jax.value_and_grad(
        lambda p, u: jnp.sum(cot * dense_loop(first, held, p, u)),
        argnums=(0, 1))(p, u)
    _assert_close(layer(p, u), want)
  assert loads['all_here'] == TOKENS * PER_TOKEN > 2 * cfg.wave_slots(TOKENS)
  assert loads['none_here'] == 0 < loads['random'] < TOKENS * PER_TOKEN
  assert layer._cache_size() == 1


def test_the_layer_is_differentiated_without_a_while_loop():
  """The apply's overflow wave is a ``lax.while_loop``, which has no
  reverse-mode rule; this layer's waves are ``cond``s, under
  ``jax.checkpoint`` too."""
  cfg, p, u = _cfg(4, 4), _params(9, 4, 4), _tokens(10)
  fn = jax.checkpoint(lambda p, u: jnp.sum(
      routed.routed_experts(cfg, p, u)[0] ** 2))
  text = str(jax.make_jaxpr(jax.grad(fn, argnums=(0, 1)))(p, u))
  assert 'while' not in text and 'cond' in text


def test_routing_stats_count_held_overflow_and_imbalance():
  cfg = _cfg(0, 4)
  # 8 tokens x 4 choices; experts 0..3 held: loads 8, 4, 0, 0
  sel = jnp.asarray([[0, 1, 8, 9]] * 4 + [[0, 10, 11, 12]] * 4, jnp.int32)
  stats = routed.routing_stats(cfg, sel)
  assert int(stats['assignments_held']) == 12
  assert float(stats['load_max_over_mean']) == pytest.approx(8 / 3)
  assert cfg.capacity(8) == 32 and int(stats['overflow_rows']) == 0
  # 32 times those tokens against the smallest wave there is (one tile
  # of 128 slots): what lies past it
  assert int(routed.routing_stats(
      _cfg(0, 4, capacity_factor=1e-9), jnp.tile(sel, (32, 1)))
             ['overflow_rows']) == 12 * 32 - 128
  tight = _cfg(0, 4, capacity_factor=0.01)
  assert tight.capacity(96) == 128    # a multiple of the buffer's tiles
  # a factor past the largest wave adds whole waves, not a larger buffer
  wide = routed.RoutedExpertsConfig(router_width=128, experts_per_token=8,
                                    num_held=16, capacity_factor=3.75)
  assert (wide.wave_slots(16384), wide.capacity(16384),
          wide.waves(16384)) == (20480, 61440, 7)


def test_a_share_outside_the_router_is_refused_by_name():
  with pytest.raises(ValueError, match='not among the router'):
    _cfg(14, 4)
