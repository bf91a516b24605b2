"""A routed (mixture-of-experts) feed-forward layer that holds a share of
the experts, drops nothing, and can be differentiated.

The layer is told the router's width (every expert of the model), how
many experts a token takes, and which contiguous run of experts lives
here (``first_expert``, ``num_held``).  It routes over ALL experts and
computes the part of the result that its own experts give; what the
absent experts would add is another chip's to compute (docs/design.md
§27).  Its work follows the assignments it holds (about ``tokens x
experts_per_token x num_held / router_width``), never ``tokens x
experts_per_token`` and never the number of experts.

Equations, ``u [T, d]`` the tokens' rows::

  s    = sigmoid(float32(u) @ router)           [T, router_width], HIGHEST
  sel  = top_k(s + expert_bias)                 expert_bias: no gradient
  w_e  = route_scale * s_e / (sum_{e' in sel} s_e' + route_norm_eps)
                                                over ALL k, held or not
  y    = sum_{e in sel, e held} w_e * expert_e(u)

  expert_e(u) = (silu(u Wgate_e) * (u Wup_e)) Wdown_e   'swiglu': gated
              = relu(u Wup_e)^2 Wdown_e                 'relu2': not gated

How it groups is the sparse apply's idiom (``parallel/sparse.py``) made
differentiable: an assignment to an absent expert takes the sentinel key
``num_held``, one stable sort by key (``routing.sort_with_order``) brings
the held assignments to the front in expert order, and a WAVE takes a
static window of the sorted assignments: it gathers the tokens'
rows into a buffer, runs one grouped product per projection over the held
experts, weights the rows and segment-sums them back to token order.  A
wave holds ``capacity_factor`` times the expected count and at most
1.25 times (its buffers are then about the tokens' own size); whatever
the router does, ``ceil(T x k / wave)`` waves cover every assignment,
so nothing is ever dropped.  The waves up to ``capacity_factor`` times
the expected count (the CAPACITY; one wave at the default 1.25) always
run, padding and all; the remaining waves lie inside ONE ``lax.cond``
(``held > capacity``: a step that fits evaluates one false predicate) as
a ``lax.scan`` of static length whose body runs its wave under a
``cond`` of its own and skips one that no assignment reaches.  So the
work follows the assignments held, a wave at a time, and
``capacity_factor`` is the one knob: a larger capacity costs its padding
every step and trips the overflow less often (what a router really
holds, and what that did to a benchmark cell's step: PERF.md section 6,
PR 31).  The apply's own
overflow wave is a ``lax.while_loop``, which has no reverse-mode rule; a
``cond`` and a ``scan`` of static length have one, and this layer sits
inside ``jax.vjp`` and ``jax.checkpoint``.  A ``cond``'s transpose makes
a zero cotangent for everything its skipped branch closes over (here the
held experts' kernels, 384 MiB at the published widths): hence one
``cond`` around a loop that carries its cotangents, not a ``cond`` a wave
(seven nested ``cond``s a layer held 24 such sets at once, compile-only
for a v5e).  All shapes are static: one compile serves every routing.

The slots of a wave past the held assignments are padding: they gather
token 0, are counted into the last expert's group so that every row of
the buffer is some expert's, and are weighted by exactly 0, forward and
backward.

The grouped products run at widths rounded up to a multiple of
``_WIDTH_TILE`` (``padded_width``), with exact zeros: XLA tiles its
``ragged-dot`` kernel on a TPU by the largest of 512, 256 and 128 that
divides each width, and the narrow tiles cost rate.  On a v5e, 7,680
rows over 8 groups, a layer's two products forward and with both
gradients take 17.96 ms at hidden 2,688 and interior 1,856 (12 and 14%
of peak) and 6.96 ms at 3,072 and 2,048 (39 and 46%), 1.26 times the
work (``examples/benchmarks/grouped_product_probe.py``, PERF.md section
6).  Once a layer, before the waves, the kernels take zero columns
of the interior (each half of a ``'swiglu'`` kernel apart) and zero
rows of the interior in ``experts_out``; a zero interior column gives
``silu(0) * 0 = relu(0)^2 = 0``, which the zero row after it multiplies
again.  Where the hidden width is rounded too, the tokens' rows and the
kernels take zero hidden columns and rows, and a wave's output is
sliced back to the hidden width before it is weighted.  The parameters,
and their gradients, keep their own shapes: a pad's transpose is a
slice.  Widths that are multiples already run as they are.  The counter
``moe.padded_expert_layers`` counts a traced layer that pads.

On one chip there is no exchange, and no code stands in for one.

Device phases (``obs.trace.phase``): ``moe/route``, ``moe/dispatch``
(keys, sort, the gather into the buffer), ``moe/experts`` (the grouped
products and the gating between them), ``moe/combine`` (weighting and the
sum back to tokens).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

from distributed_embeddings_tpu.obs import metrics as obs_metrics
from distributed_embeddings_tpu.obs import trace as obs_trace
from distributed_embeddings_tpu.parallel.routing import sort_with_order

_HIGHEST = jax.lax.Precision.HIGHEST
# The largest wave, in expected counts of held assignments: a wave's
# buffers are then about the size of the tokens' own rows.  (At the
# mixture-of-experts cell's sizes 3.75 expected counts in ONE wave put
# the step at 17.08 GiB of a v5e's 15.75; in three waves of 1.25, each
# rematerialised on its own, at 13.60: compile-only, PERF.md section 6.)
_LARGEST_WAVE = 1.25
_FORMS = ('swiglu', 'relu2')
# The width multiple at which XLA's ``ragged-dot`` kernel takes its
# widest tiles on a TPU, and the largest share of a width that rounding
# it up may add (``padded_width``).
_WIDTH_TILE = 512
_MOST_PADDING = 0.25


@dataclasses.dataclass(frozen=True)
class RoutedExpertsConfig:
  """What the layer is told.  ``router_width`` counts every expert of
  the model; ``first_expert .. first_expert + num_held - 1`` live here.
  ``expert_form`` is an expert's interior (``interior``)."""
  router_width: int
  experts_per_token: int
  num_held: int
  first_expert: int = 0
  route_scale: float = 1.0
  capacity_factor: float = 1.25
  route_norm_eps: float = 1e-20     # the normaliser's constant, a family's own
  expert_form: str = 'swiglu'

  def __post_init__(self):
    if self.expert_form not in _FORMS:
      raise NotImplementedError(
          f'routed_experts: expert_form={self.expert_form!r} (only {_FORMS})')
    if not 0 <= self.first_expert <= self.router_width - self.num_held:
      raise ValueError(
          f'routed_experts: experts {self.first_expert}..'
          f'{self.first_expert + self.num_held - 1} are not among the '
          f'router\'s {self.router_width}')
    if not 0 < self.experts_per_token <= self.router_width:
      raise ValueError('routed_experts: experts_per_token '
                       f'{self.experts_per_token} of {self.router_width}')

  def _expected(self, tokens: int) -> float:
    return (tokens * self.experts_per_token * self.num_held
            / self.router_width)

  def wave_slots(self, tokens: int) -> int:
    """Slots of one wave: ``capacity_factor`` times the expected count of
    held assignments but no more than ``_LARGEST_WAVE`` times, a
    multiple of 128 (the buffer's sublane tiles), and no more than every
    assignment there is."""
    slots = 128 * math.ceil(min(self.capacity_factor, _LARGEST_WAVE)
                            * self._expected(tokens) / 128)
    return max(1, min(slots, tokens * self.experts_per_token))

  def waves(self, tokens: int) -> int:
    """Waves that cover every assignment whatever the router does."""
    return math.ceil(tokens * self.experts_per_token
                     / self.wave_slots(tokens))

  def capacity(self, tokens: int) -> int:
    """Slots that are computed every step, padding and all:
    ``capacity_factor`` times the expected count, in whole waves."""
    wave = self.wave_slots(tokens)
    always = math.ceil(self.capacity_factor * self._expected(tokens) / wave)
    return wave * max(1, min(always, self.waves(tokens)))


def route(cfg: RoutedExpertsConfig, u, router, expert_bias):
  """``(sel [T, k] int32, weights [T, k] float32)``: the experts each
  token takes and what each one's output is multiplied by.  The scores
  are float32 at ``Precision.HIGHEST`` (a selection is discrete: a lower
  precision moves tokens between experts).  ``expert_bias`` moves the
  selection only; the weights, and every gradient, come from the scores
  themselves."""
  with obs_trace.phase('moe/route'):
    scores = jax.nn.sigmoid(jnp.matmul(
        u.astype(jnp.float32), router.astype(jnp.float32),
        precision=_HIGHEST))
    _, sel = jax.lax.top_k(
        scores + jax.lax.stop_gradient(expert_bias), cfg.experts_per_token)
    picked = jnp.take_along_axis(scores, sel, axis=-1)
    weights = cfg.route_scale * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + cfg.route_norm_eps)
    return sel, weights


def interior(form: str, h):
  """An expert's interior from its first product ``h``: ``silu(gate) *
  up`` of ``h``'s two halves (``'swiglu'``, ``h`` is ``2 x ffn`` wide) or
  ``relu(h)^2`` (``'relu2'``, ``h`` is ``ffn`` wide)."""
  if form == 'relu2':
    return jnp.square(jax.nn.relu(h))
  gate, up = jnp.split(h, 2, axis=-1)
  return jax.nn.silu(gate) * up


def padded_width(width: int) -> int:
  """The width a grouped product takes for ``width``: the next multiple
  of ``_WIDTH_TILE`` where that adds at most ``_MOST_PADDING`` of it,
  else ``width`` (more padding than that is not shown to pay: the
  widths measured grow by 14 and 10%)."""
  rounded = _WIDTH_TILE * math.ceil(width / _WIDTH_TILE)
  return rounded if rounded <= (1 + _MOST_PADDING) * width else width


def _pad_kernels(form: str, experts_in, experts_out, hidden: int,
                 ffn: int):
  """``experts_in [held, d, (2 x) f]`` and ``experts_out [held, f, d]``
  with zero rows and columns out to ``hidden`` and ``ffn``; a
  ``'swiglu'`` kernel's gate and up halves each apart."""
  d, f = experts_out.shape[2], experts_out.shape[1]
  halves = (jnp.split(experts_in, 2, axis=-1) if form == 'swiglu'
            else [experts_in])
  grown = [jnp.pad(h, ((0, 0), (0, hidden - d), (0, ffn - f)))
           for h in halves]
  return (jnp.concatenate(grown, axis=-1) if len(grown) > 1 else grown[0],
          jnp.pad(experts_out, ((0, 0), (0, ffn - f), (0, hidden - d))))


def _grouped(lhs, rhs, group_sizes):
  """``lhs [m, k]`` times ``rhs [g, k, n]``, rows ``sum(group_sizes[:e])
  ..`` by ``rhs[e]``: ``jax.lax.ragged_dot`` on float32 operands, which
  XLA lowers to a Mosaic kernel of its own on a TPU.  The Pallas
  ``megablox.gmm`` gives the same numbers to 1e-7 and takes 12 to 17%
  less time a call at the published shapes (v5e, forward and both
  gradients: ``examples/benchmarks/grouped_product_probe.py``), and its
  kernels would carry the phase they are traced in, where XLA's
  ``ragged-dot`` kernels carry none; but its 160 kernel instances add 43
  s to the compile of the mixture-of-experts cell's step (compile-only
  for a v5e: 172 s against 129), which a first run of that cell cannot
  spare (PERF.md section 6, PR 31)."""
  return jax.lax.ragged_dot(lhs, rhs, group_sizes)


def _wave(cfg, width, p, u, flat_weights, ends, lo, at, key):
  """The held experts' output for one wave's window of sorted
  assignments, summed into token order ``[T, width]``: ``at`` the flat
  assignments at the sorted places ``lo ..``, ``key`` their sorted
  keys; ``u`` and the kernels ``p`` at the products' widths, of which
  the first ``width`` hidden columns are the tokens' own."""
  k, held = cfg.experts_per_token, cfg.num_held
  slots = at.shape[0]
  with obs_trace.phase('moe/dispatch'):
    valid = key < held
    token = jnp.where(valid, at // k, 0)
    # this wave's rows of each expert; padding goes to the last one
    starts = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    sizes = (jnp.clip(ends, lo, lo + slots)
             - jnp.clip(starts, lo, lo + slots))
    sizes = sizes.at[-1].add(slots - jnp.sum(sizes))
    buffer = u[token]
  with obs_trace.phase('moe/experts'):
    out = _grouped(interior(cfg.expert_form,
                            _grouped(buffer, p['experts_in'], sizes)),
                   p['experts_out'], sizes)
    if out.shape[1] != width:
      out = out[:, :width]
  with obs_trace.phase('moe/combine'):
    weight = jnp.where(valid, flat_weights[at], 0.0)
    return jnp.zeros((u.shape[0], width), u.dtype).at[token].add(
        out * weight[:, None])


def dispatch_order(cfg: RoutedExpertsConfig, sel):
  """The assignments ``sel [T, k]`` grouped by held expert: ``(skey,
  order, ends)``, ``skey`` the sorted keys (a held expert's index here,
  ``num_held`` for an absent one), ``order`` the flat assignment
  (``token x k + slot``) at each sorted place, ``ends [num_held]`` the
  place after each held expert's last assignment."""
  local = sel.reshape(-1) - cfg.first_expert
  key = jnp.where((local >= 0) & (local < cfg.num_held), local,
                  cfg.num_held).astype(jnp.int32)
  skey, order = sort_with_order(key)
  ends = jnp.searchsorted(skey, jnp.arange(cfg.num_held, dtype=jnp.int32),
                          side='right').astype(jnp.int32)
  return skey, order, ends


def routed_experts(cfg: RoutedExpertsConfig, p: Dict, u):
  """``(y, sel)``: the held experts' part of the routed layer on ``u
  [T, d]``, and the experts each token took ``[T, k]`` (what
  ``routing_stats`` counts).  ``p``: ``router [d, router_width]``,
  ``expert_bias [router_width]``, ``experts_in [num_held, d, 2 x ffn]``
  (gate | up; ``[num_held, d, ffn]`` for ``'relu2'`` experts),
  ``experts_out [num_held, ffn, d]``.  The counters
  ``moe.ungated_expert_layers`` and ``moe.padded_expert_layers`` count
  a traced layer of ``'relu2'`` experts and one whose products take
  padded widths (``padded_width``)."""
  if cfg.expert_form == 'relu2':
    obs_metrics.inc('moe.ungated_expert_layers')
  tokens, width = u.shape
  ffn = p['experts_out'].shape[1]
  hidden, wide = padded_width(width), padded_width(ffn)
  slots, waves = cfg.wave_slots(tokens), cfg.waves(tokens)
  always = cfg.capacity(tokens) // slots
  sel, weights = route(cfg, u, p['router'], p['expert_bias'])
  with obs_trace.phase('moe/dispatch'):
    skey, order, ends = dispatch_order(cfg, sel)
    pad = waves * slots - order.shape[0]
    skey = jnp.pad(skey, (0, pad),
                   constant_values=cfg.num_held).reshape(waves, slots)
    order = jnp.pad(order, (0, pad)).reshape(waves, slots)
  kernels = {'experts_in': p['experts_in'], 'experts_out': p['experts_out']}
  rows = u
  if (hidden, wide) != (width, ffn):
    obs_metrics.inc('moe.padded_expert_layers')
    with obs_trace.phase('moe/experts'):
      kernels = dict(zip(('experts_in', 'experts_out'), _pad_kernels(
          cfg.expert_form, p['experts_in'], p['experts_out'], hidden, wide)))
      rows = jnp.pad(u, ((0, 0), (0, hidden - width)))
  flat_weights = weights.reshape(-1)
  # each wave is rematerialised on its own: the backward pass holds one
  # wave's buffers at a time.  (As one body of a scan the waves that
  # always run compile sooner and hold 1 GiB more, compile-only for a
  # v5e: the scan carries the kernels' cotangents.)
  wave = jax.checkpoint(functools.partial(_wave, cfg, width))
  y = wave(kernels, rows, flat_weights, ends, 0, order[0], skey[0])
  for w in range(1, always):
    y = y + wave(kernels, rows, flat_weights, ends, w * slots, order[w],
                 skey[w])
  if waves == always:
    return y, sel

  def overflow(args):
    """The waves past the capacity: a scan of static length (so that it
    has a reverse-mode rule) whose body skips a wave no assignment
    reaches."""
    kernels, u, flat_weights, y = args

    @jax.checkpoint
    def maybe(kernels, u, flat_weights, lo, at, key):
      # the rematerialisation is OUTSIDE the cond: what the backward
      # pass keeps is then this function's arguments, which the scan
      # knows for loop constants; residuals that leave a cond it would
      # stack a wave (six copies of the kernels, compile-only for a v5e)
      return jax.lax.cond(
          ends[-1] > lo,
          lambda: _wave(cfg, width, kernels, u, flat_weights, ends, lo, at,
                        key),
          lambda: jnp.zeros((u.shape[0], width), u.dtype))

    def body(y, xs):
      return y + maybe(kernels, u, flat_weights, *xs), None

    first = slots * jnp.arange(always, waves, dtype=ends.dtype)
    return jax.lax.scan(body, y, (first, order[always:], skey[always:]))[0]

  # one predicate a step where the capacity held everything
  return jax.lax.cond(ends[-1] > always * slots, overflow,
                      lambda args: args[3],
                      (kernels, rows, flat_weights, y)), sel


def routing_stats(cfg: RoutedExpertsConfig, sel):
  """Of one layer's selection ``sel [T, k]``: ``assignments_held`` (what
  the layer's work follows), ``load_max_over_mean`` over the held
  experts (1 is an even load) and ``overflow_rows``, the assignments
  past the capacity (``cfg.capacity``: what runs every step)."""
  _, _, ends = dispatch_order(cfg, sel)
  held = ends[-1]
  loads = jnp.diff(ends, prepend=0)
  return {
      'assignments_held': held,
      'load_max_over_mean': jnp.max(loads) * cfg.num_held
                            / jnp.maximum(held, 1),
      'overflow_rows': jnp.maximum(
          held - cfg.capacity(sel.shape[0]), 0)}
