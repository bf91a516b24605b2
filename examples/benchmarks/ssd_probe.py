"""Time one Mamba layer's selective scan on the live backend as the step
runs it: the ``lax.scan`` over ``_ssd_chunk`` against the kernels of
``ops/pallas_ssd.py`` at each head block of ``--heads``.

The shapes are ``granite-train-packed``'s (2 x 4,096 positions, 64 heads
of 64, state 128, chunks of 256) on documents of log-normal length
(median 512, sigma 1.25) packed without padding, as the cell's traffic.
Each reading is the scan's output and the gradients of ``x``, ``dt``,
``A``, ``B`` and ``C`` under one ``jax.checkpoint``, as the layer's
checkpoint has it in the step: the forward, the forward again and the
backward.  ``gap`` is the largest difference from the ``lax.scan``
path's output and gradients over their largest magnitude.

``fwd`` and ``bwd`` time each kernel alone.  With ``--layer`` the
probe also times one whole Mamba block of the cell (``hybrid_ssm.layer``
under ``jax.checkpoint``, the gradients of its parameters and input) on
either path, and traces it: device ms a call per scope path, and its
longest ops.

Usage: python examples/benchmarks/ssd_probe.py [--heads 2,4,8,16]
       [--iters 10] [--layer] [--out chiprun_out/ssd_probe.jsonl]
The readings are in docs/perf_notes.md, "The state-space scan kernels".
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SHAPE = dict(seqs=2, length=4096, heads=64, d_head=64, state=128, chunk=256)
NAMES = ('y', 'x', 'dt', 'a', 'b', 'c')


def main():
  parser = argparse.ArgumentParser()
  parser.add_argument('--heads', default='2,4,8,16')
  parser.add_argument('--iters', type=int, default=10)
  parser.add_argument('--seed', type=int, default=0)
  parser.add_argument('--layer', action='store_true')
  parser.add_argument('--out', default='chiprun_out/ssd_probe.jsonl')
  args = parser.parse_args()
  import jax
  import jax.numpy as jnp
  import numpy as np
  from attention_probe import packed_segments
  from distributed_embeddings_tpu.models import hybrid_ssm
  from distributed_embeddings_tpu.ops import pallas_ssd

  device = jax.devices()[0]
  print(f'device {device.platform} {device.device_kind}', flush=True)
  os.makedirs(os.path.dirname(args.out) or '.', exist_ok=True)

  def record(line):
    line['device'] = device.device_kind
    print(json.dumps(line), flush=True)
    with open(args.out, 'a') as f:
      f.write(json.dumps(line) + '\n')

  z = SHAPE
  rng = np.random.default_rng(args.seed)
  draw = lambda *shape: jnp.asarray(
      rng.standard_normal(shape, dtype=np.float32))
  seqs, length, heads = z['seqs'], z['length'], z['heads']
  # dt = softplus(projection + dt_bias), A = -exp(A_log): the model's own
  # ranges (hybrid_ssm.init_params)
  bias = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), heads))
  bias = bias + np.log(-np.expm1(-bias))
  operands = (draw(seqs, length, heads, z['d_head']),
              jax.nn.softplus(draw(seqs, length, heads)
                              + jnp.asarray(bias, jnp.float32)),
              -jnp.asarray(rng.uniform(1.0, 16.0, heads), jnp.float32),
              draw(seqs, length, z['state']), draw(seqs, length, z['state']))
  seg = jnp.asarray(packed_segments(rng, seqs, length))
  weights = draw(seqs, length, heads, z['d_head'])

  def clock(fn, *v):
    jax.block_until_ready(fn(*v))       # compiles
    started = time.perf_counter()
    for _ in range(args.iters):
      result = fn(*v)
    jax.block_until_ready(result)
    return (time.perf_counter() - started) / args.iters * 1e3, result

  def timed():
    def loss(*v):
      y = jax.checkpoint(lambda *v: hybrid_ssm.ssd_scan(
          *v, seg, z['chunk']))(*v)
      return jnp.sum(y * weights), y
    ms, result = clock(jax.jit(jax.grad(loss, tuple(range(5)),
                                        has_aux=True)), *operands)
    return ms, (result[1],) + tuple(result[0])

  def kernels_alone():
    """ms of the forward kernel and of the backward kernel, each alone."""
    x, dt, a, b, c = operands
    x3 = x.reshape(seqs, length, -1)
    ids = jnp.asarray(seg, jnp.int32)
    segs = dict(columns=jnp.broadcast_to(ids[:, :, None],
                                         (seqs, length, 128)),
                rows=jnp.broadcast_to(ids[:, None, :], (seqs, 8, length)))
    interpret = pallas_ssd.FORCE_INTERPRET
    run = lambda *v: pallas_ssd._forward(z['chunk'], interpret, x.shape, *v)
    fwd_ms, (_, states) = clock(jax.jit(run), x3, dt, dt * a, b, c, segs)
    back = lambda *v: pallas_ssd._backward(z['chunk'], interpret, x.shape,
                                           *v)
    bwd_ms, _ = clock(jax.jit(back), x3, dt, dt * a, b, c, segs, states,
                      weights.reshape(x3.shape))
    return dict(fwd=fwd_ms, bwd=bwd_ms)

  takes = pallas_ssd.takes
  pallas_ssd.takes = lambda *_: False
  base_ms, base = timed()
  pallas_ssd.takes = takes
  record(dict(path='scan', ms=base_ms))
  for block in (int(h) for h in args.heads.split(',')):
    pallas_ssd.HEADS = block
    line = dict(path='kernel', heads=block)
    try:
      ms, got = timed()
    except Exception as e:  # a block that does not fit VMEM
      line.update(error=f'{type(e).__name__}: {str(e)[:300]}')
    else:
      gaps = [float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
              for a, b in zip(got, base)]
      line.update(ms=ms, speedup=base_ms / ms, gap=dict(zip(NAMES, gaps)),
                  **kernels_alone())
    record(line)
  if args.layer:
    pallas_ssd.HEADS = int(args.heads.split(',')[-1])
    for path in ('scan', 'kernel'):
      pallas_ssd.takes = takes if path == 'kernel' else (lambda *_: False)
      record(dict(part='layer', path=path, heads=pallas_ssd.HEADS,
                  **layer_readings(args, seg)))
    pallas_ssd.takes = takes


def layer_readings(args, seg):
  """ms of one Mamba block's value and gradients, and device ms a call
  per scope path and of the longest ops, from a trace of 3 calls."""
  import jax
  import jax.numpy as jnp
  from benchmarks.lib import xtrace
  from distributed_embeddings_tpu.models import hybrid_ssm
  with open('benchmarks/configs/granite-4.0-h-micro.json') as f:
    cfg = hybrid_ssm.HybridSSMConfig.from_dict(json.load(f))
  d, inner, heads = cfg.hidden_size, cfg.d_inner, cfg.mamba_n_heads
  keys = iter(jax.random.split(jax.random.PRNGKey(args.seed), 8))
  kernel = lambda i, o: (jax.random.normal(next(keys), (i, o))
                         / jnp.sqrt(float(i)))
  ones = lambda n: jnp.ones((n,), jnp.float32)
  p = {'mixer_norm': ones(d), 'mlp_norm': ones(d),
       'mlp_in': kernel(d, 2 * cfg.intermediate_size),
       'mlp_out': kernel(cfg.intermediate_size, d),
       'mixer': {'in_proj': kernel(d, inner + cfg.conv_dim + heads),
                 'conv_kernel': kernel(cfg.mamba_d_conv, cfg.conv_dim),
                 'conv_bias': jnp.zeros((cfg.conv_dim,)),
                 'dt_bias': jnp.full((heads,), -4.0),
                 'A_log': jnp.log(jnp.linspace(1.0, 16.0, heads)),
                 'D': ones(heads), 'gated_norm': ones(inner),
                 'out_proj': kernel(inner, d)}}
  x = jax.random.normal(next(keys), seg.shape + (d,))
  w = jax.random.normal(next(keys), x.shape)
  block = jax.checkpoint(lambda p, x: hybrid_ssm.layer(cfg, 'mamba', p, x,
                                                       seg))
  grads = jax.jit(jax.grad(lambda p, x: jnp.sum(block(p, x) * w), (0, 1)))
  jax.block_until_ready(grads(p, x))
  started = time.perf_counter()
  for _ in range(args.iters):
    out = grads(p, x)
  jax.block_until_ready(out)
  ms = (time.perf_counter() - started) / args.iters * 1e3
  where = 'chiprun_out/.ssd_probe_trace'
  xtrace.start_trace(where)
  for _ in range(3):
    out = grads(p, x)
  jax.block_until_ready(out)
  jax.profiler.stop_trace()
  red = xtrace.reduce_trace(xtrace.find_trace(where))
  dev = red['fullest']
  per = lambda sec: round(sec * 1e3 / 3, 4)
  phases = {k: per(v) for k, v in sorted(red['phase_s'][dev].items(),
                                          key=lambda kv: -kv[1])}
  ops = {k: per(v) for k, v in sorted(red['ops'].items(),
                                       key=lambda kv: -kv[1])[:25]}
  import shutil
  shutil.rmtree(where, ignore_errors=True)
  return dict(ms=ms, busy_ms=per(red['busy_s'][dev]), phases=phases,
              ops=ops)


if __name__ == '__main__':
  main()
