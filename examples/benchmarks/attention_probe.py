"""Time the core of attention on the live backend: the unrolled
``jax.numpy`` blocks against the fused kernels.

What ``models/hybrid_ssm.blocked_attention`` chooses between, at the two
language-model cells' shapes: ``trinity-mini`` (2 x 8,192 positions, 32
query over 4 key-value heads of 128, window 2,048 and full) and
``granite-4.0-h-micro`` (2 x 4,096, 32 over 8 heads of 64, full).  Each
reading is forward plus the gradients of q, k and v under one
``jax.checkpoint``, as a layer of the step has it (a forward, then the
forward again and the backward), on documents of log-normal length
(median 512, sigma 1.25) packed without padding, as the cells' traffic.
The kernels (``kernel``: ops/pallas_attention.py; ``splash``: the
installed JAX's ``splash_attention`` multi-query kernel under two
``vmap``s with bfloat16 operands, what the hand-written kernels were
weighed against) run at each block size of ``--blocks`` that compiles;
``gap`` is the largest difference from the unrolled path's output and
gradients over their largest magnitude.

Usage: python examples/benchmarks/attention_probe.py
       [--cases trinity-window,trinity-full,granite-full]
       [--paths kernel,splash] [--blocks 256,512,1024] [--iters 5]
       [--out chiprun_out/attention_probe.jsonl]
The readings are in docs/perf_notes.md, "The attention kernel".
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

# case -> (sequences, positions, key-value heads, queries a group, head
# width, scale, window, the configuration's attention_block)
CASES = {
    'trinity-window': (2, 8192, 4, 8, 128, 128 ** -0.5, 2048, 512),
    'trinity-full': (2, 8192, 4, 8, 128, 128 ** -0.5, None, 512),
    'granite-full': (2, 4096, 8, 4, 64, 1 / 64, None, 512),
}


def packed_segments(rng, seqs, length):
  """``[seqs, length]`` int32 document numbers, documents of log-normal
  length packed back to back and cut at the sequence's end."""
  import numpy as np
  out = np.zeros((seqs, length), np.int32)
  for s in range(seqs):
    ends = np.cumsum(np.maximum(
        1, rng.lognormal(np.log(512), 1.25, size=length).astype(np.int64)))
    out[s] = np.searchsorted(ends, np.arange(length), side='right')
  return out


def splash_attention(scale, q, k, v, segment_ids, window, block):
  """``pallas_attention.attention``'s contract on the library's kernel:
  its output and gradients leave as bfloat16."""
  import jax
  import jax.numpy as jnp
  from jax.experimental.pallas.ops.tpu import splash_attention as splash
  _, length, _, group, _ = q.shape
  shape = (length, length)
  mask = (splash.CausalMask(shape) if window is None else
          splash.LocalMask(shape, window_size=(window - 1, 0), offset=0))
  sizes = splash.BlockSizes(
      block_q=block, block_kv=block, block_kv_compute=block,
      block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
      block_q_dq=block, block_kv_dq=block)
  with jax.ensure_compile_time_eval():
    kernel = splash.make_splash_mqa_single_device(
        splash.MultiHeadMask([mask] * group), block_sizes=sizes)
  qh = jnp.transpose(q * scale, (0, 2, 3, 1, 4)).astype(jnp.bfloat16)
  kh = jnp.transpose(k, (0, 2, 1, 3)).astype(jnp.bfloat16)
  vh = jnp.transpose(v, (0, 2, 1, 3)).astype(jnp.bfloat16)

  def one_sequence(qs, ks, vs, seg):
    ids = splash.SegmentIds(seg, seg)
    return jax.vmap(lambda qg, kg, vg: kernel(qg, kg, vg, ids))(qs, ks, vs)

  out = jax.vmap(one_sequence)(qh, kh, vh, segment_ids)
  return jnp.transpose(out, (0, 3, 1, 2, 4)).astype(jnp.float32)


def main():
  parser = argparse.ArgumentParser()
  parser.add_argument('--cases', default=','.join(CASES))
  parser.add_argument('--paths', default='kernel,splash')
  parser.add_argument('--blocks', default='256,512,1024')
  parser.add_argument('--iters', type=int, default=5)
  parser.add_argument('--seed', type=int, default=0)
  parser.add_argument('--out', default='chiprun_out/attention_probe.jsonl')
  args = parser.parse_args()
  import jax
  import jax.numpy as jnp
  import numpy as np
  from distributed_embeddings_tpu.models import hybrid_ssm
  from distributed_embeddings_tpu.ops import pallas_attention

  device = jax.devices()[0]
  print(f'device {device.platform} {device.device_kind}', flush=True)
  os.makedirs(os.path.dirname(args.out) or '.', exist_ok=True)

  def record(line):
    line['device'] = device.device_kind
    print(json.dumps(line), flush=True)
    with open(args.out, 'a') as f:
      f.write(json.dumps(line) + '\n')

  def timed(core, operands):
    """ms a call of ``core(q, k, v, segment_ids)``'s forward + gradients
    under ``jax.checkpoint``, and the output and gradients of a call."""
    def loss(q, k, v, seg, weights):
      out = jax.checkpoint(core)(q, k, v, seg)
      return jnp.sum(out * weights), out
    grads = jax.jit(jax.grad(loss, (0, 1, 2), has_aux=True))
    jax.block_until_ready(grads(*operands))       # compiles
    started = time.perf_counter()
    for _ in range(args.iters):
      result = grads(*operands)
    jax.block_until_ready(result)
    ms = (time.perf_counter() - started) / args.iters * 1e3
    (dq, dk, dv), out = result
    return ms, (out, dq, dk, dv)

  for case in args.cases.split(','):
    seqs, length, kv_heads, group, d, scale, window, limit = CASES[case]
    rng = np.random.default_rng(args.seed)
    draw = lambda *shape: jnp.asarray(
        rng.standard_normal(shape, dtype=np.float32))
    q, k, v = (draw(seqs, length, kv_heads, group, d),
               draw(seqs, length, kv_heads, d),
               draw(seqs, length, kv_heads, d))
    if case.startswith('granite'):   # no norm before the product there
      q, k = q * 8, k * 8
    weights = draw(seqs, length, kv_heads, group, d)
    operands = (q, k, v, jnp.asarray(packed_segments(rng, seqs, length)),
                weights)
    base_ms, base = timed(
        lambda q, k, v, seg: hybrid_ssm._unrolled_attention(
            scale, q, k, v, seg, limit, window), operands)
    record(dict(case=case, path='unrolled', block=limit, ms=base_ms))
    fused = dict(kernel=pallas_attention.attention, splash=splash_attention)
    for path, block in ((p, int(b)) for p in args.paths.split(',')
                        for b in args.blocks.split(',')):
      if length % block:
        continue
      line = dict(case=case, path=path, block=block)
      try:
        ms, got = timed(lambda q, k, v, seg: fused[path](
            scale, q, k, v, seg, window, block=block), operands)
      except Exception as e:  # a block that does not fit VMEM
        line.update(error=f'{type(e).__name__}: {str(e)[:300]}')
      else:
        gaps = [float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
                for a, b in zip(got, base)]
        line.update(ms=ms, speedup=base_ms / ms,
                    gap=dict(zip(('out', 'dq', 'dk', 'dv'), gaps)))
      record(line)


if __name__ == '__main__':
  main()
