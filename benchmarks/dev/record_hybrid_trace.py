"""Record ``benchmarks/tests/data/v5e_hybrid_step.trace.json.gz``: three
steps of a small hybrid state-space step (two Mamba-2 layers around one
attention layer, a tied vocabulary of 2048 rows of 1 KiB, two sequences
of 512 packed positions) on one TPU chip under the profiler, cut down to
the device's op and module events, for ``benchmarks/tests/test_hybrid.py``
(the readers of the head's phases and of ``apply/tied`` are pinned on it).

  JAX_COMPILATION_CACHE_DIR=$(mktemp -d) \
      python3 benchmarks/dev/record_hybrid_trace.py <out.trace.json.gz>

Re-record when the head's phases change, and re-pin the test's numbers.
"""
import gzip
import json
import os
import sys
import tempfile

sys.path.insert(0, os.getcwd())

CONFIG = dict(
    hidden_size=256, layer_types=['mamba', 'attention', 'mamba'],
    shared_intermediate_size=512, intermediate_size=512, mamba_n_heads=8,
    mamba_d_head=64, mamba_d_state=64, mamba_n_groups=1, mamba_d_conv=4,
    mamba_chunk_size=64, num_attention_heads=8, num_key_value_heads=2,
    rms_norm_eps=1e-5, embedding_multiplier=12, residual_multiplier=0.22,
    attention_multiplier=1 / 32, logits_scaling=8, vocab_size=2048)


def main(out):
  import jax
  import jax.numpy as jnp
  import optax
  from benchmarks.lib import traffic, xtrace
  from distributed_embeddings_tpu.models import hybrid_ssm
  from distributed_embeddings_tpu.parallel import (
      DistributedEmbedding, SparseAdam, TableConfig, create_mesh,
      init_hybrid_train_state, make_hybrid_train_step)
  assert jax.devices()[0].platform == 'tpu', jax.devices()
  cfg = hybrid_ssm.HybridSSMConfig.from_dict(CONFIG, attention_block=128,
                                             vocab_block=256)
  dist = DistributedEmbedding(
      [TableConfig(CONFIG['vocab_size'], cfg.hidden_size, None)],
      mesh=create_mesh(jax.devices()[:1]))
  mix = dict(global_batch=2, seq_len=512, alpha=1.05, doc_len_median=64,
             doc_len_sigma=1.0, pool_batches=1)
  (cats, batch), = traffic.train_tokens(mix, [(CONFIG['vocab_size'], 1)],
                                        CONFIG, 1)
  cats = [jnp.asarray(c) for c in cats]
  batch = jax.tree.map(jnp.asarray, batch)
  emb_opt, dense_opt = SparseAdam(3e-4), optax.adam(3e-4)
  step = make_hybrid_train_step(dist, hybrid_ssm.make_head_loss_fn(cfg),
                                dense_opt, emb_opt, head_reads_tables=(0,))
  state = init_hybrid_train_state(
      dist, {**jax.tree.map(jnp.asarray, hybrid_ssm.init_params(cfg, 1)),
             'embedding': dist.init(0)}, dense_opt, emb_opt)
  for _ in range(2):
    state, loss = step(state, cats, batch)
  jax.block_until_ready((state, loss))
  directory = tempfile.mkdtemp(prefix='hybrid_step_')
  xtrace.start_trace(directory)
  for _ in range(3):
    state, loss = step(state, cats, batch)
    loss.block_until_ready()
  jax.profiler.stop_trace()
  with gzip.open(xtrace.find_trace(directory)) as f:
    events = json.load(f)['traceEvents']
  device = {e['pid'] for e in events if e.get('name') == 'process_name'
            and e['args']['name'].startswith('/device:TPU:')}
  threads = {(e['pid'], e['tid']) for e in events
             if e.get('name') == 'thread_name' and e['pid'] in device
             and e['args']['name'] in ('XLA Ops', 'XLA Modules')}
  kept = []
  for e in events:
    if e.get('ph') == 'M' and e.get('pid') in device:
      kept.append(e)
    elif e.get('ph') == 'X' and (e['pid'], e['tid']) in threads:
      args = e.get('args', {})
      kept.append({**{k: e[k] for k in ('ph', 'pid', 'tid', 'name', 'ts',
                                        'dur')},
                   'args': {'long_name': args.get('long_name', '')[:160],
                            'tf_op': args.get('tf_op', ''),
                            'hlo_category': args.get('hlo_category', '')}})
  with gzip.open(out, 'wt') as f:
    json.dump({'traceEvents': kept}, f)
  print(f'{out}: {len(kept)} events, {os.path.getsize(out)} bytes')


if __name__ == '__main__':
  main(sys.argv[1])
