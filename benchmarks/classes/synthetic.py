"""The reference benchmark's synthetic models: embedding blocks, summed
multi-hot lookups, one MLP over the concatenation down to a logit.  The
five names a configuration of this class gives (``lib/builders``)."""

from benchmarks.lib import builders, peaks, ref_heads, weights

tables = builders.block_tables


def dense_dims(config):
  """``[(fan_in, fan_out), ...]`` of the one MLP."""
  specs, table_map, _ = tables(config)
  fan_in = (sum(specs[t][1] for t in table_map)
            + config['num_numerical_features'])
  sizes = list(config['mlp_sizes']) + [1]
  return list(zip([fan_in] + sizes[:-1], sizes))


def dense_params(config, seed):
  """``{'mlp': [{'kernel', 'bias'}, ...]}`` from ``seed``, host numpy."""
  return {'mlp': weights.dense_layers(seed, 1, dense_dims(config))}


def work(config, model, global_batch, chips, mix):
  """The head's forward+backward FLOPs for one chip's share of the batch:
  the MLP's layers (``peaks.mlp_flops``: three products of ``2 x batch x
  fan_in x fan_out`` a layer).  Beyond the distinct rows the head moves
  only its activations, which need not leave the chip: 0 bytes."""
  del model, mix
  return {'flops': peaks.mlp_flops(global_batch, dense_dims(config)) / chips,
          'bytes': 0}


def reference_head(config):
  """Concatenate the embedding outputs and the dense features, MLP down
  to one logit (``synthetic_models.py:116-175``; the average-pool
  interaction only where the configuration has a stride)."""
  import jax.numpy as jnp
  if config.get('interact_stride') is not None:
    raise NotImplementedError('interact_stride: no cell needs it yet')

  def loss(dense, emb_outs, batch, matmul, tables):
    del tables
    numerical, labels = batch
    x = jnp.concatenate(list(emb_outs) + [numerical], axis=1)
    return ref_heads.bce_with_logits(
        ref_heads.mlp(dense['mlp'], x, matmul, True), labels)

  return loss


def builder(config, mesh, seed):
  """Through the program's ``SyntheticModel`` (data-parallel input, every
  other option at its default: XLA gather, sort-compact apply, packed
  storage)."""
  from distributed_embeddings_tpu.models import synthetic as prog
  blocks = [(b['num_tables'], b['nnz'], b['num_rows'], b['width'],
             b['shared']) for b in config['embedding_blocks']]
  model_config = prog._cfg(config['name'], blocks, config['mlp_sizes'],
                           config['num_numerical_features'],
                           config['interact_stride'])
  model = prog.SyntheticModel(model_config, mesh=mesh, dp_input=True)
  return builders.finish(config, seed, model.dist_embedding,
                         builders.ctr_head_loss(model.head))
