"""MLPerf DLRM: one width, a list of table sizes, single lookups, a
bottom MLP, the pairwise dot interaction and a top MLP.  The five names
a configuration of this class gives (``lib/builders``)."""

from benchmarks.lib import builders, peaks, ref_heads, weights


def tables(config):
  specs = [(rows, config['embedding_dim']) for rows in config['table_rows']]
  return (builders.with_half_range(config['table_init'], specs),
          list(range(len(specs))), [1] * len(specs))


def dense_dims(config):
  """name -> ``[(fan_in, fan_out), ...]`` of the two MLPs."""
  n = len(config['table_rows']) + 1
  dim = config['embedding_dim']
  bottom = list(config['bottom_mlp_dims'])
  top = list(config['top_mlp_dims'])
  return {
      'bottom_mlp': list(zip([config['num_numerical_features']]
                             + bottom[:-1], bottom)),
      'top_mlp': list(zip([n * (n - 1) // 2 + dim] + top[:-1], top)),
  }


def dense_params(config, seed):
  """``{'bottom_mlp': [...], 'top_mlp': [...]}`` from ``seed``, host
  numpy, each MLP from a generator stream of its own."""
  return {name: weights.dense_layers(seed, 1 + i, dims)
          for i, (name, dims) in enumerate(sorted(dense_dims(config).items()))}


def work(config, model, global_batch, chips, mix):
  """The head's forward+backward FLOPs for one chip's share of the batch:
  the two MLPs (``peaks.mlp_flops``) and the dot interaction, ``3 x 2 x B
  x n x n x d`` (the ``n x n`` products of ``d``-vectors once forward and
  twice backward; ``n`` = tables + 1).  Beyond the distinct rows the head
  moves only its activations: 0 bytes."""
  del model, mix
  flops = sum(peaks.mlp_flops(global_batch, dims)
              for dims in dense_dims(config).values())
  n = len(config['table_rows']) + 1
  flops += 3 * 2 * global_batch * n * n * config['embedding_dim']
  return {'flops': flops / chips, 'bytes': 0}


def reference_head(config):
  """Bottom MLP, pairwise dots of the bottom output and the embedding
  outputs (strictly lower triangle, row-major), re-concatenate the bottom
  output, top MLP to one logit (``examples/dlrm/main.py:76-147``,
  ``utils.py:92-113``)."""
  import jax
  import jax.numpy as jnp
  del config

  def loss(dense, emb_outs, batch, matmul, tables):
    del tables
    numerical, labels = batch
    bottom = ref_heads.mlp(dense['bottom_mlp'], numerical, matmul, False)
    feats = jnp.stack([bottom] + list(emb_outs), axis=1)      # [B, n, d]
    n = feats.shape[1]
    pairs = jax.vmap(lambda f: matmul(f, f.T))(feats)           # [B, n, n]
    rows, cols = jnp.tril_indices(n, k=-1)
    x = jnp.concatenate([pairs[:, rows, cols], bottom], axis=1)
    return ref_heads.bce_with_logits(
        ref_heads.mlp(dense['top_mlp'], x, matmul, True), labels)

  return loss


def builder(config, mesh, seed):
  """Through the program's ``DLRM`` (defaults: data-parallel input,
  memory_balanced placement, float32 compute, fused exchange)."""
  from distributed_embeddings_tpu.models.dlrm import DLRM
  model = DLRM(table_sizes=list(config['table_rows']),
               embedding_dim=config['embedding_dim'],
               bottom_mlp_dims=tuple(config['bottom_mlp_dims']),
               top_mlp_dims=tuple(config['top_mlp_dims']),
               num_numerical_features=config['num_numerical_features'],
               mesh=mesh)
  return builders.finish(config, seed, model.dist_embedding,
                         builders.ctr_head_loss(model.head))
