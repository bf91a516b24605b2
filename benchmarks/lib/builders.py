"""Builders: one configuration file in, the program's model out.

A configuration names its builder (``module:function``); the harness
imports it.  A builder is the only place that knows the program's model
classes.  It returns a ``Model``: the program's ``DistributedEmbedding``
(its tables are written by ``lib/program_state``), the head as the
program computes it, the dense parameters drawn by this benchmark, and
the two optimizers the configuration states.
"""

import dataclasses
from typing import Any, Callable, List

import numpy as np

from benchmarks.lib import weights

DENSE_STREAM = 1  # numpy generator stream of the dense parameters


@dataclasses.dataclass
class Model:
  dist: Any                      # the program's DistributedEmbedding
  tables: List[tuple]            # (rows, width, half_range) per table
  input_table_map: List[int]
  hotness: List[int]
  head_loss_fn: Callable         # (dense_params, emb_outs, batch) -> loss
  dense_params: Any              # host numpy pytree, from the seed
  dense_optimizer: Any           # optax transformation
  emb_optimizer_cls: Any         # SparseSGD / SparseAdagrad
  emb_optimizer_kwargs: dict
  optimizer: dict                # the configuration's own statement
  dense_dims: dict               # name -> [(fan_in, fan_out), ...]


def expand_blocks(config):
  """``embedding_blocks`` -> per-table ``(rows, width)`` plus the
  input->table map and per-input hotness (the reference's
  ``synthetic_models.py:130-148``)."""
  tables, table_map, hotness = [], [], []
  for block in config['embedding_blocks']:
    for _ in range(block['num_tables']):
      tables.append((block['num_rows'], block['width']))
      for h in block['nnz']:
        table_map.append(len(tables) - 1)
        hotness.append(h)
  return tables, table_map, hotness


def table_specs(config):
  """``[(rows, width, half_range)]``, the input->table map and hotness
  for either configuration shape (blocks, or one width and a row list)."""
  if 'embedding_blocks' in config:
    tables, table_map, hotness = expand_blocks(config)
  else:
    tables = [(rows, config['embedding_dim'])
              for rows in config['table_rows']]
    table_map = list(range(len(tables)))
    hotness = [1] * len(tables)
  kind = config['table_init']['kind']
  if kind == 'uniform':
    half = lambda rows: float(config['table_init']['half_range'])
  elif kind == 'scaled_uniform':
    half = lambda rows: 1.0 / float(np.sqrt(rows))
  else:
    raise ValueError(f'unknown table_init kind {kind!r}')
  return ([(rows, width, half(rows)) for rows, width in tables], table_map,
          hotness)


def dense_dims(config):
  """name -> ``[(fan_in, fan_out), ...]`` of the configuration's MLPs."""
  tables, table_map, _ = table_specs(config)
  if 'embedding_blocks' in config:
    fan_in = (sum(tables[t][1] for t in table_map)
              + config['num_numerical_features'])
    sizes = list(config['mlp_sizes']) + [1]
    return {'mlp': list(zip([fan_in] + sizes[:-1], sizes))}
  n = len(tables) + 1
  dim = config['embedding_dim']
  bottom = list(config['bottom_mlp_dims'])
  top = list(config['top_mlp_dims'])
  return {
      'bottom_mlp': list(zip([config['num_numerical_features']]
                             + bottom[:-1], bottom)),
      'top_mlp': list(zip([n * (n - 1) // 2 + dim] + top[:-1], top)),
  }


def dense_params(config, seed):
  """The dense parameters of ``config`` from ``seed``, as host numpy."""
  return {name: weights.dense_layers(seed, DENSE_STREAM + i, dims)
          for i, (name, dims) in enumerate(sorted(dense_dims(config).items()))}


def _optimizers(config):
  import optax
  from distributed_embeddings_tpu.parallel import SparseAdagrad, SparseSGD
  opt = config['optimizer']
  lr = float(opt['learning_rate'])
  if opt['kind'] == 'adagrad':
    acc0, eps = float(opt['initial_accumulator_value']), float(opt['epsilon'])
    return (optax.adagrad(lr, initial_accumulator_value=acc0, eps=eps),
            SparseAdagrad, dict(learning_rate=lr,
                                initial_accumulator_value=acc0, epsilon=eps))
  if opt['kind'] == 'sgd':
    return optax.sgd(lr), SparseSGD, dict(learning_rate=lr)
  raise ValueError(f'unknown optimizer kind {opt["kind"]!r}')


def _finish(config, seed, dist, head, specs, table_map, hotness):
  from distributed_embeddings_tpu.models.dlrm import bce_with_logits
  for cfg, (rows, width, _) in zip(dist.table_configs, specs):
    if (cfg.input_dim, cfg.output_dim) != (rows, width):
      raise ValueError('table configs out of step with the configuration')

  def head_loss_fn(dense, emb_outs, batch):
    numerical, labels = batch
    return bce_with_logits(head(dense, numerical, emb_outs), labels)

  dense_opt, emb_cls, emb_kwargs = _optimizers(config)
  return Model(dist=dist, tables=specs, input_table_map=table_map,
               hotness=hotness, head_loss_fn=head_loss_fn,
               dense_params=dense_params(config, seed),
               dense_optimizer=dense_opt, emb_optimizer_cls=emb_cls,
               emb_optimizer_kwargs=emb_kwargs,
               optimizer=config['optimizer'], dense_dims=dense_dims(config))


def synthetic(config, mesh, seed):
  """The reference benchmark's synthetic model through the program's
  ``SyntheticModel`` (data-parallel input, every other option at its
  default: XLA gather, sort-compact apply, packed storage)."""
  from distributed_embeddings_tpu.models import synthetic as prog
  blocks = [(b['num_tables'], b['nnz'], b['num_rows'], b['width'],
             b['shared']) for b in config['embedding_blocks']]
  model_config = prog._cfg(config['name'], blocks, config['mlp_sizes'],
                           config['num_numerical_features'],
                           config['interact_stride'])
  model = prog.SyntheticModel(model_config, mesh=mesh, dp_input=True)
  specs, table_map, hotness = table_specs(config)
  return _finish(config, seed, model.dist_embedding, model.head, specs,
                 table_map, hotness)


def dlrm(config, mesh, seed):
  """MLPerf DLRM through the program's ``DLRM`` (defaults: data-parallel
  input, memory_balanced placement, float32 compute, fused exchange)."""
  from distributed_embeddings_tpu.models.dlrm import DLRM
  model = DLRM(table_sizes=list(config['table_rows']),
               embedding_dim=config['embedding_dim'],
               bottom_mlp_dims=tuple(config['bottom_mlp_dims']),
               top_mlp_dims=tuple(config['top_mlp_dims']),
               num_numerical_features=config['num_numerical_features'],
               mesh=mesh)
  specs, table_map, hotness = table_specs(config)
  return _finish(config, seed, model.dist_embedding, model.head, specs,
                 table_map, hotness)

