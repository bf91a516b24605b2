"""What a builder returns, and what builders of several classes share.

A model class is five names (``module:function``) in its configuration
file, which the harness resolves and never looks behind:

  tables          (config) -> ([(rows, width, half_range)], input->table
                  map, hotness per input): how this file lists its tables
  builder         (config, mesh, seed) -> ``Model``: the one place that
                  knows the program's model classes
  reference_head  (config) -> ``loss(dense, emb_outs, batch, matmul,
                  tables)``, plain ``jax.numpy`` (``lib/ref_heads``)
  dense_params    (config, seed) -> the dense parameters as a host numpy
                  pytree, any pytree; a leaf is named by its tree path
  work            (config, model, global_batch, chips, mix) -> ``{'flops',
                  'bytes'}``: the head's forward+backward FLOPs per chip
                  per step, and the bytes it must move at the least
                  beyond the distinct rows (``lib/layer``, ``lib/peaks``)

The classes the benchmark has are ``benchmarks/classes/<class>.py``, one
module each; a new class is a new module and a configuration that names
it.  Nothing here reads a key that only one class has.
"""

import dataclasses
from typing import Any, Callable, List

import numpy as np

from benchmarks.lib import names


@dataclasses.dataclass
class Model:
  dist: Any                      # the program's DistributedEmbedding
  tables: List[tuple]            # (rows, width, half_range) per table
  input_table_map: List[int]
  hotness: List[int]
  head_loss_fn: Callable         # (dense_params, emb_outs, batch) -> loss
  dense_params: Any              # host numpy pytree, from the seed
  dense_optimizer: Any           # optax transformation
  emb_optimizer_cls: Any         # SparseSGD / SparseAdagrad / SparseAdam
  emb_optimizer_kwargs: dict
  optimizer: dict                # the configuration's own statement
  # further arguments of the program's ``make_hybrid_train_step``
  step_kwargs: dict = dataclasses.field(default_factory=dict)


def expand_blocks(blocks):
  """``embedding_blocks`` -> per-table ``(rows, width)`` plus the
  input->table map and per-input hotness (the reference's
  ``synthetic_models.py:130-148``)."""
  tables, table_map, hotness = [], [], []
  for block in blocks:
    for _ in range(block['num_tables']):
      tables.append((block['num_rows'], block['width']))
      for h in block['nnz']:
        table_map.append(len(tables) - 1)
        hotness.append(h)
  return tables, table_map, hotness


def with_half_range(table_init, tables):
  """``[(rows, width)]`` -> ``[(rows, width, half_range)]`` by the
  configuration's ``table_init``."""
  kind = table_init['kind']
  if kind == 'uniform':
    half = lambda rows: float(table_init['half_range'])
  elif kind == 'scaled_uniform':
    half = lambda rows: 1.0 / float(np.sqrt(rows))
  else:
    raise ValueError(f'unknown table_init kind {kind!r}')
  return [(rows, width, half(rows)) for rows, width in tables]


def block_tables(config):
  """The ``tables`` of a configuration that lists ``embedding_blocks``."""
  tables, table_map, hotness = expand_blocks(config['embedding_blocks'])
  return with_half_range(config['table_init'], tables), table_map, hotness


def optimizers(spec):
  """``(optax transformation, sparse optimizer class, its kwargs)`` for
  the configuration's ``optimizer`` statement: the dense leaves and the
  tables take the same kind."""
  import optax
  from distributed_embeddings_tpu.parallel import (SparseAdagrad, SparseAdam,
                                                   SparseSGD)
  lr = float(spec['learning_rate'])
  if spec['kind'] == 'adagrad':
    acc0, eps = float(spec['initial_accumulator_value']), float(spec['epsilon'])
    return (optax.adagrad(lr, initial_accumulator_value=acc0, eps=eps),
            SparseAdagrad, dict(learning_rate=lr,
                                initial_accumulator_value=acc0, epsilon=eps))
  if spec['kind'] == 'sgd':
    return optax.sgd(lr), SparseSGD, dict(learning_rate=lr)
  if spec['kind'] == 'adam':
    b1, b2, eps = (float(spec[k]) for k in ('b1', 'b2', 'epsilon'))
    return (optax.adam(lr, b1=b1, b2=b2, eps=eps), SparseAdam,
            dict(learning_rate=lr, b1=b1, b2=b2, epsilon=eps))
  raise ValueError(f'unknown optimizer kind {spec["kind"]!r}')


def finish(config, seed, dist, head_loss_fn, **step_kwargs):
  """The ``Model`` of ``dist`` and ``head_loss_fn`` with everything the
  configuration's names and its ``optimizer`` say."""
  specs, table_map, hotness = names.resolve(config['tables'])(config)
  for cfg, (rows, width, _) in zip(dist.table_configs, specs):
    if (cfg.input_dim, cfg.output_dim) != (rows, width):
      raise ValueError('table configs out of step with the configuration')
  dense_opt, emb_cls, emb_kwargs = optimizers(config['optimizer'])
  return Model(dist=dist, tables=specs, input_table_map=table_map,
               hotness=hotness, head_loss_fn=head_loss_fn,
               dense_params=names.resolve(config['dense_params'])(config, seed),
               dense_optimizer=dense_opt, emb_optimizer_cls=emb_cls,
               emb_optimizer_kwargs=emb_kwargs,
               optimizer=config['optimizer'], step_kwargs=step_kwargs)


def ctr_head_loss(head):
  """``head_loss_fn`` of a click-through head ``head(dense, numerical,
  emb_outs) -> logits`` under the program's own binary cross-entropy,
  on a batch ``(numerical, labels)``."""
  from distributed_embeddings_tpu.models.dlrm import bce_with_logits

  def head_loss_fn(dense, emb_outs, batch):
    numerical, labels = batch
    return bce_with_logits(head(dense, numerical, emb_outs), labels)

  return head_loss_fn
