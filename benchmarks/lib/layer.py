"""What several per-layer readers share: class times from the reduced
trace, and the required work of one step of a cell."""

import numpy as np

from benchmarks.lib import peaks


def class_ms(context, classes):
  """Device milliseconds per step spent in ops of ``classes``, on the
  device that spends most there (a step ends when its slowest chip
  does).  ``None`` where the trace holds no step or no such op."""
  trace = context['trace']
  if not trace['steps'] or not trace['class_s']:
    return None
  worst = max(sum(per[c] for c in classes)
              for per in trace['class_s'].values())
  return worst / trace['steps'] * 1e3 if worst > 0 else None


def row_bytes_per_chip(context):
  """Mean over the pool's batches of the bytes one chip's share of the
  lookups needs at the least (``peaks.distinct_row_bytes`` over chips)."""
  model = context['model']
  widths = [w for _, w, _ in model.tables]
  total = np.mean([peaks.distinct_row_bytes(cats, model.input_table_map,
                                            widths)
                   for cats, _, _ in context['host_pool']])
  return float(total) / len(context['devices'])


def head_flops_per_chip(context):
  """Forward+backward FLOPs of the dense head for one chip's share of the
  batch: the MLPs (``peaks.mlp_flops``) and, where the configuration has
  the dot interaction, ``3 x 2 x B x n x n x d``."""
  model, batch = context['model'], context['global_batch']
  flops = sum(peaks.mlp_flops(batch, dims)
              for dims in model.dense_dims.values())
  if 'embedding_dim' in context['config']:
    n = len(model.tables) + 1
    flops += 3 * 2 * batch * n * n * context['config']['embedding_dim']
  return flops / len(context['devices'])


def state_slots(context):
  return 2 if context['model'].optimizer['kind'] == 'adagrad' else 1
