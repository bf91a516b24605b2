"""What several per-layer readers share: class and phase times from the
reduced trace, and the required work of one step of a cell."""

import numpy as np

from benchmarks.lib import names, peaks

# table-sized arrays the sparse apply reads and writes per distinct row:
# the table, plus Adagrad's accumulator, plus Adam's two moments
STATE_SLOTS = {'sgd': 1, 'adagrad': 2, 'adam': 3}


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


def under(path, prefix):
  """Whether scope path ``a/b/c`` lies under ``prefix``: the prefix's
  components in a row somewhere in the path, so that ``apply/dedup``
  holds ``apply/dedup/g1`` wherever JAX nested it."""
  parts, want = path.split('/'), prefix.split('/')
  return any(parts[i:i + len(want)] == want
             for i in range(len(parts) - len(want) + 1))


def phase_ms(context, prefix, classes=None):
  """Device milliseconds per step of self time in ops whose scope path
  lies under ``prefix`` (``xtrace``'s ``phase_s``; of ``classes`` alone
  where given, from ``phase_class_s``), on the device that spends most
  there.  ``None`` where the trace holds no step or nothing under it: a
  step served from a cache filled before the scopes existed reads
  nothing, never 0."""
  trace = context['trace']
  if not trace['steps']:
    return None
  if classes is None:
    per_dev = [sum(s for path, s in per.items() if under(path, prefix))
               for per in trace['phase_s'].values()]
  else:
    per_dev = [sum(s for path, by in per.items() if under(path, prefix)
                   for c, s in by.items() if c in classes)
               for per in trace['phase_class_s'].values()]
  worst = max(per_dev, default=0.0)
  return worst / trace['steps'] * 1e3 if worst > 0 else None


def row_bytes_per_chip(context):
  """Mean over the pool's batches of the bytes one chip's share of the
  lookups needs at the least (``peaks.distinct_row_bytes`` over chips)."""
  model = context['model']
  widths = [w for _, w, _ in model.tables]
  total = np.mean([peaks.distinct_row_bytes(cats, model.input_table_map,
                                            widths)
                   for cats, _ in context['host_pool']])
  return float(total) / len(context['devices'])


def head_work_per_chip(context):
  """``{'flops', 'bytes'}`` of the head for one chip's share of a step,
  by the function the configuration names (``work``)."""
  return names.resolve(context['config']['work'])(
      context['config'], context['model'], context['global_batch'],
      len(context['devices']), context['mix'])


def state_slots(context):
  return STATE_SLOTS[context['model'].optimizer['kind']]
