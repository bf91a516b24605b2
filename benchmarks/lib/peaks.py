"""Peaks of the chips this benchmark may run on, and the work a step needs.

Peaks: Google Cloud documentation, "TPU v5e" system architecture page
(197 bf16 TFLOP/s, 819 GB/s HBM, 16 GB per chip).  A device that is not
in the table is an error, never a default.

Required work is counted from shapes and ids, never from what the
implementation happens to move, so no share built on it can pass 100%
while the trace covers the step.
"""

import numpy as np

PEAKS = {
    'TPU v5 lite': {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9,
                    'hbm_bytes': 16e9,
                    'source': 'Google Cloud docs, "TPU v5e"'},
}


def peaks_for(device_kind):
  if device_kind not in PEAKS:
    raise KeyError(f'no peaks for device kind {device_kind!r}: add it to '
                   'benchmarks/lib/peaks.py with its source')
  return PEAKS[device_kind]


def distinct_row_bytes(cats, input_table_map, widths, itemsize=4):
  """Bytes the lookups of one batch need at the least: every DISTINCT row
  a table is asked for, read once (``distinct rows x width x itemsize``).

  Derivation: a gather must read each row it returns at least once; a
  row asked for twice need not be read twice (dedup, cache).  Ids come
  from the cell's own batch; nothing here knows how the gather is done.
  """
  per_table = {}
  for ids, tid in zip(cats, input_table_map):
    per_table.setdefault(tid, []).append(np.asarray(ids).reshape(-1))
  total = 0
  for tid, parts in per_table.items():
    total += np.unique(np.concatenate(parts)).size * widths[tid] * itemsize
  return int(total)


def mlp_flops(batch, dims):
  """Forward+backward FLOPs of dense layers ``[(fan_in, fan_out), ...]``.

  Derivation: forward is ``2 x batch x fan_in x fan_out`` per layer;
  backward computes two products of that size (input and kernel
  gradients), so a layer costs three forwards; recomputation not counted.
  """
  return int(sum(3 * 2 * batch * a * b for a, b in dims))


def step_floor_seconds(peaks, flops, row_bytes, state_slots, head_bytes=0):
  """The least time one chip needs for its share of a step, and which
  peak binds: the larger of dense FLOPs over peak FLOP/s and bytes over
  peak bytes/s, where bytes are the distinct rows read once forward and,
  in the apply, read and written once per state slot (``state_slots`` is
  1 for SGD's table alone, 2 with Adagrad's accumulator, 3 with Adam's
  two moments), plus ``head_bytes``, what the class's ``work`` says its
  head must move beyond them."""
  t_flops = flops / peaks['bf16_flops_per_s']
  t_bytes = ((row_bytes * (1 + 2 * state_slots) + head_bytes)
             / peaks['hbm_bytes_per_s'])
  return max(t_flops, t_bytes), ('flops' if t_flops > t_bytes else 'bytes')
