"""The held experts' grouped products' share of their roofline, in
percent: the least time the chip could take for them in one step (the
function the configuration names as ``expert_work``: three projections'
FLOPs an assignment, forward and backward, over peak bf16 FLOP/s, or the
held experts' weights read once forward and once backward and written
once, plus the buffer's rows, over peak HBM bytes/s, whichever is
larger) over ``moe_expert_ms``.  The assignments are the EXPECTED count,
``tokens x experts a token x held / router width`` a routed layer, from
the configuration and the mix alone, so that the work is the same
whatever implements the layer (the program's gauge
``moe.assignments_held`` says what a step really held); no
recomputation is counted.  FLOPs bind at the published sizes.  Source:
device trace.  Nothing to read where the configuration names no
``expert_work`` or the step has no such phase."""
from benchmarks.lib import cell, names, peaks


def read(context):
  ms = cell._function('metrics', 'moe_expert_ms', 'read')(context)
  name = context['config'].get('expert_work')
  if name is None or ms is None:
    return None
  tokens = context['global_batch'] * int(context['mix']['seq_len'])
  work = names.resolve(name)(context['config'],
                             tokens / len(context['devices']))
  peak = peaks.peaks_for(context['device_kind'])
  floor = max(work['flops'] / peak['bf16_flops_per_s'],
              work['bytes'] / peak['hbm_bytes_per_s'])
  return 100.0 * floor / (ms * 1e-3)
