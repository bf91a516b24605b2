"""The gated short convolutions' share of their roofline, in percent: the
least time the chip could take for the operators of one step (the
function the configuration names as ``short_conv_work``: the two
projections' FLOPs a position, forward and backward, over peak bf16
FLOP/s, or the kernels read forward and backward and written once plus
five rows of ``hidden`` a position over peak HBM bytes/s, whichever is
larger; derivation in ``benchmarks/classes/lfm2_moe.short_conv_work``)
over ``short_conv_ms``.  No recomputation is counted.  FLOPs bind at the
published sizes.  Source: device trace.  Nothing to read where the
configuration names no ``short_conv_work`` or the step has no such
phase."""
from benchmarks.lib import layer, names, peaks


def read(context):
  ms = layer.phase_ms(context, 'mixer/short_conv')
  name = context['config'].get('short_conv_work')
  if name is None or ms is None:
    return None
  tokens = context['global_batch'] * int(context['mix']['seq_len'])
  work = names.resolve(name)(context['config'],
                             tokens / len(context['devices']))
  peak = peaks.peaks_for(context['device_kind'])
  floor = max(work['flops'] / peak['bf16_flops_per_s'],
              work['bytes'] / peak['hbm_bytes_per_s'])
  return 100.0 * floor / (ms * 1e-3)
