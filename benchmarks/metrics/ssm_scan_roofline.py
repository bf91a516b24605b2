"""The state-space scans' share of their roofline, in percent: the least
time the chip could take for the scans of one step (the function the
configuration names as ``scan_work``: the sequential recurrence's FLOPs,
forward and backward, over peak FLOP/s, or the bytes of ``xBC``, ``dt``,
``y`` and the chunk states read and written once forward and once
backward over peak HBM bytes/s, whichever is larger; derivation in
``benchmarks/classes/hybrid_ssm.scan_work``) over the self time under
``mixer/selective_scan`` + ``mixer/conv``.  Bytes bind at the published
sizes.  Source: device trace.  Nothing to read where the configuration names no
``scan_work`` or the step has no such phase."""
from benchmarks.lib import layer, names, peaks


def read(context):
  parts = [layer.phase_ms(context, prefix)
           for prefix in ('mixer/selective_scan', 'mixer/conv')]
  name = context['config'].get('scan_work')
  if name is None or None in parts:
    return None
  tokens = context['global_batch'] * int(context['mix']['seq_len'])
  work = names.resolve(name)(context['config'],
                             tokens / len(context['devices']))
  peak = peaks.peaks_for(context['device_kind'])
  floor = max(work['flops'] / peak['bf16_flops_per_s'],
              work['bytes'] / peak['hbm_bytes_per_s'])
  return 100.0 * floor / (sum(parts) * 1e-3)
