"""The gathers' share of their roofline, in percent: the least time the
chip could take to read every distinct row the step's lookups ask for
once (``peaks.distinct_row_bytes`` over chips, over the peak HBM
bytes/s), over ``gather_ms``.  Bound by bytes.  PR 22's reading of 0.0985
under this name was a share of a different byte count (the harness is
gone); this one reads about 0.1 percent on synthetic-tiny."""
from benchmarks.lib import layer, peaks


def read(context):
  ms = layer.class_ms(context, ('gather',))
  if ms is None:
    return None
  peak = peaks.peaks_for(context['device_kind'])['hbm_bytes_per_s']
  return 100.0 * layer.row_bytes_per_chip(context) / peak / (ms * 1e-3)
