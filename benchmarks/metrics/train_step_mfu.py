"""The whole step's share of the chip's peak, in percent: the least time
one chip needs for its share of the step's required work
(``peaks.step_floor_seconds``: the head's FLOPs forward and backward, by
the ``work`` function the configuration names, over peak FLOP/s, or
distinct rows read once forward and read and written once per state slot
in the apply, plus the head's own bytes, over peak bytes/s, whichever is
larger) over
the time per step on the device's own clock: the mean time from one
step's start to the next one's on the busiest chip of the traced window,
so each step with the gap that follows it (``xtrace``'s
``step_period_s``).  Source: device trace; the host's clock is not read."""
from benchmarks.lib import layer, peaks


def read(context):
  trace = context['trace']
  if not trace['steps'] or trace['step_period_s'] <= 0:
    return None   # no step of the program in the trace: nothing to measure
  work = layer.head_work_per_chip(context)
  floor, _ = peaks.step_floor_seconds(
      peaks.peaks_for(context['device_kind']), work['flops'],
      layer.row_bytes_per_chip(context), layer.state_slots(context),
      work['bytes'])
  return 100.0 * floor / trace['step_period_s']
