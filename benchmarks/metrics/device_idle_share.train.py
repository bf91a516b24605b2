"""Percent of the traced window in which no op ran on the device, mean
over the chips: 100 x (1 - union of busy intervals / window).  Source:
device trace."""


def read(context):
  trace = context['trace']
  if not trace['devices'] or trace['window_s'] <= 0:
    return None
  return 100.0 * (1.0 - trace['busy_mean_s'] / trace['window_s'])
