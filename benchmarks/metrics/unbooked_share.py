"""Percent of the busiest chip's busy time in ops that no phase books:
``unscoped`` (traced under no scope: JAX lowers cumulative sums through
one function a module's call sites share) plus ``no_source`` (XLA's own
copies and layout ops).  100 where the step carries no scope at all.
Source: device trace (the ops' scope paths)."""
from benchmarks.lib import xtrace


def read(context):
  trace = context['trace']
  busy = trace['busy_s'].get(trace['fullest'], 0.0)
  if busy <= 0:
    return None
  phases = trace['phase_s'][trace['fullest']]
  return 100.0 * (phases.get(xtrace.UNSCOPED, 0.0)
                  + phases.get(xtrace.NO_SOURCE, 0.0)) / busy
