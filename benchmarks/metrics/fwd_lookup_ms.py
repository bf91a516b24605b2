"""Device ms per step of self time in ops traced under the phase
``fwd/lookup_combine``: the forward row gathers and their combine over
the hotness axis, on the chip that spends most there.  Source: device
trace (the ops' scope paths).  Nothing to read in a step served from a
cache filled before the phases existed."""
from benchmarks.lib import layer


def read(context):
  return layer.phase_ms(context, 'fwd/lookup_combine')
