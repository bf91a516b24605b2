"""Device ms per step of self time in ops traced under the phase
``moe/route`` (inside ``head``): the routed layers' router product in
float32 at the highest precision, the sigmoid, the top-k and the
weights, forward and backward, on the chip that spends most there.
Source: device trace (the ops' scope paths).  Nothing to read in a step
without a routed layer."""
from benchmarks.lib import layer


def read(context):
  return layer.phase_ms(context, 'moe/route')
