"""Device ms per step of self time in ops traced under the phase
``attention`` (inside ``head``): the attention layers' projections,
blocked masked softmax and output product, forward and backward, on the
chip that spends most there.  Source: device trace (the ops' scope
paths).  Nothing to read in a step without such a layer."""
from benchmarks.lib import layer


def read(context):
  return layer.phase_ms(context, 'attention')
