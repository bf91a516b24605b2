"""Device ms per step of self time in ops traced under the phase
``vocab`` (inside ``head``): the final norm, the logits over the
vocabulary a block of positions at a time, the cross-entropy, and their
backward pass with the table's dense gradient, on the chip that spends
most there.  Source: device trace (the ops' scope paths).  Nothing to
read in a step whose head has no vocabulary."""
from benchmarks.lib import layer


def read(context):
  return layer.phase_ms(context, 'vocab')
