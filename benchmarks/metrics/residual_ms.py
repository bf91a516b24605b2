"""Device ms per step of self time in ops traced under the phase
``residual`` (inside ``head``): the norm before (and, where a family has
one, after) each sub-layer of a language-model block, the residual
multiplier and the add, forward, recomputed and backward, as far as XLA
leaves them unfused (a norm fused into the product beside it keeps the
product's name), on the chip that spends most there.  Source: device
trace (the ops' scope paths).  Nothing to read in a step without such a
block, or compiled before the phase existed."""
from benchmarks.lib import layer


def read(context):
  return layer.phase_ms(context, 'residual')
