"""Device ms per step of self time in ops traced under the phase
``moe/shared``: the routed layers' shared experts (both products and the
activation between them; a squared-ReLU expert of width 3712 on every
token in the nemotron cell), forward, recomputed and backward, on the
chip that spends most there.  Source: device trace (the ops' scope
paths).  Nothing to read in a step without a shared expert."""
from benchmarks.lib import layer


def read(context):
  return layer.phase_ms(context, 'moe/shared')
