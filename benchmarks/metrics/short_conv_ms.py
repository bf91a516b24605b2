"""Device ms per step of self time in ops traced under the phase
``mixer/short_conv``: the gated short-convolution operators whole (the
in-projection, both gates, the depthwise convolution and the
out-projection), forward, recomputed and backward, on the chip that
spends most there.  Source: device trace (the ops' scope paths).
Nothing to read in a step whose stack has no such operator."""
from benchmarks.lib import layer


def read(context):
  return layer.phase_ms(context, 'mixer/short_conv')
