"""Device ms per step of self time in ops traced under the phases
``moe/dispatch`` (keys, the sort by expert, the gather of the tokens'
rows into the buffer) and ``moe/combine`` (weighting and the sum back to
token order) of the routed layers, forward and backward: what it costs
to bring assignments to their experts and back, on the chip that spends
most there.  Source: device trace.  Nothing to read in a step without a
routed layer."""
from benchmarks.lib import layer


def read(context):
  parts = [layer.phase_ms(context, prefix)
           for prefix in ('moe/dispatch', 'moe/combine')]
  return None if None in parts else sum(parts)
