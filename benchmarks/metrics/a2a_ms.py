"""Device ms per step in ``all-to-all`` ops, on the chip that spends
most there.  Source: device trace.  Nothing to read on one chip."""
from benchmarks.lib import layer


def read(context):
  return layer.class_ms(context, ('a2a',))
