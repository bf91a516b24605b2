"""Device ms per step in ops lowered from ``gather`` (the forward
lookups, and the apply's gathers of rows to update).  Source: device
trace."""
from benchmarks.lib import layer


def read(context):
  return layer.class_ms(context, ('gather',))
