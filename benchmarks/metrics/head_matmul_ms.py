"""Device ms per step in the dense head's products (``dot_general`` and
convolution fusions), forward and backward.  Source: device trace."""
from benchmarks.lib import layer


def read(context):
  return layer.class_ms(context, ('matmul',))
