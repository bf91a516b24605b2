"""Device ms per step in the sparse apply's scatters, sorts and cumulative
sums.  Source: device trace."""
from benchmarks.lib import layer


def read(context):
  return layer.class_ms(context, ('scatter', 'sort', 'cumsum'))
