"""Device ms per step in plain ``copy`` ops (layout changes and the
whole-table copies of ROADMAP S3).  Source: device trace."""
from benchmarks.lib import layer


def read(context):
  return layer.class_ms(context, ('copy',))
