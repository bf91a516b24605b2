"""Device ms per step of self time in ops traced under the phase
``apply/dedup``: the sparse apply's dedup of the update stream (sort,
segment sums, compaction, the sorted gathers of ids and payload), on the
chip that spends most there.  Source: device trace (the ops' scope
paths).  Nothing to read in a step served from a cache filled before the
phases existed."""
from benchmarks.lib import layer


def read(context):
  return layer.phase_ms(context, 'apply/dedup')
