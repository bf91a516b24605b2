"""Device ms per step of self time in ops traced under the phase
``apply/write_rows``: the sparse apply's write-back, the scatters into
table and optimizer state, on the chip that spends most there.  Source:
device trace (the ops' scope paths).  Nothing to read in a step served
from a cache filled before the phases existed."""
from benchmarks.lib import layer


def read(context):
  return layer.phase_ms(context, 'apply/write_rows')
