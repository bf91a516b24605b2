"""Device ms per step of self time in ops traced under the phase
``apply/tied``: the update of a table the head also multiplies by: the
lookups' row sums scattered into the head's dense gradient and the one
whole-table optimizer step, on the chip that spends most there.  Source:
device trace (the ops' scope paths).  Nothing to read in a step whose
head reads no table."""
from benchmarks.lib import layer


def read(context):
  return layer.phase_ms(context, 'apply/tied')
