"""Device ms per step of self time in ops traced under the phases
``mixer/selective_scan`` and ``mixer/conv`` (inside ``head``, forward and
backward alike): the state-space mixers' selective scan (softplus, decay, the
chunked recurrence and its ``D`` term) and their causal convolution, all
Mamba layers together, on the chip that spends most there.  Source:
device trace (the ops' scope paths).  Nothing to read in a step without
such a layer."""
from benchmarks.lib import layer


def read(context):
  parts = [layer.phase_ms(context, prefix)
           for prefix in ('mixer/selective_scan', 'mixer/conv')]
  parts = [p for p in parts if p is not None]
  return sum(parts) if parts else None
