"""Device ms per step of self time in ops traced under the phases
``fwd/route`` (send buffers, slot selection, owner-side id routing, the
sort and rank sums of the ids) and ``bwd/route`` (cotangent send
buffers, per-unique-row sums): what it costs to decide where ids and
gradients go, beside the exchange that carries them, on the chip that
spends most there.  Source: device trace (the ops' scope paths).  The
rank sums are booked here only in a step whose cumulative ops carry
their caller's scope (``routing.cumsum0``)."""
from benchmarks.lib import layer


def read(context):
  parts = [layer.phase_ms(context, prefix)
           for prefix in ('fwd/route', 'bwd/route')]
  found = [ms for ms in parts if ms is not None]
  return sum(found) if found else None
