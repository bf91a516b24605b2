"""Device ms per step in the routed layers' expert products: self time in
ops traced under the phase ``moe/experts`` (the gating between the two
grouped products, and the products themselves where they carry the
phase), plus the kernels XLA makes of ``ragged_dot`` on a TPU, which it
names ``ragged-dot-...`` and gives no scope (they would else be counted
nowhere but ``unbooked_share``): forward, recomputed and backward, on the
busiest chip.  The kernels found by name count only as far as the
busiest chip has time that no phase books (``unscoped`` +
``no_source``): once they carry a scope they are under the phase or
under another, and are not counted a second time here.
``tests/test_tpu_lowering.py`` holds the compiled layer to that name and
to kernels without an ``op_name``: a compiler that renames or scopes
them fails that test, not this number.  Source: device trace.  Nothing
to read in a step without a routed layer."""
from benchmarks.lib import layer, xtrace


def read(context):
  phase = layer.phase_ms(context, 'moe/experts')
  if phase is None:
    return None
  trace = context['trace']
  kernels = sum(s for name, s in trace.get('ops', {}).items()
                if name.startswith('ragged-dot'))
  booked = trace['phase_s'].get(trace.get('fullest'), {})
  unbooked = (booked.get(xtrace.UNSCOPED, 0.0)
              + booked.get(xtrace.NO_SOURCE, 0.0))
  return phase + min(kernels, unbooked) / trace['steps'] * 1e3
