"""Device ms per step of self time in ops traced under the phase
``attention/window``: the ``sliding_attention`` layers' projections,
norms, rotary embedding, blocked masked softmax over the key blocks the
window meets, gate and output product, forward and backward, on the chip
that spends most there; ``attention_ms`` holds it and the full layers'
``attention/full``.  Source: device trace.  Nothing to read in a step
without such a layer."""
from benchmarks.lib import layer


def read(context):
  return layer.phase_ms(context, 'attention/window')
