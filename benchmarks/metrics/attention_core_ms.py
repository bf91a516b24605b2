"""Device ms per step of self time in ops traced under the phase
``attention/core`` (inside ``attention``): the masked softmax products
themselves, as the fused kernels with the transposes they need or as
unrolled blocks, forward, recomputed and backward, apart from the
projections, per-head norms, rotary and gate that ``attention_ms`` also
holds, on the chip that spends most there.  Source: device trace (the
ops' scope paths).  Nothing to read in a step without an attention
layer, or compiled before the phase existed."""
from benchmarks.lib import layer


def read(context):
  return layer.phase_ms(context, 'attention/core')
