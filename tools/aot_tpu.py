#!/usr/bin/env python3
"""Run a compile-only script as the chip would compile it.

A compile-only flow (``jax.experimental.topologies``: a described v5e,
none attached) traces on the CPU backend, so code that picks a kernel by
``jax.default_backend()`` would size and compile its CPU path.  This
sets the kernels' AOT hooks (``ops/pallas_attention.ASSUME_TPU``,
``ops/pallas_segwalk.ASSUME_TPU``, ``ops/pallas_ssd.ASSUME_TPU``), runs
the script named on the command line with the arguments after it, and
prints the ``obs.metrics`` registry afterwards, in which
``attention.kernel_layers`` and ``attention.blocked_layers`` say which
path each traced attention layer took, ``mixer.scan_kernel_layers`` how
many Mamba layers' scans run on the kernels, and ``moe.kept_outputs`` how
many routed blocks keep their output for the backward pass:

  JAX_PLATFORMS=cpu python3 tools/aot_tpu.py benchmarks/dev/aot_hybrid.py \\
      --config trinity-mini --traffic train-packed-8k [--hlo step.txt]
"""

import json
import os
import runpy
import sys

sys.path.insert(0, os.getcwd())


def main():
  if len(sys.argv) < 2:
    raise SystemExit(__doc__)
  from distributed_embeddings_tpu.obs import metrics as obs_metrics
  from distributed_embeddings_tpu.ops import (pallas_attention,
                                              pallas_segwalk, pallas_ssd)
  pallas_attention.ASSUME_TPU = True
  pallas_segwalk.ASSUME_TPU = True
  pallas_ssd.ASSUME_TPU = True
  obs_metrics.enable()
  sys.argv = sys.argv[1:]
  try:
    runpy.run_path(sys.argv[0], run_name='__main__')
  finally:
    print('obs.metrics: ' + json.dumps(obs_metrics.snapshot()), flush=True)


if __name__ == '__main__':
  main()
