"""How long a process takes to reach the chip, piece by piece: the
interpreter, ``import jax``, the backend (``jax.devices()``), one tiny
program.  One JSON line.  For the chip tool, several times in one call:

  python3 benchmarks/dev/start_probe.py [tag]
"""
import time
_T0 = time.perf_counter()
import json
import sys

t = {}
import jax
t['import_jax'] = time.perf_counter() - _T0
a = time.perf_counter()
devices = jax.devices()
t['devices'] = time.perf_counter() - a
a = time.perf_counter()
import jax.numpy as jnp
jnp.zeros((8, 128)).block_until_ready()
t['first_op'] = time.perf_counter() - a
t['total'] = time.perf_counter() - _T0
print(json.dumps({'tag': sys.argv[1] if len(sys.argv) > 1 else '',
                  'platform': devices[0].platform, 'n': len(devices),
                  **{k: round(v, 3) for k, v in t.items()}}), flush=True)
