"""CPU rehearsal of the whole harness at a toy size, by hand, before a
chip call:

  python3 benchmarks/rehearse.py            # one device, then four virtual

Each toy cell runs through the same functions as the benchmark's command
(``lib/cell.run_cell``), once with ``--trace 0`` and once with
``--trace 1``, and must come out ``correct``.  Nothing printed here is a
device number: the platform is the CPU, and every line says so.
"""
import time
_STARTED = time.perf_counter()

import argparse
import json
import os
import sys

os.environ['JAX_PLATFORMS'] = 'cpu'
os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS', '')
                           + ' --xla_force_host_platform_device_count=4')
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
TOY = os.path.join(ROOT, 'benchmarks', 'tests', 'toy')


def rehearse(cell_name, seed, trace, seconds=1.0, cache_dir=None):
  import jax
  from benchmarks.lib import cell
  with open(os.path.join(TOY, 'manifest.json')) as f:
    manifest = json.load(f)
  chips = {w['name']: w['chips'] for w in manifest['workloads']}[cell_name]
  args = argparse.Namespace(workload=cell_name, seed=seed, seconds=seconds,
                            trace=trace)
  return cell.run_cell(manifest, TOY, cell_name, args,
                       jax.devices()[:chips], time.perf_counter(),
                       cache_dir or os.path.join(ROOT, '.bench_cache', 'toy'))


CELLS = ('toy-synthetic-1', 'toy-dlrm-4', 'toy-token-1')


def main():
  ok = True
  for cell_name in CELLS:
    for trace in (0, 1):
      result = rehearse(cell_name, seed=2**31 + 12345 + trace, trace=trace)
      ok &= result['correct']
      print(f'CPU REHEARSAL {cell_name} trace={trace}: '
            + json.dumps(result), flush=True)
  print('rehearsal ' + ('passed' if ok else 'FAILED'), flush=True)
  return 0 if ok else 1


if __name__ == '__main__':
  sys.exit(main())
