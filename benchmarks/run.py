"""The benchmark's command.

  python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process; runs on the machine it is started on; refuses anything but a
TPU with at least the chips the cell asks for.  Prints progress and the
per-step record on earlier lines, the numbers compared beside their
limits as the last lines of standard error, and one JSON object as the
last line of standard output.  A traffic mix may name, under
``runtime_env``, variables the TPU runtime reads as it starts: they are
set here, before JAX is imported, where the caller has not set them.
"""
import time
_STARTED = time.perf_counter()

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
  parser = argparse.ArgumentParser()
  parser.add_argument('--workload', required=True)
  parser.add_argument('--seed', type=int, required=True)
  parser.add_argument('--seconds', type=float, required=True)
  parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
  args = parser.parse_args(argv)
  with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
    manifest = json.load(f)
  cells = {w['name']: w for w in manifest['workloads']}
  if args.workload not in cells:
    raise SystemExit(f'unknown workload {args.workload!r}: {sorted(cells)}')
  chips = int(cells[args.workload]['chips'])
  # what the mix asks of the runtime (``runtime_env``), before JAX loads it
  from benchmarks.lib import names
  mix = names.load_json(os.path.join(ROOT, 'benchmarks'), 'traffic',
                        cells[args.workload]['traffic'])
  names.set_runtime_env(mix, os.environ)

  import jax
  imported = time.perf_counter() - _STARTED
  devices = jax.devices()
  held = time.perf_counter() - _STARTED
  if devices[0].platform != 'tpu' or len(devices) < chips:
    raise SystemExit(
        f'benchmarks/run.py: {args.workload} needs {chips} TPU chip(s); JAX '
        f'found {len(devices)} x {devices[0].platform}. This benchmark '
        'measures the chip and runs nowhere else.')
  from benchmarks.lib import peaks
  peaks.peaks_for(devices[0].device_kind)   # an unknown chip is an error
  from distributed_embeddings_tpu.utils import compile_cache
  cache = compile_cache.configure()
  jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
  print(f'device: {len(devices)} x {devices[0].device_kind}, using {chips}; '
        f'compile cache {cache}; jax imported at {imported:.1f} s, devices '
        f'held at {held:.1f} s', file=sys.stderr, flush=True)

  from benchmarks.lib import cell
  result = cell.run_cell(manifest, os.path.join(ROOT, 'benchmarks'),
                         args.workload, args, devices[:chips], _STARTED,
                         os.path.join(ROOT, '.bench_cache'))
  sys.stdout.flush()
  for name, c in result['compared'].items():
    print(f'compared {name}: {c["value"]:.6g} (limit {c["limit"]:.6g}, '
          f'worst {c["worst"]})', file=sys.stderr, flush=True)
  print(json.dumps(result), flush=True)
  return 0


if __name__ == '__main__':
  sys.exit(main())
