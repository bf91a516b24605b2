"""The control and the faults of a training cell, read on the chip at the
cell's own size (by hand, when a cell's limits are set):

  python3 benchmarks/control.py --workload <cell> --seeds 1,2,3

For each seed: the plain reference as the configuration states it (its
class, traffic shape and optimizer found by the names in the cell's data
files), then, put in the program's place, the reference one step of
precision lower (the control) and with each fault planted that the cell
can have.  Prints
the numbers that decide ``correct`` for each; needs no measured window
(training's readings come from the first steps alone).  Refuses anything
but a TPU: the cell's own size is the point.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
  parser = argparse.ArgumentParser()
  parser.add_argument('--workload', required=True)
  parser.add_argument('--seeds', required=True)
  args = parser.parse_args(argv)
  import jax
  if jax.devices()[0].platform != 'tpu':
    raise SystemExit('benchmarks/control.py reads the control at the '
                     "cell's own size on the chip; on a CPU run "
                     'benchmarks/tests')
  from benchmarks.lib import names, reference
  with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
    manifest = json.load(f)
  spec = {w['name']: w for w in manifest['workloads']}[args.workload]
  root = os.path.join(ROOT, 'benchmarks')
  config = names.load_json(root, 'configs', spec['config'])
  mix = names.load_json(root, 'traffic', spec['traffic'])
  variants = [('control', dict(precision='control')),
              ('half_batch', dict(fault='half_batch')),
              ('state_unchanged', dict(fault='state_unchanged'))]
  if spec['chips'] > 1:
    variants.append(('no_exchange', dict(fault='no_exchange')))
  for seed in (int(s) for s in args.seeds.split(',')):
    stated = reference.run_reference(config, mix, seed, chips=spec['chips'])
    for name, kwargs in variants:
      other = reference.run_reference(config, mix, seed, chips=spec['chips'],
                                      **kwargs)
      numbers, worst = reference.compare(other, stated)
      print(json.dumps({'workload': args.workload, 'seed': seed,
                        'in_the_programs_place': name, **numbers,
                        'worst': worst}), flush=True)


if __name__ == '__main__':
  sys.exit(main())
