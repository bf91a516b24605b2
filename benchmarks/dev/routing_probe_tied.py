"""``routing_probe.py`` for a mixture-of-experts cell whose head reads its
table (a tied vocabulary).  That probe builds its step without the
builder's ``step_kwargs``, so a head that takes ``tables`` gets none; a
PR that adds a cell edits no file the benchmark has, so this one hands
the probe a ``make_hybrid_train_step`` that already knows the
configuration's ``head_reads_tables`` and runs it as it is (a later
``benchmark`` PR passes ``**model.step_kwargs`` there and deletes this
file).  By hand, on the chip; same arguments, same lines:

  python3 benchmarks/dev/routing_probe_tied.py --workload lfm2-train-packed-8k --seed 11 --steps 16
"""
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None):
  argv = sys.argv[1:] if argv is None else argv
  from benchmarks.dev import routing_probe
  from benchmarks.lib import names
  workload = argv[argv.index('--workload') + 1]
  with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
    cell = {w['name']: w for w in json.load(f)['workloads']}[workload]
  root = os.path.join(ROOT, 'benchmarks')
  # what the mix asks of the runtime, before JAX loads it
  names.set_runtime_env(names.load_json(root, 'traffic', cell['traffic']),
                        os.environ)
  config = names.load_json(root, 'configs', cell['config'])
  from distributed_embeddings_tpu import parallel
  parallel.make_hybrid_train_step = functools.partial(
      parallel.make_hybrid_train_step,
      head_reads_tables=tuple(config.get('head_reads_tables', ())))
  return routing_probe.main(argv)


if __name__ == '__main__':
  sys.exit(main())
