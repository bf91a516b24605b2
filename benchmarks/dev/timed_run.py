"""One run of a cell with its wall seconds and the largest resident
memory of the process (by hand, when a cell's run is sized against the
check's time limit and the host's memory):

  python3 benchmarks/dev/timed_run.py --workload <cell> --seed <n> --seconds 10 --trace 0

Passes its arguments to ``benchmarks/run.py``; the last line on standard
error is ``run: <s> s wall, <GB> GB resident at the most, exit <code>``.
"""
import resource
import subprocess
import sys
import time

started = time.perf_counter()
code = subprocess.call([sys.executable, 'benchmarks/run.py'] + sys.argv[1:])
peak_kb = resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss
print(f'run: {time.perf_counter() - started:.1f} s wall, '
      f'{peak_kb * 1024 / 1e9:.1f} GB resident at the most, exit {code}',
      file=sys.stderr, flush=True)
sys.exit(code)
