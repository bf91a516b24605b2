"""Test configuration: fake an 8-device CPU mesh before JAX initialises.

The reference's distributed tests need real `horovodrun -np N` processes
(`/root/reference/tests/dist_model_parallel_test.py`); JAX lets us fake an
N-device mesh in-process on CPU instead, which covers the same collective
choreography single-machine (SURVEY.md §4).
"""

import os

# Must be set before the first JAX backend initialisation.
_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
  _flags += ' --xla_force_host_platform_device_count=8'
if (os.environ.get('DET_TESTS_REAL_TPU') != '1'
    and 'intra_op_parallelism_threads' not in _flags):
  # 8 faked devices x an intra-op Eigen pool each oversubscribes the
  # 2-core CI host ~16x; the XLA-CPU collective rendezvous occasionally
  # deadlocks CPU-idle under that thrash (observed twice across PR 5
  # runs — same tests pass in isolation).  One intra-op thread per
  # faked device keeps the schedulable thread count at the device
  # count, which is the configuration the suite was stable under.
  _flags += ' --xla_cpu_multi_thread_eigen=false intra_op_parallelism_threads=1'
os.environ['XLA_FLAGS'] = _flags
os.environ['JAX_ENABLE_X64'] = '0'
# Tests run on the fake 8-device CPU mesh whatever hardware is attached:
# JAX_PLATFORMS is set here, before JAX is imported, and it holds.  The
# exception is DET_TESTS_REAL_TPU=1, which leaves the backend JAX finds
# for the hardware-gated suite (tests/test_pallas_tpu.py).
if os.environ.get('DET_TESTS_REAL_TPU') != '1':
  os.environ['JAX_PLATFORMS'] = 'cpu'

import threading  # noqa: E402

import pytest  # noqa: E402

from distributed_embeddings_tpu.utils import compile_cache  # noqa: E402

# Persistent compilation cache, placed from outside
# (utils/compile_cache.py): repeat suite runs skip recompilation.
compile_cache.configure()


@pytest.fixture(autouse=True)
def _hang_alarm(request):
  """Per-test alarm: dump all-thread tracebacks BEFORE tier-1's outer
  timeout wedges silently.

  If the known XLA-CPU rendezvous flake (a shard_map collective
  deadlocking CPU-idle under thread oversubscription) recurs, the outer
  pytest timeout kills the whole run with no evidence of which test or
  which thread wedged.  This alarm fires first and writes the evidence:
  the resilience diagnostics dump (all-thread tracebacks, PR 3's
  watchdog machinery) plus a journaled ``test_alarm_fired`` event naming
  the test.  Dump-only — the test keeps running (a slow-but-alive test
  on a loaded host must not be killed by its diagnostics).  Tune or
  disable with ``DET_TEST_ALARM_S`` (seconds; 0 disables).
  """
  timeout_s = float(os.environ.get('DET_TEST_ALARM_S', '420'))
  if timeout_s <= 0:
    yield
    return
  from distributed_embeddings_tpu.utils import resilience

  def fire():
    resilience.dump_diagnostics(f'test alarm ({timeout_s:g}s): '
                                f'{request.node.nodeid}')
    resilience.journal('test_alarm_fired', test=request.node.nodeid,
                       timeout_s=timeout_s)
    _dump_collective_ledger(request.node.nodeid)
    _dump_commsan_journal(request.node.nodeid)

  timer = threading.Timer(timeout_s, fire)
  timer.daemon = True
  timer.start()
  try:
    yield
  finally:
    timer.cancel()


def _dump_collective_ledger(nodeid):
  """When the alarm catches a thread wedged inside a jit/shard_map
  dispatch (the known XLA-CPU rendezvous flake), print graphlint's
  checked-in collective-schedule ledger (design §18) so the stall is
  attributable to a named program's collective sequence from the
  tier-1 log alone — not just a rerun note.

  A wedged collective usually shows NO python jax frame (the C++ pjit
  fastpath dispatches straight into the executable), so the detector
  matches the INNERMOST python frame — the frame actually blocked in
  the C call — against the jax package or the library's own dispatch
  sites.  Innermost-only matters: idle pipeline daemons (batcher
  dispatcher, CsrFeed producer) carry package frames higher up their
  stacks during most tests while blocked in stdlib queue.get, and a
  hang in pure pytest/IO code must stay quiet.  Best-effort by the
  same contract as dump_diagnostics: diagnostics must never mask the
  hang they are evidence for."""
  import json
  import sys
  import traceback
  try:
    frames = sys._current_frames()
    wedged = []
    for tid, frame in frames.items():
      stack = traceback.extract_stack(frame)
      if not stack:
        continue
      fn = stack[-1].filename.replace(os.sep, '/')
      if '/jax/' in fn or ('/distributed_embeddings_tpu/' in fn
                           and '/utils/resilience' not in fn):
        wedged.append(tid)
    if not wedged:
      return
    ledger_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        'tools', 'graphlint_ledger.json')
    if not os.path.exists(ledger_path):
      return
    with open(ledger_path, 'r', encoding='utf-8') as f:
      ledger = json.load(f)
    print(f'\n=== collective-schedule ledger (test alarm: {nodeid}; '
          f'{len(wedged)} thread(s) inside jax dispatch) ===',
          file=sys.stderr)
    for name in sorted(ledger):
      ops = ledger[name].get('collectives', [])
      seq = ', '.join(f"{o['primitive']}@{o['axis']}"
                      f"{'*' if o.get('loop') else ''}" for o in ops)
      print(f'  {name}: [{seq}]', file=sys.stderr)
    print('=== a wedged shard_map collective should match one '
          'program\'s sequence above (tools/graphlint.py '
          '--tier full --write-ledger refreshes) ===', file=sys.stderr)
  except Exception as e:  # noqa: BLE001 — diagnostics stay best-effort
    print(f'collective-ledger dump failed: {e!r}', file=sys.stderr)


def _dump_commsan_journal(nodeid):
  """If the wedged test had a commsan capture window armed (design
  §22), print this process's recorded collective-site sequence — the
  runtime twin of the static ledger above, so a cross-rank wedge is
  attributable to the LAST site this rank actually reached, not just
  to a program's expected schedule.  Best-effort, same contract as
  the ledger dump."""
  import sys
  try:
    from distributed_embeddings_tpu.analysis import commsan
    rep = commsan.report_active()
    if rep is None:
      return
    print(f'\n=== commsan sequence journal (test alarm: {nodeid}) ===',
          file=sys.stderr)
    print(rep, file=sys.stderr)
    print('=== the last site above is where this rank stopped '
          'recording; compare digests across ranks ===', file=sys.stderr)
  except Exception as e:  # noqa: BLE001 — diagnostics stay best-effort
    print(f'commsan journal dump failed: {e!r}', file=sys.stderr)
