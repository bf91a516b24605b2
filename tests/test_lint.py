"""detlint static-analysis layer (docs/design.md §17).

The load-bearing claims pinned here:

- one TRUE-POSITIVE fixture per rule: the pass catches a seeded
  lock-order cycle, a blocking put under a lock, an untimed put into a
  bounded queue, a thread without a join, a silent broad-except, an
  unregistered journal/span/metric name, a derived (unverifiable)
  name, an impure jit-traced function, a dangling api.md symbol, a
  stale CLI flag, and a dangling design.md §-ref;
- the zero-unwaived-findings gate on the LIVE tree: this test IS the
  tier-1 wiring of ``python tools/detlint.py --strict`` (exit 0, every
  waiver carrying rationale);
- the waiver policy refusals: a rationale-less waiver is a
  ``BaselineError`` (CLI exit 2), a stale waiver fails ``--strict``
  (exit 3), a waived finding does not fail the gate;
- finding ids are line-stable: inserting code above a violation does
  not change its id (the waiver survival contract);
- locksan (the runtime twin): an inverted acquisition order inside a
  capture window raises ``LockOrderError`` with the witnessed cycle,
  a consistent order passes, and instrumented locks keep Condition /
  queue.Queue working.
"""

import importlib.util
import os
import pathlib
import textwrap
import threading

import pytest

from distributed_embeddings_tpu.analysis import (Baseline, BaselineError,
                                                 locksan, run_passes,
                                                 run_repo)
from distributed_embeddings_tpu.analysis import core as lint_core

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _detlint_cli():
  spec = importlib.util.spec_from_file_location(
      'detlint_for_test', str(ROOT / 'tools' / 'detlint.py'))
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod


def _fixture_tree(tmp_path, files):
  """A mini runtime tree detlint can walk: {relpath: source}."""
  for rel, src in files.items():
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
  return str(tmp_path)


def _rules(res):
  return {f.rule for f in res.findings} | {f.rule
                                           for f in res.unverifiable}


# --------------------------------------------------------------------------
# the live-tree gate: detlint --strict exits 0 (tier-1's CI wiring)
# --------------------------------------------------------------------------


def test_live_tree_detlint_strict_clean():
  """The acceptance pin: zero unwaived findings, zero unverifiable,
  zero stale waivers on the checked-in tree, with every waiver
  carrying a rationale — exactly what `tools/detlint.py --strict`
  gates in CI."""
  res = run_repo(str(ROOT))
  assert not res.findings, '\n'.join(f.brief() for f in res.findings)
  assert not res.unverifiable, \
      '\n'.join(f.brief() for f in res.unverifiable)
  assert not res.stale_waivers, res.stale_waivers
  # the waivers exist and each carries rationale (Baseline.load
  # enforces it; this pins that the file actually loads)
  base = Baseline.load(str(ROOT / 'tools' / 'detlint_baseline.toml'))
  # equality, not non-emptiness: an EMPTIED baseline (every waived
  # finding fixed) is the cleaner tree, never a failure.  The file is
  # SHARED with graphlint (design §18) — only detlint-owned waivers
  # (rule prefix naming a detlint pass) are expected to match here
  detlint_owned = [w for w in base.waivers
                   if w['id'].split('/', 1)[0]
                   in lint_core.list_passes()]
  assert len(detlint_owned) == len(res.waived)
  # every pass genuinely ran over real sites — a silently broken scan
  # must fail here, not pass vacuously (the old regex tests' guard)
  assert res.meta['registry_sites']['journal'] > 10
  assert res.meta['registry_sites']['span'] > 10
  assert res.meta['registry_sites']['metric'] > 10
  assert res.meta['lock_graph']['locks'] >= 10
  assert res.meta['lock_graph']['threads'] >= 5
  assert res.meta['purity']['roots'] > 10
  assert res.meta['docdrift_api_symbols'] > 50
  assert res.meta['docdrift_cli_flags'] > 10
  assert res.meta['docdrift_section_refs'] > 50


def test_live_tree_cli_strict_exit_zero():
  assert _detlint_cli().main(['--strict']) == 0


def test_pass_subset_does_not_stale_other_passes_waivers():
  """`--passes registry --strict` must exit 0: waivers owned by
  passes that did not run are not stale (the documented CI subset
  recipe must not fail spuriously)."""
  assert _detlint_cli().main(['--passes', 'registry', '--strict']) == 0


# --------------------------------------------------------------------------
# registry-schema fixtures
# --------------------------------------------------------------------------


def test_fixture_unregistered_journal_name(tmp_path):
  root = _fixture_tree(tmp_path, {
      'distributed_embeddings_tpu/bad.py': """
          from distributed_embeddings_tpu.utils.resilience import journal

          def oops():
            journal('definitely_not_a_registered_event', x=1)
          """})
  res = run_passes(root, passes=['registry'])
  hits = [f for f in res.findings
          if f.rule == 'registry/journal-unregistered']
  assert len(hits) == 1
  assert hits[0].symbol == 'definitely_not_a_registered_event'
  assert _detlint_cli().main(['--root', root, '--baseline',
                              str(tmp_path / 'none.toml'),
                              '--passes', 'registry']) == 1


def test_fixture_aliased_import_still_resolves(tmp_path):
  """The regex scans' blind spot: a renamed direct import.  The AST
  pass resolves it through the alias map — enforcement strictly
  stronger than the deleted scans."""
  root = _fixture_tree(tmp_path, {
      'distributed_embeddings_tpu/bad.py': """
          from distributed_embeddings_tpu.utils.resilience import (
              journal as log_event)

          def oops():
            log_event('sneaky_unregistered_event')
          """})
  res = run_passes(root, passes=['registry'])
  assert any(f.rule == 'registry/journal-unregistered'
             and f.symbol == 'sneaky_unregistered_event'
             for f in res.findings)


def test_fixture_derived_name_is_unverifiable_not_silent(tmp_path):
  root = _fixture_tree(tmp_path, {
      'distributed_embeddings_tpu/bad.py': """
          from distributed_embeddings_tpu.utils import resilience

          def oops(which):
            resilience.journal(f'event_{which}')
          """})
  res = run_passes(root, passes=['registry'])
  assert not res.findings
  assert len(res.unverifiable) == 1
  assert res.unverifiable[0].rule == 'registry/unverifiable-name'
  # warn by default, fail under --strict (the trace_report escalation)
  cli = _detlint_cli()
  assert cli.main(['--root', root, '--baseline',
                   str(tmp_path / 'none.toml'),
                   '--passes', 'registry']) == 0
  assert cli.main(['--root', root, '--baseline',
                   str(tmp_path / 'none.toml'),
                   '--passes', 'registry', '--strict']) == 3


def test_fixture_unregistered_span_and_metric(tmp_path):
  root = _fixture_tree(tmp_path, {
      'distributed_embeddings_tpu/bad.py': """
          from distributed_embeddings_tpu.obs import trace as obs_trace
          from distributed_embeddings_tpu.obs import metrics as obs_metrics

          def oops():
            with obs_trace.span('no/such_phase'):
              obs_metrics.inc('no.such_metric')
          """})
  res = run_passes(root, passes=['registry'])
  rules = {(f.rule, f.symbol) for f in res.findings}
  assert ('registry/span-unregistered', 'no/such_phase') in rules
  assert ('registry/metric-unregistered', 'no.such_metric') in rules


def test_fixture_stats_key_discipline(tmp_path):
  root = _fixture_tree(tmp_path, {
      'distributed_embeddings_tpu/bad.py': """
          class Component:
            def stats(self):
              return {'batches': 1, 'not_a_registered_stats_key': 2}
          """})
  res = run_passes(root, passes=['registry'])
  hits = [f for f in res.findings
          if f.rule == 'registry/stats-key-unregistered']
  assert [f.symbol for f in hits] == \
      ['Component.stats:not_a_registered_stats_key']
  # a DERIVED stats key is an explicit unverifiable finding, never a
  # silent skip (the same contract as derived journal names)
  root2 = _fixture_tree(tmp_path / 'derived', {
      'distributed_embeddings_tpu/bad2.py': """
          class Component:
            def stats(self):
              out = {}
              out[f'{self.prefix}_ms'] = 1.0
              return out
          """})
  res2 = run_passes(root2, passes=['registry'])
  assert any(f.rule == 'registry/unverifiable-name'
             and f.symbol.startswith('stats-key:Component.stats')
             for f in res2.unverifiable), \
      [f.brief() for f in res2.unverifiable]


def test_fixture_artifact_key_unproduced(tmp_path):
  """A registered bench-artifact key with no producing string literal
  anywhere in the runtime sources must fire (the rule arms only on
  trees that HAVE a bench.py).  Docstrings and the registry-definition
  module itself never count as producers — otherwise the check is
  vacuously true."""
  root = _fixture_tree(tmp_path, {
      'bench.py': """
          \"\"\"Fixture bench whose docstring even NAMES serve_qps —
          prose is not a producer.\"\"\"
          def emit():
            return {'metric': 'x', 'value': 1.0}
          """})
  res = run_passes(root, passes=['registry'])
  unproduced = {f.symbol for f in res.findings
                if f.rule == 'registry/artifact-key-unproduced'}
  assert 'serve_qps' in unproduced     # named only in the docstring
  assert 'lint_waivers' in unproduced  # named nowhere
  assert 'metric' not in unproduced    # genuinely produced
  assert 'value' not in unproduced
  # adding the real producer literal clears exactly that key
  (tmp_path / 'bench.py').write_text(
      "def emit():\n  return {'metric': 'x', 'value': 1.0,"
      " 'serve_qps': 2.0}\n")
  res2 = run_passes(root, passes=['registry'])
  unproduced2 = {f.symbol for f in res2.findings
                 if f.rule == 'registry/artifact-key-unproduced'}
  assert 'serve_qps' not in unproduced2
  assert 'lint_waivers' in unproduced2


# --------------------------------------------------------------------------
# concurrency fixtures
# --------------------------------------------------------------------------


def test_fixture_lock_order_cycle(tmp_path):
  root = _fixture_tree(tmp_path, {
      'distributed_embeddings_tpu/bad.py': """
          import threading

          _a = threading.Lock()
          _b = threading.Lock()

          def path_one():
            with _a:
              with _b:
                pass

          def path_two():
            with _b:
              with _a:
                pass
          """})
  res = run_passes(root, passes=['concurrency'])
  hits = [f for f in res.findings
          if f.rule == 'concurrency/lock-order-cycle']
  assert len(hits) == 1
  assert '_a' in hits[0].message and '_b' in hits[0].message
  assert _detlint_cli().main(['--root', root, '--baseline',
                              str(tmp_path / 'none.toml'),
                              '--passes', 'concurrency']) == 1


def test_fixture_call_mediated_cycle_across_modules(tmp_path):
  """The cross-module half: holding A and CALLING a helper in another
  module that takes B (and vice versa) must still close the cycle —
  the interprocedural closure, not just lexical nesting."""
  root = _fixture_tree(tmp_path, {
      'distributed_embeddings_tpu/mod_a.py': """
          import threading
          from distributed_embeddings_tpu import mod_b

          _a = threading.Lock()

          def use_a_then_b():
            with _a:
              mod_b.take_b()

          def take_a():
            with _a:
              pass
          """,
      'distributed_embeddings_tpu/mod_b.py': """
          import threading
          from distributed_embeddings_tpu import mod_a

          _b = threading.Lock()

          def use_b_then_a():
            with _b:
              mod_a.take_a()

          def take_b():
            with _b:
              pass
          """})
  res = run_passes(root, passes=['concurrency'])
  assert any(f.rule == 'concurrency/lock-order-cycle'
             for f in res.findings), [f.brief() for f in res.findings]


def test_fixture_multi_item_with_orders_like_nested(tmp_path):
  """`with a, b:` acquires left-to-right — it must contribute the same
  a->b edge as nested withs, so an inverted nested pair elsewhere
  still closes the cycle."""
  root = _fixture_tree(tmp_path, {
      'distributed_embeddings_tpu/bad.py': """
          import threading

          _a = threading.Lock()
          _b = threading.Lock()

          def path_one():
            with _a, _b:
              pass

          def path_two():
            with _b:
              with _a:
                pass
          """})
  res = run_passes(root, passes=['concurrency'])
  assert any(f.rule == 'concurrency/lock-order-cycle'
             for f in res.findings), [f.brief() for f in res.findings]


def test_fixture_thread_closure_locks_not_credited_to_parent(tmp_path):
  """A nested def (a thread target) acquiring a lock must NOT count as
  the constructing function acquiring it — the CsrFeed/_spawn shape
  would otherwise produce phantom lock-order cycles."""
  root = _fixture_tree(tmp_path, {
      'distributed_embeddings_tpu/ok.py': """
          import threading

          _a = threading.Lock()
          _b = threading.Lock()

          def start_worker():
            def worker():
              with _a:
                pass
            t = threading.Thread(target=worker, daemon=True)
            t.start()
            return t

          def under_b():
            with _b:
              t = start_worker()
              t.join()

          def legit_order():
            with _a:
              with _b:
                pass
          """})
  res = run_passes(root, passes=['concurrency'])
  assert not any(f.rule == 'concurrency/lock-order-cycle'
                 for f in res.findings), \
      [f.brief() for f in res.findings]


def test_fixture_blocking_put_under_lock_and_bounded(tmp_path):
  root = _fixture_tree(tmp_path, {
      'distributed_embeddings_tpu/bad.py': """
          import queue
          import threading

          class Pipe:
            def __init__(self):
              self._lock = threading.Lock()
              self._q = queue.Queue(maxsize=2)
              self._t = threading.Thread(target=self._run, daemon=True)
              self._t.start()

            def _run(self):
              pass

            def push(self, item):
              with self._lock:
                self._q.put(item)
          """})
  res = run_passes(root, passes=['concurrency'])
  rules = _rules(res)
  assert 'concurrency/blocking-queue-under-lock' in rules
  assert 'concurrency/untimed-put-bounded' in rules
  assert 'concurrency/thread-no-join' in rules  # self._t never joined
  # a timed put and a join satisfy all three
  ok_root = _fixture_tree(tmp_path / 'ok', {
      'distributed_embeddings_tpu/good.py': """
          import queue
          import threading

          class Pipe:
            def __init__(self):
              self._lock = threading.Lock()
              self._q = queue.Queue(maxsize=2)
              self._t = threading.Thread(target=self._run, daemon=True)
              self._t.start()

            def _run(self):
              pass

            def push(self, item):
              self._q.put(item, timeout=0.5)

            def close(self):
              self._t.join(timeout=5.0)
          """})
  ok = run_passes(ok_root, passes=['concurrency'])
  assert not ok.findings, [f.brief() for f in ok.findings]


def test_fixture_silent_except_swallow(tmp_path):
  root = _fixture_tree(tmp_path, {
      'distributed_embeddings_tpu/bad.py': """
          def teardown():
            try:
              risky()
            except Exception:
              pass

          def risky():
            raise ValueError
          """})
  res = run_passes(root, passes=['concurrency'])
  hits = [f for f in res.findings
          if f.rule == 'concurrency/silent-except']
  assert [f.symbol for f in hits] == ['teardown#0']


# --------------------------------------------------------------------------
# traced-purity fixtures
# --------------------------------------------------------------------------


def test_fixture_impure_traced_function(tmp_path):
  root = _fixture_tree(tmp_path, {
      'distributed_embeddings_tpu/bad.py': """
          import time

          import jax

          @jax.jit
          def step(x):
            t0 = time.perf_counter()
            return x * t0
          """})
  res = run_passes(root, passes=['purity'])
  hits = [f for f in res.findings
          if f.rule == 'purity/host-effect-in-traced']
  assert len(hits) == 1
  assert 'time:time.perf_counter' in hits[0].symbol
  assert _detlint_cli().main(['--root', root, '--baseline',
                              str(tmp_path / 'none.toml'),
                              '--passes', 'purity']) == 1


def test_fixture_transitive_impurity_and_call_form(tmp_path):
  """jit(fn) call form + the effect buried one call deep: journal()
  inside a helper the traced function calls."""
  root = _fixture_tree(tmp_path, {
      'distributed_embeddings_tpu/bad.py': """
          import jax

          from distributed_embeddings_tpu.utils import resilience

          def helper(x):
            resilience.journal('io_retry', x=1)
            return x

          def step(x):
            return helper(x) + 1

          jitted = jax.jit(step)
          """})
  res = run_passes(root, passes=['purity'])
  assert any(f.rule == 'purity/host-effect-in-traced'
             and 'journal' in f.symbol for f in res.findings), \
      [f.brief() for f in res.findings]


def test_fixture_phases_are_sanctioned_spans_are_not(tmp_path):
  """Inside traced code ``obs.trace.phase`` (a named scope: metadata,
  no host effect) is the sanctioned instrument (design §15) and never a
  purity finding; a host span there would time Python's tracing, and
  is one."""
  root = _fixture_tree(tmp_path, {
      'distributed_embeddings_tpu/okay.py': """
          import jax

          from distributed_embeddings_tpu.obs import trace as obs_trace

          @jax.jit
          def step(x):
            with obs_trace.phase('fwd/exchange'):
              return x + 1
          """})
  res = run_passes(root, passes=['purity'])
  assert not res.findings, [f.brief() for f in res.findings]
  root = _fixture_tree(tmp_path / 'bad', {
      'distributed_embeddings_tpu/bad.py': """
          import jax

          from distributed_embeddings_tpu.obs import trace as obs_trace

          @jax.jit
          def step(x):
            tok = obs_trace.begin('train/step')
            y = x + 1
            obs_trace.end(tok)
            return y
          """})
  res = run_passes(root, passes=['purity'])
  assert sum(f.rule == 'purity/host-effect-in-traced'
             and ':span:' in f.symbol for f in res.findings) == 2, \
      [f.brief() for f in res.findings]


# --------------------------------------------------------------------------
# doc-drift fixtures
# --------------------------------------------------------------------------


def test_fixture_dangling_api_symbol(tmp_path):
  root = _fixture_tree(tmp_path, {
      'docs/api.md': """
          # API reference

          ## `distributed_embeddings_tpu.parallel`

          | symbol | description |
          |---|---|
          | `DistributedEmbedding(embeddings, ...)` | real. |
          | `no_such_symbol_anywhere(x)` | rotted. |
          """})
  res = run_passes(root, passes=['docdrift'])
  hits = [f for f in res.findings
          if f.rule == 'docdrift/api-symbol-unresolved']
  assert [f.symbol for f in hits] == \
      ['distributed_embeddings_tpu.parallel.no_such_symbol_anywhere']


def test_fixture_stale_cli_flag_and_dangling_ref(tmp_path):
  root = _fixture_tree(tmp_path, {
      'tools/mytool.py': """
          import argparse

          def main():
            ap = argparse.ArgumentParser()
            ap.add_argument('--real_flag', action='store_true')
            return ap.parse_args()
          """,
      'docs/design.md': """
          # design

          ## 1. the only section
          """,
      'docs/userguide.md': """
          # guide

          Run `python tools/mytool.py --real_flag` and also
          `python tools/mytool.py --flag_that_was_renamed`.

          See design.md §9 for the missing section.
          """})
  res = run_passes(root, passes=['docdrift'])
  by_rule = {}
  for f in res.findings:
    by_rule.setdefault(f.rule, []).append(f.symbol)
  assert by_rule.get('docdrift/cli-flag-unknown') == \
      ['--flag_that_was_renamed']
  assert by_rule.get('docdrift/dangling-section-ref') == ['§9']
  assert _detlint_cli().main(['--root', root, '--baseline',
                              str(tmp_path / 'none.toml'),
                              '--passes', 'docdrift']) == 1


# --------------------------------------------------------------------------
# finding-id stability + waiver policy
# --------------------------------------------------------------------------


_SWALLOW = """
    def teardown():
      try:
        risky()
      except Exception:
        pass

    def risky():
      raise ValueError
    """


def test_finding_id_is_line_stable(tmp_path):
  root = _fixture_tree(tmp_path, {
      'distributed_embeddings_tpu/bad.py': _SWALLOW})
  id0 = run_passes(root, passes=['concurrency']).findings[0].id
  # shove the violation 40 lines down: the id must not move
  shifted = '# filler\n' * 40 + textwrap.dedent(_SWALLOW)
  (pathlib.Path(root) / 'distributed_embeddings_tpu'
   / 'bad.py').write_text(shifted)
  res = run_passes(root, passes=['concurrency'])
  assert res.findings[0].id == id0
  assert res.findings[0].line > 40  # display line DID move


def test_waiver_requires_rationale(tmp_path):
  bad = tmp_path / 'base.toml'
  bad.write_text('[[waiver]]\nid = "concurrency/silent-except@x::y#0"\n')
  with pytest.raises(BaselineError, match='no rationale'):
    Baseline.load(str(bad))
  root = _fixture_tree(tmp_path, {
      'distributed_embeddings_tpu/bad.py': _SWALLOW})
  assert _detlint_cli().main(['--root', root, '--baseline',
                              str(bad)]) == 2


def test_waiver_suppresses_and_stale_fails_strict(tmp_path):
  root = _fixture_tree(tmp_path, {
      'distributed_embeddings_tpu/bad.py': _SWALLOW})
  fid = run_passes(root, passes=['concurrency']).findings[0].id
  base = tmp_path / 'base.toml'
  base.write_text(
      f'[[waiver]]\nid = "{fid}"\n'
      'rationale = "fixture: deliberately swallowed"\n'
      '[[waiver]]\nid = "concurrency/silent-except@gone.py::dead#0"\n'
      'rationale = "stale on purpose"\n')
  cli = _detlint_cli()
  # waived finding + stale waiver: clean by default, strict exits 3
  assert cli.main(['--root', root, '--baseline', str(base),
                   '--passes', 'concurrency']) == 0
  assert cli.main(['--root', root, '--baseline', str(base),
                   '--passes', 'concurrency', '--strict']) == 3


def test_unknown_pass_refuses():
  with pytest.raises(ValueError, match='unknown pass'):
    run_passes(str(ROOT), passes=['no_such_pass'])


def test_expired_waiver_fails_strict_with_rationale(tmp_path):
  """The ISSUE-14 waiver-hygiene contract: an `expires`-dated waiver
  keeps suppressing by default, fails `--strict` past its date with
  the rationale echoed, stays clean while future-dated, and a
  malformed date refuses outright (exit 2)."""
  root = _fixture_tree(tmp_path, {
      'distributed_embeddings_tpu/bad.py': _SWALLOW})
  fid = run_passes(root, passes=['concurrency']).findings[0].id
  base = tmp_path / 'base.toml'

  def write(expires):
    base.write_text(
        f'[[waiver]]\nid = "{fid}"\n'
        'rationale = "tied to an open roadmap item"\n'
        f'expires = "{expires}"\n')

  cli = _detlint_cli()
  write('2001-01-01')  # long past
  # expired still SUPPRESSES by default — the lapse degrades to a
  # strict failure, never a surprise hard gate
  assert cli.main(['--root', root, '--baseline', str(base),
                   '--passes', 'concurrency']) == 0
  assert cli.main(['--root', root, '--baseline', str(base),
                   '--passes', 'concurrency', '--strict']) == 3
  # the strict failure carries the rationale (Baseline.expired echo)
  b = Baseline.load(str(base))
  exp = b.expired({'concurrency'})
  assert len(exp) == 1
  assert 'open roadmap item' in exp[0] and '2001-01-01' in exp[0]
  # ...but only for the passes that ran: another pass's subset run
  # must not fail on this waiver (the ownership rule staleness uses)
  assert b.expired({'registry'}) == []
  write('2999-12-31')  # future-dated: strict clean
  assert cli.main(['--root', root, '--baseline', str(base),
                   '--passes', 'concurrency', '--strict']) == 0
  write('soonish')     # malformed date: refuse like a bare rationale
  with pytest.raises(BaselineError, match='malformed expires'):
    Baseline.load(str(base))
  assert cli.main(['--root', root, '--baseline', str(base),
                   '--passes', 'concurrency']) == 2


# --------------------------------------------------------------------------
# locksan: the runtime twin
# --------------------------------------------------------------------------


def test_locksan_detects_inverted_acquisition_order():
  with locksan.capture('fixture') as cap:
    a = threading.Lock()
    b = threading.Lock()
    with a:
      with b:
        pass
    with b:
      with a:
        pass
  assert cap.locks_created == 2
  cyc = cap.find_cycle()
  assert cyc is not None
  with pytest.raises(locksan.LockOrderError, match='lock-order cycle'):
    cap.assert_acyclic()


def test_locksan_consistent_order_is_acyclic():
  with locksan.capture() as cap:
    a = threading.Lock()
    b = threading.Lock()
    for _ in range(3):
      with a:
        with b:
          pass
  cap.assert_acyclic()
  assert ('lock' in k for k in dict(cap.edges))
  assert len(cap.edges) == 1  # a->b only, counted 3 times
  assert list(cap.edges.values()) == [3]


def test_locksan_ducktypes_condition_and_queue():
  """Instrumented locks must survive the stdlib machinery the threaded
  pipelines build on: Condition wait/notify (lock-passing AND default
  RLock) and queue.Queue round trips."""
  import queue as queue_mod
  with locksan.capture() as cap:
    q = queue_mod.Queue(maxsize=2)
    lk = threading.Lock()
    cond = threading.Condition(lk)
    got = []

    def worker():
      got.append(q.get(timeout=5.0))
      with cond:
        cond.notify()

    t = threading.Thread(target=worker)
    t.start()
    with cond:
      q.put('x', timeout=1.0)
      cond.wait(timeout=5.0)
    t.join(timeout=5.0)
  assert got == ['x']
  assert cap.locks_created >= 2  # at least the queue's mutex + ours
  cap.assert_acyclic()


def test_locksan_reentrant_rlock_records_no_self_edge():
  with locksan.capture() as cap:
    r = threading.RLock()
    with r:
      with r:  # reentrant: no ordering information
        pass
  cap.assert_acyclic()
  assert not cap.edges
