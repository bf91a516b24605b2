"""graphlint IR-analysis layer (docs/design.md §18).

The load-bearing claims pinned here:

- the live-tree gate: the flagship program catalog (lookup dispatch
  paths, chunked + monolithic sparse train step, serving ladder rungs,
  cold-tier fetch forward) analyzes CLEAN under the shared baseline —
  the tier-1 wiring of ``python tools/graphlint.py --strict``;
- the acceptance proofs ride the same run: every sparse-train-step
  state leaf is input-output aliased in the compiled executable
  (donation), zero retraces across the monitored 3-step fit and the
  warmed serving ladder (the generalized ``compile_count`` pin), and
  the parity groups (ladder rungs; chunked vs monolithic step) share
  one collapsed collective schedule;
- no embedding-state leaf of a train step enters a ``lax.cond`` (XLA
  copies the whole shard once per branch, ISSUE 25), with the overflow
  correction IN every train program so the proof is not vacuous;
- one seeded TRUE-POSITIVE fixture per pass: an undonated state leaf,
  a state leaf inside a cond, a parity pair with divergent collective
  order, a collective under a divergent ``lax.cond``, a forced retrace via weak_type drift plus a
  recompile, an injected hot-loop ``jax.device_get``, a host-callback
  primitive inside a traced program, and an over-budget resident
  state;
- finding ids are stable across reruns (the waiver survival
  contract), the CLI refuses a rationale-less baseline fast (exit 2,
  before any tracing), and the checked-in collective-schedule ledger
  parses and names the catalog programs the conftest deadlock
  watchdog dumps.

The heaviest whole-catalog runs (the CLI subprocess-shaped entry and
the ``--tier full`` catalog with the sparsecore/pallas paths) are
``-m slow``; the module-scoped flagship fixture keeps tier-1 to ONE
catalog build.
"""

import importlib.util
import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_embeddings_tpu.analysis import core as lint_core
from distributed_embeddings_tpu.analysis import graphlint

ROOT = pathlib.Path(__file__).resolve().parents[1]
P = jax.sharding.PartitionSpec


def _graphlint_cli():
  spec = importlib.util.spec_from_file_location(
      'graphlint_cli_for_test', str(ROOT / 'tools' / 'graphlint.py'))
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod


@pytest.fixture(scope='module')
def flagship():
  """ONE flagship catalog build for the whole module — the expensive
  part (a handful of tiny-program compiles on the faked 8-device
  mesh) is paid once."""
  return graphlint.build_programs(tier='flagship')


@pytest.fixture(scope='module')
def live(flagship):
  baseline = lint_core.Baseline.load(
      str(ROOT / 'tools' / 'detlint_baseline.toml'))
  return graphlint.run_programs(flagship, baseline=baseline)


# --------------------------------------------------------------------------
# the live-tree gate + acceptance proofs
# --------------------------------------------------------------------------


def test_live_tree_graphlint_clean(live):
  """The acceptance pin: zero unwaived findings over the flagship
  catalog under the checked-in shared baseline — exactly what
  `tools/graphlint.py --strict` gates in CI."""
  assert not live.findings, '\n'.join(f.brief() for f in live.findings)
  assert not live.unverifiable, \
      '\n'.join(f.brief() for f in live.unverifiable)
  assert not live.stale_waivers, live.stale_waivers
  assert not live.expired_waivers, live.expired_waivers
  # every pass genuinely ran over real programs — a silently emptied
  # catalog must fail here, not pass vacuously
  names = set(live.meta['graphlint_programs'])
  assert {'lookup/xla', 'lookup/hot', 'train/monolithic',
          'train/chunked', 'serve/ladder-warm',
          'serve/coldfetch'} <= names, names
  assert sum(n.startswith('serve/rung') for n in names) >= 2, names
  # on the faked multi-device mesh every traced program exchanges
  sched = live.meta['graphlint_schedule']
  assert all(s['collectives'] for s in sched.values()), {
      k: len(v['collectives']) for k, v in sched.items()}


def test_donation_proves_all_train_state_leaves_aliased(live):
  """The donation acceptance proof: BOTH train-step variants report
  every state leaf (params + optimizer + step counter) input-output
  aliased in the compiled executable."""
  don = live.meta['graphlint_donation']
  assert set(don) == {'train/monolithic', 'train/chunked',
                      'train/hier-flat-twin', 'train/hierarchical'}, don
  for name, d in don.items():
    assert d['expected'] >= 4, (name, d)   # tables, kernel, accum, step
    assert d['aliased'] == d['expected'], (name, d)


TRAIN_PROGRAMS = ('train/monolithic', 'train/chunked',
                  'train/hier-flat-twin', 'train/hierarchical',
                  'train/tiered-int8')


@pytest.mark.parametrize('name', TRAIN_PROGRAMS)
def test_no_state_leaf_enters_a_cond(live, flagship, name):
  """The in-place-update proof, jaxpr half (ISSUE 25): every train
  step of the catalog (flat, chunked, both hierarchical arms, and the
  int8 cold-tier layer whose operand is the (payload, scale) pair with
  fetched rows appended) is built with a capacity that leaves the
  overflow correction in, carries its shards through a ``while``, and
  has no ``cond`` that takes or returns a value the size of a state
  leaf (the clean-tree gate enforces the last; asserted here by
  name so that dropping ``state_leaves`` from the catalog fails)."""
  prog = {p.name: p for p in flagship}[name]
  assert prog.state_leaves, name
  assert {l.dtype for l in prog.state_leaves} >= {'float32'}
  # the correction is in the program: a loop carries a state leaf
  assert graphlint.state_carriers(prog.jaxpr, prog.state_leaves,
                                  'while'), name
  assert not graphlint.state_carriers(prog.jaxpr, prog.state_leaves)
  assert not any(f.rule == 'donation/state-leaf-in-cond'
                 for f in live.findings + live.waived)
  if name == 'train/tiered-int8':
    # the pair and the tier really are in it
    assert {'int8', 'float32'} == {l.dtype for l in prog.state_leaves}


def test_retrace_zero_across_fit_and_warmed_ladder(live):
  """The retrace acceptance proof: the monitored 3-step fit and the
  one-request-per-rung warmed-ladder window both saw zero
  compile_count movement (and the fit window zero signature drift —
  enforced by the clean-tree gate above)."""
  ret = live.meta['graphlint_retrace']
  assert ret['train/monolithic']['calls'] == 3
  assert ret['train/monolithic']['compile_count_delta'] == 0
  assert ret['serve/ladder-warm']['compile_count_delta'] == 0


def test_parity_groups_share_one_schedule(live, flagship):
  """Ladder rungs and the chunked/monolithic pair each collapse to one
  (primitive, axis) sequence — the schedule-pass invariant, asserted
  directly on the extracted ledgers."""
  by_name = {p.name: p for p in flagship}
  for group, members in (('serve-ladder',
                          [n for n in by_name if n.startswith(
                              'serve/rung')]),
                         ('train-step',
                          ['train/monolithic', 'train/chunked'])):
    seqs = {
        tuple(graphlint.collapse_schedule(
            graphlint.extract_schedule(by_name[m].jaxpr)))
        for m in members
    }
    assert len(members) >= 2 and len(seqs) == 1, (group, seqs)


def test_hbm_ledger_and_budget_crosscheck(live):
  """The HBM ledger carries every compiled program with the measured
  resident state under any declared budget (the fits-ladder
  cross-check, design §18): the cold-tier program declares one and
  fits under it."""
  hbm = live.meta['graphlint_hbm']
  assert 'serve/coldfetch' in hbm
  cf = hbm['serve/coldfetch']
  assert cf['budget'] is not None
  assert 0 < cf['resident_state'] <= cf['budget'], cf
  for name, d in hbm.items():
    assert d['peak'] >= d['resident'] > 0, (name, d)
  # donation shows up in the memory analysis too: the train step's
  # aliased bytes cover its state (the in-place-update contract)
  assert hbm['train/monolithic']['alias'] > 0


# --------------------------------------------------------------------------
# seeded true-positive fixtures (one per pass)
# --------------------------------------------------------------------------


def _donation_fixture_programs():
  def step(s, x):
    return {'w': s['w'] + x, 'acc': s['acc'] * 2}, x.sum()

  s = {'w': jnp.ones((4, 4)), 'acc': jnp.ones((4, 4))}
  x = jnp.ones((4, 4))
  flat, _ = jax.tree_util.tree_flatten_with_path(s)
  expected = [(i, jax.tree_util.keystr(path))
              for i, (path, _) in enumerate(flat)]
  undonated = jax.jit(step).trace(s, x).lower().compile()
  donated = jax.jit(step, donate_argnums=(0,)).trace(
      s, x).lower().compile()
  return (graphlint.Program('fixture/undonated', compiled=undonated,
                            donate_expected=expected),
          graphlint.Program('fixture/donated', compiled=donated,
                            donate_expected=expected))


def test_fixture_undonated_leaf():
  bad, good = _donation_fixture_programs()
  res = graphlint.run_programs([bad, good], passes=['donation'])
  ids = {f.id for f in res.findings}
  assert "donation/undonated-leaf@fixture/undonated::['acc']" in ids
  assert "donation/undonated-leaf@fixture/undonated::['w']" in ids
  assert not any('fixture/donated' in i for i in ids), ids
  # the donated twin is PROVEN aliased, not just unflagged
  assert graphlint.aliased_param_indices(good.compiled) >= {0, 1}


@pytest.mark.parametrize('operand', ['shard', 'shard_plus_rows',
                                     'reshaped_view', 'update_rows'])
def test_fixture_state_leaf_in_cond(operand):
  """The shape ISSUE 25 removed: a rare correction under a two-branch
  ``lax.cond`` that carries the table.  Flagged for the shard itself,
  the shard with fetched rows concatenated on (cold tier) and a
  reshaped view of it (packed <-> natural); the same work in a
  zero-or-one-trip ``while_loop``, or a cond that yields only the
  rows to write, is clean."""
  leaf = graphlint.StateLeaf("['group_0']", (64, 16), 'float32')
  shape = {'shard': (1, 64, 16), 'shard_plus_rows': (72, 16),
           'reshaped_view': (8, 128), 'update_rows': (8, 16)}[operand]

  def correction(t):
    return t.at[0].add(1.0)

  def cond_form(t, n):
    return jax.lax.cond(n > 3, correction, lambda t: t, t)

  def loop_form(t, n):
    return jax.lax.while_loop(lambda c: c[1],
                              lambda c: (correction(c[0]), False),
                              (t, n > 3))[0]

  t, n = jnp.zeros(shape, jnp.float32), jnp.int32(5)
  progs = [graphlint.Program(f'fixture/{form.__name__}',
                             jaxpr=jax.make_jaxpr(form)(t, n),
                             state_leaves=[leaf])
           for form in (cond_form, loop_form)]
  res = graphlint.run_programs(progs, passes=['donation'])
  ids = [f.id for f in res.findings]
  if operand == 'update_rows':
    assert not ids, ids
  else:
    assert ids == ['donation/state-leaf-in-cond@fixture/cond_form::cond#0']
  assert graphlint.state_carriers(progs[1].jaxpr, [leaf], 'while') == (
      [] if operand == 'update_rows' else [(0, "['group_0']")])


def test_device_state_leaves_reads_one_devices_share():
  mesh = _mesh()
  world = mesh.devices.size
  tree = {
      'table': jax.device_put(
          np.zeros((world, 40, 8), np.float32),
          jax.sharding.NamedSharding(mesh, P('data', None, None))),
      'scale': jax.device_put(
          np.zeros((world, 40, 1), np.float32),
          jax.sharding.NamedSharding(mesh, P('data', None, None))),
      'step': jnp.zeros((), jnp.int32),      # no table: left out
      'per_row': jnp.zeros((40,), jnp.int32),
  }
  got = {l.label: (l.shape, l.dtype)
         for l in graphlint.device_state_leaves(tree)}
  assert got == {"['table']": ((40, 8), 'float32'),
                 "['scale']": ((40, 1), 'float32')}


def test_fixture_divergent_parity_schedule():
  mesh = _mesh()

  def order_a(x):
    y = jax.lax.all_to_all(x, 'data', 0, 0)
    return jax.lax.psum(y.sum(), 'data')

  def order_b(x):
    r = jax.lax.psum(x.sum(), 'data')
    y = jax.lax.all_to_all(x, 'data', 0, 0)
    return r + jax.lax.psum(y.sum(), 'data')

  progs = []
  for name, fn in (('fixture/mono', order_a), ('fixture/chunked',
                                               order_b)):
    sm = jax.shard_map(fn, mesh=mesh, in_specs=P('data'),
                       out_specs=P(), check_vma=False)
    jaxpr = jax.make_jaxpr(sm)(
        jnp.ones((8 * mesh.devices.size, 4), jnp.float32))
    progs.append(graphlint.Program(name, jaxpr=jaxpr,
                                   parity='fixture-pair'))
  res = graphlint.run_programs(progs, passes=['schedule'])
  hits = [f for f in res.findings
          if f.rule == 'schedule/parity-divergence']
  assert len(hits) == 1
  assert hits[0].path == 'fixture/chunked'
  assert hits[0].symbol == 'fixture-pair'
  # an order-PRESERVING chunk split must NOT fire: k consecutive
  # issues of one collective collapse onto the monolithic schedule
  def order_a_chunked(x):
    parts = [jax.lax.all_to_all(p, 'data', 0, 0)
             for p in jnp.split(x, 2, axis=1)]
    return jax.lax.psum(sum(p.sum() for p in parts), 'data')

  sm = jax.shard_map(order_a_chunked, mesh=mesh, in_specs=P('data'),
                     out_specs=P(), check_vma=False)
  jaxpr = jax.make_jaxpr(sm)(
      jnp.ones((8 * mesh.devices.size, 4), jnp.float32))
  ok = graphlint.run_programs(
      [progs[0],
       graphlint.Program('fixture/chunked-ok', jaxpr=jaxpr,
                         parity='fixture-pair')],
      passes=['schedule'])
  assert not ok.findings, [f.brief() for f in ok.findings]


def test_fixture_collective_in_divergent_cond():
  mesh = _mesh()

  def local(x):
    pred = x[0, 0] > 0.0
    y = jax.lax.cond(pred,
                     lambda v: jax.lax.psum(v, 'data'),
                     lambda v: v * 2.0,
                     x)
    return jax.lax.psum(y.sum(), 'data')

  sm = jax.shard_map(local, mesh=mesh, in_specs=P('data'),
                     out_specs=P(), check_vma=False)
  jaxpr = jax.make_jaxpr(sm)(
      jnp.ones((8 * mesh.devices.size, 4), jnp.float32))
  res = graphlint.run_programs(
      [graphlint.Program('fixture/divcond', jaxpr=jaxpr)],
      passes=['schedule'])
  hits = [f for f in res.findings
          if f.rule == 'schedule/collective-in-divergent-cond']
  assert len(hits) == 1 and hits[0].symbol == 'cond#0'
  # both-branch-collective with the SAME schedule stays clean
  def local_ok(x):
    pred = x[0, 0] > 0.0
    y = jax.lax.cond(pred,
                     lambda v: jax.lax.psum(v, 'data'),
                     lambda v: jax.lax.psum(v * 2.0, 'data'),
                     x)
    return jax.lax.psum(y.sum(), 'data')

  sm = jax.shard_map(local_ok, mesh=mesh, in_specs=P('data'),
                     out_specs=P(), check_vma=False)
  jaxpr = jax.make_jaxpr(sm)(
      jnp.ones((8 * mesh.devices.size, 4), jnp.float32))
  ok = graphlint.run_programs(
      [graphlint.Program('fixture/samecond', jaxpr=jaxpr)],
      passes=['schedule'])
  assert not any(f.rule == 'schedule/collective-in-divergent-cond'
                 for f in ok.findings), \
      [f.brief() for f in ok.findings]


def test_fixture_retrace_weak_type_drift_and_recompile():
  # call 1 passes a strong-typed array, call 2 the same value as a
  # weak-typed python-scalar promotion — the classic silent retrace
  sig1 = graphlint.signature({'lr': jnp.ones(())})
  sig2 = graphlint.signature({'lr': jnp.asarray(1.0)})
  rec = graphlint.RetraceRecord(calls=2, sigs=[sig1, sig2],
                                compile_count_delta=1)
  res = graphlint.run_programs(
      [graphlint.Program('fixture/drift', retrace=rec)],
      passes=['retrace'])
  rules = {f.rule for f in res.findings}
  assert rules == {'retrace/signature-drift', 'retrace/recompile'}
  drift = next(f for f in res.findings
               if f.rule == 'retrace/signature-drift')
  assert "'lr'" in drift.symbol
  assert 'weak_type False -> True' in drift.message
  # identical signatures + stable compile_count: clean
  ok = graphlint.run_programs(
      [graphlint.Program('fixture/stable',
                         retrace=graphlint.RetraceRecord(
                             calls=3, sigs=[sig1, sig1, sig1]))],
      passes=['retrace'])
  assert not ok.findings, [f.brief() for f in ok.findings]


def test_fixture_injected_host_sync_and_callback():
  # runtime half: the monitor catches a device_get issued from the
  # hot loop and attributes it to this frame
  mon = graphlint.HostSyncMonitor()
  with mon:
    jax.device_get(jnp.ones((4,)))
  assert mon.sites == ['test_graphlint.py:'
                       'test_fixture_injected_host_sync_and_callback']
  res = graphlint.run_programs(
      [graphlint.Program('fixture/sync',
                         hostsync=graphlint.HostSyncRecord(mon.sites))],
      passes=['hostsync'])
  assert [f.rule for f in res.findings] == \
      ['hostsync/device-get-in-hot-loop']
  # the wrapper restores the original binding on exit
  assert jax.device_get.__module__.startswith('jax')
  # IR half: a callback primitive inside the traced program
  def f(x):
    return jax.pure_callback(
        lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

  jaxpr = jax.make_jaxpr(f)(jnp.ones((4,)))
  res2 = graphlint.run_programs(
      [graphlint.Program('fixture/cb', jaxpr=jaxpr)],
      passes=['hostsync'])
  hits = [f for f in res2.findings
          if f.rule == 'hostsync/callback-in-program']
  assert len(hits) == 1 and 'callback' in hits[0].symbol


def test_fixture_hbm_over_budget():
  res = graphlint.run_programs(
      [graphlint.Program('fixture/oom', hbm_budget=1,
                         resident_state_bytes=4096)],
      passes=['hbm'])
  assert [f.id for f in res.findings] == \
      ['hbm/over-budget@fixture/oom::resident_bytes']
  ok = graphlint.run_programs(
      [graphlint.Program('fixture/fits', hbm_budget=8192,
                         resident_state_bytes=4096)],
      passes=['hbm'])
  assert not ok.findings


# --------------------------------------------------------------------------
# finding-id stability + waiver machinery through the graphlint runner
# --------------------------------------------------------------------------


def test_finding_ids_stable_across_reruns():
  bad, _ = _donation_fixture_programs()
  ids1 = sorted(f.id for f in graphlint.run_programs(
      [bad], passes=['donation']).findings)
  bad2, _ = _donation_fixture_programs()  # a fresh trace of the same
  ids2 = sorted(f.id for f in graphlint.run_programs(
      [bad2], passes=['donation']).findings)
  assert ids1 == ids2 and ids1


def test_waiver_suppresses_and_stale_fails_strict_semantics(tmp_path):
  bad, _ = _donation_fixture_programs()
  fid = graphlint.run_programs([bad],
                               passes=['donation']).findings[0].id
  base = tmp_path / 'base.toml'
  base.write_text(
      f'[[waiver]]\nid = "{fid}"\n'
      'rationale = "fixture: seeded undonated leaf"\n'
      '[[waiver]]\nid = "donation/undonated-leaf@gone::x"\n'
      'rationale = "stale on purpose"\n'
      '[[waiver]]\nid = "purity/host-effect-in-traced@other::y"\n'
      'rationale = "owned by detlint: must NOT go stale here"\n')
  res = graphlint.run_programs([bad], passes=['donation'],
                               baseline=lint_core.Baseline.load(
                                   str(base)))
  # one of the two seeded findings is waived, the other stays live
  assert len(res.waived) == 1 and res.waived[0].id == fid
  assert len(res.findings) == 1
  # staleness is ownership-scoped: the detlint-owned waiver is not
  # this runner's to report
  assert res.stale_waivers == ['donation/undonated-leaf@gone::x']


def test_cli_refuses_rationale_less_baseline_fast(tmp_path):
  """Baseline malformedness exits 2 BEFORE any tracing — the CLI's
  fast-fail ordering (a bad waiver file must not cost a catalog
  build)."""
  bad = tmp_path / 'base.toml'
  bad.write_text('[[waiver]]\nid = "donation/x@y::z"\n')
  assert _graphlint_cli().main(['--baseline', str(bad)]) == 2


def test_checked_in_ledger_matches_live_schedules(live):
  """tools/graphlint_ledger.json (the file the conftest deadlock
  watchdog dumps) parses, names the flagship programs, and — the
  freshness gate — carries EXACTLY the schedules the live tree traces
  for them: a PR that reorders a program's collectives must refresh
  the ledger (`python tools/graphlint.py --tier full --write-ledger`)
  or the watchdog would attribute a wedge against an outdated
  sequence."""
  if jax.default_backend() != 'cpu' or len(jax.devices()) != 8:
    # the checked-in file is written at the CI topology (forced
    # 8-device CPU mesh); under DET_TESTS_REAL_TPU=1 on other device
    # counts the live shapes legitimately differ
    pytest.skip('ledger freshness is pinned at the 8-device CPU mesh')
  with open(ROOT / 'tools' / 'graphlint_ledger.json',
            encoding='utf-8') as f:
    ledger = json.load(f)
  live_sched = live.meta['graphlint_schedule']
  # the checked-in file is the FULL-tier superset: the flagship
  # programs traced here PLUS the sparsecore/pallas paths the slow
  # tests cover — a flagship-only rewrite (which the CLI refuses on
  # the default path) must fail HERE too
  missing = set(live_sched) - set(ledger)
  assert not missing, \
      f'{missing} traced live but absent from the checked-in ledger'
  assert {'lookup/sparsecore', 'lookup/pallas'} <= set(ledger), \
      ('checked-in ledger lost its full-tier rows — refresh with '
       '`python tools/graphlint.py --tier full --write-ledger`')
  for name, entry in live_sched.items():
    assert ledger[name] == json.loads(json.dumps(entry)), (
        f'{name}: checked-in ledger is stale — refresh with '
        '`python tools/graphlint.py --tier full --write-ledger`')
  for name, entry in ledger.items():
    assert entry['collectives'], name
    for op in entry['collectives']:
      assert {'primitive', 'axis', 'shape', 'index',
              'loop'} <= set(op), (name, op)
  # the watchdog's dump helper is callable outside an alarm (it is
  # best-effort by contract and must never raise)
  import conftest
  conftest._dump_collective_ledger('fixture::nodeid')


def test_measure_resident_bytes_counts_shards_once():
  mesh = _mesh()
  world = mesh.devices.size
  x = jax.device_put(
      np.zeros((world * 4, 8), np.float32),
      jax.sharding.NamedSharding(mesh, P('data', None)))
  rep = jax.device_put(
      np.zeros((16,), np.float32),
      jax.sharding.NamedSharding(mesh, P()))
  # sharded: one shard's bytes; replicated: the full buffer
  assert graphlint.measure_resident_bytes([x]) == 4 * 8 * 4
  assert graphlint.measure_resident_bytes([rep]) == 16 * 4
  assert graphlint.measure_resident_bytes(
      {'a': x, 'b': rep}) == 4 * 8 * 4 + 16 * 4


def _mesh():
  from distributed_embeddings_tpu.parallel import create_mesh
  devs = jax.devices()
  if len(devs) < 2:
    pytest.skip('collective fixtures need a multi-device mesh')
  return create_mesh(devs[:8])


# --------------------------------------------------------------------------
# the heavy whole-catalog entries (slow: tier-1 keeps the flagship run)
# --------------------------------------------------------------------------


@pytest.mark.slow
def test_cli_strict_exit_zero_live():
  assert _graphlint_cli().main(['--strict']) == 0


@pytest.mark.slow
def test_full_tier_catalog_clean():
  """`--tier full` adds the sparsecore-emulation and pallas dispatch
  paths (pallas trace-only off-TPU) — the four-dispatch-path coverage
  of the tentpole, still clean."""
  res = graphlint.run_repo(str(ROOT), tier='full')
  assert not res.findings, '\n'.join(f.brief() for f in res.findings)
  names = set(res.meta['graphlint_programs'])
  assert {'lookup/xla', 'lookup/sparsecore', 'lookup/pallas',
          'lookup/hot'} <= names, names
  # the pallas program traced (schedule ledger row exists) even where
  # it cannot compile
  assert res.meta['graphlint_schedule']['lookup/pallas']['collectives']
