"""A training cell: set-up, the checked steps, the window, the comparison.

The window (ISSUE 23 section 1): the device is drained, the clock read
(``t0``); steps are enqueued on the device-resident pool, never more than
two ahead of the last completed one, until the clock has passed
``t0 + seconds``; the last state and loss are waited for and the clock
read again (``t1``).  The rate is every step enqueued after ``t0`` times
the global batch over ``t1 - t0``: both edges on a drained device, so a
step more or less changes the count and the time together.
"""

import collections
import contextlib
import hashlib
import json
import os
import time

import numpy as np

from benchmarks.lib import cell as cell_lib
from benchmarks.lib import names, program_state, reference, weights, xtrace

TRACE_STEPS = 8  # a traced window holds at most this many steps


def _capacity_rows(model, mix, config, cache_dir, generate, mesh, emb_params):
  """Calibrated per-group capacities of the sparse apply, from a batch of
  the mix's FIXED calibration seed: the capacities are static shapes of
  the step, so they may not move with ``--seed`` (the compiled step would
  miss the cache on every run).  Kept beside the compile cache."""
  import jax.numpy as jnp
  from distributed_embeddings_tpu.parallel import calibrate_capacity_rows
  plan = model.dist.plan
  key = hashlib.sha256(json.dumps(
      [config, mix, [(g.key, g.rows_cap) for g in plan.groups],
       len(mesh.devices.ravel())], sort_keys=True, default=str
  ).encode()).hexdigest()[:24]
  path = os.path.join(cache_dir, f'capacity_rows.{key}.json')
  if os.path.exists(path):
    with open(path) as f:
      return tuple(json.load(f))
  cats = generate(mix['calibration_seed'], batches=1)[0][0]
  caps = calibrate_capacity_rows(model.dist, [jnp.asarray(c) for c in cats],
                                 params=emb_params)
  os.makedirs(cache_dir, exist_ok=True)
  tmp = f'{path}.tmp'
  with open(tmp, 'w') as f:
    json.dump(list(caps), f)
  os.replace(tmp, path)
  return tuple(caps)


def _dense_readings(model, dense0, dense1, dense_opt_state):
  """Per dense leaf ``(name, change_norm, grad_norm, moved)``: the same
  read-back as for tables, on host copies (the dense part is a few MB);
  a leaf is named by its tree path, as the reference names it."""
  import jax
  opt = reference._Optimizer(model.optimizer, lambda a: a)
  flat0, tree = jax.tree.flatten(dense0)
  flat1 = tree.flatten_up_to(jax.tree.map(np.asarray, dense1))
  states = program_state.dense_states(opt.kind, dense_opt_state)
  states = ([None] * len(flat0) if states is None
            else tree.flatten_up_to(states))
  return [(name, *opt.leaf_readings(p0, p1, s1)) for name, p0, p1, s1 in
          zip(reference.leaf_names(dense0), flat0, flat1, states)]


def run(cell, config, mix, limits, args, devices, started, cache_dir):
  """Run one training cell.  Returns ``(result, end_to_end, context)``: the
  result line without its metrics, every end-to-end number taken, and what
  the per-layer readers read (the trace lies under ``context['trace_dir']``
  after a traced run)."""
  import jax
  from distributed_embeddings_tpu.parallel import (
      create_mesh, init_hybrid_train_state, make_hybrid_train_step)
  from distributed_embeddings_tpu.parallel.mesh import make_global_batch

  seed, seconds, trace = args.seed, args.seconds, bool(args.trace)
  stamp = cell_lib.stamper(started)
  stamp('imports done, devices held')
  mesh = create_mesh(devices)
  model = names.resolve(config['builder'])(config, mesh, seed)
  dist = model.dist
  batch = int(mix['global_batch'])
  pool_inputs = [(model.tables[t][0], h) for t, h in
                 zip(model.input_table_map, model.hotness)]
  generator = names.resolve(mix['generator'])
  generate = lambda seed, **kw: generator(mix, pool_inputs, config, seed, **kw)
  host_pool = generate(seed)
  words = weights.table_words(seed, len(model.tables))
  layout = program_state.table_layout(dist)
  stamp('model planned, pool drawn on the host')
  emb_params = program_state.make_tables(dist, layout, model.tables, words)
  caps = _capacity_rows(model, mix, config, cache_dir, generate, mesh,
                        emb_params)
  emb_opt = model.emb_optimizer_cls(capacity_rows=caps,
                                    **model.emb_optimizer_kwargs)
  dense0 = model.dense_params
  state = init_hybrid_train_state(
      dist, {**jax.tree.map(jax.numpy.asarray, dense0),
             'embedding': emb_params},
      model.dense_optimizer, emb_opt)
  del emb_params
  step = make_hybrid_train_step(dist, model.head_loss_fn,
                                model.dense_optimizer, emb_opt,
                                **model.step_kwargs)
  pool = []
  for cats, rest in host_pool:
    leaves, tree = jax.tree.flatten(rest)
    placed = make_global_batch(mesh, *cats, *leaves)
    if len(cats) + len(leaves) == 1:
      placed = (placed,)                   # one array comes back bare
    pool.append((list(placed[:len(cats)]),
                 tree.unflatten(placed[len(cats):])))
  stamp('tables written, state made, pool placed')
  compiled = step.jitted.lower(state, *pool[0]).compile()
  memory = compiled.memory_analysis()
  stamp(f'step compiled; capacity rows {caps}')

  def drive(state, k):
    """The window's own call and feed: step ``k`` on pool batch ``k``."""
    return compiled(state, *pool[k % len(pool)])

  # ---- the checked steps: the object built above, through the window's
  # own call, read after step 1 and after the last ----------------------
  checked = int(mix['checked_steps'])
  program = {'loss': [], 'grad_norm': {}, 'moved': {}, 'change_norm': {}}
  for k in range(checked):
    state, loss = drive(state, k)
    program['loss'].append(loss)
    if k == 0 or k == checked - 1:
      tables = program_state.table_readings(
          model.optimizer, model.tables, layout, state.params['embedding'],
          state.opt_state[1], words)
      dense = _dense_readings(
          model, dense0, {n: v for n, v in state.params.items()
                          if n != 'embedding'}, state.opt_state[0])
      readings = [(f'table_{t}', *r) for t, r in enumerate(tables)] + dense
      for name, change, grad, moved in readings:
        if k == 0:
          program['grad_norm'][name] = grad
          program['moved'][name] = moved
        if k == checked - 1:
          program['change_norm'][name] = change
  program['loss'] = [float(l) for l in program['loss']]
  k = checked
  state, loss = drive(state, k)            # one more, unread: warm-up
  k += 1
  jax.block_until_ready((state, loss))
  setup_s = time.perf_counter() - started
  stamp(f'set-up done; checked losses {program["loss"]}')

  # ---- the window -------------------------------------------------------
  trace_dir = os.path.join(cache_dir, 'trace')
  annotate = (jax.profiler.TraceAnnotation if trace
              else (lambda name: contextlib.nullcontext()))
  if trace:
    xtrace.start_trace(trace_dir)
  compiles = cell_lib.CompileCount()
  pending = collections.deque()
  ready_at, losses, enqueued = [], [], 0
  jax.block_until_ready((state, loss))     # the device is drained
  with compiles, annotate('bench/window'):
    t0 = time.perf_counter()
    while True:
      if len(pending) == 2:
        with annotate('bench/wait_loss'):
          losses.append(pending.popleft())
          losses[-1].block_until_ready()
        ready_at.append(time.perf_counter() - t0)
      if (time.perf_counter() - t0 >= seconds
          or (trace and enqueued >= TRACE_STEPS)):
        break
      with annotate('bench/enqueue_step'):
        state, loss = drive(state, k)
      pending.append(loss)
      enqueued += 1
      k += 1
    with annotate('bench/wait_loss'):
      while pending:
        losses.append(pending.popleft())
        losses[-1].block_until_ready()
        ready_at.append(time.perf_counter() - t0)
      jax.block_until_ready((state, loss))
    t1 = time.perf_counter()
  if trace:
    jax.profiler.stop_trace()
  window_s = t1 - t0
  gaps = np.diff([0.0] + ready_at)
  print('steps ready at (s after t0): '
        + json.dumps([round(t, 4) for t in ready_at]), flush=True)
  print(f'gaps between steps ready, s: min {gaps.min():.4f} median '
        f'{np.median(gaps):.4f} max {gaps.max():.4f}; {enqueued} steps in '
        f'{window_s:.4f} s', flush=True)
  losses = [float(l) for l in losses]
  failed = int(sum(not np.isfinite(l) for l in losses))
  peak = cell_lib.memory_peak_bytes(devices)
  stamp('window closed')

  # ---- free the program, then the reference ----------------------------
  del state, loss, compiled, pool, pending
  ref = reference.run_reference(config, mix, seed, chips=len(devices))
  numbers, worst = reference.compare(program, ref)
  stamp('reference followed and compared')
  compared = {name: {'value': numbers[name], 'limit': limits[name],
                     'worst': worst[name]}
              for name in limits if name in numbers}
  correct = (failed == 0 and enqueued > 0
             and all(c['value'] <= c['limit'] for c in compared.values()))

  result = {'correct': bool(correct), 'attempted': enqueued,
            'failed': failed, 'memory_peak_bytes': peak, 'compared': compared}
  end_to_end = {'train_samples_per_s': enqueued * batch / window_s,
                'setup_s': setup_s}
  context = {
      'cell': cell, 'config': config, 'mix': mix, 'model': model,
      'devices': devices, 'device_kind': devices[0].device_kind,
      'trace_dir': trace_dir, 'program': 'jit_step',
      'steps': enqueued, 'window_s': window_s, 'global_batch': batch,
      'compiles_in_window': compiles.count, 'memory_analysis': memory,
      'host_pool': host_pool,
  }
  return result, end_to_end, context
