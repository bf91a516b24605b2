"""chip_smoke.py: does the default path still start on the chip?

One process, no arguments, no children (the native build's ``make`` is
the only process it may start).  Synthetic "Tiny V3" at its published
rows and widths through the entry points every user and ``bench.py``
use: ``create_mesh(jax.devices())`` -> ``SyntheticModel(dp_input=True)``
-> ``init_hybrid_train_state`` -> ``make_hybrid_train_step`` with
``SparseAdagrad`` on ``InputGenerator(alpha=1.05)`` batches at global
batch 65536, then ``export_tables`` -> int8 ``ServingEngine`` ->
``DynamicBatcher`` on the same devices.  One mesh over every chip the
process sees, so the same file is the one-chip and the four-chip run.

What comes out is checked against NumPy, outside the timed steps:
forward parity, row-wise Adagrad parity on duplicate ids (the check that
the ``unique_indices``/``indices_are_sorted`` scatter hints in
``parallel/sparse.py`` are true on a backend that believes them), loss
going down, served rows against the dequantized bundle.  Any failed
check or raised phase ends the run non-zero; nothing is caught and
carried past.  Every time printed is a smoke observation of the device
named on the first line, not a benchmark.

The command line refuses anything but a TPU.  ``run_smoke`` is the body;
tests/test_chip_smoke.py calls it at a toy size on the CPU mesh.
"""

import importlib.metadata
import json
import os
import statistics
import sys
import time

import numpy as np

GLOBAL_BATCH = 65536
# bench.py's optimizer settings (keras Adagrad defaults of the reference
# benchmark, synthetic_models/main.py:105)
LR = 0.01
ACC0 = 0.1
EPS = 1e-7
EPS32 = float(np.finfo(np.float32).eps)


class SmokeFailure(RuntimeError):
  """A check did not hold."""


def _require(ok, message):
  if not ok:
    raise SmokeFailure(message)


class CompileLog:
  """Counts what JAX compiled while the block ran, from JAX's own
  monitoring events: backend compiles and their seconds (a persistent
  cache hit still passes through the backend-compile event, in
  milliseconds), and the persistent cache's hits and misses."""

  def __init__(self):
    self.compiles = 0
    self.compile_s = 0.0
    self.cache_hits = 0
    self.cache_misses = 0

  def _on_duration(self, event, duration, **_):
    if event == '/jax/core/compile/backend_compile_duration':
      self.compiles += 1
      self.compile_s += duration

  def _on_event(self, event, **_):
    if event == '/jax/compilation_cache/cache_hits':
      self.cache_hits += 1
    elif event == '/jax/compilation_cache/cache_misses':
      self.cache_misses += 1

  def __enter__(self):
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(self._on_duration)
    monitoring.register_event_listener(self._on_event)
    return self

  def __exit__(self, *exc):
    from jax import monitoring
    monitoring.unregister_event_duration_listener(self._on_duration)
    monitoring.unregister_event_listener(self._on_event)

  def snapshot(self):
    return {'compiles': self.compiles,
            'compile_s': round(self.compile_s, 1),
            'cache_hits': self.cache_hits,
            'cache_misses': self.cache_misses}


# ---------------------------------------------------------------------------
# NumPy oracles
# ---------------------------------------------------------------------------


def numpy_lookup(rows_of, ids):
  """Gather-and-combine ('sum') for one input: ``rows_of(ids)`` returns
  the f32 table rows at an integer id array of any shape.  Returns
  ``(out [n, w], abs_sum [n, w])``; ``abs_sum`` scales the f32
  summation round-off bound of a multi-hot bag."""
  ids = np.asarray(ids)
  ids2 = ids[:, None] if ids.ndim == 1 else ids
  rows = rows_of(ids2)                       # [n, h, w] f32
  if ids2.shape[1] == 1:
    return rows[:, 0], np.abs(rows[:, 0])
  return rows.sum(axis=1, dtype=np.float32), np.abs(rows).sum(axis=1)


def check_lookup(name, got, rows_of, ids):
  """One input's device output against the NumPy oracle: bit-exact for
  hotness 1 (a gather), to f32 summation round-off for a bag."""
  got = np.asarray(got)
  want, abs_sum = numpy_lookup(rows_of, ids)
  _require(got.shape == want.shape,
           f'{name}: shape {got.shape}, expected {want.shape}')
  _require(np.isfinite(got).all(), f'{name}: non-finite output')
  hot = 1 if np.asarray(ids).ndim == 1 else np.asarray(ids).shape[1]
  if hot == 1:
    _require(np.array_equal(got, want),
             f'{name}: one-hot lookup is not bit-exact '
             f'(max |diff| {np.abs(got - want).max():.3e})')
  else:
    bound = hot * EPS32 * abs_sum
    _require((np.abs(got - want) <= bound).all(),
             f'{name}: multi-hot lookup off by '
             f'{np.abs(got - want).max():.3e}, beyond f32 summation '
             'round-off')


def row_sums(ids, grads):
  """Per-row f64 sums of ``grads [n, w]`` over each id's occurrences:
  ``(rows, sums, counts)`` in ascending row order."""
  order = np.argsort(ids, kind='stable')
  sid = ids[order]
  starts = np.flatnonzero(np.r_[True, sid[1:] != sid[:-1]])
  sums = np.add.reduceat(grads[order].astype(np.float64), starts, axis=0)
  return sid[starts], sums, np.diff(np.r_[starts, sid.size])


def check_adagrad(name, before, after, acc_after, rows, grad_sum,
                  grad_tol):
  """One table's first step against NumPy row-wise Adagrad (the
  reference's dedup-then-square: ``acc += G**2; w -= lr * G /
  sqrt(acc + eps)`` with ``G`` the row's summed gradient).

  Touched rows (``rows``, with f64 ``grad_sum``) must match within what
  ``grad_tol`` (the device's absolute error in ``G``, see
  ``run_smoke``) allows after propagation through the update.  Every
  other accumulator row must be bit-identical.  Every other TABLE row
  may move by what ``G = 0 +- grad_tol`` allows and no more: the
  lane-packed apply (``sparse._lane_pack``) cuts a packed row's lanes
  out of running sums too, so a lane neighbour of a touched row can
  pick up their rounding — orders of magnitude below the smallest real
  update, so a row scattered to the wrong place still fails here.
  Returns ``(worst error over its allowance, untouched rows that
  moved)``."""
  touched = np.zeros(before.shape[0], bool)
  touched[rows] = True
  bits = lambda a: np.ascontiguousarray(a).view(np.int32)
  acc_moved = (bits(acc_after) != bits(np.float32(ACC0))).any(axis=1)
  _require(not (acc_moved & ~touched).any(),
           f'{name}: {int((acc_moved & ~touched).sum())} untouched '
           'accumulator rows changed')
  gain = LR / np.sqrt(ACC0)  # |d(update)/dG| <= lr / sqrt(acc0)
  leaked = np.flatnonzero((bits(after) != bits(before)).any(axis=1)
                          & ~touched)
  worst = 0.0
  if leaked.size:
    worst = float(np.abs(after[leaked].astype(np.float64)
                         - before[leaked]).max() / (gain * grad_tol))
    _require(worst <= 1.0,
             f'{name}: {leaked.size} untouched table rows moved, by up '
             f'to {worst:.2f}x what rounding allows')
  tol_g = grad_tol + 2.0**-7 * np.abs(grad_sum)
  acc_want = np.float64(np.float32(ACC0)) + grad_sum**2
  w_old = before[rows].astype(np.float64)
  w_want = w_old - LR * grad_sum / np.sqrt(acc_want + EPS)
  tol_w = gain * tol_g + 2 * EPS32 * np.abs(w_old)  # the f32 add rounds
  tol_a = 2 * np.abs(grad_sum) * tol_g + tol_g**2 + 2 * EPS32 * acc_want
  err_w = float((np.abs(after[rows] - w_want) / tol_w).max())
  err_a = float((np.abs(acc_after[rows] - acc_want) / tol_a).max())
  _require(err_w <= 1.0,
           f'{name}: touched rows off by {err_w:.2f}x the allowance '
           'against NumPy row-wise Adagrad')
  _require(err_a <= 1.0,
           f'{name}: touched accumulator rows off by {err_a:.2f}x the '
           'allowance')
  return max(worst, err_w, err_a), int(leaked.size)


# ---------------------------------------------------------------------------
# the body
# ---------------------------------------------------------------------------


def run_smoke(devices, config, global_batch, *, timed_steps=5,
              parity_batch=4096, serve_batch=256, serve_requests=48,
              log=print):
  """Train a few steps, check them, serve a few requests, check those.

  ``devices``: every device of one backend; one mesh is built over all
  of them.  ``config``: a ``models.synthetic.ModelConfig``.  Raises
  (``SmokeFailure`` for a failed check) on the first thing that does
  not hold; returns the observations it printed, as a dict.
  """
  import jax
  import jax.numpy as jnp
  import optax
  from distributed_embeddings_tpu import serving
  from distributed_embeddings_tpu.models.dlrm import bce_with_logits
  from distributed_embeddings_tpu.models.synthetic import (InputGenerator,
                                                           SyntheticModel)
  from distributed_embeddings_tpu.parallel import (
      QuantizedWeight, SparseAdagrad, calibrate_capacity_rows, create_mesh,
      export_tables, get_optimizer_state, get_weights,
      init_hybrid_train_state, make_hybrid_train_step, quantization)

  n_dev = len(devices)
  obs = {}
  compile_log = CompileLog()

  def phase(name, t0):
    log(f'[{time.perf_counter() - t0:7.1f}s] {name}: '
        f'{compile_log.snapshot()}')

  def device_bytes(counter):
    # None per device where the backend keeps no memory statistics (CPU)
    return [(d.memory_stats() or {}).get(counter) for d in devices]

  with compile_log:
    t_start = time.perf_counter()
    mesh = create_mesh(devices)
    model = SyntheticModel(config, mesh=mesh, dp_input=True)
    dist = model.dist_embedding
    params = model.init(0)
    gen = InputGenerator(config, global_batch, alpha=1.05, num_batches=2,
                         seed=0)
    (num0, cats0), lab0 = gen.pool[0]
    tables_of = list(dist.plan.input_table_map)
    log(f'model {config.name}: {len(dist.table_configs)} tables, '
        f'{len(tables_of)} inputs, {len(dist.plan.groups)} fusion groups, '
        f'{model.total_table_gib():.2f} GiB f32, global batch '
        f'{global_batch}, mesh {dict(mesh.shape)}')
    phase('init', t_start)

    def head_loss_fn(dense_params, emb_outs, batch):
      numerical, labels = batch
      return bce_with_logits(model.head(dense_params, numerical, emb_outs),
                             labels)

    # ---- forward parity: dist.apply vs NumPy over get_weights ----------
    weights0 = get_weights(dist, params['embedding'])
    rows_of = [lambda ids, t=t: weights0[t][ids] for t in tables_of]
    nb = max(n_dev, min(global_batch, parity_batch) // n_dev * n_dev)
    outs = dist.apply(params['embedding'],
                      [jnp.asarray(c[:nb]) for c in cats0])
    for i, out in enumerate(outs):
      check_lookup(f'forward input {i} (table {tables_of[i]})', out,
                   rows_of[i], cats0[i][:nb])
    del outs
    n_multi = sum(1 for c in cats0 if c.ndim == 2 and c.shape[1] > 1)
    log(f'forward parity: {len(cats0)} inputs x {nb} samples equal the '
        f'NumPy gather-and-combine ({len(cats0) - n_multi} one-hot '
        f'bit-exact, {n_multi} multi-hot to f32 round-off)')
    phase('forward parity', t_start)

    # ---- the Adagrad oracle's inputs: embedding-output cotangents of
    # the dense head at step 0 (jax.grad of the head is not the code
    # under test), at the NumPy forward of the FULL batch ----------------
    emb_np = tuple(numpy_lookup(rows_of[i], c)[0]
                   for i, c in enumerate(cats0))
    dense0 = {k: v for k, v in params.items() if k != 'embedding'}
    cots = jax.jit(jax.grad(head_loss_fn, argnums=1))(
        dense0, emb_np, (jnp.asarray(num0), jnp.asarray(lab0)))
    cots = [np.asarray(c) for c in cots]
    del emb_np

    # ---- train state, calibrated capacities, ONE compile of the step ---
    capacity_rows = calibrate_capacity_rows(
        dist, [jnp.asarray(c) for c in cats0], params=params['embedding'])
    optimizer = optax.adagrad(LR, initial_accumulator_value=ACC0, eps=EPS)
    emb_opt = SparseAdagrad(learning_rate=LR, capacity_rows=capacity_rows)
    state = init_hybrid_train_state(dist, params, optimizer, emb_opt)
    del params, dense0
    step = make_hybrid_train_step(dist, head_loss_fn, optimizer, emb_opt)
    pool = [([jnp.asarray(c) for c in cats],
             (jnp.asarray(num), jnp.asarray(lab)))
            for (num, cats), lab in gen.pool]
    phase('calibrate + state', t_start)
    t0 = time.perf_counter()
    compiled = step.jitted.lower(state, *pool[0]).compile()
    obs['step_compile_s'] = round(time.perf_counter() - t0, 1)
    mem = compiled.memory_analysis()
    if mem is not None:
      obs['step_memory_analysis_gib'] = {
          k: round(getattr(mem, f'{k}_size_in_bytes') / 2**30, 2)
          for k in ('argument', 'temp', 'output', 'alias')}
    log(f'train step compiled in {obs["step_compile_s"]} s (trace, lower '
        f'and XLA; see cache_hits for cold or warm); memory analysis GiB '
        f'{obs.get("step_memory_analysis_gib")}')
    if n_dev > 1:
      _require('all-to-all' in compiled.as_text(),
               f'no all-to-all in the compiled step on {n_dev} devices')
      for key, leaf in state.params['embedding'].items():
        placed = {s.device for s in leaf.addressable_shards}
        _require(len(placed) == n_dev,
                 f'{key} has shards on {len(placed)} of {n_dev} devices')
      log(f'{n_dev} devices: all-to-all in the compiled step, every '
          'table-group parameter sharded over all of them')
    phase('step compile', t_start)

    # ---- step 1 against NumPy row-wise Adagrad --------------------------
    losses = []
    state, loss = compiled(state, *pool[0])
    losses.append(float(loss))
    weights1 = get_weights(dist, state.params['embedding'])
    acc1 = get_optimizer_state(dist, state.opt_state[1])
    per_table = {}
    for i, tid in enumerate(tables_of):
      ids = cats0[i].reshape(cats0[i].shape[0], -1)
      per_table.setdefault(tid, []).append(
          (ids.reshape(-1), np.repeat(cots[i], ids.shape[1], axis=0)))
    sums = {}
    running = {}  # width -> bound on the device's running-sum magnitude
    for tid, parts in per_table.items():
      rows, gsum, counts = row_sums(np.concatenate([p[0] for p in parts]),
                                    np.concatenate([p[1] for p in parts]))
      sums[tid] = (rows, gsum, counts)
      width = gsum.shape[1]
      running[width] = running.get(width, 0.0) + float(
          np.abs(np.cumsum(gsum, axis=0)).max())
    # The device sums duplicates by sorted-cumsum differences
    # (sparse.compact_segments): a row's total carries the f32 rounding
    # of the running sum it was cut from, not of the row's own size.
    # The running sum at a segment boundary is a sum of whole rows in
    # id order, bounded per width class by the tables' own running-sum
    # maxima added up; 64 covers the cumsum's depth and the second
    # (lane-pack) pass.
    grad_tol = {w: 64 * EPS32 * r for w, r in running.items()}
    row_grads = {}  # width -> every touched row's largest |G| entry
    resolved = 0    # touched rows whose gradient exceeds the allowance
    worst, dup_rows, touched_rows, leaked_rows = 0.0, 0, 0, 0
    for tid, (rows, gsum, counts) in sums.items():
      width = gsum.shape[1]
      err, leaked = check_adagrad(
          f'adagrad table {tid}', weights0[tid], weights1[tid],
          acc1[tid]['acc'], rows, gsum, grad_tol[width])
      worst = max(worst, err)
      leaked_rows += leaked
      dup_rows += int((counts > 1).sum())
      touched_rows += rows.size
      row_grads.setdefault(width, []).append(np.abs(gsum).max(axis=1))
      resolved += int((row_grads[width][-1] > grad_tol[width]).sum())
    _require(dup_rows > 0, 'step-0 ids hold no duplicate rows: the '
             'duplicate-id check checked nothing')
    obs['adagrad'] = {
        'tables': len(sums), 'touched_rows': touched_rows,
        'rows_fed_by_duplicates': dup_rows,
        'rows_with_gradient_above_allowance': resolved,
        'untouched_rows_moved_within_rounding': leaked_rows,
        'worst_error_over_allowance': round(worst, 4),
        'grad_allowance_by_width': {w: float(f'{t:.3g}')
                                    for w, t in grad_tol.items()},
        'median_row_gradient_by_width': {
            w: float(f'{np.median(np.concatenate(g)):.3g}')
            for w, g in row_grads.items()}}
    log(f'adagrad parity vs NumPy row-wise Adagrad: {obs["adagrad"]}; '
        'untouched accumulator rows bit-identical')
    del weights0, weights1, acc1, sums, per_table, cots, rows_of
    phase('step 1 + adagrad parity', t_start)

    # ---- warm-up, then timed steps --------------------------------------
    # step n runs on batch n % 2, and n is len(losses)
    for _ in range(2):
      state, loss = compiled(state, *pool[len(losses) % 2])
      losses.append(float(loss))
    step_ms = []
    for _ in range(timed_steps):
      t0 = time.perf_counter()
      state, loss = compiled(state, *pool[len(losses) % 2])
      jax.block_until_ready((state, loss))
      step_ms.append((time.perf_counter() - t0) * 1e3)
      losses.append(float(loss))
    t0 = time.perf_counter()
    state, loss = compiled(state, *pool[len(losses) % 2])
    losses.append(float(loss))           # the scalar pull IS the sync
    pull_ms = (time.perf_counter() - t0) * 1e3
    median_ms = statistics.median(step_ms)
    agree = 0.8 <= pull_ms / median_ms <= 1.25
    obs.update(step_ms=[round(x, 2) for x in step_ms],
               scalar_pull_ms=round(pull_ms, 2),
               sync_methods_agree=agree, losses=losses)
    log(f'{timed_steps} steps timed around block_until_ready, ms: '
        f'{obs["step_ms"]} (median {median_ms:.2f}); one step timed by '
        f'scalar pull: {pull_ms:.2f} ms; the two '
        + ('agree' if agree else
           'DISAGREE - FINDING: time this backend by scalar pull'))
    _require(np.isfinite(losses).all(), f'non-finite loss in {losses}')
    # compare like with like: the last step and the first on its batch
    last = len(losses) - 1
    first_same = last % 2
    _require(losses[last] < losses[first_same],
             f'loss did not go down on the fixed pool: {losses}')
    log(f'loss finite on all {len(losses)} steps and lower at the end '
        f'(batch {first_same}: {losses[first_same]:.5f} -> '
        f'{losses[last]:.5f})')
    obs['train_peak_bytes_in_use'] = device_bytes('peak_bytes_in_use')
    in_use = device_bytes('bytes_in_use')
    # a backend that reports none is not asked; one that does must hold
    # tables on every device
    _require(all(in_use) or not any(in_use),
             f'bytes_in_use per device: {in_use}')
    log(f'after training, per device: bytes_in_use {in_use}, '
        f'peak_bytes_in_use {obs["train_peak_bytes_in_use"]}')
    phase('train', t_start)

    # ---- serving leg: same process, same devices ------------------------
    int8 = quantization.resolve_table_dtype('int8')
    bundle = [t if isinstance(t, QuantizedWeight)
              else QuantizedWeight.from_values(np.asarray(t), int8)
              for t in export_tables(dist, state.params['embedding'])]
    # the training state is DROPPED before the engine is built: the
    # engine holds its own int8 copy, and nothing below trains
    del state, compiled
    sv_batch = max(n_dev, serve_batch // n_dev * n_dev)
    engine = serving.ServingEngine(
        dist.table_configs, bundle, batch_size=sv_batch, mesh=mesh,
        input_table_map=tables_of,
        hotness=[1 if c.ndim == 1 else c.shape[1] for c in cats0])
    engine.warmup()
    requests = serving.split_requests(cats0, sizes=(1, 2, 4, 8),
                                      limit=serve_requests)
    answers = []
    with serving.DynamicBatcher(engine, max_delay_ms=2.0) as batcher:
      # waves of 2, 4, 8, ... concurrent requests, so merged batches of
      # several sizes land on several rungs of the ladder
      wave = 2
      while len(answers) < len(requests):
        futures = [batcher.submit(r)
                   for r in requests[len(answers):len(answers) + wave]]
        answers += [f.result(timeout=300.0) for f in futures]
        wave *= 2
      served = batcher.stats()
    deq_of = [
        lambda ids, q=bundle[t]: quantization.dequantize_np(
            q.payload[ids], q.scale[ids][..., None]) for t in tables_of]
    for r, (req, ans) in enumerate(zip(requests, answers)):
      _require(len(ans) == len(req),
               f'request {r}: {len(ans)} outputs for {len(req)} inputs')
      for i, out in enumerate(ans):
        check_lookup(f'served request {r} input {i}', out, deq_of[i],
                     req[i])
    obs['serve'] = {key: served[key] for key in
                    ('completed', 'batches', 'bucket_launches', 'p50_ms',
                     'p99_ms')}
    obs['serve_peak_bytes_in_use'] = device_bytes('peak_bytes_in_use')
    log(f'serving: int8 bundle, ladder {list(engine.buckets)}, '
        f'{len(answers)} requests of 1-8 samples answered through '
        f'DynamicBatcher, every served row equals the dequantized bundle '
        f'row; {obs["serve"]}; peak_bytes_in_use per device '
        f'{obs["serve_peak_bytes_in_use"]}')
    phase('serve', t_start)
  obs.update(compile_log.snapshot())
  obs['wall_s'] = round(time.perf_counter() - t_start, 1)
  return obs


def _native_line():
  """Builds (or finds) the two native host libraries and says which
  serves: the default path runs without them, but a tree from
  ``git archive`` has no binaries and must still start."""
  from distributed_embeddings_tpu.parallel import csr_native
  from distributed_embeddings_tpu.utils import fastloader, nativebuild
  parts = []
  def mtime(path):
    return os.path.getmtime(path) if os.path.exists(path) else None

  for so, lib in (('libdetcsr.so', csr_native),
                  ('libdetfastloader.so', fastloader)):
    before = mtime(nativebuild.so_path(so))
    if lib.available():
      built = mtime(nativebuild.so_path(so)) != before
      parts.append(f'{so} ' + ('built' if built else 'found current'))
    else:
      parts.append(f'{so} NOT built, its Python twin serves '
                   f'({nativebuild.toolchain_note()})')
  return 'native libraries: ' + '; '.join(parts)


def _version(package):
  try:
    return importlib.metadata.version(package)
  except importlib.metadata.PackageNotFoundError:
    return 'absent'


def main():
  import jax
  devices = jax.devices()
  device = {'platform': devices[0].platform,
            'kind': devices[0].device_kind, 'count': len(devices)}
  print(f'device: {device} jax {jax.__version__} jaxlib '
        f'{_version("jaxlib")} libtpu {_version("libtpu")}', flush=True)
  if device['platform'] != 'tpu':
    raise SystemExit(
        f'chip_smoke.py: JAX found no TPU (platform '
        f'{device["platform"]!r}); this smoke times and checks the chip '
        'and runs nowhere else - on a CPU run the tier-1 tests')
  from distributed_embeddings_tpu.models.synthetic import SYNTHETIC_MODELS
  from distributed_embeddings_tpu.utils import compile_cache
  print(f'compile cache: {compile_cache.configure()}', flush=True)
  print(_native_line(), flush=True)
  obs = run_smoke(devices, SYNTHETIC_MODELS['tiny'], GLOBAL_BATCH,
                  log=lambda line: print(line, flush=True))
  print('smoke observations (not a benchmark): ' + json.dumps(obs),
        flush=True)
  print(json.dumps({'ok': True, 'device': device}), flush=True)


if __name__ == '__main__':
  sys.exit(main())
