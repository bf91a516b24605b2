"""All priority A/B measurements in ONE process on the chip.

A chip belongs to one process at a time, and every fresh process pays
its start-up, full-size table init, capacity calibration and (on a cold
compile cache) every compile.  This harness measures every apply variant
of interest inside one process: init once, then re-use the (donated,
updated) tables across variants, so each extra data point costs only its
own step compile + 10 steps.

Each phase prints ONE JSON line (flushed immediately) so a run that dies
part-way still leaves every completed measurement in its output; a
SIGALRM watchdog turns a hang into a labelled failure line instead of a
silent stall.  Like ``bench.py`` it refuses to time anything but a TPU,
and exits 1 if any phase failed.  Lines here carry a ``phase`` field and
feed the A/B decisions.

Usage: python examples/benchmarks/sweep_oneproc.py [--steps 10]
       [--phase_budget_s 1800] [--models tiny,criteo]
"""

import argparse
import gc
import json
import os
import signal
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))

import bench  # repo-root bench.py: the TPU gate + baselines


class PhaseTimeout(Exception):
  pass


def _alarm(_sig, _frm):
  raise PhaseTimeout()


def emit(obj):
  print(json.dumps(obj), flush=True)


def main():
  p = argparse.ArgumentParser()
  p.add_argument('--steps', type=int, default=10)
  p.add_argument('--batch_size', type=int, default=65536)
  p.add_argument('--models', default='tiny,criteo')
  p.add_argument('--phase_budget_s', type=int, default=1800,
                 help='SIGALRM watchdog per phase: a hung backend becomes '
                 'a labelled failure line, not a silent stall')
  args = p.parse_args()

  signal.signal(signal.SIGALRM, _alarm)
  jax, devices = bench.require_tpu()
  from distributed_embeddings_tpu.utils import compile_cache
  compile_cache.configure()
  emit({'phase': 'backend', 'platform': devices[0].platform,
        'device_kind': devices[0].device_kind, 'n_devices': len(devices)})
  failed = []

  import jax.numpy as jnp
  import optax
  from distributed_embeddings_tpu.models.dlrm import bce_with_logits
  from distributed_embeddings_tpu.models.synthetic import (SYNTHETIC_MODELS,
                                                           InputGenerator,
                                                           SyntheticModel)
  from distributed_embeddings_tpu.parallel import (SparseAdagrad,
                                                  calibrate_capacity_rows,
                                                  create_mesh,
                                                  init_hybrid_train_state,
                                                  make_hybrid_train_step)
  from distributed_embeddings_tpu.utils.apply_eligibility import (
      eligibility_line, segwalk_serves_all_groups)

  mesh = create_mesh(devices)

  def run_model(model_name, param_dtype):
    """Init tables once, then time each apply variant on the same state."""
    config = SYNTHETIC_MODELS[model_name]
    model = SyntheticModel(config, mesh=mesh, dp_input=True,
                           param_dtype=jnp.dtype(param_dtype))
    dist = model.dist_embedding
    params = model.init(0)
    gen = InputGenerator(config, args.batch_size, alpha=1.05,
                         num_batches=2, seed=0)
    pool = [((jnp.asarray(num), tuple(jnp.asarray(c) for c in cats)),
             jnp.asarray(lab)) for (num, cats), lab in gen.pool]
    optimizer = optax.adagrad(0.01, initial_accumulator_value=0.1, eps=1e-7)

    def head_loss_fn(dense_params, emb_outs, batch):
      numerical, labels = batch
      logits = model.head(dense_params, numerical, emb_outs)
      return bce_with_logits(logits, labels)

    # calibrate once (the CPU plan mirror is minutes of host work at this
    # batch); every non-segwalk variant shares the result
    (_, cats0), _ = gen.pool[0]
    capacity_rows = calibrate_capacity_rows(
        dist, [jnp.asarray(c) for c in cats0], params=params['embedding'])

    variants = [
        ('xla', {}),
        ('segwalk', {'use_segwalk_apply': True}),
        ('segwalk-bf16stream', {'use_segwalk_apply': True,
                                'stream_dtype': 'bfloat16'}),
    ]
    if param_dtype != 'float32':
      # the jumbo-scale configuration: bf16 tables + bf16 accumulators
      # + bf16 stream through the segwalk pair-fetch path (bf16 acc on
      # f32 tables would measure the XLA fallback — bf16 models only)
      variants.append(('segwalk-bf16acc', {'use_segwalk_apply': True,
                                           'stream_dtype': 'bfloat16',
                                           'accum_dtype': 'bfloat16'}))
    baseline, baseline_ndev = bench.pick_baseline(model_name, len(devices))
    for vname, flags in variants:
      label = f'{model_name}-{param_dtype}-{vname}'
      signal.alarm(args.phase_budget_s)
      try:
        need_cap = not (flags.get('use_segwalk_apply')
                        and segwalk_serves_all_groups(
                            dist, param_dtype,
                            accum_dtype=flags.get('accum_dtype',
                                                  'float32')))
        emb_opt = SparseAdagrad(learning_rate=0.01,
                                capacity_rows=(capacity_rows
                                               if need_cap else None),
                                **flags)
        state = init_hybrid_train_state(dist, params, optimizer, emb_opt)
        raw_step = make_hybrid_train_step(dist, head_loss_fn, optimizer,
                                          emb_opt, jit=False)

        def body(state, batch):
          (numerical, cats), labels = batch
          return raw_step(state, list(cats), (numerical, labels))

        step = jax.jit(body, donate_argnums=(0,))
        t0 = time.perf_counter()
        for i in range(3):  # compile, then cached calls
          state, loss = step(state, pool[i % len(pool)])
        float(loss)
        warmup_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for i in range(args.steps):
          state, loss = step(state, pool[i % len(pool)])
        float(loss)
        step_ms = (time.perf_counter() - t0) / args.steps * 1000
        signal.alarm(0)
        note = eligibility_line(dist, param_dtype,
                                flags.get('use_segwalk_apply', False),
                                accum_dtype=flags.get('accum_dtype',
                                                      'float32'))
        emit({'phase': label, 'value': round(step_ms, 3), 'unit': 'ms/step',
              'warmup_s': round(warmup_s, 1),
              'comparable': args.batch_size == 65536,
              'vs_baseline': (round(baseline / step_ms, 4)
                              if baseline and args.batch_size == 65536
                              else None),
              'baseline': (f'{baseline_ndev}xA100 {baseline} ms'
                           if baseline else None),
              'throughput_Msamples_s': round(
                  args.batch_size / step_ms / 1000, 3),
              'eligibility': note})
        # keep the trained tables for the next variant; drop its opt state
        params = state.params
        del state, step, raw_step
        gc.collect()
      except PhaseTimeout:
        emit({'phase': label, 'value': None,
              'error': f'phase hung > {args.phase_budget_s}s'})
        raise  # backend is wedged: later phases would hang too
      except Exception as e:  # phase-local failure: keep measuring
        signal.alarm(0)
        failed.append(label)
        emit({'phase': label, 'value': None,
              'error': f'{type(e).__name__}: {e}',
              'trace_tail': traceback.format_exc()[-800:]})
        # a failure AFTER the first donated step call has already consumed
        # the buffers backing `params`; rebind from the last live state
        # (or re-init) so later variants don't die on deleted arrays
        # (advisor r4)
        try:
          st = locals().get('state')
          cand = st.params if st is not None else params
          jax.block_until_ready(cand)
          params = cand
        except Exception:
          params = model.init(0)
    del params
    gc.collect()

  for model_name in args.models.split(','):
    dtypes = (['float32', 'bfloat16'] if model_name == 'tiny'
              else ['float32'])
    for dt in dtypes:
      try:
        run_model(model_name, dt)
      except PhaseTimeout:
        emit({'phase': f'{model_name}-{dt}', 'value': None,
              'error': 'aborting sweep: backend wedged'})
        raise SystemExit(1)
      except Exception as e:
        failed.append(f'{model_name}-{dt}')
        emit({'phase': f'{model_name}-{dt}', 'value': None,
              'error': f'{type(e).__name__}: {e}',
              'trace_tail': traceback.format_exc()[-800:]})
  emit({'phase': 'oneproc-complete', 'failed': failed})
  if failed:
    raise SystemExit(1)


if __name__ == '__main__':
  main()
