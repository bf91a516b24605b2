"""DLRM training example on TPU.

Port of the reference example (`/root/reference/examples/dlrm/main.py`):
MLPerf-configuration DLRM over Criteo (raw binary format) or synthetic
dummy data, hybrid data+model parallel over the TPU mesh, SGD with
warmup+poly-decay LR, AUC evaluation.

Run (synthetic):  python examples/dlrm/main.py --num_batches 100
Run (Criteo):     python examples/dlrm/main.py --dataset_path /data/criteo
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))


def parse_args():
  parser = argparse.ArgumentParser(description='DLRM on TPU')
  parser.add_argument('--dataset_path', default=None,
                      help='path to Criteo split-binary dataset '
                           '(with model_size.json)')
  parser.add_argument('--learning_rate', type=float, default=24)
  parser.add_argument('--batch_size', type=int, default=64 * 1024)
  parser.add_argument('--top_mlp_dims', default='1024,1024,512,256,1')
  parser.add_argument('--bottom_mlp_dims', default='512,256,128')
  parser.add_argument('--num_numerical_features', type=int, default=13)
  parser.add_argument('--num_batches', type=int, default=340)
  parser.add_argument('--table_sizes', default=','.join(['1000'] * 26))
  parser.add_argument('--embedding_dim', type=int, default=128)
  parser.add_argument('--dp_input', action='store_true')
  parser.add_argument('--dist_strategy', default='memory_balanced')
  parser.add_argument('--column_slice_threshold', type=int, default=None)
  parser.add_argument('--segwalk_apply', action='store_true',
                      help='opt into the fused segment-walk table apply '
                      '(ops/pallas_segwalk.py) on TPU')
  parser.add_argument('--row_slice', type=int, default=None,
                      help='element threshold above which tables shard '
                      'along rows (fits tables bigger than one chip)')
  parser.add_argument('--hot_cache', action='store_true',
                      help='frequency-aware hot-row cache (design §10): '
                      'a calibration pass counts id frequencies over '
                      '--hot_calib_batches sample batches, the top rows '
                      'per table (to --hot_coverage occurrence coverage) '
                      'replicate on every device and leave the dp<->mp '
                      'exchange; cold ids sort-unique before the '
                      'exchange.  Requires --dp_input')
  parser.add_argument('--overlap_chunks', type=int, default=1,
                      help='split each dp<->mp exchange into this many '
                      'static slot chunks and software-pipeline '
                      'collective against compute (docs/design.md §11). '
                      '1 = the monolithic program; > 1 requires '
                      '--dp_input and --trainer sparse')
  parser.add_argument('--fused_exchange', default=True,
                      action=argparse.BooleanOptionalAction,
                      help='coalesce every exchange phase into one '
                      'all_to_all per direction via the traced '
                      'LookupPlan offsets (docs/design.md §21). '
                      'Default on; --no-fused_exchange keeps the '
                      'legacy one-collective-per-group schedule '
                      '(bit-exact either way — the A/B lever)')
  parser.add_argument('--wire_dtype', default='none',
                      choices=['none', 'bfloat16', 'table'],
                      help='wire format of the fused-exchange row/'
                      'gradient legs (docs/design.md §24): bfloat16 '
                      'casts the float legs on the wire (~2x fewer '
                      'row bytes, pinned drift bound); table ships a '
                      'quantized table\'s stored int8/fp8 payload + '
                      'scale directly (bit-exact, ~4x fewer bytes; '
                      'requires --table_dtype).  The passthrough '
                      'narrows the PRE-COMBINE legs — pair it with '
                      '--hot_cache (cold rows) or a DCN mesh; combined '
                      'row sums are not grid values and stay float.  '
                      'Requires --fused_exchange and --trainer sparse')
  parser.add_argument('--hot_coverage', type=float, default=0.8,
                      help='per-table occurrence-coverage target for the '
                      'hot set calibration')
  parser.add_argument('--hot_calib_batches', type=int, default=2,
                      help='sample batches the calibration pass counts '
                      '(power-law id streams are stationary; one or two '
                      'batches are representative)')
  parser.add_argument('--hot_budget_mb', type=float, default=None,
                      help='per-device replication budget for hot rows + '
                      'optimizer state (None = coverage-sized)')
  parser.add_argument('--table_dtype', default='none',
                      choices=['none', 'int8', 'float8_e4m3'],
                      help='quantized table storage (design §12): rows '
                      'store as int8/fp8 payloads with one f32 scale '
                      'per row, dequantized at the gather; the sparse '
                      'apply requants exactly the touched rows.  int8 '
                      'is 4x fewer table bytes/row than f32.  Requires '
                      '--trainer sparse and --param_dtype float32')
  parser.add_argument('--cold_tier_budget_mb', type=float, default=None,
                      help='host-DRAM cold tier (design §12): per-device '
                      'HBM byte budget the resident table head must '
                      'fit; the tail rows pin in host memory and '
                      'stream through the deduplicated cold exchange '
                      '(double-buffered fetch pre-pass behind device '
                      'steps).  Requires --dp_input, --hot_cache and '
                      '--trainer sparse; prints the fetch/overlap '
                      'stats at the end')
  parser.add_argument('--param_dtype', default='float32',
                      choices=['float32', 'bfloat16'],
                      help='table + MLP storage dtype (bfloat16 halves '
                      'table HBM: the AMP-baseline analog, reference '
                      'examples/dlrm/README.md:8)')
  parser.add_argument('--compute_dtype', default=None,
                      choices=['float32', 'bfloat16'],
                      help='activation dtype (default: param_dtype)')
  parser.add_argument('--eval', action='store_true',
                      help='run AUC evaluation after training')
  parser.add_argument('--eval_every', type=int, default=0,
                      help='run AUC eval every N train steps (0 = off): '
                      'the AUC-vs-step curve')
  parser.add_argument('--eval_batches', type=int, default=0,
                      help='cap eval to this many batches (0 = all)')
  parser.add_argument('--loader_bench', action='store_true',
                      help='time one pure pass over the train dataset '
                      'first (data-pipeline headroom vs the step)')
  parser.add_argument('--csr_feed', action='store_true',
                      help='pipeline the SparseCore host feed (sparse '
                      'trainer only): batch N+1\'s padded static-CSR '
                      'buffers build on worker threads — the native '
                      'C++ builder when built — while the device '
                      'executes batch N (parallel/csr_feed.CsrFeed); '
                      'prints the build/overlap stats at the end')
  parser.add_argument('--fast_compile', action='store_true',
                      help='compile the sparse step with exec_time_'
                      'optimization_effort=-1.0 / memory_fitting_effort='
                      '-1.0 (measured 2.75x faster XLA compile) — for '
                      'landing a labelled DLRM line inside a short '
                      'chip budget; NOT for official throughput rows')
  parser.add_argument('--max_steps', type=int, default=0,
                      help='stop after this many train steps (0 = the '
                      'whole dataset) — the --budget chip-row mode')
  parser.add_argument('--save_weights', default=None,
                      help='npz path for final embedding weights')
  parser.add_argument('--trainer', default='sparse',
                      choices=['sparse', 'dense'],
                      help='sparse = O(nnz) row-wise embedding updates '
                      '(the perf path; exact for SGD); dense = autodiff '
                      'table grads through optax')
  parser.add_argument('--save_state', default=None,
                      help='npz path for a full resumable checkpoint '
                      '(embedding weights + sparse-optimizer state + step)')
  parser.add_argument('--load_state', default=None,
                      help='resume from a --save_state checkpoint (any '
                      'world size / strategy: the layout reshards on load)')
  parser.add_argument('--resume_dir', default=None,
                      help='auto-resume directory: load the NEWEST VALID '
                      'checkpoint in it (corrupt/truncated/plan-mismatched '
                      'files are rejected with a journaled reason and the '
                      'previous valid one loads instead — '
                      'checkpoint.load_latest_valid); an empty/missing '
                      'dir starts fresh.  --load_state takes precedence.')
  parser.add_argument('--on_batch_error', default='raise',
                      choices=['raise', 'skip'],
                      help="poison-batch policy for the --csr_feed "
                      "pipeline: 'raise' fails the run on a batch whose "
                      "build errors (after transient-I/O retries); 'skip' "
                      'drops it, counts it in the feed stats and journals '
                      'it — never silent')
  parser.add_argument('--audit_every', type=int, default=0,
                      help='state-integrity audit cadence (parallel/'
                      'audit.py, design §13): every N steps the live '
                      'state is checked for diverged replicated hot '
                      'buffers, quantized-row contract violations, '
                      'non-finite params/optimizer slots and host-tier '
                      'digest mismatches; failures journal with '
                      '(device, leaf, row) provenance and trigger '
                      '--on_anomaly.  0 (default) disables — the '
                      'audited-off program is byte-identical')
  parser.add_argument('--on_anomaly', default='terminate',
                      choices=['terminate', 'rollback'],
                      help="response to an audit failure or non-finite "
                      "loss: 'terminate' exits nonzero with the reason "
                      "journaled; 'rollback' restores the newest VALID "
                      'checkpoint from --resume_dir IN-PROCESS '
                      '(quarantining corrupt files as *.corrupt) and '
                      'continues with the CURRENT input position — '
                      'skip-window semantics, the right default for a '
                      'sequential reader (design §13).  rollback '
                      'requires --resume_dir')
  parser.add_argument('--rollback_budget', type=int, default=2,
                      help='max in-process rollbacks per run under '
                      '--on_anomaly rollback; the next anomaly past the '
                      'budget terminates (journaled '
                      'rollback_budget_exhausted)')
  parser.add_argument('--trace', default=None, metavar='PATH',
                      help='arm the observability layer (obs/, design '
                      '§15) and write the Chrome-trace JSON of the run '
                      'to PATH — open it in Perfetto '
                      '(https://ui.perfetto.dev) or feed it to '
                      'tools/trace_report.py for the per-step phase '
                      'breakdown and stall attribution.  Default: off '
                      '(the untraced program is identical)')
  return parser.parse_args()


def main():
  args = parse_args()

  if args.trace:
    from distributed_embeddings_tpu import obs
    obs.enable(trace_path=args.trace)

  import jax
  import jax.numpy as jnp
  import optax
  from distributed_embeddings_tpu.utils import compile_cache
  compile_cache.configure()
  from distributed_embeddings_tpu.models.dlrm import DLRM, bce_with_logits
  from distributed_embeddings_tpu.parallel import (SparseSGD, create_mesh,
                                                   export_tables,
                                                   get_optimizer_state,
                                                   get_weights,
                                                   init_hybrid_train_state,
                                                   init_train_state,
                                                   make_hybrid_train_step,
                                                   make_train_step,
                                                   restore_train_state,
                                                   save_npz,
                                                   save_train_npz)
  from distributed_embeddings_tpu.utils.data import DummyDataset
  from distributed_embeddings_tpu.utils.fastloader import (
      open_raw_binary_dataset)
  from distributed_embeddings_tpu.utils.metrics import StreamingAUC
  from distributed_embeddings_tpu.utils.schedules import warmup_poly_decay_schedule

  table_sizes = [int(s) for s in args.table_sizes.split(',')]
  if args.dataset_path is not None:
    # table sizes come from the dataset (reference main.py:68-73)
    with open(os.path.join(args.dataset_path, 'model_size.json'),
              encoding='utf-8') as f:
      table_sizes = [s + 1 for s in json.load(f).values()]

  mesh = create_mesh()
  world = len(mesh.devices.ravel())

  # frequency-aware hot cache (design §10): calibration pass over a few
  # sample batches -> per-table HotSets wired into the planner.  Uses a
  # throwaway reader so the training iterator's position is untouched.
  if args.overlap_chunks > 1:
    if not args.dp_input:
      raise SystemExit('--overlap_chunks > 1 requires --dp_input (the '
                       'chunked pipeline overlaps the dp->mp id '
                       'exchange, which only the data-parallel input '
                       'path has)')
    if args.trainer != 'sparse':
      raise SystemExit('--overlap_chunks > 1 pairs with --trainer '
                       'sparse (the chunked gradient exchange/apply '
                       'lives in the sparse row-wise path)')
  if args.table_dtype != 'none':
    if args.trainer != 'sparse':
      raise SystemExit('--table_dtype requires --trainer sparse (dense '
                       'autodiff cannot differentiate through integer '
                       'payloads; design §12 refusal matrix)')
    if args.param_dtype != 'float32':
      raise SystemExit('--table_dtype requires --param_dtype float32 '
                       '(the per-row scale carries the dynamic range; '
                       'design §12 refusal matrix)')
  if args.wire_dtype != 'none':
    if not args.fused_exchange:
      raise SystemExit('--wire_dtype requires --fused_exchange: the '
                       'codec lives at the fused-leg seam '
                       '(docs/design.md §24)')
    if args.trainer != 'sparse':
      raise SystemExit('--wire_dtype pairs with --trainer sparse (the '
                       'gradient legs it narrows ride the sparse '
                       'row-wise backward)')
    if args.wire_dtype == 'table' and args.table_dtype == 'none':
      raise SystemExit("--wire_dtype table requires --table_dtype "
                       "(int8/float8_e4m3): the passthrough ships the "
                       "stored quantized payload; use --wire_dtype "
                       "bfloat16 for f32 tables")
  if args.cold_tier_budget_mb is not None:
    if not args.dp_input or not args.hot_cache:
      raise SystemExit('--cold_tier_budget_mb requires --dp_input and '
                       '--hot_cache: the tier streams tail rows '
                       'through the deduplicated cold exchange of the '
                       'hot-cache forward (design §12 refusal matrix)')
    if args.trainer != 'sparse':
      raise SystemExit('--cold_tier_budget_mb requires --trainer sparse '
                       '(tier writeback rides the sparse apply)')
    if args.fast_compile:
      raise SystemExit('--cold_tier_budget_mb is incompatible with '
                       '--fast_compile: the tier step owns its own jit '
                       'boundary (host fetch outside, writeback after) '
                       'and cannot be re-wrapped by the low-effort '
                       'compile path')
    if args.csr_feed:
      raise SystemExit('--cold_tier_budget_mb is incompatible with '
                       '--csr_feed: each pipelines the host pre-pass '
                       'over the same data iterator — use the cold '
                       'tier\'s own fetch pipeline')
  hot_sets = None
  if args.hot_cache:
    if not args.dp_input:
      raise SystemExit('--hot_cache requires --dp_input (the cache '
                       'partitions the dp->mp id exchange, which only '
                       'the data-parallel input path has)')
    if args.trainer != 'sparse':
      raise SystemExit('--hot_cache pairs with --trainer sparse (the '
                       'split hot/cold optimizer state lives in the '
                       'sparse row-wise path)')
    from distributed_embeddings_tpu.parallel import TableConfig, hotcache
    cal_ids = list(range(len(table_sizes)))
    if args.dataset_path is not None:
      cal_ds = open_raw_binary_dataset(
          data_path=args.dataset_path, batch_size=args.batch_size,
          numerical_features=args.num_numerical_features,
          categorical_features=cal_ids,
          categorical_feature_sizes=table_sizes, prefetch_depth=2,
          drop_last_batch=True, offset=0, lbs=args.batch_size,
          dp_input=True)
    else:
      cal_ds = DummyDataset(args.batch_size, args.num_numerical_features,
                            len(cal_ids), args.hot_calib_batches)
    cfgs = [TableConfig(s, args.embedding_dim) for s in table_sizes]
    batches = []
    try:
      for bi, (_, cats_b, _) in enumerate(cal_ds):
        if bi >= args.hot_calib_batches:
          break
        batches.append([np.asarray(c) for c in cats_b])
    finally:
      # release the throwaway reader's prefetch thread + fds now rather
      # than carrying them through the whole training run
      if hasattr(cal_ds, 'close'):
        cal_ds.close()
    hot_sets = hotcache.calibrate_hot_sets(
        cfgs, cal_ids, batches, coverage=args.hot_coverage,
        budget_bytes=(int(args.hot_budget_mb * 2**20)
                      if args.hot_budget_mb else None))
    print(f'hot_cache: calibrated '
          f'{sum(h.size for h in hot_sets.values())} hot rows over '
          f'{len(hot_sets)} table(s) from {len(batches)} batch(es) '
          f'(coverage target {args.hot_coverage})')

  model = DLRM(table_sizes=table_sizes,
               embedding_dim=args.embedding_dim,
               bottom_mlp_dims=[int(d) for d in args.bottom_mlp_dims.split(',')],
               top_mlp_dims=[int(d) for d in args.top_mlp_dims.split(',')],
               num_numerical_features=args.num_numerical_features,
               mesh=mesh,
               dist_strategy=args.dist_strategy,
               column_slice_threshold=args.column_slice_threshold,
               row_slice=args.row_slice,
               dp_input=args.dp_input,
               param_dtype=jnp.dtype(args.param_dtype),
               compute_dtype=jnp.dtype(args.compute_dtype
                                       or args.param_dtype),
               hot_cache=hot_sets,
               overlap_chunks=args.overlap_chunks,
               fused_exchange=args.fused_exchange,
               wire_dtype=(None if args.wire_dtype == 'none'
                           else args.wire_dtype),
               table_dtype=(None if args.table_dtype == 'none'
                            else args.table_dtype),
               cold_tier=args.cold_tier_budget_mb is not None,
               device_hbm_budget=(int(args.cold_tier_budget_mb * 2**20)
                                  if args.cold_tier_budget_mb is not None
                                  else None))
  params = model.init(0)
  if args.table_dtype != 'none':
    from distributed_embeddings_tpu.parallel import quantization
    tb = quantization.table_bytes_stats(model.dist_embedding.plan)
    print(f"table_dtype: {tb['table_dtype']} — "
          f"{tb['table_bytes_per_row']:.1f} payload B/row + "
          f"{tb['table_scale_bytes_per_row']} scale B/row over "
          f"{tb['table_rows']:,} rows "
          f"({tb['table_payload_bytes'] + tb['table_scale_bytes']:,} "
          f"bytes total vs {tb['table_payload_bytes'] * 4:,} at f32)")
  if args.cold_tier_budget_mb is not None:
    tiers = model.dist_embedding.plan.cold_tier_groups
    if model.dist_embedding.cold_tier is None:
      print(f'cold_tier: everything fits the '
            f'{args.cold_tier_budget_mb} MB/device budget — 0 tiered '
            'groups, no host tail')
    else:
      print(f'cold_tier: {len(tiers)} tiered group(s); resident/tail rows '
            f'per group: '
            f'{[(model.dist_embedding.plan.groups[gi].device_rows, model.dist_embedding.plan.groups[gi].tier_rows) for gi in tiers]}; '
            f'host bytes {model.dist_embedding.cold_tier.host_bytes():,}')

  if args.dp_input:
    table_ids = list(range(len(table_sizes)))
  else:
    table_ids = [
        i for dev in model.dist_embedding.plan.input_ids_list for i in dev
    ]

  if args.dataset_path is not None:
    common = dict(data_path=args.dataset_path,
                  batch_size=args.batch_size,
                  numerical_features=args.num_numerical_features,
                  categorical_features=table_ids,
                  categorical_feature_sizes=table_sizes,
                  prefetch_depth=10,
                  drop_last_batch=True,
                  offset=0,
                  lbs=args.batch_size,
                  dp_input=args.dp_input)
    train_dataset = open_raw_binary_dataset(**common)
    eval_dataset = open_raw_binary_dataset(valid=True, **common)
  else:
    train_dataset = DummyDataset(args.batch_size,
                                 args.num_numerical_features,
                                 len(table_ids), args.num_batches)
    eval_dataset = DummyDataset(args.batch_size,
                                args.num_numerical_features,
                                len(table_ids), 10)

  schedule = warmup_poly_decay_schedule(base_lr=args.learning_rate,
                                        warmup_steps=8000,
                                        decay_start_step=48000,
                                        decay_steps=24000)
  optimizer = optax.sgd(schedule)
  dist = model.dist_embedding

  if args.trainer == 'sparse':
    # embedding tables update through row-wise sparse SGD (exact; the
    # reference's IndexedSlices path), dense params through optax
    def head_loss_fn(dense_params, emb_outs, hbatch):
      numerical, labels = hbatch
      return bce_with_logits(model.head(dense_params, numerical, emb_outs),
                             labels)

    emb_opt = SparseSGD(learning_rate=args.learning_rate,
                        use_segwalk_apply=args.segwalk_apply)
    if args.fast_compile:
      # low-effort XLA compile for short-window chip rows (--budget):
      # same program, ~2.75x faster compile, executable quality
      # unguaranteed — the printed lines carry the label below
      raw_step = make_hybrid_train_step(dist, head_loss_fn, optimizer,
                                        emb_opt, lr_schedule=schedule,
                                        jit=False)
      step = jax.jit(raw_step, donate_argnums=(0,),
                     compiler_options={
                         'exec_time_optimization_effort': -1.0,
                         'memory_fitting_effort': -1.0,
                     })
    else:
      step = make_hybrid_train_step(dist, head_loss_fn, optimizer, emb_opt,
                                    lr_schedule=schedule)
    state = init_hybrid_train_state(dist, params, optimizer, emb_opt)
  else:
    def loss_fn(p, batch):
      numerical, cats, labels = batch
      return bce_with_logits(model.apply(p, numerical, list(cats)), labels)

    step = make_train_step(loss_fn, optimizer)
    state = init_train_state(params, optimizer)

  def flat_with_paths(tree):
    """Pytree -> ({path_string: leaf}, treedef) for npz round-tripping."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): v for p, v in leaves}, treedef

  # resume: one explicit checkpoint (--load_state) or auto-resume from
  # the newest VALID file in --resume_dir (corrupt/plan-mismatched
  # candidates are rejected with a journaled reason and the previous
  # valid one loads instead).  restore_train_state reshards the tables
  # + sparse-optimizer state and restores the dense params/optax state
  # (incl. the schedule counts) from the flattened extras, so the MLP
  # towers and both LR schedules resume exactly where they stopped.
  resume_step = 0
  resume_source = args.load_state or (
      args.resume_dir if args.resume_dir and os.path.isdir(args.resume_dir)
      else None)
  if resume_source is not None:
    try:
      state, ckpt_path = restore_train_state(dist, state, resume_source)
    except FileNotFoundError as e:
      if args.load_state:
        raise
      print(f'resume_dir: no valid checkpoint yet ({e}); starting fresh')
    else:
      resume_step = int(state.step)
      print(f'resumed from {ckpt_path} at step {resume_step}')

  if args.loader_bench:
    # pure data-pipeline throughput, no device work: must exceed the
    # trained samples/s below or the loader is the bottleneck (the
    # reference's loader was designed around the same constraint,
    # examples/dlrm/utils.py:157-307)
    t0 = time.perf_counter()
    n = 0
    for numerical, cats, labels in train_dataset:
      n += len(labels)
    dt = time.perf_counter() - t0
    print(f'loader: {n} samples in {dt:.1f}s '
          f'({n / dt / 1e6:.2f}M samples/s, no device work)')

  eval_fwd = None
  auc_history = []

  def run_eval(step_no):
    nonlocal eval_fwd
    if eval_fwd is None:
      eval_fwd = jax.jit(lambda p, n, c: jax.nn.sigmoid(
          model.apply(p, n, list(c))))
    auc_metric = StreamingAUC(num_thresholds=8000)
    for bi, (numerical, cats, labels) in enumerate(eval_dataset):
      if args.eval_batches and bi >= args.eval_batches:
        break
      preds = eval_fwd(state.params, jnp.asarray(numerical),
                       tuple(jnp.asarray(c) for c in cats))
      auc_metric.update(np.asarray(labels), np.asarray(preds))
    auc = auc_metric.result()
    auc_history.append((step_no, auc))
    print(f'step: {step_no}  eval AUC: {auc:.5f}', flush=True)
    return auc

  # self-healing (design §13): periodic state-integrity audits over the
  # live train state, with terminate-or-rollback response.  The example
  # loop's rollback keeps the CURRENT input position (skip-window
  # semantics: a sequential reader cannot rewind mid-epoch; the window
  # between the restored step and the detection is skipped, journaled).
  auditor = None
  if args.audit_every > 0:
    if args.trainer != 'sparse':
      raise SystemExit('--audit_every requires --trainer sparse (the '
                       'auditor checks the hybrid embedding state)')
    from distributed_embeddings_tpu.parallel import StateAuditor
    auditor = StateAuditor(dist, every=args.audit_every)
    print(f'audit: state-integrity checks every {args.audit_every} '
          f'step(s), on_anomaly={args.on_anomaly}')
  if args.on_anomaly == 'rollback' and not args.resume_dir:
    raise SystemExit('--on_anomaly rollback needs --resume_dir (the '
                     'checkpoint directory to restore from)')
  rollbacks = 0

  def handle_anomaly(step_no, why):
    """terminate (exit 3) or roll back in-process; returns after a
    successful rollback.

    Deliberately a SIBLING of fit()'s policy handler (grad.py), not a
    call into it: this loop terminates with a process exit code and
    cannot reposition its sequential reader, so only the skip leg
    applies.  The JOURNAL SCHEMA is the shared contract — both
    implementations emit the same registered event names/fields
    (resilience.REGISTERED_EVENTS + the source-scan test pin them), so
    consumers never see two shapes."""
    nonlocal state, rollbacks
    from distributed_embeddings_tpu.utils import resilience
    # ONE policy label per incident: this loop's rollback keeps the
    # current input position, i.e. rollback_skip semantics — every
    # event of the incident journals that same label
    policy = ('rollback_skip' if args.on_anomaly == 'rollback'
              else args.on_anomaly)
    resilience.journal('anomaly_detected', anomaly=why, step=step_no,
                       policy=policy)
    if args.on_anomaly == 'rollback' and rollbacks < args.rollback_budget:
      try:
        state, pth = restore_train_state(dist, state, args.resume_dir,
                                         quarantine=True)
      except (FileNotFoundError, ValueError) as e:
        resilience.journal('rollback_failed', step=step_no, anomaly=why,
                           error=str(e))
        print(f'on_anomaly=rollback: {why} at step {step_no} and no '
              f'valid checkpoint to roll back to ({e}); terminating')
        sys.exit(3)
      rollbacks += 1
      resilience.journal('rollback', anomaly=why, detect_step=step_no,
                         at_step=step_no, to_step=int(state.step),
                         path=pth, attempt=rollbacks, policy=policy)
      resilience.journal('skip_window', from_step=int(state.step),
                         to_step=step_no,
                         batches=step_no - int(state.step))
      print(f'on_anomaly=rollback: {why} at step {step_no} -> restored '
            f'{pth} at step {int(state.step)} (attempt {rollbacks}/'
            f'{args.rollback_budget}); input continues at the current '
            'batch (offending window skipped)')
      return
    if args.on_anomaly == 'rollback':
      resilience.journal('rollback_budget_exhausted',
                         budget=args.rollback_budget, step=step_no,
                         anomaly=why)
      print(f'on_anomaly=rollback: {why} at step {step_no} but the '
            f'rollback budget ({args.rollback_budget}) is exhausted; '
            'terminating')
    else:
      print(f'on_anomaly=terminate: {why} at step {step_no}; '
            'terminating (journaled)')
    sys.exit(3)

  start = time.perf_counter()
  steady_start = None  # set after warmup so samples/s excludes compiles
  samples = 0
  loss = None
  data_iter = iter(train_dataset)
  if resume_step:
    # the raw-binary reader is sequential: skip the batches the resumed
    # run already consumed (one epoch's worth at most)
    import itertools
    skip = resume_step % max(1, len(train_dataset)) \
        if hasattr(train_dataset, '__len__') else resume_step
    data_iter = itertools.islice(data_iter, skip, None)
  feed = None
  if args.csr_feed and args.trainer == 'sparse':
    # pipelined host feed: the producer pulls batches from the loader
    # and builds their padded static-CSR buffers on worker threads
    # while the device executes the previous step (docs/design.md §8).
    # Capacities CALIBRATE from one sample batch so every batch's
    # buffers share the static hardware layout (the make_csr_feed
    # contract) — without them each batch would size to its own worst
    # partition, unusable as a real SC feed and paying an extra
    # counting pass per (group, device) pair.
    from distributed_embeddings_tpu.parallel import CsrFeed, sparsecore

    _, cats_s, _ = train_dataset[0]
    sc_caps = sparsecore.calibrate_max_ids_per_partition(
        dist, [jnp.asarray(np.asarray(c)) for c in cats_s],
        params=state.params['embedding'])
    feed = CsrFeed(dist, data_iter,
                   cats_fn=lambda b: [np.asarray(c) for c in b[1]],
                   max_ids_per_partition=sc_caps,
                   on_batch_error=args.on_batch_error)
    print(f'csr_feed: pipelined host feed active '
          f'({feed.builder} builder, caps calibrated from batch 0, '
          f'on_batch_error={args.on_batch_error})')
    data_iter = (fed.item for fed in feed)
  tier_pipe = None
  if args.cold_tier_budget_mb is not None:
    # cold-tier fetch pipeline (design §12): the host pre-pass (route +
    # dedup the batch's tail rows) for batch N+1 runs on a worker
    # thread while the device executes batch N; the payload gather
    # stays consumer-side, after the previous step's writeback landed.
    # Batches queue through a deque so numerical/labels stay aligned
    # with the (ordered) pipeline output.
    import collections
    from distributed_embeddings_tpu.parallel import ColdFetchPipeline
    _tier_q = collections.deque()

    def _tier_cats(it):
      for b in it:
        _tier_q.append(b)
        yield [np.asarray(c) for c in b[1]]

    tier_pipe = ColdFetchPipeline(dist, _tier_cats(data_iter))

    def _tier_batches():
      for cats_b, fetch in tier_pipe:
        numerical_b, _, labels_b = _tier_q.popleft()
        yield numerical_b, cats_b, labels_b, fetch

    batch_iter = _tier_batches()
  else:
    batch_iter = ((n, c, l, None) for n, c, l in data_iter)
  from distributed_embeddings_tpu.obs import trace as obs_trace
  for i, (numerical, cats, labels, fetch) in enumerate(batch_iter):
    numerical = jnp.asarray(numerical)
    cats = tuple(jnp.asarray(c) for c in cats)
    labels = jnp.asarray(labels)
    with obs_trace.span('train/step', step=resume_step + i + 1):
      if args.trainer == 'sparse':
        if tier_pipe is not None:
          state, loss = step(state, list(cats), (numerical, labels),
                             cold_fetch=fetch)
        else:
          state, loss = step(state, list(cats), (numerical, labels))
      else:
        state, loss = step(state, (numerical, cats, labels))
    if tier_pipe is not None and i == 0:
      jax.block_until_ready(loss)
      tier_pipe.reset_stats()  # batch 0 has no prior step to hide behind
    samples += args.batch_size
    if feed is not None:
      # per-step sync: this blocking window is the device time the
      # NEXT batch's build hides behind, making the feed's overlap
      # stats a direct measurement (CsrFeed.stats)
      jax.block_until_ready(loss)
      if i == 0:
        feed.reset_stats()  # batch 0 has no prior step to hide behind
    if auditor is not None and (i + 1) % args.audit_every == 0:
      step_no = resume_step + i + 1
      findings = auditor.check_state(state, step=step_no)
      if findings:
        handle_anomaly(step_no, 'audit_failure: '
                       + '; '.join(f.brief() for f in findings[:3]))
      elif not np.isfinite(float(loss)):  # sync already paid by audit
        handle_anomaly(step_no, 'non_finite_loss')
    elif i % 1000 == 0 and not np.isfinite(float(loss)):
      # the non-finite-loss response is INDEPENDENT of the auditor:
      # --on_anomaly promises it, and this print-cadence sync point
      # already pays the float(loss) host pull
      handle_anomaly(resume_step + i + 1, 'non_finite_loss')
    if i == 2:
      # steps 0-2 pay the compile + donation-relayout recompile; the
      # steady-state rate starts here (sync first so queued dispatches
      # don't leak compile time into the steady window)
      jax.block_until_ready(loss)
      steady_start = (time.perf_counter(), samples)
    if i % 1000 == 0:
      print(f'step: {resume_step + i}  loss: {float(loss):.5f}')
    if args.eval_every and (i + 1) % args.eval_every == 0:
      jax.block_until_ready(loss)
      run_eval(resume_step + i + 1)
    if args.max_steps and i + 1 >= args.max_steps:
      break
  if feed is not None:
    fstats = feed.stats()
    feed.close()
    if fstats['overlap_pct'] is not None:
      print(f"csr_feed: built {fstats['batches']} batches in "
            f"{fstats['build_ms']:.1f} ms on workers; consumer blocked "
            f"{fstats['blocked_ms']:.1f} ms -> {fstats['overlap_pct']}% "
            f"of host build time hidden behind the device step "
            f"({fstats['builder']} builder)")
    if fstats['skipped'] or fstats['io_retries'] or fstats['respawns']:
      print(f"csr_feed: degraded-mode events — {fstats['skipped']} "
            f"batch(es) skipped, {fstats['io_retries']} I/O retries, "
            f"{fstats['respawns']} producer respawn(s); details in the "
            'fault journal')
  if tier_pipe is not None:
    tstats = tier_pipe.stats()
    print(f"cold_tier: fetch pre-pass built {tstats['batches']} "
          f"batch(es) in {tstats['build_ms']:.1f} ms on the worker; "
          f"consumer blocked {tstats['blocked_ms']:.1f} ms -> "
          f"{tstats['overlap_pct'] * 100:.1f}% of the host pre-pass "
          'hidden behind the device step')
  if loss is None:
    print('no batches to train on (resume skipped the whole dataset)')
    return
  jax.block_until_ready(loss)
  elapsed = time.perf_counter() - start
  print(f'trained {samples} samples in {elapsed:.1f}s '
        f'({samples / elapsed:,.0f} samples/s on {world} chip(s))')
  if steady_start is not None and samples > steady_start[1]:
    t0, s0 = steady_start
    dt = time.perf_counter() - t0
    if args.eval_every:
      print('  (steady-state rate below excludes compile AND eval pauses '
            'only if eval_every > total steps; with interleaved evals it '
            'is a lower bound)')
    fc = (' [fast_compile: low XLA optimization effort — not an '
          'official row]' if args.fast_compile else '')
    print(f'steady-state: {(samples - s0) / dt:,.0f} samples/s '
          f'({(samples - s0)} samples after warmup; reference DLRM '
          f'8xA100 TF32: 9,158,000 samples/s){fc}')

  if args.wire_dtype != 'none':
    # the traced plan's leg ledger is ground truth for what the
    # collectives shipped (design §24) — print the on-wire vs
    # compute-dtype bytes so the chip A/B rows carry the ratio
    from distributed_embeddings_tpu.parallel import planner
    rec = planner.reconcile_exchange(dist, journal=False)
    wb = rec['counted_wire_bytes']
    pb = rec['counted_payload_bytes']
    wired = sorted(k for k, v in rec['wire_legs'].items() if v.get('wire'))
    print(f'wire_dtype {args.wire_dtype}: narrowed leg(s) '
          f'{wired or "none"}; forward exchange ships {wb:,} bytes on '
          f'the wire vs {pb:,} at compute dtype '
          f'({pb / max(wb, 1):.2f}x fewer)')

  if args.eval:
    auc = run_eval(int(state.step))
    print(f'Evaluation completed, AUC: {auc:.5f}')
  if len(auc_history) > 1:
    print('AUC curve: ' +
          ' '.join(f'{s}:{a:.4f}' for s, a in auc_history))

  weights = None
  if args.save_weights or args.save_state:
    # quantized plans export payload+scale pairs (design §12): the
    # resumable file carries quantized table bytes; save_npz's
    # positional arr_i format dequantizes exactly (value-lossless)
    weights = export_tables(dist, state.params['embedding'])

  if args.save_weights:
    save_npz(args.save_weights, weights)
    print(f'saved embedding weights to {args.save_weights}')

  if args.save_state:
    st_tables = (get_optimizer_state(dist, state.opt_state[1])
                 if args.trainer == 'sparse' else None)
    extras = {'step': np.int64(int(state.step))}
    dense_params = {k: v for k, v in state.params.items()
                    if k != 'embedding'}
    for k, v in flat_with_paths(dense_params)[0].items():
      extras['dense:' + k] = np.asarray(v)
    dense_opt = (state.opt_state[0] if args.trainer == 'sparse'
                 else state.opt_state)  # small with SGD; see --help
    for k, v in flat_with_paths(dense_opt)[0].items():
      extras['opt:' + k] = np.asarray(v)
    save_train_npz(args.save_state, weights, st_tables, extras=extras,
                   plan=dist)
    print(f'saved resumable state to {args.save_state}')

  if args.trace:
    from distributed_embeddings_tpu.obs import trace as obs_trace
    path = obs_trace.save(args.trace)
    print(f'obs trace: {obs_trace.event_count()} event(s) -> {path} '
          '(open in Perfetto, or: python tools/trace_report.py '
          f'{path})')


if __name__ == '__main__':
  main()
