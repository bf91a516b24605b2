#!/bin/bash
# Criteo-shaped DLRM end-to-end on the available chip (VERDICT r3 item 4):
# generate a one-chip-sized synthetic Criteo-format dataset (26 tables,
# width 128, learnable labels), measure pure loader throughput, train with
# an AUC-vs-step curve, and report steady-state samples/s against the
# reference's 9.16M samples/s 8xA100 number (chip-count caveat applies;
# this is ONE v5e).
#
# --budget: the ~5-minute variant — smaller batch, low-effort XLA compile
# (--fast_compile, measured 2.75x faster), steps-only throughput with
# NO eval, pipelined host feed on.  The printed lines carry the
# fast_compile label so the row can never read as the official number.
# Usage: bash examples/dlrm/chip_run.sh [--budget] [data_dir] [batch] [train_rows]
set -eu
BUDGET=0
if [ "${1:-}" = "--budget" ]; then
  BUDGET=1
  shift
fi
cd "$(dirname "$0")/../.."
DATA=${1:-/tmp/criteo_synth}
if [ "$BUDGET" = 1 ]; then
  BATCH=${2:-8192}
  ROWS=${3:-1048576}
else
  BATCH=${2:-65536}
  ROWS=${3:-8388608}
fi

# build the native pieces (loader + CSR builder) so the run exercises
# them (falls back to the Python twins if the toolchain is missing;
# main.py prints which)
make -C distributed_embeddings_tpu/cc >/dev/null 2>&1 || true

# the lint gate, all three analysis tiers in one fail-fast line
# (design §17/§18/§22): detlint's AST invariants, graphlint's traced
# collective-schedule/donation/retrace/host-sync contracts on a forced
# 8-device CPU mesh, and commlint's cross-rank protocol (plan-predicted
# schedules vs the checked-in ledger, rendezvous model-check) — a chip
# window is too expensive to burn on a tree that fails any of them
python tools/lintall.py --strict

# hierarchical DCNxICI A/B (design §20): flat vs dcn_sharding arms over
# a (2, n/2) two-axis mesh on this backend, one mesh-tagged artifact
# line carrying both steady-state walls AND the exact dedup counters
# (dcn_rows / dcn_rows_off / dcn_dedup_ratio) — the journaled evidence
# that each distinct row crossed DCN once per slice, and the line the
# perf sentinel bands only against same-mesh history.  Needs an even
# device count >= 4; a single-chip window skips the row rather than
# faking a pod topology.
NDEV=$(python -c 'import jax; print(len(jax.devices()))')
if [ "$NDEV" -ge 4 ] && [ $((NDEV % 2)) -eq 0 ]; then
  python bench.py --model tiny --steps 10 --warmup 2 --dcn_ab
fi

if [ ! -f "$DATA/model_size.json" ]; then
  python examples/dlrm/gen_data.py --data_path "$DATA" \
    --train_rows "$ROWS" --eval_rows 524288 --preset onechip
fi

if [ "$BUDGET" = 1 ]; then
  # steps-only labelled DLRM line: 40 steps past the 3-step warmup is a
  # steady-state samples/s + loss-descent signal; no eval, no loader pass
  python examples/dlrm/main.py \
    --dataset_path "$DATA" \
    --batch_size "$BATCH" \
    --dp_input \
    --fast_compile \
    --csr_feed \
    --max_steps 40

  # cheap hot-cache A/B (design §10): the same 40-step steps-only row
  # with the frequency-aware cache calibrated + on — compare the two
  # steady-state samples/s lines (the cache-off row above is the
  # baseline arm)
  python examples/dlrm/main.py \
    --dataset_path "$DATA" \
    --batch_size "$BATCH" \
    --dp_input \
    --fast_compile \
    --hot_cache \
    --max_steps 40

  # cheap chunked-exchange A/B (design §11): the same steps-only row
  # with the dp<->mp exchanges split into 4 pipelined chunks — the
  # --max_steps 40 row above (overlap_chunks=1, program-identical to
  # pre-chunking) is the off arm
  python examples/dlrm/main.py \
    --dataset_path "$DATA" \
    --batch_size "$BATCH" \
    --dp_input \
    --fast_compile \
    --overlap_chunks 4 \
    --max_steps 40

  # cheap fused-exchange A/B (design §21): the plain --max_steps 40
  # row above is the ON arm (fused_exchange defaults on — one
  # coalesced all_to_all per direction); this arm reverts to the
  # legacy one-collective-per-group schedule — the steady-state
  # samples/s pair prices the per-collective launch/rendezvous
  # overhead the fusion removes (bit-exact either way)
  python examples/dlrm/main.py \
    --dataset_path "$DATA" \
    --batch_size "$BATCH" \
    --dp_input \
    --fast_compile \
    --no-fused_exchange \
    --max_steps 40

  # cheap quantized-storage A/B (design §12): int8 rows + per-row f32
  # scales, 4x less table HBM — the plain --max_steps 40 row above is
  # the f32 off arm; compare steady-state samples/s AND the printed
  # table-bytes line
  python examples/dlrm/main.py \
    --dataset_path "$DATA" \
    --batch_size "$BATCH" \
    --dp_input \
    --fast_compile \
    --table_dtype int8 \
    --max_steps 40

  # cheap wire-compression A/B (design §24): the passthrough narrows
  # the PRE-COMBINE cold-row legs, so both arms run hot_cache + int8 —
  # off ships the cold rows as dequantized f32, on ships the stored
  # int8 payload + po2 scale directly (bit-exact, ~4x fewer row
  # bytes).  Compare the steady-state samples/s pair and the printed
  # wire_dtype bytes line.
  python examples/dlrm/main.py \
    --dataset_path "$DATA" \
    --batch_size "$BATCH" \
    --dp_input \
    --fast_compile \
    --hot_cache \
    --table_dtype int8 \
    --max_steps 40
  python examples/dlrm/main.py \
    --dataset_path "$DATA" \
    --batch_size "$BATCH" \
    --dp_input \
    --fast_compile \
    --hot_cache \
    --table_dtype int8 \
    --wire_dtype table \
    --max_steps 40

  # cheap audit off/on A/B (design §13): the plain --max_steps 40 row
  # above is the audit-off arm (byte-identical program); this arm runs
  # the state-integrity auditor every 10 steps — compare the two
  # steady-state samples/s lines to price leaving SDC detection armed
  python examples/dlrm/main.py \
    --dataset_path "$DATA" \
    --batch_size "$BATCH" \
    --dp_input \
    --fast_compile \
    --audit_every 10 \
    --max_steps 40

  # cheap cold-tier row (design §12): int8 + hot cache + a per-device
  # HBM budget tight enough to force tail rows into host DRAM — proves
  # the beyond-HBM path trains on this chip and prints the measured
  # fetch-overlap pct (the int8 row above is the untiered arm).  NO
  # --fast_compile here: the tier step owns its own jit boundary and
  # main.py refuses the combination, so this row compiles at full
  # effort (still bounded by --max_steps 40).
  python examples/dlrm/main.py \
    --dataset_path "$DATA" \
    --batch_size "$BATCH" \
    --dp_input \
    --hot_cache \
    --table_dtype int8 \
    --cold_tier_budget_mb 1024 \
    --max_steps 40
  exit 0
fi

python examples/dlrm/main.py \
  --dataset_path "$DATA" \
  --batch_size "$BATCH" \
  --dp_input \
  --loader_bench \
  --csr_feed \
  --eval_every 32 --eval_batches 4 \
  --eval

# cheap hot-cache A/B (design §10): two short steps-only rows, cache
# off vs on, same batch — the steady-state samples/s pair is the chip
# measurement of the exchange/scatter cut the CPU counters predict
python examples/dlrm/main.py \
  --dataset_path "$DATA" \
  --batch_size "$BATCH" \
  --dp_input \
  --max_steps 40
python examples/dlrm/main.py \
  --dataset_path "$DATA" \
  --batch_size "$BATCH" \
  --dp_input \
  --hot_cache \
  --max_steps 40

# chunked-exchange A/B (design §11): the off arm is the plain
# --max_steps 40 row above (overlap_chunks=1 IS the monolithic
# program); the on arm pipelines each exchange in 4 slot chunks so the
# device overlaps collective and compute — the steady-state samples/s
# pair is the chip measurement of the hidden exchange wall the bench's
# a2a_overlap_pct predicts
python examples/dlrm/main.py \
  --dataset_path "$DATA" \
  --batch_size "$BATCH" \
  --dp_input \
  --overlap_chunks 4 \
  --max_steps 40

# fused-exchange A/B (design §21): the plain --max_steps 40 row above
# is the ON arm (fused_exchange defaults on — exchange collectives
# independent of the fusion-group count); the off arm issues one
# all_to_all per group per direction, the pre-§21 schedule — the
# steady-state samples/s pair is the chip measurement of the
# per-collective overhead the bench's exchange_collectives_* gap
# predicts (bit-exact either way)
python examples/dlrm/main.py \
  --dataset_path "$DATA" \
  --batch_size "$BATCH" \
  --dp_input \
  --no-fused_exchange \
  --max_steps 40

# quantized-storage A/B (design §12): int8 rows + per-row f32 scales
# cut table HBM 4x (the scaling model's binding resource); the plain
# --max_steps 40 row above is the f32 off arm
python examples/dlrm/main.py \
  --dataset_path "$DATA" \
  --batch_size "$BATCH" \
  --dp_input \
  --table_dtype int8 \
  --max_steps 40

# wire-compression A/B (design §24): the bf16 wire vs the plain row
# above (float row/gradient legs cast on the wire, pinned drift
# bound), then the int8 payload+scale passthrough off/on pair under
# hot_cache — the passthrough narrows the PRE-COMBINE cold-row legs,
# bit-exact between its arms.  Each on arm prints the on-wire vs
# compute-dtype byte ratio next to its steady-state samples/s line.
python examples/dlrm/main.py \
  --dataset_path "$DATA" \
  --batch_size "$BATCH" \
  --dp_input \
  --wire_dtype bfloat16 \
  --max_steps 40
python examples/dlrm/main.py \
  --dataset_path "$DATA" \
  --batch_size "$BATCH" \
  --dp_input \
  --hot_cache \
  --table_dtype int8 \
  --max_steps 40
python examples/dlrm/main.py \
  --dataset_path "$DATA" \
  --batch_size "$BATCH" \
  --dp_input \
  --hot_cache \
  --table_dtype int8 \
  --wire_dtype table \
  --max_steps 40

# audit off/on A/B (design §13): the plain --max_steps 40 row above is
# the audit-off arm (byte-identical program); the on arm checks the
# live state every 10 steps (replicated digests, quantized row
# contract, finiteness) — the steady-state samples/s pair prices
# leaving SDC detection armed on an unattended run
python examples/dlrm/main.py \
  --dataset_path "$DATA" \
  --batch_size "$BATCH" \
  --dp_input \
  --audit_every 10 \
  --max_steps 40

# cold-tier row (design §12): int8 + hot cache + a per-device HBM
# budget tight enough to force tail rows into host DRAM — the
# beyond-HBM regime on one chip, with the fetch pre-pass overlap pct
# printed (the int8 row above is the untiered arm)
python examples/dlrm/main.py \
  --dataset_path "$DATA" \
  --batch_size "$BATCH" \
  --dp_input \
  --hot_cache \
  --table_dtype int8 \
  --cold_tier_budget_mb 1024 \
  --max_steps 40

# AMP-analog variant (reference examples/dlrm/README.md:8, 10.4M
# samples/s 8xA100 fp16 = f32 variables + half-precision compute):
# f32 tables, bf16 activations
python examples/dlrm/main.py \
  --dataset_path "$DATA" \
  --batch_size "$BATCH" \
  --dp_input \
  --compute_dtype bfloat16 \
  --eval_every 64 --eval_batches 4

# bf16 STORAGE variant (beyond the reference's AMP: halves table HBM,
# the scaling model's binding resource; f32 accumulation in the step)
python examples/dlrm/main.py \
  --dataset_path "$DATA" \
  --batch_size "$BATCH" \
  --dp_input \
  --param_dtype bfloat16 \
  --eval_every 64 --eval_batches 4
