"""Multi-chip volume/scaling model for the synthetic benchmarks.

Answers, with checkable arithmetic, "how does the per-chip work shrink as
chips are added, and where does that land against the published A100
baselines?" (VERDICT r2: the scale-out story must be quantified, not
asserted).  Everything below derives from the REAL ``ShardingPlan`` at
each world size — the same pure-Python planner the runtime uses — plus
the v5e primitive costs measured on hardware (docs/perf_notes.md):

- XLA random-row gather   ~29 ns/row   (lookup forward)
- XLA scatter             ~100 ns/row  (optimizer apply; 2 passes for
                                        Adagrad: acc set + table add)
- argsort                 ~5 ns/row, cumsum/compaction gathers ~15 ns/row
  (the compaction pipeline, charged per RAW stream row)
- ICI: ~90 GB/s/chip usable all_to_all bandwidth on a v5e pod slice
  (4.5e10 x 2 directions, public v5e spec), DCN ignored (single slice)

Per-chip quantities at world size D, global batch B, from the plan:

- lookup rows  = sum over this chip's slots of B_slice * hotness
  (every id gathers one row; slice_batch = B on one slice)
- a2a bytes    = input ids int32 [slots * B * h * 4] + output floats
  [out-slots * B * w * 4], counting the (D-1)/D fraction that leaves
  the chip; row-sliced inputs count ONE output slot (psum_scatter)
- update rows  = the same slot walk (every looked-up row produces one
  gradient row); the apply's scatters run on the COMPACTED unique rows,
  bounded by min(stream, fused rows resident on the chip) — the
  power-law duplicate factor only helps further (measured 859k uniques
  vs the 1.44M bound on tiny's big group at D=1)

Run: python examples/benchmarks/scaling_model.py [--model tiny]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))

from distributed_embeddings_tpu.models.synthetic import (SYNTHETIC_MODELS,
                                                         expand_tables)
from distributed_embeddings_tpu.parallel.planner import ShardingPlan

GATHER_NS = 29.0
SCATTER_NS = 100.0
SCATTER_PASSES = 2          # Adagrad: accumulator set + table add
COMPACT_NS = 20.0           # sort + cumsum + compaction gathers per raw row
ICI_BYTES_PER_S = 90e9      # usable per-chip all_to_all bandwidth, v5e
MLP_MS = {'tiny': 2.0, 'small': 4.0}  # measured fwd+bwd head cost, tiny

# segwalk-apply pricing (the round-3/4 kernel; docs/perf_notes.md):
SORT_NS = 5.0               # argsort of the raw id stream
HBM_BYTES_PER_S = 819e9     # v5e HBM bandwidth (stream passes)
# segwalk stream passes, per group (round 5, g_index): groups with
# multi-hot slots gather the comb straight from the compact per-bag
# rows — write + kernel read of the one live [n, 128] copy + slack for
# the padded compact-row materialisation = 3 passes (measured: one
# fewer full copy at jumbo, 25.9 -> 19.1 GiB temps); pure hotness-1
# groups take the identity shortcut and keep the round-4 pipeline
# (comb write + sorted-gather read/write + kernel read = 4)
STREAM_PASSES_MULTIHOT = 3
STREAM_PASSES_H1 = 4
DMA_ISSUE_NS = 47.0         # measured scalar-core DMA issue floor
DMA_PER_UNIQUE = 4          # table r/w + acc r/w per unique packed row

# ---------------------------------------------------------------------------
# Chip parameter sets (VERDICT r4 item 7: price the v5p north star, don't
# wave at it).  Every v5e number was MEASURED on one v5e in 2026-07
# (docs/perf_notes.md — before PRs 1-20, so dated, not current); the v5p
# numbers are DERIVED from public specs with
# the scaling rule stated per line:
#
#   - issue-bound costs (random-row gather/scatter, the scalar-core DMA
#     issue floor): v5e's measured 29 ns/row gather moves only ~17.6 GB/s,
#     far under HBM bandwidth — these are core-clock-bound, so they scale
#     with the clock ratio 1.75 GHz (v5p) / 0.94 GHz (v5e) = 1.86x.
#   - streaming costs (compaction passes, sort, segwalk stream passes):
#     HBM-bandwidth-bound, scale with 2765 / 819 GB/s = 3.38x.
#   - ICI: v5p has 4800 Gbps/chip vs v5e's 1600 (3x); usable all_to_all
#     scales the measured 90 GB/s to 270 GB/s.
#   - MLP: MXU-bound, scales with bf16 peak 459 / 197 TFLOPs = 2.33x.
#
# 'v5p_sc' additionally models SparseCore offload (docs/design.md §8): the
# DMA-issue floor — the residual that keeps v5e behind A100 — moves to the
# 4 SparseCores' independent fetch units.  ASSUMPTION (stated, unmeasured):
# 4 cores issue concurrently, so every random-access per-row cost (gather,
# scatter, DMA issue) divides by 4 on top of the clock scaling.  The
# streaming and ICI sides are unchanged — SC accelerates random access
# only.
_V5E_V5P_CLOCK = 1.75 / 0.94
_V5E_V5P_HBM = 2765e9 / 819e9
CHIPS = {
    'v5e': dict(gather_ns=GATHER_NS, scatter_ns=SCATTER_NS,
                compact_ns=COMPACT_NS, sort_ns=SORT_NS,
                ici_Bps=ICI_BYTES_PER_S, hbm_Bps=HBM_BYTES_PER_S,
                dma_issue_ns=DMA_ISSUE_NS, mlp_scale=1.0,
                hbm_gib=15.75),
    'v5p': dict(gather_ns=GATHER_NS / _V5E_V5P_CLOCK,
                scatter_ns=SCATTER_NS / _V5E_V5P_CLOCK,
                compact_ns=COMPACT_NS / _V5E_V5P_HBM,
                sort_ns=SORT_NS / _V5E_V5P_HBM,
                ici_Bps=270e9,
                hbm_Bps=2765e9,
                dma_issue_ns=DMA_ISSUE_NS / _V5E_V5P_CLOCK,
                mlp_scale=197.0 / 459.0,
                hbm_gib=95.0),
}
CHIPS['v5p_sc'] = dict(CHIPS['v5p'],
                       dma_issue_ns=CHIPS['v5p']['dma_issue_ns'] / 4,
                       gather_ns=CHIPS['v5p']['gather_ns'] / 4,
                       scatter_ns=CHIPS['v5p']['scatter_ns'] / 4)


def analyze(name: str, world: int, batch: int, row_slice=None,
            apply='xla', stream_bytes_per_elem=4, chip='v5e'):
  hw = CHIPS[chip]
  config = SYNTHETIC_MODELS[name]
  tables, input_table_map, hotness = expand_tables(config)
  plan = ShardingPlan(tables, world_size=world,
                      input_table_map=input_table_map,
                      row_slice_threshold=row_slice)
  D = world

  # per-device walk over the plan's request slots (the runtime's
  # _subgroups classes requests by (group, hotness); volumes only need
  # the per-slot hotness/width, so the walk below is equivalent).
  # Per-GROUP streams are kept so the segwalk pricing can apply each
  # group's pack factor to its unique bound.
  hot_of = {i: hotness[i] for i in range(len(input_table_map))}
  per_dev = [dict(lookup=0, in_bytes=0, out_bytes=0, stream=0, rows=0,
                  groups=[]) for _ in range(D)]
  for g in plan.groups:
    pack = 128 // g.width if g.width < 128 else 1
    for dev in range(D):
      per_dev[dev]['rows'] += g.rows[dev]
      gstream = 0
      for r in g.requests[dev]:
        h = hot_of[r.input_id]
        per_dev[dev]['lookup'] += batch * h
        per_dev[dev]['stream'] += batch * h
        gstream += batch * h
        per_dev[dev]['in_bytes'] += batch * h * 4
        row_sliced = (r.row_start, r.row_end) != (
            0, tables[r.table_id].input_dim)
        # row shards: the summed output leaves through ONE psum_scatter
        # slot shared by all shards — charge it once, on the first shard
        if not row_sliced or r.row_start == 0:
          per_dev[dev]['out_bytes'] += batch * g.width * 4
      # mirrors sparse.py's use_idx rule: the indirection engages only
      # at >=2x duplication (n >= 2m); below that the fused broadcast
      # (4-pass pipeline) is kept
      nreq = len(g.requests[dev])
      per_dev[dev]['groups'].append(
          dict(stream=gstream, rows=g.rows[dev], pack=pack,
               width=g.width,
               multihot=nreq > 0 and gstream >= 2 * batch * nreq))
  off_chip = (D - 1) / D if D > 1 else 0.0
  worst = max(per_dev, key=lambda d: d['lookup'] + d['stream'])
  unique_bound = min(worst['stream'], worst['rows'])
  lookup_ms = worst['lookup'] * hw['gather_ns'] * 1e-6
  if apply == 'segwalk':
    # sort + per-group sequential stream passes (3 with the g_index
    # indirection, 4 on the hotness-1 shortcut) over the dense
    # [*, 128] stream + the kernel's random DMAs per unique PACKED row
    compact_ms = worst['stream'] * hw['sort_ns'] * 1e-6
    stream_pass_bytes = sum(
        gr['stream'] * 128 * stream_bytes_per_elem *
        (STREAM_PASSES_MULTIHOT if gr['multihot'] else STREAM_PASSES_H1)
        for gr in worst['groups'])
    compact_ms += (stream_pass_bytes / hw['hbm_Bps']) * 1e3
    uniq_packed = sum(
        min(gr['stream'], -(-gr['rows'] // gr['pack']))
        for gr in worst['groups'])
    scatter_ms = uniq_packed * hw['dma_issue_ns'] * DMA_PER_UNIQUE * 1e-6
    unique_bound = uniq_packed
  else:
    compact_ms = worst['stream'] * hw['compact_ns'] * 1e-6
    scatter_ms = unique_bound * hw['scatter_ns'] * SCATTER_PASSES * 1e-6
  a2a_bytes = (worst['in_bytes'] + worst['out_bytes']) * off_chip
  a2a_ms = a2a_bytes / hw['ici_Bps'] * 1e3
  mlp_ms = MLP_MS.get(name, 2.0) * hw['mlp_scale']
  total_ms = lookup_ms + compact_ms + scatter_ms + a2a_ms + mlp_ms
  mem_gib = plan.padded_memory_elements() * 4 / 2**30
  return dict(D=D, tables_per_chip=max(len(t) for t in plan.table_ids),
              mem_gib=mem_gib, lookup_rows=worst['lookup'],
              stream_rows=worst['stream'], unique_bound=unique_bound,
              a2a_mb=a2a_bytes / 1e6, lookup_ms=lookup_ms,
              compact_ms=compact_ms, scatter_ms=scatter_ms, a2a_ms=a2a_ms,
              mlp_ms=mlp_ms, total_ms=total_ms)


def main(argv=None):
  p = argparse.ArgumentParser()
  p.add_argument('--model', default='tiny')
  p.add_argument('--batch', type=int, default=65536)
  p.add_argument('--worlds', type=int, nargs='+',
                 default=[1, 8, 64, 256])
  p.add_argument('--row_slice', type=int, default=None,
                 help='row-slice element threshold (needed to spread '
                 'width-capped tables past ~64 chips)')
  p.add_argument('--apply', default='xla', choices=['xla', 'segwalk'],
                 help='price the XLA compaction+scatter apply or the '
                 'fused segment-walk kernel')
  p.add_argument('--stream_dtype', default='float32',
                 choices=['float32', 'bfloat16'],
                 help='segwalk stream payload dtype (halves stream '
                 'passes for bfloat16)')
  p.add_argument('--chip', default='v5e', choices=sorted(CHIPS),
                 help='hardware parameter set (v5p derived from public '
                 'specs; v5p_sc adds the SparseCore-offload scenario)')
  p.add_argument('--compare', action='store_true',
                 help='one row per world with v5e / v5p / v5p_sc totals '
                 'side by side against the published A100 baseline at '
                 'that device count (the BASELINE.md north star)')
  args = p.parse_args(argv)
  sbe = 2 if args.stream_dtype == 'bfloat16' else 4

  if args.compare:
    import bench  # repo-root baselines table
    print(f'# {args.model}, global batch {args.batch}, {args.apply} '
          f'apply, stream {args.stream_dtype}: projected worst-chip '
          f'ms/step per chip generation vs published A100 baseline')
    print('D | A100_ms | v5e_ms | v5p_ms | v5p_sc_ms | v5p_vs_A100 | '
          'v5p_sc_vs_A100')
    for w in args.worlds:
      try:
        totals = {
            c: analyze(args.model, w, args.batch,
                       row_slice=args.row_slice, apply=args.apply,
                       stream_bytes_per_elem=sbe, chip=c)['total_ms']
            for c in ('v5e', 'v5p', 'v5p_sc')
        }
      except (ValueError, AssertionError) as e:
        print(f'{w} | plan failed: {e}')
        continue
      base, base_n = bench.pick_baseline(args.model, w)
      base_s = f'{base:.2f}@{base_n}' if base else '-'
      ratios = [(f'{base / totals[c]:.2f}x' if base else '-')
                for c in ('v5p', 'v5p_sc')]
      print(f'{w} | {base_s} | {totals["v5e"]:.2f} | '
            f'{totals["v5p"]:.2f} | {totals["v5p_sc"]:.2f} | '
            f'{ratios[0]} | {ratios[1]}')
    return 0

  print(f'# {args.model}, global batch {args.batch}, chip {args.chip}, '
        f'per-chip estimates (worst chip)')
  cols = ('D', 'mem_gib', 'lookup_rows', 'stream_rows', 'unique_bound',
          'a2a_mb', 'lookup_ms', 'compact_ms', 'scatter_ms', 'a2a_ms',
          'mlp_ms', 'total_ms')
  print(' | '.join(cols))
  for w in args.worlds:
    try:
      r = analyze(args.model, w, args.batch, row_slice=args.row_slice,
                  apply=args.apply, stream_bytes_per_elem=sbe,
                  chip=args.chip)
    except (ValueError, AssertionError) as e:
      print(f'{w} | plan failed: {e}')
      continue
    print(' | '.join(
        f'{r[c]:.2f}' if isinstance(r[c], float) else str(r[c])
        for c in cols))
  return 0


if __name__ == '__main__':
  sys.exit(main())
