#!/bin/bash
# two sets of runs of one cell, the same seeds in both, one call
# usage: [OUT=<dir>] chip_sets.sh <cell> <seconds> <out-tag> <seed>...
# run from the root of a checkout; logs go to ${OUT:-chiprun_out}/<out-tag>
cell=$1; seconds=$2; out=${OUT:-chiprun_out}/$3; shift 3
mkdir -p $out
for set in A B; do
  for s in "$@"; do
    timeout 900 python3 benchmarks/run.py --workload $cell --seed $s --seconds $seconds --trace 0 > $out/$set-$s.out 2> $out/$set-$s.err
    echo "set $set seed $s exit $?"; tail -n 1 $out/$set-$s.out | cut -c1-900
    grep "^gaps between" $out/$set-$s.out
  done
done
