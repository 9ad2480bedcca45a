#!/bin/bash
# Two checkouts of the repo, run in turn on the same seeds in one chip
# call, each with a compile cache of its own:
#
#   chiprun --timeout <s> -- bash benchmark/chip/pairs.sh <tag> <cell> <seconds> <trace> <dir A> <dir B> <seed>...
#
# A and B are directories of the copy that .gitignore lists (say
# .chip_proof/parent and .chip_proof/change, each unpacked from a
# `git archive`).  Order: A B, B A, A B, ... so that neither side always
# runs first.  Each run goes through that checkout's own runs.sh; the
# outputs are chiprun_out/<tag>-A-<seed>.out and <tag>-B-<seed>.out.
cd "$(dirname "$0")/../.." || exit 2
tag=$1 cell=$2 seconds=$3 trace=$4 a=$5 b=$6
shift 6
export CHIPRUN_OUT=$PWD/chiprun_out
mkdir -p "$CHIPRUN_OUT"
rc=0 k=0
for seed in "$@"; do
  if [ $((k % 2)) -eq 0 ]; then order="A B"; else order="B A"; fi
  for side in $order; do
    if [ $side = A ]; then dir=$a; else dir=$b; fi
    bash "$dir/benchmark/chip/runs.sh" "$tag-$side" "$cell" "$seconds" "$trace" "$seed" || rc=$?
  done
  k=$((k + 1))
done
exit $rc
