#!/bin/bash
# Runs of one cell in one chip call, one after another, each a process of
# its own, as the driver makes them:
#
#   chiprun --timeout <s> -- bash benchmark/chip/runs.sh <tag> <cell> <seconds> <trace> <seed>... [-- <option of run.py>...]
#
# Each run's standard output and error go to chiprun_out/<tag>-<seed>.out
# and .err (under $CHIPRUN_OUT where that is set: a copy of the tree
# unpacked elsewhere writes where the chip tool collects); what a reader of the call needs (set-up, the window's seconds,
# the numbers compared, the result line) is printed here.  The call's
# arguments are in chiprun_out/chip_calls.jsonl, so a set of readings can
# be followed back to its call (PERF.md names the call numbers).
cd "$(dirname "$0")/../.." || exit 2
tag=$1 cell=$2 seconds=$3 trace=$4
shift 4
seeds=()
while [ $# -gt 0 ] && [ "$1" != "--" ]; do seeds+=("$1"); shift; done
[ "$1" = "--" ] && shift
outdir=${CHIPRUN_OUT:-chiprun_out}
mkdir -p "$outdir"
rc=0
for seed in "${seeds[@]}"; do
  out=$outdir/$tag-$seed
  t0=$(date +%s)
  python3 benchmark/run.py --workload "$cell" --seed "$seed" \
    --seconds "$seconds" --trace "$trace" "$@" >"$out.out" 2>"$out.err"
  code=$?
  [ $code -ne 0 ] && rc=$code
  echo "== $tag seed $seed seconds $seconds trace $trace $*: exit $code, $(( $(date +%s) - t0 )) s wall"
  grep -E "shape walk|warm-up|set-up:|records at the sink in each second|cores busy|XLA compiles inside|declines:|comparison:|memory:|generator:|run:" "$out.out" | cut -c1-700
  tail -n 1 "$out.err" | cut -c1-400
  tail -n 1 "$out.out" | cut -c1-3000
done
du -sh .jax_cache benchmark/work 2>/dev/null
exit $rc
