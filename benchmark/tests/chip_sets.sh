#!/bin/bash
# How a cell's bounds and limits are measured (PR 24), as one command for
# the chip, run from the checkout's root:
#
#   bash benchmark/tests/chip_sets.sh <out dir> <cell> <seconds> sets <seed>...
#       two sets of runs with --trace 0, the same seeds in both: the first
#       by benchmark/run.py, the second by tests/chip_controls.py (the same
#       run, with the check's controls read on its record afterwards);
#   bash benchmark/tests/chip_sets.sh <out dir> <cell> <seconds> trial <seed> <seed>
#       one run with the controls on the first seed, one --trace 1 run on
#       the second.
#
# Each run's stdout goes to <out dir>/<cell>.<tag>.<seed>.out; the result
# lines are echoed.  Put <out dir> under chiprun_out/ to get it back.
out=$1; cell=$2; seconds=$3; mode=$4; shift 4
mkdir -p "$out"
run() { # tag seed program extra...
  tag=$1; seed=$2; program=$3; shift 3
  t0=$SECONDS
  python3 "$program" --workload "$cell" --seed "$seed" \
    --seconds "$seconds" "$@" > "$out/$cell.$tag.$seed.out" 2> "$out/$cell.$tag.$seed.err"
  echo "$cell $tag seed=$seed rc=$? wall=$((SECONDS-t0))s $(tail -n 1 "$out/$cell.$tag.$seed.out" | cut -c1-420)"
}
if [ "$mode" = sets ]; then
  for s in "$@"; do run set1 "$s" benchmark/run.py --trace 0; done
  for s in "$@"; do run set2 "$s" benchmark/tests/chip_controls.py --trace 0; done
else
  run seed "$1" benchmark/tests/chip_controls.py --trace 0
  run trace "$2" benchmark/run.py --trace 1
fi
