#!/bin/bash
# Two sets of N runs (same seeds in both sets; N = 6 unless given) of a
# workload, then T traced runs (3 unless given); one JSON line per run in
# $OUT/sets.<workload>.jsonl. Stops at a run that prints no result. Run by hand
# through the chip tool, from the checkout the runs are to be of:
#   [OUT=../chiprun_out] bash benchmark/tools/run_sets.sh <workload> [N [T]]
W=$1; N=${2:-6}; T=${3:-3}
OUT=${OUT:-chiprun_out}
mkdir -p $OUT
SEEDS=(7101 2147490102 7103 3000007104 7105 7106)
TRACED=(8201 2147491202 8203)
one() {  # set seed trace
  local t0=$(date +%s%N)
  python3 benchmark/run.py --workload $W --seed $2 --seconds 40 --trace $3 2>$OUT/err.txt | tail -1 > $OUT/line.txt
  grep -E "set-up:|warm-up batch|reference check|compile cache|window:|Error|error" $OUT/err.txt | tr '\n' ';'
  echo "wall $(( ($(date +%s%N) - t0) / 1000000 )) ms"
  grep -q '"correct"' $OUT/line.txt || { echo "no result: stopping"; tail -5 $OUT/err.txt; exit 1; }
  sed "s/^/{\"set\": $1, \"seed\": $2, \"line\": /; s/$/}/" $OUT/line.txt >> $OUT/sets.$W.jsonl
}
for SEED in "${TRACED[@]:0:$T}"; do one 0 $SEED 1; done
for SET in 1 2; do
  for SEED in "${SEEDS[@]:0:$N}"; do one $SET $SEED 0; done
done
