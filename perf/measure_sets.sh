# The measurement the bounds are set from (run on the chip, from the root):
#   bash perf/measure_sets.sh <workload> <run_seconds> <tag> [seeds=6]
# two sets of runs of one cell, the same seeds in both sets (six unless
# the chip budget forces fewer, at least three), each
# run's log under $OUT (chiprun_out/ unless set: a run from an unpacked
# `git archive` inside the repo sets OUT=../chiprun_out) and its result
# line echoed.
w=$1; sec=$2; tag=$3; n=${4:-6}; out=${OUT:-chiprun_out}
mkdir -p $out
for set in 1 2; do for seed in $(echo 2147483659 2000000011 1000003 1500000001 700000001 123456789 | cut -d' ' -f1-$n); do
  s=$(date +%s)
  python3 perf/run.py --workload $w --seed $seed --seconds $sec --trace 0 > $out/${tag}_${w}_s${set}_${seed}.log 2>&1
  echo "set=$set seed=$seed rc=$? wall=$(( $(date +%s) - s ))s $(grep '^{' $out/${tag}_${w}_s${set}_${seed}.log | tail -1)"
  grep "ttft median\|steps in" $out/${tag}_${w}_s${set}_${seed}.log | cut -c1-400
done; done
