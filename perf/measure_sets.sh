# The measurement the bounds are set from (run on the chip, from the root):
#   bash perf/measure_sets.sh <workload> <run_seconds> <tag> [seeds=6]
# two sets of runs of one cell, the same seeds in both sets (six unless
# the chip budget forces fewer, at least three), each
# run's log under $OUT (chiprun_out/ unless set: a run from an unpacked
# `git archive` inside the repo sets OUT=../chiprun_out) and its result
# line echoed; a generate cell's per-request records ([send - open s,
# prompt, budget, ttft ms], the log's "requests as sent" line) are kept
# beside the log as <run>.ttft.json.  SEEDS and SETS in the environment
# replace the six seeds and the sets "1 2" (a set made in another call).
w=$1; sec=$2; tag=$3; n=${4:-6}; out=${OUT:-chiprun_out}
mkdir -p $out
for set in ${SETS:-1 2}; do for seed in $(echo ${SEEDS:-2147483659 2000000011 1000003 1500000001 700000001 123456789} | cut -d' ' -f1-$n); do
  s=$(date +%s); run=$out/${tag}_${w}_s${set}_${seed}
  python3 perf/run.py --workload $w --seed $seed --seconds $sec --trace 0 > $run.log 2>&1
  echo "set=$set seed=$seed rc=$? wall=$(( $(date +%s) - s ))s $(grep '^{' $run.log | tail -1)"
  grep "ttft mid\|steps in\|loss read to" $run.log | cut -c1-400
  grep -o "requests as sent .*" $run.log | sed 's/^[^:]*: //' > $run.ttft.json
  [ -s $run.ttft.json ] || rm -f $run.ttft.json
done; done
