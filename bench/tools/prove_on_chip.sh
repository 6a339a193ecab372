#!/bin/bash
# What the benchmark's cells need measured on one chip, in one call of
# under an hour:
#   bash bench/tools/prove_on_chip.sh OUTDIR
# For each cell, the most needed first:
# 1. (open-loop cells) the rate sweep, and the rate set to 0.8 x the knee;
# 2. a traced run, its events dumped (kernel names, fixtures);
# 3. two sets of six runs (same seeds) and two more traced runs;
# 4. the readings of 3 more sound seeds, 3 control seeds (bf16) and 3
#    seeds of a refresh that keeps its basis (refresh_iters 0), in one
#    process.
# With the runs of 2 and 3 that makes a dozen sound seeds.  Each run's
# result line is appended to OUTDIR/<cell>/runs.jsonl.  Every command has a
# time limit, and a failed one does not stop the rest.
O=${1:?usage: prove_on_chip.sh OUTDIR}
CELLS=${CELLS:-"wsn1m-region.saturate intel-lab-fleet.open"}
export PYTHONUNBUFFERED=1
stamp() { echo "[$(date +%H:%M:%S)] $*"; }

run() {  # run CELL TAG SEED TRACE [ARGS...]
  local c=$1 tag=$2 seed=$3 tr=$4 t0 t1 rc line
  shift 4
  t0=$(date +%s%N)
  timeout 1300 python3 bench/run.py --workload $c --seed $seed --seconds 10 \
    --trace $tr "$@" > "$O/$c/${tag}_$seed.out" 2> "$O/$c/${tag}_$seed.err"
  rc=$?; t1=$(date +%s%N)
  line=$(tail -1 "$O/$c/${tag}_$seed.out" | grep '^{' || echo null)
  echo "{\"tag\": \"$tag\", \"seed\": $seed, \"rc\": $rc, \"wall_ms\": $(( (t1 - t0) / 1000000 )), \"line\": $line}" >> "$O/$c/runs.jsonl"
  [ $rc = 0 ] || tail -5 "$O/$c/${tag}_$seed.err" | cut -c1-400
}

for cell in $CELLS; do
  mkdir -p "$O/$cell"
  if [ "$cell" = intel-lab-fleet.open ]; then
    timeout 900 python3 bench/tools/sweep.py --workload $cell \
      --rates ${RATES:-500,1000,2000,4000,8000,16000} --seconds 8 --drain 10 \
      --out "$O/$cell/sweep.jsonl" > "$O/$cell/sweep.out" 2> "$O/$cell/sweep.err"
    stamp "sweep rc=$?"; cut -c1-700 "$O/$cell/sweep.jsonl"
    tail -5 "$O/$cell/sweep.err" | cut -c1-400
    python3 bench/tools/pick_rate.py "$O/$cell/sweep.jsonl" $cell
    cp bench/workloads/$cell.json "$O/$cell/"
  fi
  run $cell traced 4101 1 --dump-trace "$O/$cell/trace.json"
  stamp "$cell first traced run"; tail -4 "$O/$cell/traced_4101.out" | cut -c1-3000
  for set in 1 2; do for seed in 4001 4002 4003 4004 4005 4006; do
    run $cell set$set $seed 0; done; done
  for seed in 4102 4103; do run $cell traced $seed 1; done
  stamp "$cell sets done"; cut -c1-500 "$O/$cell/runs.jsonl"
  timeout 1200 python3 bench/tools/readings.py --workload $cell \
    --seeds 2001,2002,2003 --control-seeds 2101,2102,2103 \
    --fault-seeds 2201,2202,2203 --seconds 10 \
    --out "$O/$cell/readings.jsonl" > "$O/$cell/readings.out" \
    2> "$O/$cell/readings.err"
  stamp "$cell readings rc=$?"; cut -c1-600 "$O/$cell/readings.jsonl"
  tail -5 "$O/$cell/readings.err" | cut -c1-300
done
