#!/bin/bash
# Developer's tool, run on the chip: the two sets of runs that a bound is
# set from (the same seeds in both sets, every run a new process), then
# traced runs. Results: chiprun_out/sets_<cell>.jsonl, one result line a run.
#   bash benchmarks/tests/sets.sh <cell> <seconds> "<seeds>" "<traced seeds>"
W=$1; S=$2; SEEDS=$3; TSEEDS=$4
mkdir -p chiprun_out
OUT=chiprun_out/sets_$W.jsonl
: > $OUT
for set in 1 2; do
  for seed in $SEEDS; do
    t0=$(date +%s%N)
    python3 benchmarks/run.py --workload $W --seed $seed --seconds $S --trace 0 > chiprun_out/_run.out 2> chiprun_out/_run.err
    rc=$?
    t1=$(date +%s%N)
    echo "{\"set\": $set, \"seed\": $seed, \"rc\": $rc, \"wall_s\": $(( (t1 - t0) / 1000000 ))e-3, \"line\": $(tail -n 1 chiprun_out/_run.out)}" >> $OUT
    [ $rc -ne 0 ] && tail -c 1500 chiprun_out/_run.err
  done
done
for seed in $TSEEDS; do
  t0=$(date +%s%N)
  python3 benchmarks/run.py --workload $W --seed $seed --seconds $S --trace 1 > chiprun_out/_run.out 2> chiprun_out/_run.err
  rc=$?
  t1=$(date +%s%N)
  echo "{\"set\": \"traced\", \"seed\": $seed, \"rc\": $rc, \"wall_s\": $(( (t1 - t0) / 1000000 ))e-3, \"line\": $(tail -n 1 chiprun_out/_run.out)}" >> $OUT
  [ $rc -ne 0 ] && tail -c 1500 chiprun_out/_run.err
done
cut -c1-600 $OUT
