#!/bin/bash
# usage: set.sh <outdir> <workload> <trace> <seed>...
out=$1; wl=$2; tr=$3; shift 3
mkdir -p chiprun_out/$out
for seed in "$@"; do
  python3 benchmarks/run.py --workload $wl --seed $seed --seconds 51 --trace $tr --out chiprun_out/$out 2>> chiprun_out/$out/err.log | tail -1 | tee -a chiprun_out/$out/lines.jsonl | cut -c1-600
  echo "RC=$? seed=$seed"
done
