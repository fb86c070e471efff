#!/bin/bash
# One set of runs of one cell, each with another seed, result lines to a file.
#   tools/run_set.sh <cell> <seconds> <trace> <out-file> <seed> [seed ...]
cell=$1; secs=$2; trace=$3; out=$4; shift 4
mkdir -p "$(dirname "$out")"; : > "$out"; : > "$out.log"
for seed in "$@"; do
  python3 benchmarks/chip/run.py --workload "$cell" --seed "$seed" --seconds "$secs" --trace "$trace" > "$out.tmp" 2>&1
  echo "seed $seed rc=$?" >> "$out.log"
  grep 'bench\]' "$out.tmp" | grep -v 'measured' >> "$out.log"
  grep -i 'error\|Traceback' "$out.tmp" | head -5 >> "$out.log"
  tail -n 1 "$out.tmp" >> "$out"
done
rm -f "$out.tmp"
