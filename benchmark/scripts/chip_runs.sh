#!/bin/bash
# Runs of one cell on the chip, one after the other, each a process of its
# own, from a checkout made with `git archive` (the files git would commit
# and nothing else). What PERF.md's sets and trial runs were made with:
#
#   git add -A && mkdir -p _checkout && git archive $(git write-tree) | tar -x -C _checkout
#   chiprun --chips 1 --timeout 3000 -- bash benchmark/scripts/chip_runs.sh \
#       q4A nexmark-q4.saturated 0 44 1002 1004 2147483711 3000000005
#   python3 benchmark/scripts/spreads.py chiprun_out q4A q4B
#
# usage: [CHECKOUT=<dir>] chip_runs.sh <label> <workload> <trace 0|1> <seconds> <seed>...
# CHECKOUT names another checkout to run from (the parent's, `git archive
# HEAD` unpacked into _parent: an older benchmark beside this one).
# A label that ends in "ctl" adds `--control 1` (prints what the controls
# read; the runs of a set never have it). Output of each run goes to
# chiprun_out/<label>_s<seed>.out and .err; the result line is echoed.
set -u
label=$1; workload=$2; trace=$3; seconds=$4; shift 4
out=$PWD/chiprun_out
mkdir -p "$out"
dir=${CHECKOUT:-_checkout}
[ -d "$dir/benchmark" ] || dir=.
extra=""
case $label in *ctl) extra="--control 1";; esac
for seed in "$@"; do
  t0=$SECONDS
  (cd "$dir" && python3 benchmark/run.py --workload "$workload" --seed "$seed" \
      --seconds "$seconds" --trace "$trace" $extra) \
      > "$out/${label}_s${seed}.out" 2> "$out/${label}_s${seed}.err"
  echo "== $label $workload seed=$seed trace=$trace rc=$? secs=$((SECONDS - t0)) dir=$dir"
  grep -h '"phase": "control"' "$out/${label}_s${seed}.out"
  tail -n 1 "$out/${label}_s${seed}.out" | cut -c1-1500
done
