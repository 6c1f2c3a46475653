# Runs of one cell, each a new process, as the driver makes them:
#   bash perfbench/tools/sets.sh <cell> <seconds> <label> <trace> <seed> [<seed> ...]
# One line a run goes to chiprun_out/sets/<cell>.jsonl: label, seed, wall
# seconds and the run's result line; the trainer's progress lines and the
# numbers compared go to chiprun_out/sets/<cell>.<label>.<seed>.log.
cell=$1; seconds=$2; label=$3; trace=$4; shift 4
mkdir -p chiprun_out/sets
for seed in "$@"; do
  t0=$(date +%s%N)
  python3 perfbench/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" --trace "$trace" \
      > chiprun_out/sets/last.out 2> chiprun_out/sets/last.err
  rc=$?
  t1=$(date +%s%N)
  line=$(tail -n 1 chiprun_out/sets/last.out)
  [ -z "$line" ] && line=null
  win=$(grep -a "^perfbench window: " chiprun_out/sets/last.err | tail -n 1 | sed 's/^perfbench window: //')
  [ -z "$win" ] && win=null
  rd=$(grep -a "^perfbench readings: " chiprun_out/sets/last.err | tail -n 1 | sed 's/^perfbench readings: //')
  [ -z "$rd" ] && rd=null
  echo "{\"label\": \"$label\", \"seed\": $seed, \"trace\": $trace, \"rc\": $rc, \"wall_s\": $(( (t1 - t0) / 1000000 ))e-3, \"window\": $win, \"readings\": $rd, \"result\": $line}" \
      | tee -a "chiprun_out/sets/$cell.jsonl" | cut -c 1-1500
  grep -a "round=\|compared\|perfbench" chiprun_out/sets/last.err | cut -c 1-400 > "chiprun_out/sets/$cell.$label.$seed.log"
  if [ $rc -ne 0 ]; then tail -n 30 chiprun_out/sets/last.err; fi
done
