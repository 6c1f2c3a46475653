# the two full sets of one cell (same seeds in both) and two traced runs
cell=$1; t1=$2; t2=$3
S="1001 1002 1003 2147483747 2147483801 1234567891"
bash perfbench/tools/sets.sh $cell 30 setA 0 $S
bash perfbench/tools/sets.sh $cell 30 setB 0 $S
bash perfbench/tools/sets.sh $cell 30 trace 1 $t1 $t2
python3 perfbench/tools/spread.py chiprun_out/sets/$cell.jsonl
