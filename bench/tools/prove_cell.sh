#!/bin/bash
# Calibrate one cell on the chip and prove it, in one process tree:
#   bash bench/tools/prove_cell.sh <cell> <seed base> <out dir> [phases]
# 1. a short run (the cold compile; stops here if it fails);
# 2. the drain time at moderate load -> the forget rate (choose_rates.py);
# 3. a knee sweep of generate rates at that forget rate -> the generate
#    rate (4/5 of the knee), at the cell's own pool width, for 51 s runs;
# 4. the chosen rates once more, then the workload file rewritten in place
#    (a copy goes to the out dir);
# 5. the proof, by phase: T three traced runs (the first's trace cut into
#    the test fixture), A/B two sets of six runs on the same seeds (a/b:
#    three), C three control runs, X three more sound seeds.
# Summarise the out dir with spread.py.  Run from the repository's root.
W=$1; b=$2; O=$3; PH=${4:-"T A B C X"}
mkdir -p $O
T=bench/tools
timeout 1000 python3 bench/run.py --workload $W --seed $((b+1)) --seconds 5 --trace 0 > $O/smoke.out 2> $O/smoke.err; rc=$?
echo "smoke rc=$rc $(tail -1 $O/smoke.out | cut -c1-1500)"; grep "^\[bench\]" $O/smoke.err | cut -c1-600
if [ $rc != 0 ]; then tail -60 $O/smoke.err | cut -c1-400; exit 1; fi
timeout 400 python3 bench/calibrate.py --workload $W --seed $((b+2)) --grace 15 --phase 3:1:12 > $O/cal1.out 2> $O/cal1.err
cat $O/cal1.out
F=$(python3 $T/choose_rates.py drain $O/cal1.out 2>>$O/choose.log); cat $O/choose.log
[ -z "$F" ] && { tail -30 $O/cal1.err; exit 1; }
timeout 900 python3 bench/calibrate.py --workload $W --seed $((b+3)) --grace 15 --phase 3:$F:10 --phase 5:$F:10 --phase 7:$F:10 --phase 9:$F:10 --phase 12:$F:10 --phase 16:$F:10 > $O/cal2.out 2> $O/cal2.err
cat $O/cal2.out
read G POOL SECS < <(python3 $T/choose_rates.py knee $O/cal2.out $F $W 2>>$O/choose.log); tail -8 $O/choose.log
[ -z "$SECS" ] && exit 1
timeout 400 python3 bench/calibrate.py --workload $W --seed $((b+4)) --grace 15 --set pool_width=$POOL --phase $G:$F:$SECS > $O/cal3.out 2> $O/cal3.err
cat $O/cal3.out
python3 $T/choose_rates.py write $W $G $F $POOL $O
echo "chosen: generate $G forget $F pool $POOL seconds $SECS"
run() { # name seed trace control [keep]
  extra=""; [ -n "$5" ] && extra="--keep-trace $5"
  timeout 700 python3 bench/run.py --workload $W --seed $2 --seconds $SECS --trace $3 --control $4 $extra > $O/$1.out 2> $O/$1.err
  echo "$1 seed=$2 rc=$? $(tail -1 $O/$1.out | cut -c1-1200)"
  grep -E "^\[bench\] (set-up|window|samples|reference|MISSING)" $O/$1.err | cut -c1-600
}
p0=$((b+1000))
for p in $PH; do case $p in
  T) run T_1 $((p0+101)) 1 0 $O/trace_T1
     python3 bench/tests/cut_trace.py $O/trace_T1 $O/fixture.txtpb.gz --start-ms 2000 --ms 400 && ls -la $O/fixture.txtpb.gz
     rm -rf $O/trace_T1
     for i in 2 3; do run T_$i $((p0+100+i)) 1 0; done ;;
  A|B) for i in 1 2 3 4 5 6; do run ${p}_$i $((p0+i)) 0 0; done ;;
  a|b) P=$(echo $p | tr ab AB); for i in 1 2 3; do run ${P}_$i $((p0+i)) 0 0; done ;;
  X) for i in 1 2 3; do run X_$i $((p0+200+i)) 0 0; done ;;
  C) for i in 1 2 3; do run C_$i $((p0+300+i)) 0 1; done ;;
esac; done
