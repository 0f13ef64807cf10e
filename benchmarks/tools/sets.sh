#!/bin/bash
# Two sets of 6 runs of one cell with the same seeds in both, then short
# runs on further seeds, one traced run and the control; logs under
# chiprun_out/<cell>/ (or $OUT_ROOT/<cell>/; SKIP_TAIL=1 stops after the sets).
#   bash benchmarks/tools/sets.sh <cell> <seconds> [runs-per-set] [extra-seeds]
cell=$1; secs=$2; per=${3:-6}; extra=${4:-6}; out=${OUT_ROOT:-chiprun_out}/$cell; mkdir -p $out
run() { name=$1; shift; t0=$(date +%s); python3 benchmarks/run.py --workload $cell "$@" > $out/$name.log 2> $out/$name.err; echo "$name rc=$? wall=$(( $(date +%s) - t0 ))s"; }
setup_of() { grep -o '"setup_s": [0-9.]*' $out/$1.log | head -1 | grep -o '[0-9]*\.' | tr -d .; }
seeds=$(echo 1000003 2147483777 3000000019 77 4100200300 123456789 | cut -d' ' -f1-$per)
first=1
for set in 1 2; do for s in $seeds; do
  run set${set}_$s --seed $s --seconds $secs --trace 0
  if [ $first = 2 ] && [ -z "$NO_GUARD" ]; then a=$(setup_of set1_1000003); b=$(setup_of set1_$s); echo "setup cold=$a warm=$b"; du -sm .jax_cache
    if [ -z "$b" ] || [ $(( b * 2 )) -gt $a ]; then echo "CACHE NOT SERVING: stop"; grep -v "^W0\|^I0" $out/set1_$s.err | tail -5 | cut -c1-500; python3 benchmarks/tools/summarize.py $out; exit 1; fi; fi
  first=$(( first + 1 ))
done; done
[ "$extra" -gt 0 ] && for s in $(echo 11 2222222222 333 4040404040 55555 3999999999 | cut -d" " -f1-$extra); do run extra_$s --seed $s --seconds 5 --trace 0; done
[ -n "$SKIP_TAIL" ] && { python3 benchmarks/tools/summarize.py $out; exit 0; }
run traced --seed 987654321 --seconds $secs --trace 1
python3 benchmarks/tools/control.py $cell kfac 301 302 303 > $out/control.txt 2> $out/control.err; echo "control rc=$?"; cut -c1-700 $out/control.txt
python3 benchmarks/tools/summarize.py $out
grep -h '"phase": "memory"' $out/set1_77.log | cut -c1-600
