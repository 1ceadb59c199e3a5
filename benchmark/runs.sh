#!/bin/bash
# usage: runs.sh <cell> <seconds> <trace> <tag> <seed>...
cell=$1; secs=$2; trace=$3; tag=$4; shift 4
OUT=${BENCH_OUT:-chiprun_out}; mkdir -p $OUT
for seed in "$@"; do
  t0=$(date +%s)
  python3 benchmark/run.py --workload $cell --seed $seed --seconds $secs --trace $trace > $OUT/_one.out 2> $OUT/_one.err
  rc=$?
  t1=$(date +%s)
  line=$(tail -n 1 $OUT/_one.out)
  echo "{\"tag\": \"$tag\", \"cell\": \"$cell\", \"seed\": $seed, \"rc\": $rc, \"wall_s\": $((t1-t0)), \"line\": ${line:-null}}" >> $OUT/$tag.jsonl
  if [ $rc -ne 0 ]; then tail -c 2000 $OUT/_one.err; fi
done
python3 - <<PY
import json, statistics as st
rows=[json.loads(l) for l in open("$OUT/$tag.jsonl")]
vals={}
for r in rows:
    l=r["line"] or {}
    print(r["seed"], r["rc"], r["wall_s"], l.get("correct"), l.get("attempted"), l.get("failed"), {k: round(v["value"],3) for k,v in (l.get("metrics") or {}).items()}, (l.get("device") or {}).get("memory_peak_bytes"))
    for k,v in (l.get("metrics") or {}).items(): vals.setdefault(k,[]).append(v["value"])
for k,v in vals.items():
    if len(v)>=2:
        q=st.quantiles(v,n=4); med=st.median(v)
        print(k, "median", round(med,4), "iqr/median", round((q[2]-q[0])/med,5), "min", round(min(v),3), "max", round(max(v),3))
PY
