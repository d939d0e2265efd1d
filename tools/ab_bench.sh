#!/bin/sh
# Parent against change on the same chip, in one chip call: runs
# benchmarks/run.py in this tree (C), in the parent commit unpacked under
# _parent/ (P: `git archive <commit> | tar -x -C _parent`) or in the files
# git would commit, unpacked under _archive/ (A: `git archive $(git
# write-tree)`), in the order given; each run's output is kept under
# chiprun_out/<tag>/.
#   chiprun -- sh tools/ab_bench.sh <tag> <P|C|A>:<cell>:<seed>:<trace>...
tag=$1; shift
out=$(pwd)/chiprun_out/$tag; mkdir -p "$out"
i=0
for spec in "$@"; do
  i=$((i+1))
  side=${spec%%:*}; rest=${spec#*:}
  cell=${rest%%:*}; rest=${rest#*:}
  seed=${rest%%:*}; trace=${rest#*:}
  case $side in P) dir=_parent;; A) dir=_archive;; *) dir=.;; esac
  name=$(printf '%02d' $i).$side.$cell.s$seed.t$trace
  (cd $dir && python3 benchmarks/run.py --workload "$cell" --seed "$seed" \
     --trace "$trace") > "$out/$name.out" 2> "$out/$name.err"
  echo "$name rc=$?"
  python3 - "$out/$name.out" <<'PY'
import json, sys
try:
    r = json.loads(open(sys.argv[1]).read().strip().splitlines()[-1])
    m = {k: round(v["value"], 3) for k, v in r["metrics"].items()}
    print("  correct", r["correct"], "failed", r["failed"], m,
          r["device"]["kind"])
except (OSError, ValueError, KeyError, IndexError) as e:
    print("  no result line:", e)
PY
done
