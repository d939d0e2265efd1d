# usage: sets.sh <cell> <seconds> <outdir> <seed...>   (two sets, same seeds)
cell=$1; secs=$2; out=$3; shift 3
mkdir -p $out
for set in A B; do for seed in "$@"; do
python benchmarks/run.py --workload $cell --seed $seed --seconds $secs --trace 0 > $out/$cell.$set.$seed.out 2> $out/$cell.$set.$seed.err
echo "rc=$? $cell $set $seed $(tail -n 1 $out/$cell.$set.$seed.out | cut -c1-330)"
done; done
