# usage: archive_run.sh <tag> <cell> <seed> <seconds>   (from the repo's root)
# Runs one cell from _archive/, the copy of the files git would commit
# (git add -A; rm -rf _archive; mkdir _archive;
#  git archive $(git write-tree) | tar -x -C _archive): proof that the
# committed files are enough, with the compile cache inside that copy.
tag=$1; cell=$2; seed=$3; secs=$4
out=$PWD/chiprun_out/$tag; mkdir -p $out
cd _archive || exit 1
python3 benchmarks/run.py --workload $cell --seed $seed --seconds $secs --trace 0 > $out/archive.$cell.$seed.out 2> $out/archive.$cell.$seed.err
echo "rc=$? archive $cell $seed $(tail -n 1 $out/archive.$cell.$seed.out | cut -c1-330)"
