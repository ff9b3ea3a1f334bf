# Two sets of runs of one cell, the same seeds in both, with one traced run
# between them, all in one call: what the bounds in BENCHMARK.json were set
# from (PERF.md section 2).  On the chip, from the root of a checkout:
#   chiprun --timeout 3000 -- sh benchmark/tests/full_sets.sh <cell> <outdir> <traced seed> <seed> <seed> ...
cell=$1; out=$2; traced=$3; shift 3; mkdir -p $out
seconds=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
one() {  # <label> <seed> <trace>
python3 benchmark/run.py --workload $cell --seed $2 --seconds $seconds --trace $3 > $out/$1$2.out 2> $out/$1$2.err; echo "$1 $2 rc=$?"
tail -n 1 $out/$1$2.out | cut -c1-$4
}
for s in "$@"; do one a $s 0 330; done
one t $traced 1 2500
for s in "$@"; do one b $s 0 330; done
