# The runs a bound is set from: bash benchmark/tools/sets.sh <cell> <seconds>
# makes two sets of six runs with the same six seeds, then three traced runs
# on other seeds; every result line goes to chiprun_out/sets_<cell>.jsonl and
# the end of each run's standard error to chiprun_out/sets_<cell>.err.
cell=$1; seconds=$2; first=${3:-3100000007}
mkdir -p chiprun_out
for set in 1 2; do
  for i in 0 1 2 3 4 5; do
    python3 benchmark/run.py --workload "$cell" --seed $((first + 104729 * i)) \
      --seconds "$seconds" --trace 0 2> .bench_scratch.err | tail -n 1 \
      | tee -a "chiprun_out/sets_$cell.jsonl" | cut -c1-420
    grep -v "^WARNING\|warnings.warn\|UserWarning" .bench_scratch.err | tail -n 9 >> "chiprun_out/sets_$cell.err"
  done
done
for i in 6 7 8; do
  python3 benchmark/run.py --workload "$cell" --seed $((first + 104729 * i)) \
    --seconds "$seconds" --trace 1 2> .bench_scratch.err | tail -n 1 \
    | tee -a "chiprun_out/traced_$cell.jsonl" | cut -c1-1500
  grep -v "^WARNING\|warnings.warn\|UserWarning" .bench_scratch.err | tail -n 9 >> "chiprun_out/sets_$cell.err"
done
rm -f .bench_scratch.err
