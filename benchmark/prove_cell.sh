#!/bin/sh
# One chip call that proves a cell: a cold run (compiles), N warm runs
# with N seeds, then (unless N_TRACED=0) a traced run whose .xplane.pb is
# kept.  Everything a run prints goes to chiprun_out/<cell>/; the result
# lines and the set-up marks are echoed.
#
#   chiprun --timeout 3000 -- sh benchmark/prove_cell.sh <cell> <seconds> <n_warm> [first_seed]
#
# COLD=0 skips the cold run (a second set in the same call as the first).
set -u
cell=$1; seconds=$2; n_warm=$3; seed=${4:-3000000019}
out=chiprun_out/$cell
mkdir -p "$out"
one() {   # label seed trace
    log=$out/$1.log
    python -m benchmark.run --workload "$cell" --seed "$2" \
        --seconds "$seconds" --trace "$3" --out "$out/$1" >"$log" 2>"$out/$1.err"
    rc=$?
    echo "== $1 seed=$2 trace=$3 rc=$rc"
    grep -E "^(setup:|window:|check:|  \[|trace|serve:|sweep)" "$log" | cut -c1-400
    tail -n 1 "$log" | cut -c1-3000
    [ $rc -ne 0 ] && tail -n 30 "$out/$1.err" | cut -c1-600
    return 0
}
[ "${COLD:-1}" = 1 ] && one cold "$seed" 0
i=1
while [ "$i" -le "$n_warm" ]; do
    one "warm_${SET:-a}$i" $((seed + i)) 0
    i=$((i + 1))
done
[ "${N_TRACED:-1}" = 1 ] && one traced $((seed + 100)) 1
find "$out" -name "*.xplane.pb" -size +40M -delete   # too large to come back
du -sh "$out" | cut -c1-200
