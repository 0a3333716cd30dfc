#!/bin/bash
# Several runs of one cell in one chip call:
#   chip_runs.sh <tag> <workload> <seconds> <seed:trace> ...
# from the root of the repo (or of a copy of it); logs to chiprun_out/<tag>.log.
# With CHIP_RUNS_BUDGET_S set, no run is started after that many seconds
# (less RUN_ALLOW_S, default 180, for the run itself): the call ends in time.
tag=$1; wl=$2; secs=$3; shift 3
mkdir -p chiprun_out
start=$SECONDS
for st in $@; do
  seed=${st%%:*}; tr=${st##*:}
  if [ -n "$CHIP_RUNS_BUDGET_S" ] && [ $((SECONDS - start + ${RUN_ALLOW_S:-180})) -gt $CHIP_RUNS_BUDGET_S ]; then
    echo "=== SKIPPED $wl seed=$seed: out of time after $((SECONDS - start)) s" | tee -a chiprun_out/$tag.log
    continue
  fi
  echo "=== RUN $wl seed=$seed trace=$tr $(date +%T)" | tee -a chiprun_out/$tag.log
  python3 benchmark/run.py --workload $wl --seed $seed --seconds $secs --trace $tr > chiprun_out/$tag.out 2> chiprun_out/$tag.err
  rc=$?
  echo "rc=$rc" | tee -a chiprun_out/$tag.log
  grep "^\[bench" chiprun_out/$tag.err | tee -a chiprun_out/$tag.log
  [ $rc -ne 0 ] && tail -40 chiprun_out/$tag.err | tee -a chiprun_out/$tag.log
  cat chiprun_out/$tag.out | tee -a chiprun_out/$tag.log
  # a run that failed is not repeated with other seeds on chip time
  [ $rc -ne 0 ] && exit $rc
done
exit 0
