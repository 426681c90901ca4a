#!/usr/bin/env bash
# CI entry point: byte-compile the whole package (catches syntax/import-time
# breakage in files no test imports), then run the tier-1 test command from
# ROADMAP.md verbatim. Exits non-zero on either failure.
set -uo pipefail
cd "$(dirname "$0")/.."

python -m compileall -q sparknet_tpu || exit 1
echo "compileall OK"

# static analysis: JAX hazard rules + lock-discipline checker, strict
# mode (any non-baselined finding fails the build — scripts/lint.sh)
bash scripts/lint.sh || exit 1
echo "sparknet lint OK"

# multi-host fault domains, end to end: a real 2-process run where one
# host is SIGKILLed mid-run — the survivor must evict it on lease
# expiry, finish, and exit 0 (the fast stage of scripts/smoke.sh)
bash scripts/smoke.sh multihost || exit 1
echo "multihost smoke OK"

# async bounded staleness, end to end: the chaos slow-worker run must
# finish under a wall-clock budget the synchronous barrier cannot meet,
# with the straggler parked+readmitted and the staleness section in
# `sparknet report` (scripts/smoke.sh stage h)
bash scripts/smoke.sh async || exit 1
echo "async smoke OK"

# elastic world resizing, end to end: a 2-process world's checkpoint
# resumes at N-1 and N+1 under --reshard auto (strict refusal names
# the remedy), a preempted host rejoins through the rendezvous, and a
# live run admits a late-started --grow host with zero recompiles
bash scripts/smoke.sh resize || exit 1
echo "resize smoke OK"

# serving tier, end to end: serve a snapshot, bench it across a live
# hot reload with zero rejects/errors, drain on SIGTERM with exit 0,
# and render the serving section (scripts/smoke.sh stage i)
bash scripts/smoke.sh serve || exit 1
echo "serve smoke OK"

# serving fleet, end to end: a real 3-replica fleet behind `sparknet
# route` — SIGKILLed replica evicted on lease expiry with the
# availability dip bounded (asserted from the metrics stream), grow
# admission under load, canary auto-rollback of a corrupt checkpoint,
# router drained on SIGTERM with exit 0 (scripts/smoke.sh stage n)
bash scripts/smoke.sh routefleet || exit 1
echo "routefleet smoke OK"

# input pipeline, end to end: a real 2-process run whose per-host
# `ingest` events stay inside each host's owned record shard, and a
# --echo 2 run beating the no-echo wall clock under chaos slow_h2d
# (scripts/smoke.sh stage j)
bash scripts/smoke.sh ingest || exit 1
echo "ingest smoke OK"

# FSDP one-big-model, end to end: a d-small LM under --fsdp on
# --precision bf16 whose metrics stream proves the sharded update
# executed (fsdp kind=exec off the live arrays), SIGTERM + resume from
# the gathered manifest, and the same checkpoint consumed by plain DP
# (scripts/smoke.sh stage k)
bash scripts/smoke.sh fsdp || exit 1
echo "fsdp smoke OK"

# fleet simulation, end to end: replay validation of a recorded real
# multi-coordinator crash run must match membership-event-exactly,
# then a 1,000-host x 200-round chaos cell under the 60 s CPU wall
# budget with report/monitor rendering (scripts/smoke.sh stage l)
bash scripts/smoke.sh simfleet || exit 1
echo "simfleet smoke OK"

# fleet observability, end to end: a real 2-process run with a chaos
# slow_host straggler merged into one clock-aligned Chrome trace, the
# critical path naming the straggler from the metrics alone, and the
# simfleet cell rendering through the same path (scripts/smoke.sh
# stage m)
bash scripts/smoke.sh trace || exit 1
echo "trace smoke OK"

set -o pipefail
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)
exit $rc
