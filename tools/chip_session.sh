#!/bin/bash
# The chip-session checklist, runnable as one command so one session
# on the chip captures every cell, in value order (`python
# chip_smoke.py` is the quick proof that the serving path runs):
#   1. full default bench  -> headline + per-config numbers
#   2. Keccak unroll lever matrix on the headline shape
#   3. Pallas fused-Keccak kernel on the headline shape (first-ever
#      hardware execution of the 12-round form)
# Each step has its own timeout; a hang or crash in one step must not
# cost the rest of the window (run() tolerates per-step failure), but
# a scaffolding failure — bad cwd, unwritable log, broken git — must
# abort loudly instead of producing a silent partial session log, so
# the script runs under -euo pipefail with an exit trap that names
# the matrix entry that was executing.
set -euo pipefail
cd "$(dirname "$0")/.."
LOG="${1:-chip_session.log}"
exec >>"$LOG" 2>&1

CURRENT="(setup)"
on_exit() {
    local rc=$?
    if [ "$rc" -ne 0 ]; then
        echo "=== chip session ABORTED (exit=$rc) at matrix entry:" \
             "$CURRENT ==="
    fi
}
trap on_exit EXIT

echo "=== chip session $(date -u +%FT%TZ) rev=$(git rev-parse --short HEAD) ==="

run() {
    local name="$1"; shift
    CURRENT="$name: $*"
    echo "--- $name: $* ---"
    local rc=0
    timeout 2400 "$@" || rc=$?
    echo "--- $name: exit=$rc ---"
}

# 1. The one number the framework exists for.
run full python bench.py

# 2. Lever matrix: unroll x pallas on the headline shape (headline-only
# keeps each cell ~minutes).  The default is unroll=1 since r5, so the
# matrix probes the non-default cells.
for unroll in 4 8; do
    run "unroll-$unroll" python bench.py --headline-only \
        --keccak-unroll "$unroll"
done
run pallas python bench.py --headline-only --keccak-pallas
run aes-pallas python bench.py --headline-only --aes-pallas

# 3b. The fused level-step megakernel (ops/level_pallas.py): the
# whole extend->correct->convert->proof pipeline in VMEM — the
# HBM-roofline lever (PERF.md §3).  It does not compile for a v5e yet
# (tests/test_tpu_compile.py), so this cell fails with the compiler's
# message until that is fixed.
run level-pallas python bench.py --headline-only --level-pallas

# 4. Pipelined chunk-streaming executor (drivers/pipeline.py): the
# chunked PRODUCTION round with MASTIC_PIPELINE on vs off, so the
# overlap + ahead-of-time-compile gain is measured in one call.  The
# JSON lines carry the per-phase timeline and overlap_efficiency.
run pipeline-on python bench.py --chunked-round-only --pipeline on
run pipeline-off python bench.py --chunked-round-only --pipeline off

# 5. Mesh-sharded production round (r10, drivers/chunked.py +
# parallel/mesh.py): the chunked pipelined round at --mesh 1 vs every
# attached chip, so one call measures multi-chip scaling (per-shard
# rate, psum bytes, shard skew).  The
# r10 bit-identity proof itself runs in CI (make multichip); these
# cells are the HARDWARE rate measurement.
run mesh-1 python bench.py --chunked-round-only --mesh 1
run mesh-all python bench.py --chunked-round-only --mesh all

# 6. Unattended collector-service soak (drivers/service.py +
# tools/serve.py): continuous admit -> epoch -> drain on the chip
# for two minutes, every epoch's hitters checked — a service that
# wedges, leaks, or degrades mid-soak fails this cell, and the JSON
# line records epochs/rounds completed plus the full counter ledger
# (scheduler-overhead numbers for PERF.md).
run serve-soak python tools/serve.py --soak 120 --bits 4 --reports 32

# 6d. Overlapped multi-tenant epoch execution on the chip (ISSUE 10):
# the round-robin-vs-overlap throughput comparison where it actually
# means something — host-side stage/collect work hiding behind real
# device dispatch.  The JSON line stamps baseline_reports_per_sec /
# overlap_reports_per_sec / speedup with bit-identity and the
# zero-steady-state-compile assertion (PERF.md §12); the soak twin
# runs the live service with the overlapped executor + ingest front
# armed for two minutes.
run serve-overlap python bench.py --service-overlap
run serve-overlap-soak python tools/serve.py --soak 120 --bits 4 \
    --reports 32 --overlap 2 --ingest-threads 2

# 6e. The network front on the chip host (ISSUE 11): the serve-load
# cell drives the DAP-shaped upload endpoint with 10^6 simulated
# clients (zipf mix, bursts, adversarial fraction) and stamps
# p50/p95/p99 admission latency + reports/s + the shed ledger — the
# first end-to-end SLO cell; parties-wan runs the network-separated
# leader/helper over the shaped-link ladder and stamps the
# communication-vs-computation crossover with chip-speed compute
# (PERF.md §13 tracks both).
run serve-load python tools/loadgen.py --clients 1000000 \
    --duration 30 --rate 600 --workers 8 --slo-p99-ms 250
run parties-wan python bench.py --parties-wan

# 6f. Survivable multi-host parties on the chip host (ISSUE 14):
# parties-tcp runs the seeded chaos campaign — standalone TCP+mTLS
# party processes (tools/party.py), reconnect-and-replay under
# injected conn_drop/partition/tls_handshake/slow_loris, bit-identity
# vs the loopback path — with the party processes on the CPU (one
# process per chip: spawned parties never touch it); chaos-soak
# widens it to eight seeds for an unattended soak of the recovery
# machinery (every run's JSON line stamps reconnects/replayed_frames).
run parties-tcp python tools/serve.py --chaos-drill 7 --chaos-seeds 3
run chaos-soak python tools/serve.py --chaos-drill 100 \
    --chaos-seeds 8

# 6g. Durable admission on the chip host (ISSUE 18): the WAL drill's
# disk-fault campaign — kill-9 at every WAL checkpoint plus eight
# seeded kill/short_write/enospc schedules — with chip-speed epoch
# compute; every resumed run stamps replayed-record counts and
# recovery wall time, and must end bit-identical with exactly the
# clean run's admissions (USAGE.md "Durability", PERF.md §14).
run wal-soak python tools/serve.py --wal-drill 100 --wal-seeds 8

# 6c. On-chip AOT bake + trace-free load cycle (ISSUE 9,
# drivers/artifacts.py): bake the cold-start family on the chip,
# then bench.py --cold-start reuses the store (MASTIC_ARTIFACT_DIR
# under the hood) and measures fresh-process time-to-first-round,
# traced vs warm — the cold_start_seconds / warm_store_seconds pair
# PERF.md §11 tracks on real silicon.
run artifacts-bake python tools/bake.py \
    --out artifacts/aot-chip --bits 8 --rows 16 --hitters 2 \
    --ctx "bench cold-start"
run artifacts-cold python bench.py --cold-start \
    --artifact-dir artifacts/aot-chip

# 6b. The live status surface on the chip (ISSUE 7): the smoke
# scenario with --status-port armed self-curls /metrics, /statusz
# and /varz mid-run and asserts the per-tenant series, so the
# observability endpoints are proven against real chip rounds (the
# chunk-phase histograms carry hardware numbers here, not CPU ones).
run serve-status python tools/serve.py --smoke --status-port 8321

echo "=== chip session complete $(date -u +%FT%TZ) ==="
