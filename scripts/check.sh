#!/usr/bin/env bash
# Single-command correctness gate: ruff -> mypy -> project analysis ->
# tier-1 tests. Each tool-based stage degrades to a notice when the tool
# is not installed (the CI container bakes neither ruff nor mypy); the
# project analyzer and the test suite always run and always gate.
set -euo pipefail
cd "$(dirname "$0")/.."

fail=0

echo "== ruff =="
if command -v ruff >/dev/null 2>&1; then
    ruff check oncilla_tpu tests || fail=1
else
    echo "check.sh: ruff not installed - skipping (pip install ruff)"
fi

echo "== mypy (runtime package) =="
if command -v mypy >/dev/null 2>&1; then
    mypy oncilla_tpu/runtime || fail=1
else
    echo "check.sh: mypy not installed - skipping (pip install mypy)"
fi

echo "== project analysis =="
# Both families (concurrency lint + handle-lifecycle dataflow) gate here;
# surface the per-family counts so CI logs show which one tripped.
alog=$(mktemp)
if python -m oncilla_tpu.analysis | tee "$alog"; then
    :
else
    fail=1
fi
summary=$(grep -E '^analysis: ' "$alog" | tail -1 || true)
echo "check.sh: findings by family: ${summary#analysis: }"
rm -f "$alog"

echo "== wire conformance + async safety =="
# The cross-language conformance family (Python vs native wire surface,
# fencing, strip order, audit<->journal cross-reference, and the
# capability-matrix drift check against docs/ARCHITECTURE.md) plus the
# asyncio lint, run in isolation so CI logs pin which family tripped.
# Drift fix: `python -m oncilla_tpu.analysis --write-matrix`.
python -m oncilla_tpu.analysis --families conformance,asyncsafety || fail=1

echo "== rpc wait-graph =="
# Distributed wait-graph family (analysis/rpcgraph.py): every daemon
# handler's outbound RPCs fused with the resources held at each call
# site — relay cycles, pool stratification (native OCM_NATIVE_WORKERS
# pool included), locks held across peer dials, unbounded waits on
# budgeted paths, and the RPC-topology appendix drift check against
# docs/ARCHITECTURE.md (fix: --write-topology). The live tree must
# scan clean AND the analyzer must still catch the seeded relay-cycle
# fixture — a silent no-op analyzer fails the second leg.
python -m oncilla_tpu.analysis --families rpcgraph || fail=1
if python -m oncilla_tpu.analysis --families rpcgraph --no-baseline \
        tests/fixtures/analysis/seeded_rpc_relay_cycle.py >/dev/null; then
    echo "check.sh: rpc wait-graph analyzer missed the seeded relay cycle"
    fail=1
else
    echo "check.sh: seeded relay-cycle fixture caught - OK"
fi

echo "== obs smoke =="
# End-to-end observability proof: a put/get over an in-process cluster
# under OCM_EVENTS=1, exported to a merged Perfetto/Chrome trace, which
# must parse as JSON and contain >= 1 cross-track (client->daemon) flow.
JAX_PLATFORMS=cpu python -m oncilla_tpu.obs --smoke || fail=1

echo "== dcn smoke =="
# Loopback DCN data-plane smoke: tiny striped + single-stream put/get
# roundtrips through an in-process 2-daemon cluster, byte-exactness
# asserted; runs in seconds and needs no chip.
JAX_PLATFORMS=cpu python -m oncilla_tpu.benchmarks.dcn --smoke || fail=1

echo "== native dcn smoke =="
# Python-client-vs-NATIVE-daemon byte-exactness: an unmodified Python
# client runs a 4-stripe coalesced 256 MiB put/get against a live C++
# daemon pair — the daemon must grant FLAG_CAP_COALESCE and serve it
# byte-exactly. Skips cleanly (with the real build error) when the
# container has neither cmake nor a C++ compiler.
JAX_PLATFORMS=cpu python -m oncilla_tpu.benchmarks.dcn --smoke --daemon native || fail=1

echo "== fabric smoke =="
# One-sided fabric proof: shm put/get roundtrip on a 2-daemon local
# cluster — must actually ride shm (transfer-ring fabric tag), come back
# byte-exact, drain the alloctrace ledger, and leave /dev/shm clean.
JAX_PLATFORMS=cpu python -m oncilla_tpu.fabric --smoke || fail=1

echo "== mux smoke =="
# Async multiplexed client runtime (runtime/mux.py): the paired
# lockstep-vs-mux sweep at smoke scale over live daemon processes —
# byte-exactness asserted via readback + verified large cells, and the
# fd budget pinned (the whole tenant fleet holds <= live peers + 1
# sockets) — followed by the multi-tenant QoS soak riding mux end to
# end (tenant fleet over one connection per daemon, quota/pressure/
# chaos phases unchanged, footprint + p99 histograms asserted).
JAX_PLATFORMS=cpu python -m oncilla_tpu.benchmarks.dcn --smoke --mux || fail=1
JAX_PLATFORMS=cpu python -m oncilla_tpu.qos --soak --smoke --mux || fail=1

echo "== qos smoke =="
# Multi-tenant QoS proof: simulated tenants with skewed sizes/priorities
# against an in-process cluster — quota enforcement, back-pressure BUSY,
# low-priority eviction under pressure (never an active higher class),
# a chaos daemon kill mid-soak, and a drained alloctrace ledger.
JAX_PLATFORMS=cpu python -m oncilla_tpu.qos --soak --smoke || fail=1

echo "== elastic smoke =="
# Elastic membership proof, seeded so the chaos interleavings replay
# identically in CI: kill-owner-mid-migration (never forks a chain),
# joiner partitioned mid-JOIN (converges, no half-member), and a full
# join -> rebalance -> leave cycle with byte-exact gets and a drained
# alloctrace ledger on every rank.
JAX_PLATFORMS=cpu python -m oncilla_tpu.elastic --smoke || fail=1

echo "== chaos smoke =="
# Kill-the-owner failover proof: OCM_REPLICAS=2 on a 3-daemon in-process
# cluster, seeded chaos kills the owner mid-workload; every subsequent
# get must be byte-exact via the promoted replica, re-replication must
# restore k, and the same seed must replay the identical interleaving.
JAX_PLATFORMS=cpu python -m oncilla_tpu.resilience --smoke || fail=1

echo "== leader chaos smoke =="
# Decentralized control plane proof: kill the LEADER mid-alloc-storm
# (consistent-hash placement, zero leader round trips pinned), a
# split-brain partition (the fenced old leader must answer STALE_EPOCH,
# never coordinate), and a leader+owner double kill — each run twice
# with identical seeded interleavings, wrapped in the flight-recorder
# audit including the leader-unique and placement-agreement invariants.
JAX_PLATFORMS=cpu python -m oncilla_tpu.resilience --leader-smoke || fail=1

echo "== deadline chaos smoke =="
# Time-bounded data plane proof (resilience/timebudget.py): under a
# seeded delay/partition schedule every budgeted op resolves — success
# or typed DEADLINE_EXCEEDED, nothing reserved for expired work —
# within 1.5x its budget; hedged replica reads stay byte-exact through
# an owner kill; the per-peer breaker opens on a sick-but-not-DEAD rank
# and half-open recovers after the heal; an AsyncOcm cancel storm is
# revoked server-side with every registry drained. Twice, identical
# interleavings, audited with the no-ack-after-cancel-ack invariant.
JAX_PLATFORMS=cpu python -m oncilla_tpu.resilience --deadline-smoke || fail=1
# Paired hedged-vs-unhedged replicated-read cells with one slow primary
# chain member: strictly lower hedged p99 at equal byte-exactness.
JAX_PLATFORMS=cpu python -m oncilla_tpu.benchmarks.dcn --hedge --smoke || fail=1

echo "== persist smoke =="
# FROZEN tier (persist/): FrozenStore CRC round-trip + corrupt-entry
# typed refusal (quarantined WHOLE, reported lost), then the full
# demote -> chaos restart -> warm-boot -> promote loop on a live
# daemon: acked PRIO_LOW writes spill to disk under arena pressure,
# a hard kill + same-address relaunch re-adopts every surviving
# extent, the same handles read byte-exact from the fresh
# incarnation, and frees drain the frozen dir, the registry, and the
# alloctrace ledger. Two runs with identical seeded interleavings,
# each wrapped in the flight-recorder invariant audit. CPU-only.
JAX_PLATFORMS=cpu python -m oncilla_tpu.persist --smoke || fail=1

echo "== serving smoke =="
# Flagship serving workload (serving/): paired shared-vs-noshare decode
# cells over a 3-daemon cluster (outputs must be byte-identical, sharing
# must show prefix hits + a CoW adoption + strictly fewer remote bytes,
# and its fused steps must have seated more than one session), the
# AsyncOcm prefetch leg under OCM_MUX, the chaos leg — kill the
# cold-page owner mid-decode with OCM_REPLICAS=2, decode byte-exact
# through failover, twice with identical fault schedules, wrapped in the
# flight-recorder invariant audit — and the warm-boot leg; alloctrace
# ledger drained on every surviving rank. CPU-only.
JAX_PLATFORMS=cpu python -m oncilla_tpu.serving --smoke || fail=1

echo "== obs audit smoke =="
# Flight recorder + cross-rank invariant auditor, end to end through
# the CLI: re-run the kill-owner chaos scenario with OCM_FLIGHTREC
# armed so every rank's journal (the killed owner's included) spills to
# CRC-framed segments, then audit the on-disk timelines cluster-wide —
# epoch monotonicity, migration pairing, fan-out-before-ack, lease
# termination — asserting zero findings. A failure keeps the black box.
frdir=$(mktemp -d)
if JAX_PLATFORMS=cpu OCM_FLIGHTREC="$frdir" \
        python -m oncilla_tpu.resilience --smoke >/dev/null \
    && JAX_PLATFORMS=cpu python -m oncilla_tpu.obs audit "$frdir"; then
    rm -rf "$frdir"
else
    echo "check.sh: obs audit smoke failed (black box kept at $frdir)"
    fail=1
fi

echo "== obs slo + critpath =="
# The evaluation layer, end to end: (1) chaos smoke under OCM_EVENTS=1
# with the flight recorder armed, then critical-path attribution over
# the capture — the gate demands >=1 cross-rank op tree with >=95% of
# its wall time attributed to NAMED phases (client queue, daemon queue,
# replica fan-out, handler self time); (2) the SLO selftest — a healthy
# in-process run must evaluate green with active objectives and a
# validating ocm_slo_* exposition, and a planted slow handler
# (handler_delay_s) must trip the multi-window burn-rate alert.
cpdir=$(mktemp -d)
if JAX_PLATFORMS=cpu OCM_EVENTS=1 OCM_FLIGHTREC="$cpdir" \
        python -m oncilla_tpu.resilience --smoke >/dev/null \
    && JAX_PLATFORMS=cpu python -m oncilla_tpu.obs critpath "$cpdir"/* \
        --min-attrib 0.95 --require-cross-rank \
    && JAX_PLATFORMS=cpu python -m oncilla_tpu.obs slo --selftest; then
    rm -rf "$cpdir"
else
    echo "check.sh: obs slo/critpath stage failed (capture kept at $cpdir)"
    fail=1
fi

echo "== native obs smoke =="
# The native daemon's black box, end to end: the native dcn smoke runs
# with OCM_FLIGHTREC armed (the C++ daemons stream CRC-framed segments
# in the Python reader's exact format), the auditor merges them with the
# client's and must report ZERO findings; a deliberately corrupted copy
# must flip the exit nonzero; and one native STATUS_PROM scrape must
# pass the Prometheus text-format validator. Skips cleanly with the dcn
# stage's own toolchain probe.
nfrdir=$(mktemp -d)
if ! JAX_PLATFORMS=cpu OCM_FLIGHTREC="$nfrdir" \
        python -m oncilla_tpu.benchmarks.dcn --smoke --daemon native \
            --nbytes $((32 << 20)) >/dev/null; then
    echo "check.sh: native obs smoke failed (dcn leg; black box at $nfrdir)"
    fail=1
elif [ -z "$(find "$nfrdir" -name '*.seg' -print -quit)" ]; then
    # The dcn stage skipped (no native toolchain): nothing spilled.
    echo "check.sh: native obs smoke skipped (no segments - toolchain absent)"
    rm -rf "$nfrdir"
elif JAX_PLATFORMS=cpu python -m oncilla_tpu.obs audit "$nfrdir" \
    && JAX_PLATFORMS=cpu python - "$nfrdir" <<'EOF'
import subprocess, sys, os, shutil
d = sys.argv[1]
# Nonzero-exit path: a corrupted segment copy must be CAUGHT.
bad = d + "-bad"
shutil.copytree(d, bad)
segs = [f for f in os.listdir(bad) if f.endswith(".seg")]
seg = max(segs, key=lambda f: os.path.getsize(os.path.join(bad, f)))
with open(os.path.join(bad, seg), "r+b") as fh:
    fh.seek(-3, 2)
    fh.write(b"\xff\xff\xff")
rc = subprocess.run(
    [sys.executable, "-m", "oncilla_tpu.obs", "audit", bad],
    capture_output=True,
).returncode
shutil.rmtree(bad)
assert rc != 0, "auditor missed a corrupted native segment"
print("native obs smoke: corrupt-segment path exits nonzero - OK")
EOF
then
    rm -rf "$nfrdir"
else
    echo "check.sh: native obs smoke failed (black box kept at $nfrdir)"
    fail=1
fi

echo "== native prom scrape =="
# One STATUS_PROM scrape from a live native daemon through the library
# format validator (oncilla_tpu.obs.prom.validate).
JAX_PLATFORMS=cpu python - <<'EOF' || fail=1
import socket, time, tempfile, sys
from oncilla_tpu.runtime.native import native
from oncilla_tpu.runtime import protocol as P
from oncilla_tpu.obs import prom

try:
    native.build()
except Exception as e:  # toolchain absent: same clean skip as the dcn stage
    print(f"native prom scrape: skipped ({e})")
    sys.exit(0)
s = socket.socket(); s.bind(("127.0.0.1", 0))
port = s.getsockname()[1]; s.close()
nf = tempfile.NamedTemporaryFile("w", suffix=".nodes", delete=False)
nf.write(f"0 127.0.0.1 {port}\n"); nf.close()
proc = native.spawn(nf.name, 0, host_arena_bytes=8 << 20)
try:
    deadline = time.time() + 15
    while time.time() < deadline:
        try:
            c = socket.create_connection(("127.0.0.1", port), timeout=0.5)
            break
        except OSError:
            time.sleep(0.05)
    else:
        raise AssertionError("native daemon did not come up")
    try:
        r = P.request(c, P.Message(P.MsgType.STATUS_PROM, {}))
    finally:
        c.close()
    fams = prom.validate(bytes(r.data).decode())
    assert "ocm_nnodes" in fams and "ocm_live_allocs" in fams
    print(f"native prom scrape: {len(fams)} families validate - OK")
finally:
    proc.terminate(); proc.wait(timeout=10)
EOF

echo "== tier-1 tests =="
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider || fail=1

if [ "$fail" -ne 0 ]; then
    echo "check.sh: FAILED"
    exit 1
fi
echo "check.sh: all gates clean"
