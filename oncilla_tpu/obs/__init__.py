"""Cluster observability: distributed tracing, event journal, exporters.

The reference's entire observability story is ``printd`` behind
``OCM_VERBOSE`` (/root/reference/inc/debug.h:22); the seed grew that into
per-process op counters (:mod:`oncilla_tpu.utils.debug`). This package is
the cross-process layer on top — the Dapper model of low-overhead
always-on trace-context propagation:

- :mod:`~.trace` — (trace_id, span_id) context minted per logical op,
  carried on the wire as a capability-negotiated 16-byte prefix so one
  trace_id stitches client span → local daemon span → peer daemon span.
- :mod:`~.journal` — bounded per-process JSONL event ring
  (``OCM_EVENTS=1``): spans, lease renewals/reclaims, stripe retries,
  tuner window changes, slow-op flags.
- :mod:`~.flightrec` — the ring's crash-safe twin
  (``OCM_FLIGHTREC=dir``): every event also streams into bounded
  CRC-framed segment files, and kill paths flush the ring, so a dead
  daemon leaves its black box on disk.
- :mod:`~.audit` — the post-mortem correctness oracle: merges segments
  cluster-wide and runs cross-rank invariant checks (epoch
  monotonicity, migration pairing, fan-out-before-ack, lease
  termination, eviction priority, fenced silence) with typed findings
  and a nonzero CLI exit (``python -m oncilla_tpu.obs audit <dir>``).
- :mod:`~.export` — merge client + daemon journals into one
  Perfetto/Chrome-trace JSON (pid track per process/daemon, trace_id
  stitched as flow events across tracks).
- :mod:`~.prom` — Prometheus text exposition of the Tracer counters,
  arena occupancy, and lease health, served in-band through the
  STATUS_PROM protocol request (no extra listening port).
- :mod:`~.watchdog` — ``OCM_SLOWOP_US``: a thread that flags spans
  exceeding the threshold into the journal with their trace context.
- :mod:`~.devgaps` — the join of those spans with a profiler trace of the
  device: idle time by the innermost ``ocm:*`` span open on the host
  (``python -m oncilla_tpu.obs gaps <trace>``; needs JAX, imported on use).
- ``python -m oncilla_tpu.obs`` — the cluster CLI (status table,
  ``--prom``, ``--trace``; see :mod:`~.__main__`).

This module must stay import-light: :mod:`oncilla_tpu.utils.debug`
imports :mod:`~.trace` / :mod:`~.journal` at module level, which runs
while ``oncilla_tpu/__init__`` may still be mid-import — submodules here
therefore depend on the stdlib only (and never on the package root).
"""
