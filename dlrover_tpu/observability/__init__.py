"""The goodput observatory: one structured event spine for every
instrument the repo grew separately.

- :mod:`dlrover_tpu.observability.trace` — the typed spans every
  emitter (trainer, live reshard, checkpoint tiers, rendezvous, the
  dataloader, the garbage collector) records: always-on per-name
  counters, per-kind seconds, gauges and step rows, the JAX profiler's
  host plane, and the ring, exportable as chrome-trace JSON mergeable
  with the interposer ``/timeline`` dump.
- :mod:`dlrover_tpu.observability.digest` — windowed per-rank
  step-time digests (count/mean/p50/p95/max) that ride the step RPC to
  the master, feeding straggler detection
  (``master/monitor/straggler.py``) and the lost-time attribution in
  the goodput report (``master/monitor/speed_monitor.py``).

The ring and its dumps are behind ``DLROVER_TPU_TRACE``
(common/flags.py); see ``docs/design/observability.md``.
"""

from dlrover_tpu.observability import trace  # noqa: F401
from dlrover_tpu.observability.digest import StepTimeDigest  # noqa: F401
from dlrover_tpu.observability.trace import (  # noqa: F401
    SPAN_KINDS,
    TraceRing,
    trace_ring,
)
