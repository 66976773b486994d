"""Per-collective communication attribution.

Reference parity: xpu_timer classifies every NCCL kernel launch, parses
its buffer size / algorithm / protocol and exports per-collective bus
bandwidth (``xpu_timer/nvidia/hook.cc:54-580``,
``nvidia/intercepted.cc:1-354``, ``nvidia/parse_params.cc``). On TPU
there is no launch to intercept — XLA compiles the collectives into the
program — so the attribution happens at the two places the information
actually exists:

1. **Trace time**: the framework's own collectives (ring-attention kv
   hops, ulysses all-to-alls, pipeline activation/grad hops, fsdp/dp
   grad reductions) self-report ``(name, kind, axis, bytes, count)`` to
   the process-wide :data:`comm_ledger` while their program is traced —
   the TPU-correct analogue of parse_params' buffer-size extraction.
   Each site also opens a ``jax.named_scope`` so the region is visible
   by name in real profiler timelines and HLO dumps.
2. **Measurement**: :func:`measure_axis_bandwidth` times an actual
   sized collective over a mesh axis (jit'd, warm) giving the axis's
   *achieved* bandwidth; :func:`axis_links` classifies each axis as ICI
   or DCN from the multislice layout (slice-major ``dp`` is the only
   axis that crosses slices — ``parallel/mesh.py``).

``prometheus_lines()`` joins the two into the exported rows:
per-collective bytes/step, estimated seconds/step on the measured link,
and per-axis bandwidth — the fleet-level signal the reference's
per-collective bus-bandwidth metrics provide.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

__all__ = [
    "CollectiveEvent",
    "CommLedger",
    "comm_ledger",
    "record_collective",
    "collective_scope",
    "axis_links",
    "measure_axis_bandwidth",
    "measure_mesh_bandwidths",
]


@dataclasses.dataclass(frozen=True)
class CollectiveEvent:
    """One collective site in one compiled program.

    ``nbytes`` is the PER-SHARD payload of one issue; ``count`` is how
    many times the site executes per unit of ``per``: ``"step"`` (one
    optimizer step) or ``"loss_call"`` (one microbatch loss evaluation —
    scaled by the trainer's gradient-accumulation factor at export).

    ``link``: explicit link class ("ici" | "dcn") for sites that know
    better than the per-axis map — the hierarchical dp reduction
    (ops/hier_collectives.py) runs BOTH link classes over the same
    axis, so its legs self-classify. Empty = derive from the axis via
    ``set_links`` (the flat-path behavior, unchanged)."""

    name: str      # site label, e.g. "ring_attention.kv_hop"
    kind: str      # ppermute | all_to_all | psum | all_gather | ...
    axis: str      # mesh axis the collective runs over
    nbytes: int
    count: int = 1
    per: str = "step"  # "step" | "loss_call"
    link: str = ""     # "" = derive from axis

    def bytes_per_step(self, accum_steps: int = 1) -> int:
        scale = accum_steps if self.per == "loss_call" else 1
        return self.nbytes * self.count * scale


class CommLedger:
    """Process-wide registry of collective sites.

    Sites record at trace time, so a cached jit never double-counts:
    events are keyed by their full identity and re-recording is
    idempotent. ``clear()`` starts a fresh inventory (e.g. after a mesh
    rebuild)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._events: Dict[Tuple, CollectiveEvent] = {}
        self._bandwidth_gbps: Dict[str, float] = {}  # axis -> measured
        self._links: Dict[str, str] = {}             # axis -> ici|dcn
        self._accum_steps = 1  # trainer-set loss_call -> step multiplier
        # share of DCN bytes the current program's schedule hides
        # behind compute (ops/hier_collectives.py overlap engine);
        # -1.0 = no program has reported yet (the wire sentinel —
        # 0.0 means "measured, fully exposed", which is a real signal)
        self._overlap_ratio = -1.0

    def record(self, name: str, kind: str, axis: str, nbytes: int,
               count: int = 1, per: str = "step", link: str = ""):
        ev = CollectiveEvent(name, kind, str(axis), int(nbytes),
                             int(count), per, str(link))
        key = (ev.name, ev.kind, ev.axis, ev.nbytes, ev.count, ev.per,
               ev.link)
        with self._lock:
            self._events[key] = ev

    def _link_of(self, ev: CollectiveEvent, links: Dict[str, str]) -> str:
        return ev.link or links.get(ev.axis, "ici")

    def _link_totals(
        self, events, links: Dict[str, str], accum: int
    ) -> Dict[str, int]:
        """The one per-link aggregation: link_bytes() and the
        /metrics ``dlrover_tpu_comm_bytes_total`` rows must never
        diverge (the goodput report's comm_links is documented to
        carry the same split the endpoint exports)."""
        out: Dict[str, int] = {}
        for ev in events:
            link = self._link_of(ev, links)
            out[link] = out.get(link, 0) + ev.bytes_per_step(accum)
        return out

    def link_bytes(self) -> Dict[str, int]:
        """Per-link-class bytes/step: ``{"ici": N, "dcn": M}`` (absent
        class = 0 bytes on it). The per-step analogue of the census's
        link split, from the analytic inventory — the signal the
        brain/tuner reads to trade mesh layout against the slow link."""
        with self._lock:
            events = list(self._events.values())
            links = dict(self._links)
            accum = self._accum_steps
        return self._link_totals(events, links, accum)

    def set_accum_steps(self, n: int):
        with self._lock:
            self._accum_steps = max(1, int(n))

    def set_bandwidth(self, axis: str, gbps: float):
        with self._lock:
            self._bandwidth_gbps[str(axis)] = float(gbps)

    def set_links(self, links: Dict[str, str]):
        with self._lock:
            self._links.update(links)

    def set_overlap_ratio(self, ratio: float):
        """Trainer-reported share of the program's DCN grad bytes the
        schedule overlaps behind compute (0.0 = fully exposed/flat;
        see ``_record_data_parallel_comm``)."""
        with self._lock:
            self._overlap_ratio = float(ratio)

    def overlap_ratio(self) -> float:
        """Last reported overlap share, ``-1.0`` when no program has
        reported one (absent ≠ zero on the wire)."""
        with self._lock:
            return self._overlap_ratio

    def clear(self):
        with self._lock:
            self._events.clear()
            self._overlap_ratio = -1.0

    def events(self) -> List[CollectiveEvent]:
        with self._lock:
            return list(self._events.values())

    def summary(self) -> Dict:
        """Aggregate per (axis, link): bytes/step and est seconds/step."""
        out: Dict[str, Dict] = {}
        with self._lock:
            events = list(self._events.values())
            bw = dict(self._bandwidth_gbps)
            links = dict(self._links)
            accum = self._accum_steps
        for ev in events:
            link = self._link_of(ev, links)
            row = out.setdefault(ev.axis, {
                "link": link, "bytes_per_step": 0, "est_seconds": 0.0,
                "collectives": [],
            })
            ev_bytes = ev.bytes_per_step(accum)
            row["bytes_per_step"] += ev_bytes
            gbps = bw.get(ev.axis, 0.0)
            est = (ev_bytes / (gbps * 2**30)) if gbps > 0 else None
            if est is not None:
                row["est_seconds"] += est
            row["collectives"].append({
                "name": ev.name, "kind": ev.kind,
                "bytes_per_step": ev_bytes, "count": ev.count,
                "est_seconds": est,
            })
        return out

    def prometheus_lines(self) -> List[str]:
        """Prometheus text rows (same endpoint family as the native
        interposer's per-program histograms)."""
        lines = [
            "# TYPE dlrover_tpu_comm_bytes_per_step gauge",
            "# TYPE dlrover_tpu_comm_est_seconds_per_step gauge",
            "# TYPE dlrover_tpu_comm_bytes_total gauge",
            "# TYPE dlrover_tpu_axis_bandwidth_gbps gauge",
        ]
        with self._lock:
            events = list(self._events.values())
            bw = dict(self._bandwidth_gbps)
            links = dict(self._links)
            accum = self._accum_steps
        for ev in sorted(events, key=lambda e: (e.axis, e.name)):
            link = self._link_of(ev, links)
            label = (
                f'collective="{ev.name}",kind="{ev.kind}",'
                f'axis="{ev.axis}",link="{link}"'
            )
            ev_bytes = ev.bytes_per_step(accum)
            lines.append(
                f"dlrover_tpu_comm_bytes_per_step{{{label}}} {ev_bytes}"
            )
            gbps = bw.get(ev.axis, 0.0)
            if gbps > 0:
                est = ev_bytes / (gbps * 2**30)
                lines.append(
                    f"dlrover_tpu_comm_est_seconds_per_step{{{label}}} "
                    f"{est:.9f}"
                )
        # per-link-class rollup: total analytic bytes/step per ici|dcn
        # (the fleet-level "is the slow link loaded" signal — the
        # goodput report carries the same split via GlobalStepReport)
        per_link = self._link_totals(events, links, accum)
        for link in sorted(per_link):
            lines.append(
                f'dlrover_tpu_comm_bytes_total{{link="{link}"}} '
                f"{per_link[link]}"
            )
        with self._lock:
            ratio = self._overlap_ratio
        if ratio >= 0.0:
            lines.append("# TYPE dlrover_tpu_comm_dcn_overlap_ratio "
                         "gauge")
            lines.append(
                f"dlrover_tpu_comm_dcn_overlap_ratio {ratio:.6f}"
            )
        for axis, gbps in sorted(bw.items()):
            link = links.get(axis, "ici")
            lines.append(
                f'dlrover_tpu_axis_bandwidth_gbps{{axis="{axis}",'
                f'link="{link}"}} {gbps:.3f}'
            )
        return lines


#: process-wide ledger the op libraries report into
comm_ledger = CommLedger()


def record_collective(name: str, kind: str, axis: str, nbytes: int,
                      count: int = 1, per: str = "step", link: str = ""):
    """Module-level convenience used by call sites at trace time."""
    comm_ledger.record(name, kind, axis, nbytes, count, per, link)


@contextlib.contextmanager
def collective_scope(name: str, kind: str, axis: str, nbytes: int,
                     count: int = 1):
    """Record the site AND open a ``jax.named_scope`` so the collective
    shows up as a named region in profiler timelines / HLO dumps."""
    import jax

    record_collective(name, kind, axis, nbytes, count)
    with jax.named_scope(name):
        yield


_server_singleton: Optional[Tuple[object, int]] = None
_server_lock = threading.Lock()


def start_metrics_server(port: int = 0):
    """Serve the ledger's Prometheus rows on ``/metrics`` (worker-side
    sibling of the native interposer's per-program endpoint). Returns
    (server, port); the server runs on a daemon thread. Workers enable
    it with ``DLROVER_TPU_COMM_METRICS_PORT`` (see train/trainer.py).

    Process-wide singleton: the ledger being served is process-global,
    and rebuilding trainers (elastic resizes) must not
    leak one listener thread per trainer."""
    global _server_singleton
    with _server_lock:
        if _server_singleton is not None:
            return _server_singleton
        _server_singleton = _start_metrics_server(port)
        return _server_singleton


def stop_metrics_server():
    """Shut the singleton down (tests / graceful worker exit)."""
    global _server_singleton
    with _server_lock:
        if _server_singleton is not None:
            try:
                _server_singleton[0].shutdown()
                _server_singleton[0].server_close()  # release the fd/port
            except Exception:
                pass
            _server_singleton = None


def _start_metrics_server(port: int):
    import http.server

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            if self.path.rstrip("/") in ("", "/metrics".rstrip("/")):
                rows = comm_ledger.prometheus_lines()
                try:
                    # compile-seconds gauges ride the same endpoint: the
                    # fleet-level signal for whether elastic resizes are
                    # landing warm (train/warm_compile.py)
                    from dlrover_tpu.train.warm_compile import (
                        prometheus_lines as compile_lines,
                    )

                    rows = rows + compile_lines()
                except Exception:
                    pass
                try:
                    # per-resize downtime breakdown (rendezvous /
                    # compile / state transfer) — the state half of the
                    # same signal (train/live_reshard.py)
                    from dlrover_tpu.train.live_reshard import (
                        prometheus_lines as resize_lines,
                    )

                    rows = rows + resize_lines()
                except Exception:
                    pass
                try:
                    # trace-spine rollup: cumulative seconds per span
                    # kind + the last step-time digest window (p50/p95)
                    # — the per-rank signal the master's straggler
                    # detector consumes (observability/trace.py)
                    from dlrover_tpu.observability.trace import (
                        prometheus_lines as trace_lines,
                    )

                    rows = rows + trace_lines()
                except Exception:
                    pass
                try:
                    # per-kernel step-time attribution: cumulative
                    # seconds + last-step share per op family, from the
                    # HLO-walk roofline ledger (profiler/kernel_ledger)
                    from dlrover_tpu.profiler.kernel_ledger import (
                        prometheus_lines as kernel_lines,
                    )

                    rows = rows + kernel_lines()
                except Exception:
                    pass
                body = ("\n".join(rows) + "\n").encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            else:
                self.send_response(404)
                self.end_headers()

        def log_message(self, *a):  # quiet
            pass

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", port), Handler)
    t = threading.Thread(target=srv.serve_forever,
                         name="comm-metrics", daemon=True)
    t.start()
    return srv, srv.server_address[1]


class CommMetricsSource:
    """Callable for ``DiagnosisAgent.set_comm_metrics_source``: scrape
    each local worker's comm ``/metrics`` endpoint (the agent assigns
    port base + local_rank) and condense per-axis byte/second totals —
    the agent-side collector tier of the per-collective attribution,
    mirroring how tpu_timer metrics flow into diagnosis (reference:
    xpu_timer_metric_collector.py)."""

    _ROW = None  # compiled regex cache

    def __init__(self, ports):
        self._ports = (
            list(ports) if isinstance(ports, (list, tuple)) else [ports]
        )

    def __call__(self) -> Dict:
        import re
        import urllib.request

        if CommMetricsSource._ROW is None:
            CommMetricsSource._ROW = re.compile(
                r"dlrover_tpu_comm_(bytes|est_seconds)_per_step\{"
                r'collective="([^"]+)",kind="[^"]+",axis="([^"]+)",'
                r'link="([^"]+)"\} ([\d.eE+-]+)'
            )
        axes: Dict[str, Dict] = {}
        workers = 0
        for port in self._ports:
            try:
                text = urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=2
                ).read().decode()
            except OSError:
                continue
            rows = list(CommMetricsSource._ROW.finditer(text))
            if not rows:
                # responding but ledger still empty (worker booted, no
                # program traced yet): counting it would dilute the
                # per-worker average below
                continue
            workers += 1
            for m in rows:
                unit, _coll, axis, link, val = m.groups()
                row = axes.setdefault(
                    axis, {"link": link, "bytes_per_step": 0.0,
                           "est_seconds_per_step": 0.0},
                )
                key = ("bytes_per_step" if unit == "bytes"
                       else "est_seconds_per_step")
                row[key] += float(val)
        if not workers or not axes:
            return {}
        # per-worker average: every worker reports the same program set
        for row in axes.values():
            row["bytes_per_step"] = int(row["bytes_per_step"] / workers)
            row["est_seconds_per_step"] = (
                row["est_seconds_per_step"] / workers
            )
        return {"workers": workers, "axes": axes}


def axis_links(mesh, n_slices: int = 1) -> Dict[str, str]:
    """Classify each mesh axis as "ici" or "dcn". With the slice-major
    multislice layout (``parallel/mesh.py build_mesh``), only the
    outermost slab of ``dp`` spans slices; every other axis stays on a
    single slice's ICI."""
    links = {}
    for axis in mesh.shape:
        links[axis] = "dcn" if (axis == "dp" and n_slices > 1) else "ici"
    return links


def _bench_collective(mesh, axis: str, kind: str, nbytes: int):
    """Build the jitted microbenchmark collective for one axis."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    n = mesh.shape[axis]
    # per-shard length divisible by n too (all_to_all re-splits the
    # local shard n ways), so round to a multiple of n*n
    elems = max(nbytes // 4, n * n)
    elems -= elems % (n * n)
    x = jnp.arange(elems, dtype=jnp.float32)

    def body(x):
        if kind == "psum":
            return lax.psum(x, axis)
        if kind == "ppermute":
            return lax.ppermute(
                x, axis, [(i, (i + 1) % n) for i in range(n)]
            )
        if kind == "all_to_all":
            xs = x.reshape(n, -1)
            return lax.all_to_all(xs, axis, 0, 0, tiled=False).reshape(-1)
        if kind == "all_gather":
            return lax.all_gather(x, axis)
        raise ValueError(f"unknown collective kind {kind!r}")

    fn = shard_map(
        body, mesh=mesh, in_specs=P(axis), out_specs=(
            P() if kind == "all_gather" else P(axis)
        ),
        check_vma=False,
        axis_names={axis},
    )
    return jax.jit(fn), x


def measure_axis_bandwidth(
    mesh, axis: str, kind: str = "psum", nbytes: int = 4 << 20,
    iters: int = 5,
) -> float:
    """Achieved GB/s of ``kind`` over ``axis`` (algorithm bandwidth:
    payload bytes / wall time — the reference's busbw analogue). Runs a
    real sized collective on the mesh, warm, and records the result in
    the ledger."""
    import jax

    fn, x = _bench_collective(mesh, axis, kind, nbytes)
    out = fn(x)
    jax.block_until_ready(out)  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    # PER-SHARD bytes moved per issue — the unit ledger events use — not
    # the global array size: crediting the whole array would overstate
    # per-link bandwidth by the axis size and understate est_seconds
    per_shard = (x.size * 4) / mesh.shape[axis]
    gbps = per_shard / 2**30 / max(dt, 1e-9)
    comm_ledger.set_bandwidth(axis, gbps)
    return gbps


def measure_mesh_bandwidths(
    mesh, n_slices: int = 1, nbytes: int = 4 << 20, iters: int = 5,
    kinds: Optional[Dict[str, str]] = None,
) -> Dict[str, Dict]:
    """Measure every non-trivial axis of a mesh; classify links; feed
    the ledger. Returns {axis: {gbps, link, kind}}."""
    links = axis_links(mesh, n_slices)
    comm_ledger.set_links(links)
    out = {}
    for axis, size in mesh.shape.items():
        if size <= 1:
            continue
        kind = (kinds or {}).get(
            axis, "ppermute" if axis in ("pp", "sp") else "psum"
        )
        gbps = measure_axis_bandwidth(
            mesh, axis, kind=kind, nbytes=nbytes, iters=iters
        )
        out[axis] = {"gbps": gbps, "link": links[axis], "kind": kind}
    return out
