"""In-process wire for the fleet harness.

1k real gRPC channels would measure grpc's threading, not the control
plane's behavior — and make the run nondeterministic. This loopback
keeps everything that matters about the wire and drops the sockets:
every call serializes the request through :mod:`common.serde`, passes
the admission gate (:class:`~dlrover_tpu.rpc.transport.RequestGate` —
the same class the real server runs), dispatches into the *real*
``MasterServicer``, and serializes the response back. A message that
would not survive the real wire does not survive this one.

Link faults are modeled per worker (:class:`LinkState`): a partitioned
link raises ``ConnectionError`` (classified ``unavailable``, like a
dead master address); a slow link QUEUES the worker's messages with a
latency distribution (delayed delivery through the SimWorker outbox —
a lease renewal or heartbeat genuinely arrives late on the master's
clock, it is not merely sent less often). The master itself can be
"down" (relaunch gap) via :class:`MasterEndpoint`.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

from dlrover_tpu.common.serde import (
    UnknownMessageError,
    deserialize,
    serialize,
)
from dlrover_tpu.rpc.policy import OverloadedError, UnknownMessageTypeError
from dlrover_tpu.rpc.transport import RequestGate


class MasterEndpoint:
    """The swappable in-process 'address' of the real master: the live
    servicer plus the shared admission gate. ``set_down()`` during a
    relaunch makes every call fail like a dead address; ``set_master``
    points the fleet at the relaunched servicer."""

    def __init__(self, gate: Optional[RequestGate] = None):
        from dlrover_tpu.lint.lock_tracker import maybe_track

        self.gate = gate or RequestGate()
        self._lock = maybe_track(
            threading.Lock(), "fleet.loopback.MasterEndpoint._lock"
        )
        self._servicer = None
        #: schedule-perturbation hook (docs/design/racecheck.md): when
        #: set, called as ``perturb(point, kind)`` immediately before
        #: ("pre") and after ("post") every servicer dispatch — the
        #: runner's SchedulePerturber fires master sweeps there, in the
        #: middle of a logical RPC, which the tick loop never does
        self.perturb = None

    def set_master(self, servicer):
        with self._lock:
            self._servicer = servicer

    def set_down(self):
        with self._lock:
            self._servicer = None

    @property
    def up(self) -> bool:
        with self._lock:
            return self._servicer is not None

    def servicer(self):
        with self._lock:
            return self._servicer


class LinkState:
    """One worker's RPC link: partitioned / delayed by the injector.

    ``latency_s``/``jitter_s`` parameterize the delayed-delivery model:
    a message sent at virtual time T is DELIVERED (dispatched into the
    servicer) at T + latency ± jitter through the worker's outbox
    queue. 0 = immediate (the deterministic default)."""

    def __init__(self):
        self.partitioned = False
        self.latency_s = 0.0
        self.jitter_s = 0.0

    def delay_s(self, rng) -> float:
        """One message's queued-delivery delay draw."""
        if self.latency_s <= 0.0:
            return 0.0
        jitter = self.jitter_s * (2.0 * rng.random() - 1.0)
        return max(0.0, self.latency_s + jitter)


class RpcStats:
    """Fleet-wide wire statistics (thread-safe): per-call wall latency
    (the "no RPC sees unbounded latency" gate reads ``max_s``), a
    log-bucketed latency histogram for percentiles (the SpeedMonitor
    lock-split satellite measures servicer p99 under combined
    report+lease load), send errors and sheds observed client-side."""

    # ~48 log-spaced buckets, 1 µs .. ~10 s, x1.58 per bucket
    _EDGE_BASE = 1e-6
    _EDGE_RATIO = 1.584893  # 10**0.2: 5 buckets per decade
    _N_BUCKETS = 48

    def __init__(self):
        from dlrover_tpu.lint.lock_tracker import maybe_track

        self._lock = maybe_track(
            threading.Lock(), "fleet.loopback.RpcStats._lock"
        )
        self.calls = 0
        self.errors = 0
        self.sheds = 0
        #: unknown-message decode failures observed at the CLIENT side
        #: of the wire — the version_skew scenarios gate this at zero
        #: (every skewed exchange must degrade through a typed path,
        #: never a raw decode error)
        self.decode_errors = 0
        self.total_s = 0.0
        self.max_s = 0.0
        self._hist = [0] * (self._N_BUCKETS + 1)

    def _bucket(self, dur_s: float) -> int:
        import math

        if dur_s <= self._EDGE_BASE:
            return 0
        b = int(
            math.log(dur_s / self._EDGE_BASE)
            / math.log(self._EDGE_RATIO)
        ) + 1
        return min(self._N_BUCKETS, b)

    def record(self, dur_s: float):
        with self._lock:
            self.calls += 1
            self.total_s += dur_s
            if dur_s > self.max_s:
                self.max_s = dur_s
            self._hist[self._bucket(dur_s)] += 1

    def record_error(self):
        with self._lock:
            self.errors += 1

    def record_shed(self):
        with self._lock:
            self.sheds += 1

    def record_decode_error(self):
        with self._lock:
            self.decode_errors += 1

    def percentile(self, q: float) -> float:
        """Upper edge of the bucket holding the q-quantile call."""
        with self._lock:
            total = sum(self._hist)
            if total == 0:
                return 0.0
            rank = q * (total - 1)
            acc = 0
            for i, n in enumerate(self._hist):
                acc += n
                if acc > rank:
                    return self._EDGE_BASE * (self._EDGE_RATIO ** i)
            return self.max_s

    def snapshot(self) -> Dict:
        p99 = self.percentile(0.99)
        with self._lock:
            return {
                "calls": self.calls,
                "errors": self.errors,
                "sheds_seen": self.sheds,
                "decode_errors": self.decode_errors,
                "mean_latency_s": (
                    self.total_s / self.calls if self.calls else 0.0
                ),
                "max_latency_s": self.max_s,
                "p99_latency_s": round(p99, 6),
            }


class LoopbackClient:
    """Drop-in for :class:`~dlrover_tpu.rpc.transport.RpcClient`
    (get/report/available/close) over the in-process wire. Retries are
    immediate — the virtual clock owns time; a sim worker that should
    back off does so in virtual seconds through its own cadence."""

    def __init__(
        self,
        endpoint: MasterEndpoint,
        link: Optional[LinkState] = None,
        stats: Optional[RpcStats] = None,
        node_id: int = -1,
        shim=None,
    ):
        self._endpoint = endpoint
        self.link = link or LinkState()
        self._stats = stats
        # the cheap node-id header (parity with RpcClient's gRPC
        # metadata): the gate learns who it shed pre-deserialization
        self._node_id = int(node_id)
        #: version-skew shim (lint/skew_shim.py): when set, every
        #: request/response byte stream passes through it so this wire
        #: behaves like an N-1 peer sits on the other end — fields the
        #: old side never knew are dropped, message types it never knew
        #: are answered the way an old servicer answers them
        self.shim = shim

    def available(self, timeout: float = 5.0) -> bool:
        return self._endpoint.up and not self.link.partitioned

    def close(self):
        pass

    def get(
        self, msg, retries: int = 3, timeout=None, on_overload="retry",
        policy=None,
    ):
        # policy accepted for RpcClient interface parity; retries are
        # immediate here — the virtual clock owns time
        return self._call("get", msg, retries, on_overload)

    def report(
        self, msg, retries: int = 3, timeout=None, on_overload="retry",
        policy=None,
    ):
        return self._call("report", msg, retries, on_overload)

    def _call(self, kind: str, msg, retries: int, on_overload: str):
        from dlrover_tpu.common import messages as wire_msg

        last: Optional[BaseException] = None
        for _ in range(max(1, retries)):
            if self.link.partitioned:
                if self._stats:
                    self._stats.record_error()
                last = ConnectionError("rpc link partitioned")
                continue
            servicer = self._endpoint.servicer()
            if servicer is None:
                if self._stats:
                    self._stats.record_error()
                last = ConnectionError("master unavailable")
                continue
            gate = self._endpoint.gate
            t0 = time.perf_counter()
            payload = serialize(msg)  # the REAL wire format, both ways
            override = None
            if self.shim is not None:
                payload, override = self.shim.request_wire(payload)
            if override is not None:
                # the shim's simulated old peer answered without ever
                # dispatching (unknown message type -> SimpleResponse,
                # exactly what transport._skew_reply sends on the real
                # wire)
                wire = override
            elif not gate.try_enter(kind, self._node_id):
                wire = serialize(gate.overload_reply(kind))
            else:
                try:
                    perturb = self._endpoint.perturb
                    if perturb is not None:
                        perturb("pre", kind)
                    try:
                        request = deserialize(payload)
                    except UnknownMessageError as e:
                        # server-half parity with the real transport:
                        # an unknown request type degrades to the typed
                        # SimpleResponse, never an exception out of the
                        # dispatch (wirecheck WC003)
                        from dlrover_tpu.rpc.transport import _skew_reply

                        request = None
                        wire = serialize(_skew_reply(e))
                    if request is not None:
                        resp = (
                            servicer.get(request, None)
                            if kind == "get"
                            else servicer.report(request, None)
                        )
                        wire = serialize(resp) if resp is not None else b""
                    if perturb is not None:
                        perturb("post", kind)
                finally:
                    gate.leave(kind)
            if self.shim is not None:
                wire = self.shim.response_wire(wire)
            try:
                decoded = deserialize(wire)
            except UnknownMessageError as e:
                # RpcClient._call parity: a response type this side
                # cannot decode maps to the typed classification error, never
                # a raw ValueError — and the harness counts it (the
                # version_skew verdict gates decode_errors at zero)
                if self._stats:
                    self._stats.record_decode_error()
                raise UnknownMessageTypeError(
                    e.type_name, peer="loopback"
                ) from e
            if self._stats:
                self._stats.record(time.perf_counter() - t0)
            if isinstance(decoded, wire_msg.OverloadedResponse):
                if self._stats:
                    self._stats.record_shed()
                err = OverloadedError(
                    decoded.retry_after_s,
                    decoded.queue_depth,
                    getattr(decoded, "max_interval_s", 0.0),
                )
                if on_overload == "raise":
                    raise err
                last = err
                continue
            return decoded
        raise last if last is not None else ConnectionError("loopback failed")
