"""gRPC transport for the control plane.

The reference exposes one gRPC service with two generic RPCs ``get`` and
``report`` carrying pickled payloads (``dlrover/proto/elastic_training.proto:18-31``,
``master/servicer.py:106-153``). We keep the two-generic-RPC shape — it makes
the protocol evolvable without proto regeneration — but payloads are the safe
JSON serde from :mod:`dlrover_tpu.common.serde`, and the methods are declared
as raw-bytes unary RPCs so no generated stubs are needed.

Fleet-scale hardening (ROADMAP item 5, docs/design/fleet_harness.md):

- the server runs every request through a :class:`RequestGate` — a
  bounded admission counter that *sheds* excess load with an explicit
  :class:`~dlrover_tpu.common.messages.OverloadedResponse` instead of
  letting the executor's unbounded queue hide saturation behind
  unbounded latency.  Reports shed first (they are periodic and
  resendable); gets shed at a higher watermark (a shed ``get_task``
  stalls training, a shed heartbeat costs nothing).
- the client retries through the unified policy in
  :mod:`dlrover_tpu.rpc.policy`: jittered exponential backoff with a
  budget, and an error classification distinguishing unavailable vs deadline
  vs application errors.  ``Overloaded`` replies either retry after the
  server's hint (default) or raise :class:`OverloadedError` for
  periodic reporters that honor backpressure by widening their
  interval.
"""

from __future__ import annotations

import dataclasses
import random
import threading
import time
from concurrent import futures
from typing import Any, Callable, Dict, List, Optional

import grpc

from dlrover_tpu.common.log import logger
from dlrover_tpu.common.serde import (
    UnknownMessageError,
    deserialize,
    serialize,
)
from dlrover_tpu.rpc import policy as rpc_policy
from dlrover_tpu.rpc.policy import OverloadedError

SERVICE = "dlrover_tpu.Master"
GET = f"/{SERVICE}/get"
REPORT = f"/{SERVICE}/report"
#: the cheap node-id header: lets the admission gate record WHICH node
#: it shed before paying any deserialization (shed-aware liveness)
NODE_ID_HEADER = "dlrover-node-id"

_identity = lambda b: b  # noqa: E731


class _Handler(grpc.GenericRpcHandler):
    def __init__(self, get_fn: Callable, report_fn: Callable):
        self._get_fn = get_fn
        self._report_fn = report_fn

    def service(self, handler_call_details):
        method = handler_call_details.method
        if method == GET:
            return grpc.unary_unary_rpc_method_handler(
                self._get_fn,
                request_deserializer=_identity,
                response_serializer=_identity,
            )
        if method == REPORT:
            return grpc.unary_unary_rpc_method_handler(
                self._report_fn,
                request_deserializer=_identity,
                response_serializer=_identity,
            )
        return None


class RequestGate:
    """Bounded admission for the servicer, shared by the real gRPC
    server and the fleet harness's in-process loopback.

    ``depth`` is the number of requests currently *inside* the
    servicer.  Admission above ``report_cap`` (or ``get_cap`` for
    gets) is refused — the caller returns an ``OverloadedResponse``
    built from :meth:`overload_reply`, a reply that costs microseconds,
    so saturation turns into explicit, bounded-latency sheds instead of
    an invisible executor queue.  Counters are cumulative and exported
    on the master ``/metrics``."""

    def __init__(self, report_cap: int = 16, get_cap: Optional[int] = None):
        self.report_cap = max(1, int(report_cap))
        # gets shed later: a shed get stalls the caller's actual work
        self.get_cap = (
            max(self.report_cap, int(get_cap))
            if get_cap is not None
            else self.report_cap * 2
        )
        # the liveness ceiling advertised on Overloaded replies: how far
        # a client may widen its report cadence before the heartbeat
        # evictor would declare it dead. The master that owns this gate
        # sets it from its heartbeat timeout (a safe fraction, so a
        # widened-but-honoring worker always lands >=2 reports per
        # timeout window). 0 = don't advertise.
        self.liveness_ceiling_s = 0.0
        # clock for the shed-recency ledger (injectable: the fleet
        # harness stamps sheds in virtual time)
        self.clock = time.time
        from dlrover_tpu.lint.lock_tracker import maybe_track

        self._lock = maybe_track(
            threading.Lock(), "rpc.transport.RequestGate._lock"
        )
        self._inflight = 0
        self._inflight_reports = 0
        self._peak = 0
        self._served: Dict[str, int] = {"get": 0, "report": 0}
        self._rejected: Dict[str, int] = {"get": 0, "report": 0}
        # shed-aware liveness: node_id -> last shed timestamp. The
        # node id arrives as a cheap header (gRPC metadata / loopback
        # arg) so it is known BEFORE deserialization — the whole point
        # of shedding is not paying the parse, and the heartbeat
        # evictor still must not evict workers the master itself
        # silenced. Bounded; pruned oldest-first past the cap.
        self._shed_nodes: Dict[int, float] = {}
        self._shed_cap = 8192

    def try_enter(self, kind: str, node_id: int = -1) -> bool:
        with self._lock:
            if kind == "get":
                # gets compete for the TOTAL budget (they shed last,
                # at the higher watermark)
                admitted = self._inflight < self.get_cap
            else:
                # reports compete only with OTHER reports: a get-heavy
                # episode (a 1k-node re-rendezvous polling the world)
                # must never starve heartbeats/failure reports into
                # 100% shed — that would walk healthy workers into
                # eviction while their failure reports are shed too
                admitted = self._inflight_reports < self.report_cap
            if not admitted:
                self._rejected[kind] = self._rejected.get(kind, 0) + 1
                if node_id >= 0:
                    self._shed_nodes[node_id] = self.clock()
                    if len(self._shed_nodes) > self._shed_cap:
                        oldest = min(
                            self._shed_nodes, key=self._shed_nodes.get
                        )
                        del self._shed_nodes[oldest]
                return False
            self._inflight += 1
            if kind != "get":
                self._inflight_reports += 1
            if self._inflight > self._peak:
                self._peak = self._inflight
            self._served[kind] = self._served.get(kind, 0) + 1
            return True

    def recently_shed(
        self, node_id: int, window_s: float, now: Optional[float] = None
    ) -> bool:
        """Did the gate shed a request from this node within the
        window? The heartbeat evictor treats such a node as alive: it
        was talking, the master refused to listen."""
        with self._lock:
            ts = self._shed_nodes.get(int(node_id))
        if ts is None:
            return False
        now = self.clock() if now is None else now
        return now - ts <= window_s

    def leave(self, kind: str = "report"):
        with self._lock:
            self._inflight = max(0, self._inflight - 1)
            if kind != "get":
                self._inflight_reports = max(0, self._inflight_reports - 1)

    @property
    def depth(self) -> int:
        with self._lock:
            return self._inflight

    @staticmethod
    def _retry_hint_s(depth: int) -> float:
        """Shed-reply backoff hint: grows with depth so a deeper
        overload pushes the fleet further out."""
        return min(10.0, max(0.5, 0.05 * depth))

    def overload_reply(self, kind: str = "report"):
        from dlrover_tpu.common import messages as msg

        with self._lock:
            depth = self._inflight
        return msg.OverloadedResponse(
            retry_after_s=self._retry_hint_s(depth),
            queue_depth=depth,
            reason=f"{kind} admission cap reached",
            max_interval_s=self.liveness_ceiling_s,
        )

    def stats(self) -> Dict:
        with self._lock:
            return {
                "inflight": self._inflight,
                "peak_inflight": self._peak,
                "report_cap": self.report_cap,
                "get_cap": self.get_cap,
                "served": dict(self._served),
                "rejected": dict(self._rejected),
            }

    def prometheus_lines(self) -> List[str]:
        s = self.stats()
        lines = [
            "# TYPE dlrover_tpu_master_rpc_inflight gauge",
            f"dlrover_tpu_master_rpc_inflight {s['inflight']}",
            f"dlrover_tpu_master_rpc_inflight_peak {s['peak_inflight']}",
            "# TYPE dlrover_tpu_master_rpc_total counter",
        ]
        for kind in sorted(s["served"]):
            lines.append(
                f'dlrover_tpu_master_rpc_total{{method="{kind}",'
                f'outcome="served"}} {s["served"][kind]}'
            )
        for kind in sorted(s["rejected"]):
            lines.append(
                f'dlrover_tpu_master_rpc_total{{method="{kind}",'
                f'outcome="rejected"}} {s["rejected"][kind]}'
            )
        return lines


class RpcServer:
    """Wraps a servicer object exposing ``get(msg)`` / ``report(msg)``."""

    def __init__(
        self,
        servicer,
        port: int = 0,
        max_workers: int = 32,
        gate: Optional[RequestGate] = None,
    ):
        from dlrover_tpu.common import flags

        self._servicer = servicer
        if gate is None:
            # admission caps BELOW the thread count: in-handler depth
            # can never exceed max_workers, so a cap at or above it
            # would never reject — the gate would silently vanish and
            # overload would hide in the executor queue again. Shed
            # replies also need free threads to stay fast.
            cap = int(flags.RPC_INFLIGHT_CAP.get()) or max(
                8, max_workers // 2
            )
            ceiling = max(1, max_workers - 8)
            if cap > ceiling:
                logger.warning(
                    "RPC admission cap %d >= server threads %d would "
                    "disable shedding; clamping to %d",
                    cap, max_workers, ceiling,
                )
                cap = ceiling
            gate = RequestGate(report_cap=cap, get_cap=min(
                max_workers - 2, cap * 2
            ))
        self.gate = gate
        self._server = grpc.server(
            futures.ThreadPoolExecutor(max_workers=max_workers),
            options=[
                ("grpc.max_send_message_length", 256 * 1024 * 1024),
                ("grpc.max_receive_message_length", 256 * 1024 * 1024),
            ],
        )
        self._server.add_generic_rpc_handlers(
            [_Handler(self._handle_get, self._handle_report)]
        )
        self.port = self._server.add_insecure_port(f"0.0.0.0:{port}")

    @staticmethod
    def _peer_node_id(context) -> int:
        """The cheap node-id header (gRPC metadata): read BEFORE the
        payload deserializes so a shed still records WHO it silenced.
        -1 = absent (pre-header client) — shed-blind for that caller,
        exactly the old behavior."""
        try:
            for key, value in context.invocation_metadata() or ():
                if key == NODE_ID_HEADER:
                    return int(value)
        except (TypeError, ValueError, AttributeError):
            pass
        return -1

    def _handle_get(self, request: bytes, context) -> bytes:
        if not self.gate.try_enter("get", self._peer_node_id(context)):
            return serialize(self.gate.overload_reply("get"))
        try:
            msg = deserialize(request)
            resp = self._servicer.get(msg, context)
            return serialize(resp) if resp is not None else b""
        except UnknownMessageError as e:
            # a newer client's request on an older master: degrade to
            # the same typed SimpleResponse the servicer's unknown-
            # handler path returns (wirecheck WC003) — the client's
            # feature-detection fallbacks (e.g. lease_shards ->
            # get_task) key on exactly this reply, an INTERNAL abort
            # would read as a master outage and burn the retry budget
            return serialize(_skew_reply(e))
        except Exception:
            logger.exception("error handling get RPC")
            context.abort(grpc.StatusCode.INTERNAL, "get failed")
        finally:
            self.gate.leave("get")

    def _handle_report(self, request: bytes, context) -> bytes:
        if not self.gate.try_enter("report", self._peer_node_id(context)):
            return serialize(self.gate.overload_reply("report"))
        try:
            msg = deserialize(request)
            resp = self._servicer.report(msg, context)
            return serialize(resp) if resp is not None else b""
        except UnknownMessageError as e:
            return serialize(_skew_reply(e))
        except Exception:
            logger.exception("error handling report RPC")
            context.abort(grpc.StatusCode.INTERNAL, "report failed")
        finally:
            self.gate.leave("report")

    def start(self):
        self._server.start()

    def stop(self, grace: Optional[float] = None):
        self._server.stop(grace)


class RpcClient:
    """Client side of the two generic RPCs, with the unified retry
    policy (jittered exponential backoff, budget-bounded, error
    classification — :mod:`dlrover_tpu.rpc.policy`)."""

    def __init__(
        self,
        addr: str,
        timeout: float = 30.0,
        policy: rpc_policy.BackoffPolicy = rpc_policy.DEFAULT_RPC,
        rng: Optional[random.Random] = None,
        node_id: int = -1,
    ):
        self.addr = addr
        self._timeout = timeout
        self._policy = policy
        self._rng = rng
        # the cheap node-id header rides every call's metadata so the
        # server's admission gate knows who it shed without touching
        # the payload (-1 = anonymous caller, e.g. master-to-master)
        self._metadata = (
            ((NODE_ID_HEADER, str(int(node_id))),) if node_id >= 0 else None
        )
        self._lock = threading.Lock()
        self._channel = None
        self._get = None
        self._report = None
        self._connect()

    def _connect(self):
        self._channel = grpc.insecure_channel(
            self.addr,
            options=[
                ("grpc.max_send_message_length", 256 * 1024 * 1024),
                ("grpc.max_receive_message_length", 256 * 1024 * 1024),
                ("grpc.enable_retries", 1),
                # a master relaunch is a DESIGNED-FOR event: gRPC's
                # default reconnect backoff grows toward 120s, so a
                # channel that watched the old master die can keep
                # replaying "connection refused" long after the new
                # master is serving — defeating the RELAUNCH_TOLERANT
                # retry budget at the application layer. Bound the
                # re-dial so a relaunched address is probed within
                # seconds (found by the SIGKILL-the-master e2e: the
                # agent's succeeded report burned all its retries
                # inside the channel's backoff window while the master
                # was up and reachable).
                ("grpc.initial_reconnect_backoff_ms", 500),
                ("grpc.min_reconnect_backoff_ms", 500),
                ("grpc.max_reconnect_backoff_ms", 3000),
            ],
        )
        self._get = self._channel.unary_unary(
            GET, request_serializer=_identity, response_deserializer=_identity
        )
        self._report = self._channel.unary_unary(
            REPORT, request_serializer=_identity, response_deserializer=_identity
        )

    def available(self, timeout: float = 5.0) -> bool:
        try:
            grpc.channel_ready_future(self._channel).result(timeout=timeout)
            return True
        except Exception:
            return False

    def _reconnect(self):
        """Tear down and re-dial the channel. A long-lived channel that
        watched its master die can wedge in a state no reconnect
        backoff escapes (observed in the SIGKILL-the-master e2e:
        subchannel fds kept failing with 'FD Shutdown' for 60+ s while
        a FRESH channel from a new process connected instantly). The
        relaunch-tolerance story therefore includes rebuilding the
        channel after consecutive unavailable failures — the client
        half of master-relaunch survival."""
        with self._lock:
            try:
                self._channel.close()
            except Exception:
                pass
            self._connect()

    def _stub(self, kind: str):
        with self._lock:
            return self._get if kind == "get" else self._report

    def _call(
        self,
        kind: str,
        msg: Any,
        retries: int,
        timeout: Optional[float],
        on_overload: str = "retry",
        policy: Optional[rpc_policy.BackoffPolicy] = None,
    ):
        """One logical call. ``retries`` bounds attempts (compat with
        the old signature); delays come from the policy's jittered,
        budget-bounded schedule. ``on_overload``: "retry" sleeps at
        least the server's hint and tries again; "raise" surfaces
        :class:`OverloadedError` immediately — periodic reporters
        honor it by widening their cadence, not by retrying. The stub
        re-resolves every attempt so a mid-call channel rebuild takes
        effect immediately."""
        timeout = timeout or self._timeout
        pol = dataclasses.replace(
            policy or self._policy, max_attempts=max(1, retries)
        )
        delays = pol.delays(self._rng)
        payload = serialize(msg)
        err: Optional[BaseException] = None
        unavailable_streak = 0
        while True:
            hint = 0.0
            try:
                try:
                    resp = deserialize(
                        self._stub(kind)(
                            payload, timeout=timeout, metadata=self._metadata
                        )
                    )
                except UnknownMessageError as e:
                    # version skew INSIDE the retry loop: map to the
                    # typed classification error (named _t, actionable) and
                    # never retry — the peer is healthy, replaying the
                    # call replays the identical decode failure. This
                    # closes the documented OverloadedResponse hazard
                    # class: a raw ValueError used to escape here and
                    # surface at whatever site touched the response
                    raise rpc_policy.UnknownMessageTypeError(
                        e.type_name, peer=self.addr
                    ) from e
                if _is_overloaded(resp):
                    err = OverloadedError(
                        resp.retry_after_s,
                        resp.queue_depth,
                        getattr(resp, "max_interval_s", 0.0),
                    )
                    if on_overload == "raise":
                        raise err
                    hint = resp.retry_after_s
                else:
                    return resp
            except OverloadedError:
                raise
            except grpc.RpcError as e:
                if rpc_policy.classify(e) not in rpc_policy.RETRYABLE:
                    raise
                err = e
                if rpc_policy.classify(e) == "unavailable":
                    unavailable_streak += 1
                    if unavailable_streak >= 2:
                        logger.warning(
                            "master %s unavailable %d attempts in a "
                            "row; rebuilding the channel",
                            self.addr, unavailable_streak,
                        )
                        self._reconnect()
            delay = next(delays, None)
            if delay is None:
                raise err
            time.sleep(max(delay, hint))

    def get(
        self,
        msg: Any,
        retries: int = 3,
        timeout: Optional[float] = None,
        on_overload: str = "retry",
        policy: Optional[rpc_policy.BackoffPolicy] = None,
    ):
        return self._call(
            "get", msg, retries, timeout, on_overload, policy
        )

    def report(
        self,
        msg: Any,
        retries: int = 3,
        timeout: Optional[float] = None,
        on_overload: str = "retry",
        policy: Optional[rpc_policy.BackoffPolicy] = None,
    ):
        return self._call(
            "report", msg, retries, timeout, on_overload, policy
        )

    def close(self):
        if self._channel:
            self._channel.close()


def _is_overloaded(resp: Any) -> bool:
    from dlrover_tpu.common import messages as msg

    return isinstance(resp, msg.OverloadedResponse)


def _skew_reply(e: UnknownMessageError):
    """The server half of unknown-message degradation: a typed
    SimpleResponse naming the unknown ``_t``, identical in shape to the
    servicer's no-handler reply so clients have ONE skew signature to
    feature-detect on."""
    from dlrover_tpu.common import messages as msg

    logger.warning(
        "request carried unknown message type %r (version skew); "
        "answering SimpleResponse", e.type_name,
    )
    return msg.SimpleResponse(
        success=False,
        reason=f"unknown message type {e.type_name!r} (version skew)",
    )
