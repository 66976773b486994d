"""Brain service: cluster-level resource optimization over job history.

Parity: reference ``dlrover/go/brain/pkg/server/server.go:52-135``
(BrainServer.Optimize/PersistMetrics over gRPC, MySQL datastore). Runs as
``python -m dlrover_tpu.brain.server --port 50051 --db /var/lib/brain.db``;
masters connect via ``BrainResourceOptimizer``
(dlrover_tpu/master/resource/brain_optimizer.py).
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import threading

from dlrover_tpu.brain import messages as bmsg
from dlrover_tpu.brain.datastore import BrainDataStore
from dlrover_tpu.brain.optimizer import BrainOptimizer
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.messages import SimpleResponse
from dlrover_tpu.rpc.transport import RpcServer


class BrainServicer:
    def __init__(self, store: BrainDataStore):
        self.store = store
        self.optimizer = BrainOptimizer(store)

    def get(self, request, context=None):
        if isinstance(request, bmsg.BrainOptimizeRequest):
            try:
                plan = self.optimizer.optimize(request)
                return bmsg.BrainOptimizeResponse(success=True, plan=plan)
            except Exception as e:
                logger.exception("optimize failed")
                return bmsg.BrainOptimizeResponse(success=False, reason=str(e))
        if isinstance(request, bmsg.BrainConfigRequest):
            return bmsg.BrainConfigResponse(
                values=self.store.master_config(request.job_name)
            )
        if isinstance(request, bmsg.BrainJobMetricsRequest):
            return bmsg.BrainJobMetricsResponse(
                job_uuid=request.job_uuid,
                samples=self.store.job_samples(
                    request.job_uuid, request.limit
                ),
            )
        return SimpleResponse(success=False, reason="unknown message")

    def report(self, request, context=None):
        if isinstance(request, bmsg.BrainPersistMetrics):
            self.store.upsert_job(
                request.job_uuid,
                request.job_name,
                tpu_type=request.tpu_type,
                min_workers=request.min_workers,
                max_workers=request.max_workers,
                node_unit=request.node_unit,
            )
            if request.samples:
                self.store.append_samples(request.job_uuid, request.samples)
            return SimpleResponse()
        if isinstance(request, bmsg.BrainConfigUpdate):
            if not request.key:
                return SimpleResponse(success=False, reason="empty key")
            self.store.set_master_config(
                request.key, request.value, request.job_name
            )
            logger.info(
                "config update: %s[%s] = %r",
                request.job_name or "<cluster>", request.key, request.value,
            )
            return SimpleResponse()
        if isinstance(request, bmsg.BrainJobEndReport):
            self.store.finish_job(
                request.job_uuid,
                request.status,
                request.worker_num,
                request.exit_reason,
            )
            return SimpleResponse()
        return SimpleResponse(success=False, reason="unknown message")


class BrainServer:
    def __init__(self, port: int = 0, db_path: str = ":memory:"):
        self.store = BrainDataStore(db_path)
        self.servicer = BrainServicer(self.store)
        self._server = RpcServer(self.servicer, port=port)
        self.port = self._server.port

    def start(self):
        self._server.start()
        logger.info("brain service on port %s", self.port)

    def stop(self):
        self._server.stop(grace=1)


def main(argv=None) -> int:
    p = argparse.ArgumentParser("dlrover_tpu brain")
    p.add_argument("--port", type=int, default=50051)
    p.add_argument(
        "--db",
        default=os.path.join(tempfile.gettempdir(), "dlrover_tpu_brain.db"),
    )
    p.add_argument(
        "--watch_cluster", action="store_true",
        help="poll k8s pods into cluster_state so optimize() sees cluster "
             "pressure (reference go/brain k8s watchers)",
    )
    p.add_argument("--watch_interval", type=float, default=30.0)
    args = p.parse_args(argv)
    server = BrainServer(port=args.port, db_path=args.db)
    server.start()
    if args.watch_cluster:
        from dlrover_tpu.brain.cluster_watcher import ClusterWatcher
        from dlrover_tpu.scheduler.k8s_client import get_k8s_client

        watcher = ClusterWatcher(
            get_k8s_client(), server.store,
            interval_secs=args.watch_interval,
        )
        watcher.start()
        logger.info("cluster watcher polling every %ss", args.watch_interval)
    threading.Event().wait()  # serve forever
    return 0


if __name__ == "__main__":
    sys.exit(main())
