"""Stats collection, error monitor, and the paral-config chain:
autoscaler plan -> node config -> servicer -> client -> tuner file ->
ElasticDataLoader batch size (reference ParalConfigTuner + ElasticDataLoader,
§2.5/§2.6)."""

import os
import time

import numpy as np
import pytest

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.paral_config_tuner import (
    PARAL_CONFIG_PATH_ENV,
    ParalConfigTuner,
    read_paral_config,
)
from dlrover_tpu.common.constants import NodeType
from dlrover_tpu.master.local_master import start_local_master
from dlrover_tpu.master.monitor.error_monitor import ErrorMonitor, K8sErrorMonitor
from dlrover_tpu.master.node.job_context import JobContext, get_job_context
from dlrover_tpu.master.stats.job_collector import (
    JobMetricCollector,
    LocalStatsReporter,
)
from dlrover_tpu.train.data import ElasticDataLoader
from tests.k8s_fakes import make_fake_client


@pytest.fixture
def local_master():
    master = start_local_master(node_num=1)
    yield master
    master.stop()


def test_metric_collector_samples_context(local_master):
    client = MasterClient(f"127.0.0.1:{local_master.port}", node_id=0)
    client.report_node_address("127.0.0.1")
    client.report_used_resource(cpu_percent=55.0, memory_mb=2048.0)
    reporter = LocalStatsReporter()
    collector = JobMetricCollector(
        speed_monitor=local_master.speed_monitor, reporters=[reporter]
    )
    sample = collector.collect_once()
    assert sample.worker_num == 1
    assert sample.cpu_percent_avg == 55.0
    assert sample.memory_mb_max == 2048.0
    assert reporter.metrics.samples[-1] is sample


def test_error_monitor_collects_failures(local_master):
    client = MasterClient(f"127.0.0.1:{local_master.port}", node_id=0)
    client.report_node_address("127.0.0.1")
    client.report_failure("Traceback ... ValueError", 0)
    events = local_master.error_monitor.events
    assert events and events[-1].instance == "worker-0"
    assert "ValueError" in events[-1].message


def test_k8s_error_monitor_emits_events():
    k8s, transport = make_fake_client()
    monitor = K8sErrorMonitor(k8s, "job-x", "dlrover")
    monitor.report("error", "worker-2", "chip failure")
    assert len(transport.events) == 1
    ev = transport.events[0]
    assert ev["involvedObject"]["name"] == "job-x"
    assert ev["reason"] == "worker-2"


def test_paral_config_chain_end_to_end(local_master, tmp_path, monkeypatch):
    """Autoscaler pushes an HBM-OOM adjustment; the worker's dataloader
    halves its micro batch after the tuner writes the file."""
    client = MasterClient(f"127.0.0.1:{local_master.port}", node_id=0)
    client.report_node_address("127.0.0.1")

    # master side: a plan with scales lands on the node
    from dlrover_tpu.master.node.job_auto_scaler import JobAutoScaler
    from dlrover_tpu.master.resource.optimizer import LocalOptimizer

    scaler = JobAutoScaler(
        optimizer=LocalOptimizer(),
        scaler=_NoopScaler(),
        speed_monitor=local_master.speed_monitor,
    )
    scaler._push_paral_config(
        {"micro_batch_scale": 0.5, "grad_accum_scale": 2.0, "restart": True,
         "bogus_key": 1}
    )
    node = get_job_context().get_node(NodeType.WORKER, 0)
    assert "bogus_key" not in node.paral_config
    assert node.paral_config["dataloader_version"] == 1

    # agent side: tuner polls and writes the file
    path = str(tmp_path / "paral.json")
    tuner = ParalConfigTuner(client, "j", 0, path=path, interval=3600)
    assert tuner.poll_once() is True
    config = read_paral_config(path)
    assert config["micro_batch_scale"] == 0.5
    assert config["dataloader_version"] == 1
    assert tuner.poll_once() is False  # unchanged -> no rewrite

    # worker side: dataloader applies the scale to its base batch size
    monkeypatch.setenv(PARAL_CONFIG_PATH_ENV, path)
    dataset = [np.full((4,), i, np.float32) for i in range(64)]
    loader = ElasticDataLoader(dataset, batch_size=8, shuffle=False)
    # force single-replica sampler regardless of test env
    loader.sampler.num_replicas = 1
    loader.sampler.rank = 0
    batches = list(iter(loader))
    assert batches[0].shape[0] == 4  # 8 * 0.5
    assert loader.batch_size == 4


def test_elastic_dataloader_without_config(tmp_path, monkeypatch):
    monkeypatch.delenv(PARAL_CONFIG_PATH_ENV, raising=False)
    dataset = [np.full((2,), i, np.float32) for i in range(16)]
    loader = ElasticDataLoader(dataset, batch_size=4, shuffle=False)
    loader.sampler.num_replicas = 1
    loader.sampler.rank = 0
    batches = list(iter(loader))
    assert len(batches) == 4
    assert batches[0].shape == (4, 2)
    # mid-epoch resume carries through state_dict
    state = loader.state_dict()
    assert state["epoch"] == 1


class _NoopScaler:
    def scale(self, plan):
        pass


def test_prefetch_to_device_preserves_order_and_places():
    """Async h2d double-buffering: same batches, same order, arrays on
    device; size=0 degrades to plain iteration."""
    import jax
    import numpy as np

    from dlrover_tpu.train.data import prefetch_to_device

    batches = [np.full((2, 2), i, np.float32) for i in range(5)]
    # a bare iterable (no __next__) must work: ElasticDataLoader only
    # defines __iter__, and restarting it per-enqueue would loop forever
    out = list(prefetch_to_device(batches, size=2))
    assert len(out) == 5
    for i, b in enumerate(out):
        assert isinstance(b, jax.Array)
        assert float(b[0, 0]) == i

    # size=0: no overlap, but placement still applies
    plain = list(prefetch_to_device(iter(batches), size=0))
    assert len(plain) == 5 and isinstance(plain[0], jax.Array)


def test_prefetch_to_device_applies_sharding():
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dlrover_tpu.train.data import prefetch_to_device

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("dp",))
    sh = NamedSharding(mesh, P("dp"))
    batches = [np.arange(8, dtype=np.float32).reshape(8) for _ in range(3)]
    out = list(prefetch_to_device(iter(batches), size=2, sharding=sh))
    assert all(b.sharding == sh for b in out)


def test_dataloader_fetch_traced_with_every_flag_unset(monkeypatch):
    """Dataloader fetch spans land in the spine (reference py_tracing
    dataloader interception): its counters and per-kind seconds always,
    its ring behind DLROVER_TPU_TRACE."""
    import numpy as np

    from dlrover_tpu.observability import trace
    from dlrover_tpu.train.data import ElasticDataLoader

    ds = [np.zeros((2,), np.float32) for _ in range(8)]
    loader = ElasticDataLoader(ds, batch_size=4, shuffle=False)
    monkeypatch.delenv("DLROVER_TPU_TRACE", raising=False)
    fetched = trace.counters().get("dataloader.next", (0, 0.0))
    waited = trace.trace_ring.kind_seconds().get("input_wait", 0.0)
    list(loader)
    count, seconds = trace.counters()["dataloader.next"]
    assert count == fetched[0] + 2 and seconds > fetched[1]
    assert trace.trace_ring.kind_seconds()["input_wait"] == pytest.approx(
        waited + seconds - fetched[1])
    assert "dataloader.next" not in [
        e["name"] for e in trace.trace_ring.events()]
    monkeypatch.setenv("DLROVER_TPU_TRACE", "1")
    try:
        list(loader)
        mine = [e for e in trace.trace_ring.events()
                if e["name"] == "dataloader.next"]
        assert len(mine) == 2 and {e["kind"] for e in mine} == {"input_wait"}
    finally:
        trace.trace_ring.clear()


def test_prefetch_pytree_sharding():
    """Per-leaf shardings for dict batches; single-process shardings take
    the device_put path (multi-host assembly is covered by the
    make_array_from_process_local_data branch)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dlrover_tpu.train.data import prefetch_to_device

    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    sh = {"x": NamedSharding(mesh, P("dp")), "y": None}
    batches = [
        {"x": np.ones((4,), np.float32), "y": np.zeros((2,), np.float32)}
        for _ in range(3)
    ]
    out = list(prefetch_to_device(iter(batches), size=1, sharding=sh))
    assert out[0]["x"].sharding == sh["x"]
    assert isinstance(out[0]["y"], jax.Array)


def test_runtime_lr_chain_end_to_end(local_master, tmp_path, monkeypatch):
    """Round 4: the hyperparam refinement's lr lands in the trainer —
    master pushes optimizer fields, the tuner writes the file, and the
    trainer's poll applies the update multiplier without recompiling."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.master.node.job_auto_scaler import JobAutoScaler
    from dlrover_tpu.master.resource.optimizer import LocalOptimizer
    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel import MeshConfig, build_mesh, named_shardings
    from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

    client = MasterClient(f"127.0.0.1:{local_master.port}", node_id=0)
    client.report_node_address("127.0.0.1")

    auto = JobAutoScaler(
        optimizer=LocalOptimizer(), scaler=_NoopScaler(),
        speed_monitor=local_master.speed_monitor,
    )
    auto._push_paral_config({
        "dataloader_batch_size": 4,
        "optimizer_learning_rate": 2e-2,  # 2x the trainer's base lr
        "grad_accum_steps": 1,
    })
    path = str(tmp_path / "paral.json")
    tuner = ParalConfigTuner(client, "j", 0, path=path, interval=3600)
    assert tuner.poll_once() is True
    monkeypatch.setenv(PARAL_CONFIG_PATH_ENV, path)

    cfg = llama.LlamaConfig.tiny()
    mc = MeshConfig(dp=1, fsdp=1, sp=1, tp=1)
    mesh = build_mesh(mc, devices=jax.devices()[:1])
    specs = llama.param_specs(cfg)
    params = jax.device_put(
        llama.init_params(cfg, jax.random.key(0)),
        named_shardings(mesh, specs),
    )
    tc = TrainConfig(global_batch_size=4, micro_batch_size=4,
                     learning_rate=1e-2, warmup_steps=0, total_steps=10)
    tr = ElasticTrainer(
        lambda p, t: llama.loss_fn(p, t, cfg, mesh), specs, mesh, mc, tc,
        worker_ctx=object(),  # non-None enables the per-step poll
    )
    state = tr.init_state(params)
    state = tr.poll_runtime_config(state, every_steps=1)
    assert float(state["lr_scale"]) == 2.0
