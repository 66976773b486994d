"""Test session config: force an 8-device virtual CPU mesh.

Mirrors the reference's strategy of testing all elasticity logic without
accelerators (SURVEY.md §4): JAX runs on 8 virtual CPU devices so sharding
and collectives are exercised for real.
"""

import os

# Must be set before jax initializes its backend; the config update
# below wins over a JAX_PLATFORMS the environment already carries.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_job_container():
    """Each test gets a fresh per-job state world: dropping every
    JobContainer resets JobContext, MasterConfigContext, SpeedMonitor,
    metrics and state-store handles in one move (the old per-singleton
    reset dance)."""
    from dlrover_tpu.master import job_container

    job_container.reset()
    yield
    job_container.reset()


@pytest.fixture
def local_master():
    """In-process master + live gRPC server (the reference's key harness)."""
    from dlrover_tpu.master.local_master import start_local_master

    master = start_local_master(node_num=2)
    yield master
    master.stop()


@pytest.fixture
def master_client(local_master):
    from dlrover_tpu.agent.master_client import MasterClient

    client = MasterClient(f"127.0.0.1:{local_master.port}", node_id=0)
    MasterClient.reset_singleton(client)
    yield client
    MasterClient.reset_singleton(None)
    client.close()
