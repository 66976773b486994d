"""Test session config: force an 8-device virtual CPU mesh.

Mirrors the reference's strategy of testing all elasticity logic without
accelerators (SURVEY.md §4): JAX runs on 8 virtual CPU devices so sharding
and collectives are exercised for real.
"""

import collections
import os

# Must be set before jax initializes its backend; the config update
# below wins over a JAX_PLATFORMS the environment already carries.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# A test's program runs once on a batch of a hundred tokens, and most of
# a model file's seconds were XLA's CPU backend compiling it. No test
# reads what LLVM's expensive passes make of it, so the session compiles
# at backend level 0 (the HLO passes, buffer assignment and ``op_name``
# metadata stay): a constant of the session, in the environment for the
# processes the e2e tests start. A file whose tests assert on optimised
# output puts the optimiser back in a module fixture of its own.
os.environ["JAX_DISABLE_MOST_OPTIMIZATIONS"] = "1"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_disable_most_optimizations", True)

import pytest  # noqa: E402


def pytest_terminal_summary(terminalreporter, config):
    """Where the run's seconds went: the total and the ten dearest files,
    from the reports the session (under xdist, its controller) was
    handed. ``tests/README.md`` has the budget they are read against."""
    if hasattr(config, "workerinput"):
        return
    seconds = collections.Counter()
    for reports in terminalreporter.stats.values():
        for report in reports:
            if hasattr(report, "duration") and hasattr(report, "nodeid"):
                seconds[report.nodeid.split("::")[0]] += report.duration
    terminalreporter.write_line(
        f"test-seconds: {sum(seconds.values()):.0f} in {len(seconds)} files")
    for name, spent in seconds.most_common(10):
        terminalreporter.write_line(f"  {spent:7.1f} s  {name}")


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
