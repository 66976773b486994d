"""``step_host_cpu_ms``: see ``step_host_cpu_ms.json``."""
from benchmarks.harness.step_rows import read  # noqa: F401
