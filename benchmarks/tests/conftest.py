"""``pytest benchmarks/tests`` runs on the CPU, as ``tests/conftest.py``
arranges for the repo's own tests (which also collect these files,
through ``tests/benchmark_suite/``)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
