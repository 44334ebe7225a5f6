"""Test configuration: run all tests on a virtual 8-device CPU mesh.

The reference (krunt/apex) requires real GPUs for every test (SURVEY.md §4). We
improve on that: XLA's CPU backend with ``--xla_force_host_platform_device_count=8``
lets every distributed code path (DP/TP/PP/SP shardings, collectives, pipeline
schedules) compile and execute on any host. Real-TPU benchmarking happens in
``bench.py``, not in the test suite.

Note: the environment may pre-set ``JAX_PLATFORMS`` and something may import
jax before this conftest runs, so we switch platforms via ``jax.config`` —
which works any time before the backend is first used — rather than via
environment variables. The tier-1 command also passes ``JAX_PLATFORMS=cpu``.
"""

import os

# Bench smoke tests drive bench.py's real _emit path; their shrunken-shape
# numbers must never land in the repo's longitudinal BENCH_HISTORY.jsonl.
# Tests that exercise the history round-trip re-point this at a tmp path.
os.environ.setdefault("APEX_BENCH_HISTORY", "off")

from apex_tpu.utils.hostmesh import force_virtual_cpu_devices  # noqa: E402

force_virtual_cpu_devices(8)

import jax  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)


def pytest_report_header(config):
    return f"jax {jax.__version__} devices: {jax.device_count()} ({jax.default_backend()})"
