"""Test harness config: run on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding logic is
validated on XLA:CPU with 8 virtual devices (the driver separately
dry-runs the multi-chip path via __graft_entry__.dryrun_multichip).

The platform is forced with jax.config.update (before any backend is
touched) and not left to JAX_PLATFORMS, so the suite runs on the CPU
whatever the caller's environment says — on a TPU host too.
"""

import os

# The static Program verifier runs at first compile for every program
# the suite executes (FLAGS_check_program is read from the env at first
# access; default off in production, on under tests). The book programs
# in test_book.py thereby double as the verifier's end-to-end positive
# sweep — see tests/test_program_verifier.py.
os.environ.setdefault("FLAGS_check_program", "1")

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1")
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection suite (tools/ci.sh gate)")
# float32 matmuls at full precision for numerical test parity
jax.config.update("jax_default_matmul_precision", "highest")
# allow float64 — OpTest numerical grad checks run in fp64 like the
# reference's op_test.py harness
jax.config.update("jax_enable_x64", True)
