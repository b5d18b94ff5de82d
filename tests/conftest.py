"""Test config: force an 8-device virtual CPU mesh (the reference's
multi-process-on-localhost simulation strategy, SURVEY.md §4, mapped to
jax's host-platform device-count flag).

The suite checks the framework's semantics, not how fast XLA:CPU's code
runs — and on the installed jax ~2/3 of its wall time is XLA:CPU
compiling thousands of tiny eager programs (~43 ms each). The two
code-generation flags pick the cheap paths (legacy elemental emitters,
LLVM -O0): about half the compile time on eager-heavy files, same HLO,
same results to the tolerances the tests state. An XLA that drops a flag
rejects XLA_FLAGS loudly at backend start-up: this is the one place."""
import os

os.environ.setdefault(
    "XLA_FLAGS",
    "--xla_force_host_platform_device_count=8 "
    "--xla_cpu_use_fusion_emitters=false "
    "--xla_backend_optimization_level=0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running; excluded from the tier-1 run")
    config.addinivalue_line(
        "markers", "chaos: deterministic fault-injection tests "
        "(testing.faults kill-points); the fast subset runs in tier-1, "
        "run `pytest -m chaos` to select the whole family")


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle
    paddle.seed(102)
    yield
