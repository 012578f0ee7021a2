"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding paths are
exercised without TPU hardware; the program runs on the chip through
`python -m perf.run` (BENCHMARK.json).  Env vars must be set before jax
imports anywhere.
"""

import os

# force, don't setdefault: a caller's JAX_PLATFORMS (a chip machine's
# default) must never route the suite onto a TPU
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import threading  # noqa: E402

import pytest  # noqa: E402

from openr_tpu.analysis import race as _race  # noqa: E402


def pytest_configure(config):
    # OPENR_TSAN=1 arms the happens-before race detector HERE — before
    # test modules import and construct modules/locks/queues, so every
    # Lock/Condition created for the suite is a proxy and every tracked
    # class carries its access hooks (no-op otherwise; docs/OPERATIONS.md)
    _race.maybe_enable()


@pytest.fixture(autouse=True)
def tsan_guard():
    """Zero-unsuppressed-findings gate for armed (OPENR_TSAN=1) runs.

    Drains stale findings before the test, and fails the test that
    actually produced a race — with both stacks — after it.  Unarmed runs
    pay one `is None` check."""
    det = _race.TSAN
    if det is None:
        yield
        return
    det.drain()
    yield
    findings = det.drain()
    if findings:
        pytest.fail(_race.format_findings(findings), pytrace=False)


@pytest.fixture
def cpu_devices():
    import jax

    return jax.devices("cpu")


class CpuBurner:
    """Background threads spinning pure-Python arithmetic to steal GIL
    slices from the test body.

    On this 1-CPU container the chaos suites only flake when the whole
    suite runs — other tests' threads perturb scheduling enough that a
    convergence wait which merely *polled once* passes standalone and
    races under load.  Burners reproduce that contention deterministically
    in a single test, so hold-based waits (pinned write counters, observed
    quiescence) are exercised rather than lucky instantaneous polls.
    """

    def __init__(self, threads: int = 2) -> None:
        self._stop = threading.Event()
        self._threads = [
            threading.Thread(target=self._burn, daemon=True, name=f"burn-{i}")
            for i in range(threads)
        ]

    def _burn(self) -> None:
        x = 1
        while not self._stop.is_set():
            x = (x * 1103515245 + 12345) % (1 << 31)

    def start(self) -> "CpuBurner":
        for t in self._threads:
            t.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=5)


@pytest.fixture(scope="module")
def cpu_burner():
    """Shared CPU-contention fixture for the chaos suites (test_ocs,
    test_chaos, test_flapstorm, test_replicafleet).  Module-scoped so
    module- and class-scoped scenario fixtures can run under it."""
    burner = CpuBurner(threads=2).start()
    yield burner
    burner.stop()
