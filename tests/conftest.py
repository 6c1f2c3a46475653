"""Test harness: run all tests on a virtual 8-device CPU mesh.

Mirrors how the reference exercised its distributed path on one machine
(multiple slaves against one CommMaster, reference: bin/cluster_optimizer.sh)
— here XLA's host-platform device-count flag gives us 8 virtual devices so
every psum/psum_scatter/all_gather path runs for real, without TPU hardware.

Must set env vars before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: the shell env may pin a TPU platform
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)
# the *_main entry points place the persistent compile cache; tests count
# compiles (retrace sentinel, compile ledger), which a warm cache would hide
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--ytk-sanitize",
        action="store_true",
        default=False,
        help="run @pytest.mark.hotpath tests under "
        "jax.transfer_guard('disallow') + jax_debug_nans, proving the jit "
        "hot paths perform no implicit host<->device transfer and produce "
        "no NaNs (docs/static_analysis.md, 'Runtime sanitizer mode')",
    )
    parser.addoption(
        "--ytk-lockwatch",
        action="store_true",
        default=False,
        help="run @pytest.mark.threaded tests with threading.Lock/RLock "
        "monkey-wrapped: per-thread held-lock stacks with acquisition "
        "sites, a global acquisition-order graph that fails the test on "
        "any observed lock-order cycle, and a hold-time budget "
        "(YTK_LOCKWATCH_HOLD_MS) — the runtime twin of the ytklint "
        "concurrency rules (docs/static_analysis.md)",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "hotpath(subsystem): marks a steady-state jit hot-path test; under "
        "--ytk-sanitize it runs with the transfer guard set to disallow "
        "and jax_debug_nans on — the runtime pin of the ytklint "
        "host-sync-in-jit rule",
    )
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 `-m 'not slow'` run (870s wall "
        "guard); still covered by the full suite under "
        "scripts/check_suite_time.sh's 40-minute budget",
    )
    config.addinivalue_line(
        "markers",
        "threaded(subsystem): marks a genuinely multi-threaded test "
        "(fleet kill-9 hammer, batcher drain, registry hot reload, "
        "retrain-lock heartbeat); under --ytk-lockwatch it runs with "
        "instrumented locks — the runtime pin of the ytklint "
        "lock-order / hold-time rules",
    )


@pytest.fixture(autouse=True)
def _ytk_sanitizer(request):
    """With --ytk-sanitize, wrap marked hot-path tests in the real tracer's
    guards. Module-scoped fixtures (model builds, warmup compiles — load
    time, where transfers are legitimate) set up BEFORE this function-scoped
    fixture, so the guard covers exactly the steady-state body."""
    if not (
        request.config.getoption("--ytk-sanitize")
        and request.node.get_closest_marker("hotpath")
    ):
        yield
        return
    prev_nans = jax.config.jax_debug_nans
    jax.config.update("jax_debug_nans", True)
    try:
        with jax.transfer_guard("disallow"):
            yield
    finally:
        jax.config.update("jax_debug_nans", prev_nans)


@pytest.fixture(autouse=True)
def _ytk_lockwatch(request):
    """With --ytk-lockwatch, watch every lock a threaded-marked test
    creates. Staging mirrors the sanitizer: module-scoped fixtures (and
    their locks) build BEFORE this function-scoped fixture, so the watch
    covers exactly what the test body constructs and drives."""
    if not (
        request.config.getoption("--ytk-lockwatch")
        and request.node.get_closest_marker("threaded")
    ):
        yield
        return
    from tools.ytklint.lockwatch import LockWatch

    watch = LockWatch()
    watch.install()
    try:
        yield
    finally:
        watch.uninstall()
    violations = watch.report()
    if violations:
        pytest.fail(
            "ytk-lockwatch: %d violation(s) observed:\n  %s"
            % (len(violations), "\n  ".join(violations)),
            pytrace=False,
        )


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(scope="session")
def mesh8():
    from ytklearn_tpu.parallel.mesh import make_mesh

    return make_mesh(n_devices=8)
