import os

# the suite runs on the CPU backend, on a virtual 8-device mesh for the
# sharding tests
os.environ["JAX_PLATFORMS"] = "cpu"
# gated connectors (reference parity: ~25 features need a free key) run
# under the demo license, exactly like the reference's own test setup
os.environ.setdefault("PATHWAY_LICENSE_KEY", "demo-license-key-no-telemetry")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()

# pyarrow 25's default (mimalloc) pool segfaults in `pq.read_table` in most
# fresh processes that also hold jax (tests/test_deltalake_mysql.py as a
# worker's first file: 4 of 6 runs; with the system pool 0 of 6), so whether
# tier-1 passed hung on which worker xdist gave that file to
os.environ.setdefault("ARROW_DEFAULT_MEMORY_POOL", "system")

# tests must not depend on what an earlier run compiled: compile counts
# are asserted, and a persistent-cache hit is not a compile
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'` (ROADMAP.md); register the mark so slow
    # variants (e.g. interpreted Pallas kernels) don't warn
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 budgeted run"
    )


# -- tier-1 skip budget (Round-16) -------------------------------------------
# The tier-1 run skips exactly 4 tests, each for one of the
# REVIEWED reasons below.  Skips are where coverage quietly erodes: a
# refactor that starts skipping a suite ("import failed -> skip") reads
# as green.  This guard fails the run when a skip fires whose reason
# matches none of the reviewed strings — adding a new skip means adding
# its reason here, in the same diff, where review sees it.
_REVIEWED_SKIP_REASONS = (
    # test_aws_sharepoint_bq: verify-side dependency (present in this
    # image, so it does not fire here)
    "cryptography not installed",
    # test_compiled_query: inductor compile is ~20s; opt-in
    "inductor compile is ~20s",
    # test_e2e_rag x2 + test_obs timing guard: wall-clock-paced tests on
    # oversubscribed container hosts
    "flaky under container CPU contention",
    # test_chip_compile: a host without the TPU compiler cannot describe
    # the chip (here it can: these tests run)
    "no v5e:2x2 topology can be described here",
)
_BASELINE_SKIP_COUNT = 4
_observed_skips: list[tuple[str, str]] = []


def pytest_runtest_logreport(report):
    if not report.skipped or getattr(report, "wasxfail", None):
        return
    if isinstance(report.longrepr, tuple):
        reason = report.longrepr[2]
    else:  # pragma: no cover - non-tuple skip reprs are rare
        reason = str(report.longrepr)
    _observed_skips.append((report.nodeid, reason))


def pytest_sessionfinish(session, exitstatus):
    rogue = [
        (nodeid, reason) for nodeid, reason in _observed_skips
        if not any(r in reason for r in _REVIEWED_SKIP_REASONS)
    ]
    if rogue:
        tr = session.config.pluginmanager.getplugin("terminalreporter")
        lines = [
            "tier-1 skip guard: %d skip(s) with no reviewed reason "
            "(baseline: %d reviewed skips).  A new skip must add its "
            "reason string to _REVIEWED_SKIP_REASONS in tests/conftest.py:"
            % (len(rogue), _BASELINE_SKIP_COUNT)
        ] + [f"  {nodeid}: {reason}" for nodeid, reason in rogue]
        msg = "\n".join(lines)
        if tr is not None:
            tr.write_line(msg, red=True)
        else:  # pragma: no cover - no terminal plugin
            print(msg)
        # pytest.exit from sessionfinish is the supported way to force
        # the process exit code (wrap_session catches it and adopts
        # returncode; assigning session.exitstatus here is overwritten)
        pytest.exit("tier-1 skip guard failed", returncode=1)


@pytest.fixture(autouse=True)
def clear_parse_graph():
    """Reference parity: autouse fixture clears the global ParseGraph after
    every test (python/pathway/conftest.py:21-77)."""
    from pathway_tpu.internals import parse_graph as pg
    from pathway_tpu.io._synchronization import clear_groups

    pg.G.clear()
    clear_groups()
    yield
    pg.G.clear()
    clear_groups()


@pytest.fixture(autouse=True, scope="session")
def _obs_flusher_shutdown():
    """Round-11/14 hygiene: neither the flight recorder's background
    flusher nor the cost store's writer thread may outlive the test
    session (a dangling thread flakes --continue-on-collection-errors
    runs)."""
    yield
    from pathway_tpu import obs
    from pathway_tpu.obs import costdb

    obs.shutdown()
    costdb.shutdown()
