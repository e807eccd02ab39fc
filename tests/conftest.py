"""Test env: force the CPU platform with 8 virtual devices so the suite is
hermetic and deterministic — a chip belongs to one process at a time, and
every kernel under test is either platform-independent XLA or a Pallas
kernel the interpreter runs (ops/match.pallas_interpret).  TPU execution is
covered by chip_smoke.py, not unit tests.  The driver's multi-chip dryrun
provisions the same virtual-device setup itself
(__graft_entry__.dryrun_multichip)."""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# ANTREA_TPU_TEST_PLATFORM overrides the hermetic default so kernels can
# occasionally be validated on real hardware (e.g. =tpu).
os.environ["JAX_PLATFORMS"] = os.environ.get("ANTREA_TPU_TEST_PLATFORM", "cpu")

# Persistent XLA compilation cache: the suite's wall clock is dominated by
# program compiles (every engine/world/batch-shape variant is its own
# executable), so repeat runs in one container — the developer loop and the
# CI re-run — skip straight to execution.  Cache entries are keyed by
# program + compiler version, so a stale dir can only miss, never serve a
# wrong executable.  ANTREA_TPU_TEST_NO_COMPILE_CACHE=1 opts out (e.g. when
# bisecting compile-time itself).
if not os.environ.get("ANTREA_TPU_TEST_NO_COMPILE_CACHE"):
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          "/tmp/antrea_tpu_xla_cache")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")


def cpu_devices():
    import jax

    return jax.devices("cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: datapath-compile / scale / process-boundary tests (minutes). "
        "Quick developer loop: pytest -m 'not slow' (< 2 min); CI and the "
        "driver run everything.",
    )
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection tier (tests/test_chaos_dissemination.py): "
        "scripted connection resets, agent crashes, and install failures "
        "with convergence-to-oracle-parity assertions.  The single-fault "
        "smoke rides the tier-1 'not slow' set; the kill/revive soak and "
        "process-boundary faults are also marked slow.",
    )
