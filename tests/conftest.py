"""Test configuration: force CPU JAX with a virtual 8-device mesh so
multi-chip sharding logic is exercised without TPU hardware (SURVEY.md §4's
"multi-node-without-cluster" trick, TPU edition)."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # force: driver env may say otherwise
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("KUBEDL_CI", "true")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Lock-order witness (docs/static-analysis.md): KUBEDL_LOCKWITNESS=1 arms
# witness-instrumented Lock/RLock/Condition BEFORE any other kubedl_tpu
# import, so every lock the subsystems create at module/instance init is
# classified by creation site. Disarmed (the default) this is a no-op and
# threading primitives stay untouched.
from kubedl_tpu.analysis import lockwitness  # noqa: E402

lockwitness.install()


def pytest_sessionfinish(session, exitstatus):
    """Witnessed runs fail on any lock-order cycle observed across the
    whole suite (pytest reads session.exitstatus back after this hook)."""
    cycles = lockwitness.check()
    if cycles:
        w = lockwitness.active()
        sys.stderr.write("\n" + w.report() + "\n")
        session.exitstatus = 3
