"""Root conftest: paths + JAX virtual-device environment.

Must run before anything imports jax: tests exercise multi-chip sharding on a
virtual 8-device CPU mesh (``xla_force_host_platform_device_count``), per the
repo build contract.  Real-TPU tests opt out via the ``tpu`` marker and are
deselected by default.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(__file__))


from tests._env import tpu_lane_enabled  # noqa: E402


def pytest_configure(config):
    """With the real-chip lane enabled, a plain ``pytest`` must run the tpu
    lane and ONLY the tpu lane: override the default markexpr (which
    deselects tpu) so the combination can't come up empty, and never send
    the CPU suite at the accelerator."""
    if tpu_lane_enabled():
        config.option.markexpr = "tpu"


def pytest_collection_modifyitems(config, items):
    """Belt for the buckle above: with the lane enabled, drop anything
    unmarked even if a caller passed an explicit -m."""
    if not tpu_lane_enabled():
        return
    keep, dropped = [], []
    for item in items:
        (keep if item.get_closest_marker("tpu") else dropped).append(item)
    if dropped:
        config.hook.pytest_deselected(items=dropped)
        items[:] = keep


if not tpu_lane_enabled():
    # the CPU lane: virtual devices for the multi-chip tests, and no
    # persistent compile cache — the chip tool copies the tree as it
    # stands on disk, so tests must not fill <checkout>/.jax_cache (set
    # through the environment so child processes inherit both)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()
