"""Root conftest: paths + JAX virtual-device environment.

Must run before anything imports jax: tests exercise multi-chip sharding on a
virtual 8-device CPU mesh (``xla_force_host_platform_device_count``), per the
repo build contract.  Real-TPU tests opt out via the ``tpu`` marker and are
deselected by default.
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(__file__))


from tests._env import tpu_lane_enabled  # noqa: E402


def pytest_configure(config):
    """With the real-chip lane enabled, a plain ``pytest`` must run the tpu
    lane and ONLY the tpu lane: override the default markexpr (which
    deselects tpu) so the combination can't come up empty, and never send
    the CPU suite at the accelerator."""
    if tpu_lane_enabled():
        config.option.markexpr = "tpu"


# The CPU lane under ``-p xdist --dist loadfile`` hands FILES to its workers in
# collection order and lasts as long as its last file: the longest files start
# first, so that the run's tail is made of short ones (tier-1's junit file at
# PR 43, test-seconds: from 495 down to 173; a file that is not named keeps its
# alphabetical place behind them).
LONGEST_FIRST = (
    "test_cohere2_moe_engine.py", "test_hybrid_mamba.py", "test_inference.py",
    "test_tpu_compile.py", "test_gdn_moe_engine.py", "test_paged_decode_attention.py",
    "test_kda_mla_moe.py", "test_kda_mla_moe_engine.py", "test_deferred_wave_landing.py",
    "test_ragged_waves.py", "test_latent_decode_attention.py", "test_benchmark_files.py",
    "test_mla_moe.py", "test_spec_decode.py", "test_gdn_moe.py",
)


def pytest_collection_modifyitems(config, items):
    """Belt for the buckle above: with the lane enabled, drop anything
    unmarked even if a caller passed an explicit -m."""
    if not tpu_lane_enabled():
        rank = {name: i for i, name in enumerate(LONGEST_FIRST)}
        items.sort(key=lambda item: rank.get(item.path.name, len(rank)))  # stable
        return
    keep, dropped = [], []
    for item in items:
        (keep if item.get_closest_marker("tpu") else dropped).append(item)
    if dropped:
        config.hook.pytest_deselected(items=dropped)
        items[:] = keep


if not tpu_lane_enabled():
    # the CPU lane: virtual devices for the multi-chip tests, and its
    # workers share compiles through JAX's persistent cache in ONE fixed
    # directory OUTSIDE the checkout — the chip tool copies the tree as it
    # stands on disk, so tests must not fill <checkout>/.jax_cache; a toy
    # engine's programs are the same bytes in every test and worker that
    # builds it, and an entry torn by a concurrent writer reads as a
    # warning and a rebuild (set through the environment so child
    # processes inherit all of it; the lane's setting, no option of the
    # program: the rule's own tests record what the code would set and
    # tests/test_tpu_compile.py switches the cache off around its compiles)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        tempfile.gettempdir(), "calfkit-tpu-test-lane-jax-cache")
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()
