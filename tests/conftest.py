"""Test harness: 8 virtual CPU devices so the multi-chip sharding paths are
exercised without TPU hardware (SURVEY §7 / driver contract)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

# Whatever platform the environment names, the suite runs on the virtual
# 8-device CPU mesh.
jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache: most of this suite's wall-clock is
# XLA:CPU compilation of federated round programs, and many tests rebuild
# the same program shapes. Warm runs skip those compiles entirely.
# $JAX_COMPILATION_CACHE_DIR, when set, is honoured by jax itself and nothing
# is set here. Otherwise the directory is the repo-local gitignored
# .jax_cache/, spelled EXACTLY as below and not by the canonical path of
# fedml_tpu/core/compile_cache.py: jax hashes the directory string into every
# cache key, so respelling it would miss every entry a warm tier-1 lives on
# (it runs within seconds of its 870 s kill). Nothing is lost by the two
# spellings: CPU test entries and TPU entries never collide anyway.
# Entry points called in-process (main_fedavg.main, tools/*_smoke) leave an
# already-configured directory alone.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(os.path.dirname(__file__),
                                   "..", ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.RandomState(0)


# ``tests/benchmark_tests/conftest.py`` hands a test that reads ``BENCHMARK.json``'s
# lists whole the manifest as that test found it (its ``READS_TAILS``), because a
# file the benchmark has is not a later PR's to edit. That holds for the table
# too, so a PR that appends a cell after such a test was written names the test
# here; the next ``benchmark`` PR moves the entry into the table.
# test_benchmark_loop_reduce.py (PR 40) holds the manifest to its five cells;
# test_benchmark_lfm2.py (PR 42) reads its entries as the lists' last;
# test_benchmark_keye.py (PR 48) holds the metrics of its cell alone to its own list
# (PR 49 appended ``dsa_select_tie_blocks_pct``, which test_benchmark_dsa_select.py holds;
# PR 50 ``dsa_index_loss_roofline``, which test_benchmark_dsa_index_loss.py holds as the list's last).
READS_TAILS_SINCE = {
    ("test_benchmark_loop_reduce", "test_manifest_lists_the_five_for_the_cells_they_read"): {
        "configs": "kimi_linear_48b_a3b_cut", "workloads": "kimilinear_silo2",
        "per_layer": "loop_steps_carry_passes"},
    ("test_benchmark_lfm2", "test_manifest_entries_and_the_configuration_file"): {
        "configs": "lfm2_24b_a2b_cut", "workloads": "lfm2moe_silo2",
        "per_layer": "loop_steps_time_pct_lfm2"},
    ("test_benchmark_keye", "test_manifest_entries_and_the_configuration_file"): {
        "configs": "keye_vl2_30b_a3b_cut", "workloads": "keyevl2_silo2",
        "per_layer": "moe_experts_roofline_keye"},
}


def pytest_collection_finish(session):
    for plugin in session.config.pluginmanager.get_plugins():
        tails = getattr(plugin, "READS_TAILS", None)
        if isinstance(tails, dict):
            tails.update(READS_TAILS_SINCE)
