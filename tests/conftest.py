"""Test bootstrap: force an 8-device virtual CPU platform.

Mirrors the reference's test strategy (SURVEY.md §4): all distributed logic
must be exercisable on one host without accelerators — their Gloo fallback is
our XLA host-platform multi-device trick. Must run before jax initializes.
"""

import os

# PADDLE_TPU_TEST_LANE=1 (set by benchmarks/tpu_test_lane.py) keeps the
# REAL TPU backend so the pallas-kernel tests run on the chip and their
# results can be recorded as a per-round artifact (TPU_TESTS_r<N>.json).
_TPU_LANE = os.environ.get("PADDLE_TPU_TEST_LANE") == "1"

if not _TPU_LANE:
    # set before jax is imported, and again through jax.config below in
    # case something imported jax first: no backend is initialized yet
    # when conftest loads, so the runtime update still takes effect
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax

if not _TPU_LANE:
    jax.config.update("jax_platforms", "cpu")
    assert jax.devices()[0].platform == "cpu", (
        "tests must run on the virtual CPU platform; jax was initialized on "
        f"{jax.devices()[0].platform} before conftest could redirect it"
    )
    assert len(jax.devices()) == 8, \
        "expected 8 virtual CPU devices for distributed tests"

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seeded():
    import paddle_tpu as paddle

    paddle.seed(2024)
    np.random.seed(2024)
    yield


@pytest.fixture
def compile_cache_restored():
    """For a test that turns jax's persistent compilation cache on
    (``paddle.jit.enable_persistent_cache``). jax decides at a compile
    whether the cache is in use and keeps the answer and the open cache
    until ``reset_cache()``: setting the directory back to None is not
    enough, and every later file of the same xdist worker then compiles
    through a cache whose key leaves metadata out — it hands
    ``test_tracing_spans.py::TestNames`` the executable WITH scope names
    for the program it traced without them."""
    import paddle_tpu as paddle
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in was.items():
        jax.config.update(n, v)
    paddle.jit._PERSISTENT_CACHE_DIR[0] = None
    cc.reset_cache()


@pytest.fixture(scope="session")
def tiny_llama():
    """Session-scoped tiny llama (r12 suite-time satellite): ONE seeded
    (cfg, params) shared by the serving/paged/fleet test modules —
    params are deterministic (PRNGKey(0)) and every test builds its own
    engine, so nothing leaks between tests or files; the per-module
    init_params + first-dispatch warmups were pure overhead. The shared
    geometry also maximises hits in the engines' process-wide compiled-
    program cache (serving._SHARED_PROGS)."""
    from paddle_tpu.models import llama
    from paddle_tpu.parallel import set_mesh

    set_mesh(None)
    cfg = llama.LlamaConfig.tiny(max_seq_len=96)
    params = llama.init_params(cfg)
    return cfg, params
