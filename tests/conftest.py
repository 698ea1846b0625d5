"""Test bring-up: 8 virtual CPU devices in one process.

The TPU-native analogue of torch's gloo-on-CPU distributed testing
(SURVEY.md §4): ``--xla_force_host_platform_device_count=8`` gives a real
8-device mesh with real XLA collectives, so DP sharding, psum gradient
equivalence, and cross-replica BN are all testable with no TPU attached.
Must run before jax initializes, hence module scope here.
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

jax.config.update("jax_threefry_partitionable", True)

# persistent compilation cache, placed by tpudist/utils/cache.py (at
# JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache; opt OUT
# with TPUDIST_NO_JAX_CACHE=1): without it the cold suite compiles every
# program in every run. Known environment wart: ONE program — the bert
# ring-collective train step — SIGABRTs in XLA:CPU when executed from a
# cache-loaded (AOT-deserialized) executable: measured 2/6 child runs
# abort with the cache, 0/6 without, and capping --xla_cpu_max_isa does
# not help (so it is the AOT round trip, not the ISA mismatch the
# cpu_aot_loader warnings suggest). That test runs subprocess-contained
# and CACHE-LESS (tests/test_bert.py), so a crash cannot take down a
# whole run. If aborts appear elsewhere, flip the env switch and purge the
# cache directory.
if os.environ.get("TPUDIST_NO_JAX_CACHE", "").lower() in ("1", "true", "yes"):
    jax.config.update("jax_enable_compilation_cache", False)
else:
    from tpudist.utils.cache import place_compile_cache

    place_compile_cache()
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


# The smoke tier: the fastest high-signal slice of the suite, sized for a
# COLD 1-core host (no persistent compile cache) to finish well inside a
# 10-minute budget — `pytest -m smoke`. Selection rule: every reference-
# parity layer gets at least one file (sampler shard math, metrics
# contract, mesh/shardings, DP-step equivalence, data paths, native C++
# round-trips, decode/generation), but compile-heavy model files
# (bert/t5/vit/pipeline/fsdp/moe/flash) and all subprocess tests stay out.
# Measured cold on this 1-core host: see README "Testing" for the number
# recorded at marking time.
_SMOKE_FILES = {
    "test_dp_equivalence.py",
    "test_generate.py",
    "test_lm_data.py",
    "test_lm_loss.py",
    "test_mesh.py",
    "test_metrics.py",
    "test_native.py",
    "test_packed.py",
    "test_sampler.py",
    "test_transforms.py",
}


def pytest_configure(config):
    """Opt-in tier-1 marker audit (tools/marker_audit.py): with
    ``TPUDIST_MARKER_AUDIT`` set, every executed test's call duration is
    checked against the per-test budget and the session FAILS (exit 3)
    if an over-budget test is missing the ``slow`` marker — the guard
    that keeps the ``not slow`` suite inside its 870 s tier-1 window."""
    if not os.environ.get("TPUDIST_MARKER_AUDIT"):
        return
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tools = os.path.join(repo, "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    import marker_audit

    # is_registered, not a name check: the same module may already be
    # loaded under its own name via `-p marker_audit` or the CLI wrapper,
    # and registering the object twice is a pytest startup error
    if not config.pluginmanager.is_registered(marker_audit):
        config.pluginmanager.register(marker_audit, "tpudist-marker-audit")


def pytest_collection_modifyitems(config, items):
    """Tests marked ``subproc_only`` run ONLY inside their wrapper's child
    process (TPUDIST_SUBPROC_TEST=1) — the containment mechanism for the
    crash-capable ring-collective test (see test_bert.py). Files in
    ``_SMOKE_FILES`` are additionally marked ``smoke`` (the cold-budget
    tier; ``slow``-marked tests inside them stay excluded via
    ``-m "smoke and not slow"`` semantics — the smoke command selects
    both)."""
    import pytest as _pytest

    if os.environ.get("TPUDIST_SUBPROC_TEST"):
        return
    skip = _pytest.mark.skip(reason="runs only inside its subprocess wrapper")
    for item in items:
        if "subproc_only" in item.keywords:
            item.add_marker(skip)
        if item.fspath.basename in _SMOKE_FILES and "slow" not in item.keywords:
            item.add_marker(_pytest.mark.smoke)


def assert_kernel_parity(got, want, *, rtol=None, atol=None):
    """The ONE interpret-mode parity bar for the Pallas kernels (flash /
    vmem attention, fused LN, fused AdamW): full-precision references get
    the flash/vmem suites' historical ``rtol=atol=2e-5``; half-precision
    references (bf16/fp16) get 2% of the reference's max magnitude —
    ≈2 ulp at the output scale, because a kernel computing its interior in
    fp32 legitimately differs from a reference that rounds intermediates
    to bf16 by up to an output-magnitude ulp. Kernel tests share this
    helper (the ``kernel_parity`` fixture) so the bar cannot drift
    per-file."""
    import numpy as np

    ref_dtype = np.asarray(want).dtype
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    if ref_dtype.itemsize <= 2:
        scale = float(max(np.max(np.abs(w)), 1e-6))
        np.testing.assert_allclose(
            g, w, rtol=rtol or 0.0,
            atol=atol if atol is not None else 2e-2 * scale,
        )
    else:
        np.testing.assert_allclose(
            g, w, rtol=2e-5 if rtol is None else rtol,
            atol=2e-5 if atol is None else atol,
        )


@pytest.fixture
def kernel_parity():
    """Fixture handle on :func:`assert_kernel_parity` — request it in any
    Pallas-kernel test instead of hand-picking tolerances."""
    return assert_kernel_parity


def tiny_resnet():
    """2-stage/1-block/8-filter ResNet: same BN + residual + strided-stage
    topology as resnet18 at a fraction of the compile bill. The shared
    helper for compile-heavy ResNet tests — test_device_cache.py compiles
    each data path as its own program, and test_amp_optim.py's guard test
    runs cache-less every time (see no_persistent_compile_cache), so the
    geometry must stay identical between them."""
    from tpudist.models.resnet import ResNet, ResNetBlock

    return ResNet(stage_sizes=[1, 1], num_filters=8, block_cls=ResNetBlock,
                  num_classes=10, small_inputs=True)


@pytest.fixture
def no_persistent_compile_cache():
    """Disable the persistent compilation cache for ONE test.

    Second documented wart of the cache's AOT round trip on this XLA:CPU
    (the first is the bert ring-collective SIGABRT above): an executable
    LOADED from the persistent cache has been observed to misexecute the
    select-guarded optimizer-update pattern (``jnp.where(ok, new, old)``
    over donated state: the post-skip clean step leaves params frozen —
    measured failing with the cache, passing without, tpudist.telemetry's
    guard tests and test_amp_optim's), and a cache HIT emits no compile
    log at all, starving ``jax.log_compiles`` assertions. Tests touching
    either pattern opt out here; everything else keeps the >1h-saving
    cache.

    Flipping the config alone is NOT enough: whether the cache is used is
    decided once per process (``is_cache_used`` never re-reads the
    config), so once any earlier test compiled anything, the update is
    silently ignored. The singleton must be reset around the config change
    — and reset again on exit so the cache comes back for the next test.
    The enable flag, not the directory, is what is flipped: the directory
    may have been placed from outside (``JAX_COMPILATION_CACHE_DIR``).
    """
    from jax._src import compilation_cache as _cc

    was = jax.config.jax_enable_compilation_cache
    _cc.reset_cache()
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        _cc.reset_cache()
