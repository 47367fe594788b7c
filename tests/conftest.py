"""Test harness: CPU backend with an 8-device virtual mesh.

SURVEY.md §4.4: multi-chip behavior is tested without hardware via
`--xla_force_host_platform_device_count` — the moral equivalent of the
reference's fake k8s dynamic client (handlers_test.go:19-20). These env vars
must be set before jax is first imported, hence module scope here.
"""

import os
import sys
import tempfile

# Force CPU (the session env may point JAX at a real TPU; tests must be
# hermetic and run the virtual 8-device mesh).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# NaN checking is off by default (it disables some fusions and slows the
# 1-core CPU runs); individual numerical tests opt in via the `debug_nans`
# fixture below (SURVEY §5.2 — adopters: test_train.py, test_postprocess.py).
os.environ.setdefault("JAX_DEBUG_NANS", "False")
# Parity tests compare against fp32 torch; JAX's CPU backend defaults to a
# lower-precision oneDNN path (~1e-2 drift per conv), so pin full precision.
os.environ.setdefault("JAX_DEFAULT_MATMUL_PRECISION", "highest")

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

# Tests neither read nor write the checkout's persistent compile cache
# (`<checkout>/.jax_cache`, serving/lifecycle.py): an entry left by an
# earlier run could mask a compile failure or shift a timing-sensitive test.
# Caching is off for this process and every subprocess that inherits its
# environment, and the directory is named, so code that resolves it (the
# supervisor's quarantine) never touches the checkout's own.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
    tempfile.gettempdir(), "spotter-tpu-test-jax-cache"
)

import pytest  # noqa: E402


@pytest.fixture
def debug_nans():
    """Run a test with `jax_debug_nans` enabled (SURVEY §5.2).

    Any NaN produced inside jitted or eager numerics fails the test at the
    producing op instead of propagating into an assertion tolerance miss.
    Opt-in per test: it disables some fusions and re-runs de-optimized code on
    hit, too slow to be the suite-wide default on the 1-core CPU runner.
    """
    import jax

    prev = jax.config.jax_debug_nans
    jax.config.update("jax_debug_nans", True)
    try:
        yield
    finally:
        jax.config.update("jax_debug_nans", prev)
