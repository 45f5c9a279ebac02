"""Test config: run on a virtual 8-device CPU mesh (the reference's
"fake cluster" pattern: test_dist_base.py uses localhost subprocesses; here
XLA's forced host device count gives 8 fake TPU chips — SURVEY.md §4)."""

import os

# must be set before the XLA backend initializes
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

# Hermetic autotune cache: the fusion gates consult the per-checkout cache
# (.autotune_cache/), and a developer's local sweep recording a
# calibration factor would silently flip gate decisions inside the
# suite.  Point at a per-process temp file (explicit env still wins;
# autotune tests monkeypatch their own paths on top).
import tempfile

os.environ.setdefault(
    "PADDLE_TPU_AUTOTUNE_CACHE",
    os.path.join(tempfile.gettempdir(),
                 "paddle_tpu_autotune_test_%d.json" % os.getpid()))

# Analyzer brackets every rewrite pass with the static_analysis verifier
# (off by default in production, ON in tests): a pass that breaks
# producer/consumer links fails HERE with structured diagnostics instead
# of surfacing as an opaque trace-time JAX error downstream.
os.environ.setdefault("PADDLE_TPU_VERIFY_PASSES", "1")

import pytest

import jax


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavyweight tests excluded from the tier-1 "
                   "run (ROADMAP.md runs -m 'not slow')")


@pytest.fixture
def verify_clean():
    """Run ``verify_program`` on a program and assert no ERROR-severity
    findings; returns all diagnostics (advisories included) so tests can
    also assert on warnings.  Usage: ``verify_clean(program, targets=[...])``.
    """
    def _check(program, targets=None):
        from paddle_tpu.static_analysis import assert_valid

        return assert_valid(program, targets=targets)

    return _check


if not os.environ.get("PADDLE_TPU_TESTS_ON_TPU"):
    # tests run on the CPU backend whatever the machine holds, so they
    # are hermetic and can use the 8-device mesh.
    # PADDLE_TPU_TESTS_ON_TPU=1 leaves the default backend active — the
    # reference's backend-flag rerun pattern (unittests/mkldnn/* reruns
    # the same OpTest classes with use_mkldnn on; SURVEY §4): the op-test
    # files then execute on the chip with bf16-tolerant bounds.
    jax.config.update("jax_platforms", "cpu")
else:
    import pytest

    def pytest_collection_modifyitems(config, items):
        """TPU rerun covers the OpTest corpus only: non-OpTest tests
        assert CPU-tight tolerances (1e-5/1e-6) that bf16 MXU matmuls
        legitimately miss, and some drive multi-device meshes that the
        single chip doesn't have."""
        from op_test import OpTest

        mark = pytest.mark.skip(
            reason="TPU backend rerun covers OpTest classes only")
        for item in items:
            cls = getattr(item, "cls", None)
            if cls is None or not issubclass(cls, OpTest):
                item.add_marker(mark)
