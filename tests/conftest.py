import os
import sys

# tests must see exactly ONE device (the dry-run sets 512 in its own process)
assert "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""), \
    "tests must run without the dry-run's device-count override"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# the plain references the benchmark keeps (``chipbench``) back some tests
sys.path.insert(1, os.path.join(os.path.dirname(__file__), ".."))

import pytest


@pytest.fixture
def expect_compiles():
    """The runtime compile-count sanitizer (``repro.lint``) as a fixture:
    ``with expect_compiles(n): run(...)`` asserts the block builds exactly
    ``n`` engine artifacts (and names the forking keys when it doesn't)."""
    from repro import lint
    return lint.expect_compiles


# hypothesis is optional (see requirements-dev.txt); property tests fall back
# to the deterministic sampler in tests/_hyp_compat.py when it is absent.
try:
    from hypothesis import settings
except ImportError:
    settings = None

if settings is not None:
    settings.register_profile("ci", max_examples=25, deadline=None)
    settings.load_profile("ci")
