"""Test configuration: force an 8-device virtual CPU platform.

Multi-chip TPU hardware is not available in CI; JAX's host-platform device
virtualization gives every test a deterministic 8-device mesh — the
"fake backend" story the reference never had (its only distributed test,
ImageNetLoaderSpec, was @ignore'd; see SURVEY.md section 4).
"""

import os

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# No persistent compile cache under test: cli.main() would otherwise point
# every worker at the checkout's cache directory (utils/compile_cache.py),
# and a tier-1 run must leave `git status` clean. Children inherit this.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

REFERENCE = "/root/reference"


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`; slow covers the multi-GB big-model
    # proofs (tests/test_fsdp.py::TestOneBigModel) that compile for
    # minutes on a 1-core CI box
    config.addinivalue_line(
        "markers", "slow: multi-minute / multi-GB tests, excluded from "
        "the tier-1 sweep")


def _skip_without_reference():
    if not os.path.isdir(REFERENCE):
        pytest.skip(f"reference checkout absent: {REFERENCE}")


def reference_path(*parts):
    """A path inside the reference checkout; where the checkout is absent
    the calling test skips, with that reason."""
    _skip_without_reference()
    return os.path.join(REFERENCE, *parts)


def _reference_or_skip():
    """Most tests of the stock prototxts spell the path themselves (in
    parametrize lists, where nothing can skip) and used to die on
    FileNotFoundError: a missing file UNDER an absent checkout is the same
    skip. Any other error, and a missing file in a checkout that is there,
    stays a failure."""
    try:
        return (yield)
    except FileNotFoundError as e:
        if str(e.filename or "").startswith(REFERENCE + os.sep):
            _skip_without_reference()
        raise


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    return (yield from _reference_or_skip())


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    return (yield from _reference_or_skip())
