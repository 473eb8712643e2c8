"""Test harness: force an 8-device CPU mesh.

This is the "fake backend" SparkRDMA never had (SURVEY.md §4): real
``all_to_all`` semantics on any machine via XLA's forced host platform,
standing in for an 8-chip ICI mesh.

The forcing runs here, at import, because the mesh must exist before the
first backend use; the recipe lives in the shared ``_hostmesh`` module at
the repo root, also used by ``__graft_entry__.dryrun_multichip``'s
subprocess child.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _hostmesh import force_cpu_devices  # noqa: E402

assert force_cpu_devices(8), "forced 8-device CPU mesh unavailable"

import jax  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 forced CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def runtime():
    from sparkrdma_tpu import MeshRuntime, ShuffleConf

    rt = MeshRuntime(ShuffleConf(slot_records=256))
    yield rt
    rt.stop()


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def native_codec():
    """Build (incremental ``make``) and load the native staging library
    with the serde codec entry points; tests needing the native path
    depend on this fixture and skip cleanly on hosts without a C++
    toolchain, keeping tier-1 green everywhere."""
    from sparkrdma_tpu.api.serde import native_codec_available

    if not native_codec_available():
        pytest.skip("native serde codec unavailable "
                    "(no C++ toolchain or unsupported object layout)")
    return True
