"""Test configuration.

Tests run on CPU with 8 virtual XLA devices so that multi-chip sharding
(tp/dp/sp meshes) is exercised without TPU hardware — the same seam the
driver's dryrun uses. Must be set before jax is imported anywhere.
"""

import os

# Force CPU even on a machine with a chip (where jax would default to
# it): the tests must not depend on hardware, a chip belongs to one
# process at a time, and e2e subprocess pods inherit this environment.
os.environ["JAX_PLATFORMS"] = "cpu"
# Engine pods the tests start place jax's persistent compile cache in
# the checkout (engine/coldstart.py), and KUBEAI_COLDSTART_OVERLAP=auto
# would then add a background AOT compile of every step shape to each
# pod start. Tier-1 has no time for that; tests of the overlap ask for
# it explicitly.
os.environ.setdefault("KUBEAI_COLDSTART_OVERLAP", "0")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Thread-dump-on-timeout: tier-1 runs under `timeout -k 10 870`, which
# kills a wedged run SILENTLY. Schedule a faulthandler dump of every
# thread's stack shortly before that deadline so a future hang produces
# a diagnosis instead of nothing. exit=False: diagnostic only — the
# driver's timeout still owns the kill.
import faulthandler  # noqa: E402

faulthandler.enable()
faulthandler.dump_traceback_later(timeout=840, exit=False)

import pytest  # noqa: E402

# The CPU backend's oneDNN fastmath path computes f32 matmuls at ~bf16
# precision (observed ~1e-1 abs error vs f64); force full precision so
# numerical comparisons against transformers are meaningful.
import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")
# Belt and braces: if jax was imported before this conftest (plugin import
# order), the env var above is too late — set the config directly too.
jax.config.update("jax_platforms", "cpu")


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax

    devices = jax.devices()
    assert len(devices) >= 8, "expected 8 virtual CPU devices"
    return devices
