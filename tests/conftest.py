import os

# Smoke tests and benches must see ONE device (the 512-device override lives
# exclusively in launch/dryrun.py and the subprocess sharding tests).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)


@pytest.fixture(autouse=True)
def _reset_sharding_state():
    """Tests may register (fake) meshes / seq-parallel flags; never leak."""
    yield
    from repro.sharding import set_mesh
    from repro.sharding.specs import set_manual_axes, set_seq_parallel

    set_mesh(None)
    set_manual_axes(())
    set_seq_parallel(False)


@pytest.fixture(scope="module")
def described_chip():
    """One chip of a described v5e:2x2, to compile for with no TPU attached,
    with the persistent compile cache off (a program compiled for a
    described chip cannot be read back). Described inside a module-scoped
    fixture, never while a module is imported: only one process at a time
    may load the TPU library, and pytest-xdist workers must all collect the
    same tests."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
