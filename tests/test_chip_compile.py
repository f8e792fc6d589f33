"""The TPU's compiler, asked without a chip: the main path's programs at
the shapes chip_smoke.py drives them compile for a *described* v5e
device (tools/chip_compile.py holds the program table and runs the
minutes-long ones by hand).

Nothing executes here — a pass says the chip's compiler accepts the
program, never that it is right or fast.  The topology is described
inside a module-scoped fixture: only the xdist worker that is given
this file loads libtpu, and every worker collects the same tests.
"""
import importlib.util
import os

import pytest

import jax
from jax.sharding import SingleDeviceSharding

_spec = importlib.util.spec_from_file_location(
    "chip_compile", os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "chip_compile.py"))
cc = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cc)

HBM_BYTES = 16 * 1024 ** 3      # one v5e chip

# under about a minute each in the sandbox; the rest trace for tens of
# seconds and compile for minutes (EC scans, Pallas engines)
QUICK = ("route", "route_hubs", "mcf")
SLOW = tuple(n for n in cc.PROGRAMS if n not in QUICK)
PALLAS = tuple(n for n in cc.PROGRAMS if n.startswith("pallas"))


@pytest.fixture(scope="module")
def one_chip():
    try:
        topo = cc.describe_topology()
    except Exception as e:
        pytest.skip(f"no {cc.TOPOLOGY} topology can be described here: {e}")
    # an executable compiled for a described device cannot be read back
    # without the chip: keep it out of the persistent cache
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_and_check(name, one_chip):
    compiled, _, _ = cc.compile_program(name, one_chip)
    mem = compiled.memory_analysis()
    need = mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes
    assert need < HBM_BYTES, f"{name} needs {need} bytes on the device"
    if name in PALLAS:
        # the Mosaic kernel itself, not an interpreted stand-in
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", QUICK)
def test_compiles_for_a_described_v5e(name, one_chip):
    _compile_and_check(name, one_chip)


@pytest.mark.slow
@pytest.mark.parametrize("name", SLOW)
def test_compiles_for_a_described_v5e_slow(name, one_chip):
    _compile_and_check(name, one_chip)


def test_program_table_covers_every_selectable_engine():
    """Every engine resolve_dual_mul / LIGHTNING_TPU_VERIFY_PREP can
    select has a compile entry, beside the main path's programs."""
    from lightning_tpu.crypto import secp256k1 as S

    for engine in PALLAS:
        if engine == "pallas_prep":
            assert S.resolve_prep("pallas") is not None
        else:
            assert S.resolve_dual_mul(engine) is not None
    assert len(PALLAS) == 6
    assert {"fused_verify_mb4", "fused_verify_mb8", "sign_simple",
            "route", "route_hubs", "mcf"} <= set(cc.PROGRAMS)
