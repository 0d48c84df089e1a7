"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts: misaligned Pallas
blocks, scalar stores into VMEM, programs larger than the chip's HBM.
These tests compile the contingency kernels, the device engine and the
exact core at the kdd99 flagship's widths (granule capacity 2^17, 41
attributes, 23 classes, v_max 10) for one chip of a described ``v5e:2x2``
topology, so such a regression fails here and costs no chip time.  Nothing
runs: results are covered by the interpret-mode tests and by
``chip_smoke.py`` on the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import importlib.util
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.engine import SelectionState, make_engine_run
from repro.core.granularity import build_granularity
from repro.core.plan import ladder_rungs
from repro.core.reduction import _exact_core, _exact_core_ids
from repro.kernels.contingency.fused import fused_theta_pallas
from repro.kernels.contingency.kernel import contingency_pallas
from repro.kernels.contingency.model import select_tiles
from repro.kernels.contingency.sweep import sweep_theta_pallas
from repro.launch.roofline import PEAKS, chip_peaks

CAP, A, N_DEC, V_MAX = 2 ** 17, 41, 23, 10
NC, M = 64, 128                           # candidates per launch, lane-padded classes
RUNG = ladder_rungs(CAP * V_MAX)[4]       # one ladder rung of bins (4096)
V5E_HBM_BYTES = 16 * 2 ** 30


def _smoke_mp_chunk() -> int:
    """The kdd99 mp_chunk chip_smoke.py runs with (its one home)."""
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.KDD99_MP_CHUNK


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without one: keep the cache out of this file.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _is_mosaic(lowered) -> bool:
    return "tpu_custom_call" in lowered.compile().as_text()


def test_described_chip_has_peaks(topo):
    dev = topo.devices[0]
    assert dev.platform == "tpu"
    assert chip_peaks(dev) is PEAKS[dev.device_kind]


def test_contingency_kernel_compiles(spec):
    bk, bg = select_tiles("contingency", NC, CAP, RUNG, M)
    low = contingency_pallas.lower(
        spec((NC, CAP), jnp.int32), spec((CAP, M), jnp.float32),
        n_bins=RUNG, bk=bk, bg=bg, interpret=False)
    assert _is_mosaic(low)


@pytest.mark.parametrize("delta", ["PR", "SCE", "LCE", "CCE"])
def test_fused_kernel_compiles(spec, delta):
    bk, bg = select_tiles("fused", NC, CAP, RUNG, M, delta=delta)
    low = fused_theta_pallas.lower(
        spec((NC, CAP), jnp.int32), spec((CAP, M), jnp.float32),
        n_bins=RUNG, delta=delta, bk=bk, bg=bg, interpret=False)
    assert _is_mosaic(low)


@pytest.mark.parametrize("delta", ["PR", "SCE", "LCE", "CCE"])
def test_sweep_kernel_compiles(spec, delta):
    bc, bk, bg = select_tiles("sweep", NC, CAP, RUNG, M, v_max=V_MAX,
                              delta=delta)
    assert bc % 8 == 0
    low = sweep_theta_pallas.lower(
        spec((NC, CAP), jnp.int32), spec((CAP,), jnp.int32),
        spec((CAP, M), jnp.float32), v_max=V_MAX, n_bins=RUNG, delta=delta,
        bc=bc, bk=bk, bg=bg, interpret=False)
    assert _is_mosaic(low)


def test_engine_fits_hbm_at_smoke_mp_chunk(spec):
    runner = make_engine_run("SCE", "incremental", "segment", A, CAP, N_DEC,
                             V_MAX, 1e-6, 1e-5, False, A, _smoke_mp_chunk())
    vec = lambda dtype: spec((CAP,), dtype)
    scalar = lambda dtype: spec((), dtype)
    state = SelectionState(
        r_ids=vec(jnp.int32), h1=vec(jnp.uint32), h2=vec(jnp.uint32),
        active=vec(jnp.bool_), remaining=spec((A,), jnp.bool_),
        theta_history=spec((A,), jnp.float32), order=spec((A,), jnp.int32),
        k=scalar(jnp.int32), theta_r=scalar(jnp.float32),
        pr_correction=scalar(jnp.float32), n_selected=scalar(jnp.int32))
    compiled = runner.lower(
        state, spec((CAP, A), jnp.int32), vec(jnp.int32), vec(jnp.int32),
        scalar(jnp.int32), scalar(jnp.float32), spec((A,), jnp.int32),
        scalar(jnp.int32)).compile()
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < V5E_HBM_BYTES, f"{total / 2**30:.1f} GiB"


def test_grc_merge_compiles(spec):
    # the streaming fold's largest merge: accumulator at capacity + a chunk
    n = CAP + 65536
    compiled = build_granularity.lower(
        spec((n, A), jnp.int32), spec((n,), jnp.int32), n_dec=N_DEC,
        v_max=V_MAX, w=spec((n,), jnp.int32), valid=spec((n,), jnp.bool_),
        exact=True, capacity=CAP).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < V5E_HBM_BYTES


def test_exact_core_compiles(spec):
    # the core's pair ids (prefix and suffix scans, a two-key sort a
    # subset) once a shape, and its contingency and Θ once a measure
    vec = lambda dtype: spec((CAP,), dtype)
    ids = _exact_core_ids.lower(spec((CAP, A), jnp.int32), vec(jnp.bool_),
                                v_max=V_MAX).compile()
    mem = ids.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 2 ** 30
    _exact_core("SCE", CAP, N_DEC).lower(
        spec((A, CAP), jnp.int32), vec(jnp.int32), vec(jnp.int32),
        vec(jnp.bool_), spec((), jnp.int32)).compile()
