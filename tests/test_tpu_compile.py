"""The stream tick's Pallas kernels compile for a TPU v5e chip.

Each test hands one kernel, at the shapes ``chip_smoke.py`` runs
(8192-event batches, 2^24-slot slate tables), to the TPU compiler for a
*described* v5e chip — no chip attached — and checks that the kernel is
in the compiled program.  This catches what interpret mode cannot: block
shapes the chip's tiling refuses, refs Mosaic cannot index, scoped
memory overruns.  A compile that passes is not a chip run.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.countmin import kernel as cm_kernel
from repro.kernels.histogram import kernel as hist_kernel
from repro.kernels.slate_lookup import kernel as lookup_kernel
from repro.kernels.slate_update import kernel as update_kernel
from repro.slates.table import PROBES

B = 8192          # events per tick
C = 1 << 24       # slates per table
D = 8             # packed slate width (core/packing.LANE_ALIGN)
Q = 1 << 17       # batched read: every key a smoke run touches


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_kernels(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("op", ["sum", "max"])
def test_slate_update_compiles(one_chip, op):
    calls = _compiled_kernels(
        lambda k, d, s, t: update_kernel.slate_update(k, d, s, t, op=op),
        one_chip, ((B,), jnp.int32), ((B, D), jnp.float32),
        ((B,), jnp.int32), ((C, D), jnp.float32))
    assert len(calls) == 1 and "%slate_update" in calls[0]


def test_slate_lookup_compiles(one_chip):
    calls = _compiled_kernels(
        lookup_kernel.slate_lookup, one_chip, ((C,), jnp.int32),
        ((Q,), jnp.int32), ((PROBES, Q), jnp.int32), ((C, D), jnp.float32))
    assert len(calls) == 1 and "%slate_lookup" in calls[0]


def test_countmin_compiles(one_chip):
    calls = _compiled_kernels(
        cm_kernel.countmin_update, one_chip, ((2, 2048), jnp.int32),
        ((2, B), jnp.int32), ((B,), jnp.int32))
    assert len(calls) == 1 and "%countmin_update" in calls[0]


def test_histogram_compiles(one_chip):
    calls = _compiled_kernels(
        hist_kernel.histogram_update, one_chip, ((1, 128), jnp.int32),
        ((1, B), jnp.int32), ((B,), jnp.int32))
    assert len(calls) == 1 and "%histogram_update" in calls[0]
