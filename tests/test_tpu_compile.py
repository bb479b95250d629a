"""The serving path's Pallas hop kernels compile for a TPU v5e chip.

Interpret mode checks what a kernel computes, not whether Mosaic accepts
its blocks.  These tests hand each kernel to the TPU compiler for a
described (not attached) v5e chip at the widths the served path uses:
frontier rows 1024, feature width 128, output widths 128 and 256, and the
PNA gather over a table of ogbn-arxiv's vertex count plus the zero
sentinel row.  Each compiled program must hold exactly one
``tpu_custom_call`` carrying the kernel's name.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, so under several test workers only
the worker that runs this file does.
"""
import functools

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.delta_apply import delta_apply
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.extremum_apply import extremum_apply
from repro.kernels.mlp_apply import mlp_apply

ROWS, D = 1024, 128
BAG_ROWS = 169_344


@pytest.fixture(scope="module")
def one_chip():
    """A sharding on one chip of a described v5e:2x2 host, with the
    persistent compile cache off (a compile for a described chip is
    written there but cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _tpu_kernels(fn, sharding, *shapes) -> list[str]:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _f32(*shape):
    return shape, jnp.float32


@pytest.mark.parametrize("dout", [128, 256])
@pytest.mark.parametrize("kernel", ["delta_apply", "extremum_apply",
                                    "extremum_apply_masked", "mlp_apply"])
def test_hop_kernel_compiles_for_v5e(one_chip, kernel, dout):
    rows = [_f32(ROWS, D), _f32(ROWS, D)]
    if kernel == "delta_apply":
        fn = functools.partial(delta_apply, mean=True, relu=True,
                               interpret=False)
        shapes = rows + [_f32(ROWS), _f32(D, dout), _f32(dout)]
    elif kernel == "extremum_apply":
        fn = functools.partial(extremum_apply, maximize=True, relu=True,
                               interpret=False)
        shapes = rows + [_f32(D, dout), _f32(dout)]
    elif kernel == "extremum_apply_masked":
        def fn(S, M, W, b, RG, MK):
            return extremum_apply(S, M, W, b, reagg=RG, mask=MK,
                                  maximize=False, relu=True,
                                  interpret=False)
        shapes = rows + [_f32(D, dout), _f32(dout)] + rows
    else:
        fn = functools.partial(mlp_apply, mean=True, relu=True,
                               interpret=False)
        shapes = rows + [_f32(ROWS, D), _f32(ROWS), _f32(), _f32(D, D),
                         _f32(D), _f32(D, dout), _f32(dout)]
    calls = _tpu_kernels(fn, one_chip, *shapes)
    assert len(calls) == 1 and f"%{kernel}" in calls[0], calls


@pytest.mark.parametrize("rows,width", [(ROWS, 32), (256, 1024),
                                        (8192, 32)])
def test_embedding_bag_compiles_for_v5e(one_chip, rows, width):
    """Index rectangles the PNA hop builds: the served frontier, the warm
    rung's wide one, and the deepest hop's tall one — both of the latter
    hold more indices than one SMEM prefetch."""
    calls = _tpu_kernels(
        functools.partial(embedding_bag_pallas, interpret=False), one_chip,
        ((rows, width), jnp.int32), _f32(BAG_ROWS, D))
    assert len(calls) == 1 and "%embedding_bag" in calls[0], calls
