"""Compile the main-path Pallas kernels for a described TPU v5e at real
widths — no chip needed.  The TPU compiler refuses here what interpret
mode cannot show: blocks not aligned to the (8, 128) tiling, scratch
shapes Mosaic cannot lay out, more VMEM than a kernel may use, and a
kernel GSPMD would have to partition.

Shapes: the lattice at the CRDNN cell (T≈318 after the stride-4 CNN,
B=32, U+1=61); stage-B Grams at 100 h scale (7 partitions of ~4,077
units, 64x64 sketches); the LM stage-A sketch at ``starcoder2-3b``
widths (d=3072, V=49152, k1=k2=64).  The topology is described inside a
fixture, never at import, and the persistent compilation cache is off
while these compile (entries compiled for a described chip cannot be
read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

pytestmark = pytest.mark.kernel


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_rnnt_lattice_compiles_for_v5e(one_chip):
    from repro.kernels.rnnt_lattice.kernel import rnnt_lattice
    x = _sds((318, 32, 61), one_chip)
    txt = _compiled_text(lambda m, a, e: rnnt_lattice(m, a, e,
                                                      interpret=False),
                         x, x, x)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("tn,td", [(256, 512), (100, 300)])
def test_omp_gram_batched_compiles_for_v5e(one_chip, tn, td):
    """Default tiles and an unaligned request (rounded to lane tiles)."""
    from repro.kernels.omp_gram.kernel import omp_gram_batched
    g = _sds((7, 4077, 4096), one_chip)
    txt = _compiled_text(lambda g: omp_gram_batched(g, tn=tn, td=td,
                                                    interpret=False), g)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("tn,tv", [(256, 0), (20, 1000)])
def test_grad_sketch_units_compiles_for_v5e(one_chip, tn, tv):
    """Auto vocab tile and an unaligned request (rounded to tiles)."""
    from repro.kernels.grad_sketch.kernel import grad_sketch_units
    U, n, d, V, k = 8, 256, 3072, 49152, 64
    args = (_sds((U, n, d), one_chip), _sds((d, V), one_chip),
            _sds((d, k), one_chip), _sds((V, k), one_chip),
            _sds((U, n), one_chip, jnp.int32), _sds((U, n), one_chip))
    txt = _compiled_text(
        lambda *a: grad_sketch_units(*a, tn=tn, tv=tv, interpret=False),
        *args)
    assert "tpu_custom_call" in txt


def _fused_loss_grad_args(sharding_of):
    B, T, U, J, V = 16, 300, 60, 1024, 1000
    return (_sds((B, T, J), sharding_of(3)), _sds((B, U + 1, J),
                                                  sharding_of(3)),
            _sds((J, V), sharding_of(0)),
            _sds((B, U), sharding_of(2), jnp.int32),
            _sds((B,), sharding_of(1), jnp.int32),
            _sds((B,), sharding_of(1), jnp.int32))


def _fused_loss_grad(part):
    from repro.core.rnnt_loss import rnnt_loss_fused

    def f(ze, zp, w, labels, t_lens, u_lens):
        loss = lambda ze, zp, w: rnnt_loss_fused(
            ze, zp, w, labels, t_lens, u_lens, lattice_impl="pallas",
            lattice_part=part).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(ze, zp, w)
    return f


def test_fused_rnnt_loss_grad_compiles_for_v5e(one_chip):
    """The CRDNN joint (J=1024, V=1000) loss + gradient with the compiled
    lattice kernel, on one chip."""
    args = _fused_loss_grad_args(lambda nd: one_chip)
    assert "tpu_custom_call" in _compiled_text(_fused_loss_grad(None),
                                               *args)


def test_fused_rnnt_loss_grad_compiles_on_v5e_data_mesh(topo):
    """On a 4x1 data mesh the lattice kernel runs under shard_map, one
    batch slice per chip — GSPMD cannot partition a Mosaic kernel."""
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 1), ("data", "model"), devices=topo.devices)

    def sharding_of(ndim):
        if ndim == 0:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P("data", *([None] * (ndim - 1))))
    args = _fused_loss_grad_args(sharding_of)
    txt = _compiled_text(_fused_loss_grad((mesh, "data")), *args)
    assert "tpu_custom_call" in txt
