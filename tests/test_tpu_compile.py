"""Compile-only checks of the main-path Pallas kernels for a TPU v5e chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a *described* ``v5e:2x2`` topology.  That catches what interpret mode
cannot — lowerings Mosaic lacks (a value scatter) and blocks that
overflow the scoped VMEM — at the local sizes the apps run on the chip.
Nothing executes.  The topology is described inside a fixture (never at
import time) and the tests skip where it cannot be described.
"""

import math
import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.solver3d import ops as sops
from repro.kernels.stencil3d.ops import heat_step


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compiled_hlo(fn, shapes, sharding, dtype=jnp.float32):
    args = [jax.ShapeDtypeStruct(s, dtype, sharding=sharding) for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# Heat3D at 256^3 local: the plain step, and the shapes
# hide_communication(width=(16, 2, 2)) launches on a mesh that splits
# every dim: x/y/z boundary slabs of 2h + w rows and the (n - 2w) interior.
HEAT_SHAPES = [(256, 256, 256), (18, 256, 256), (256, 4, 256),
               (256, 256, 4), (224, 252, 252)]


@pytest.mark.parametrize("shape", HEAT_SHAPES)
def test_heat_step_compiles(one_chip, shape):
    bx = dispatch.pick_bx(shape, 4, dispatch.VMEM_BLOCKS["heat"])
    assert bx is not None
    hlo = _compiled_hlo(
        lambda T, Ci: heat_step(T, Ci, 1.0, 1e-6, 0.01, 0.01, 0.01,
                                use_kernel="pallas"),
        [shape, shape], one_chip)
    assert "tpu_custom_call" in hlo


# The Poisson3D smoke size: the finest level (130^3 local) and the
# coarsest (6^3), where bx is the whole extent.
@pytest.mark.parametrize("n", [130, 6])
@pytest.mark.parametrize("op", ["residual", "jacobi", "cheb"])
def test_center_solver_kernels_compile(one_chip, op, n):
    shape = (n, n, n)
    sp = (1.0 / (n - 1),) * 3
    fns = {
        "residual": (lambda u, c, f: sops.residual_op(
            u, c, f, spacing=sp, use_kernel="pallas"), 3),
        "jacobi": (lambda u, c, f, dia: sops.jacobi_sweep(
            u, c, f, dia, omega=0.8, spacing=sp, use_kernel="pallas"), 4),
        "cheb": (lambda u, c, f, dia, d: sops.cheb_sweep(
            u, c, f, dia, d, a=0.5, b=0.3, spacing=sp, use_kernel="pallas"),
            5),
    }
    fn, nargs = fns[op]
    hlo = _compiled_hlo(fn, [shape] * nargs, one_chip)
    assert "tpu_custom_call" in hlo


def test_face_jacobi_compiles(one_chip):
    shape = (34, 34, 34)
    sp = (1.0 / 33,) * 3
    hlo = _compiled_hlo(
        lambda u, c, f, dia, m: sops.jacobi_sweep(
            u, c, f, dia, omega=0.8, spacing=sp, loc="yface", imask=m,
            use_kernel="pallas"),
        [shape] * 5, one_chip)
    assert "tpu_custom_call" in hlo


# (mesh dims, {scope: kernel shapes}) of the hidden 256^3 step with
# width=(16, 2, 2): one chip has nothing to exchange and runs the plain
# step; v5e:2x2 as (2, 2, 1) launches x and y slabs and an interior that
# spans z whole.
HIDDEN_PHASES = [
    ((1, 1, 1), {"hide.interior": [(256, 256, 256)]}),
    ((2, 2, 1), {"hide.shell": [(18, 256, 256)] * 2 + [(256, 4, 256)] * 2,
                 "hide.interior": [(224, 252, 256)]}),
]


@pytest.mark.parametrize("dims,phases", HIDDEN_PHASES)
def test_hidden_heat_step_names_its_kernels_by_phase(topo, dims, phases):
    """The hidden 256^3 step as ``Heat3D`` builds it: its heat kernels
    are ``stencil3d_heat`` instructions, the slab launches of the
    exchanging dims under ``hide.shell`` and the interior launch under
    ``hide.interior``."""
    from repro import telemetry as tele
    from repro.core.grid import ImplicitGlobalGrid
    from repro.core.topology import make_grid_mesh

    g = ImplicitGlobalGrid(256, 256, 256, mesh=make_grid_mesh(
        3, dims, devices=topo.devices[:math.prod(dims)]))

    @g.parallel
    def dstep(T, Ci):
        return g.hide(lambda T, Ci: heat_step(
            T, Ci, 1.0, 1e-6, 0.01, 0.01, 0.01, use_kernel="pallas"),
            (T, Ci), width=(16, 2, 2))

    field = jax.ShapeDtypeStruct(g.stacked_shape, jnp.float32,
                                 sharding=g.sharding)
    text = dstep.lower(field, field).compile().as_text()
    kernels = re.findall(
        r"%(stencil3d_heat\.\d+) = f32\[([\d,]+)\]\S* custom-call\(", text)
    scopes = tele.op_scopes(text)
    found = {}
    for name, shape in kernels:
        found.setdefault(scopes[name], []).append(
            tuple(int(n) for n in shape.split(",")))
    assert {k: sorted(v) for k, v in found.items()} == {
        k: sorted(v) for k, v in phases.items()}
