"""The tensor-parallel products that carry their own collectives
(parallel/collective_matmul.py), against ``lax.dot_general`` on the 8-device
CPU mesh: values and both gradients, for the four products of a block, on a
ring of two and of four."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from fleetx_tpu.parallel import collective_matmul
from fleetx_tpu.parallel.mesh import MeshConfig, build_mesh, use_mesh
from fleetx_tpu.parallel.sharding import make_rules

# name -> (kernel's logical axes, x shape, w shape, dimension numbers)
PRODUCTS = {
    "qkv_proj": (("embed", "heads", "kv"), (4, 16, 32), (32, 4, 24),
                 (((2,), (0,)), ((), ()))),
    "out_proj": (("heads", "kv", "embed"), (4, 16, 4, 8), (4, 8, 32),
                 (((2, 3), (0, 1)), ((), ()))),
    "up_proj": (("embed", "mlp"), (4, 16, 32), (32, 64),
                (((2,), (0,)), ((), ()))),
    "down_proj": (("mlp", "embed"), (4, 16, 64), (64, 32),
                  (((2,), (0,)), ((), ()))),
}


def _operands(name):
    _, x_shape, w_shape, dims = PRODUCTS[name]
    rng = np.random.RandomState(len(name))
    return (jnp.asarray(rng.randn(*x_shape), jnp.float32),
            jnp.asarray(rng.randn(*w_shape), jnp.float32), dims)


@pytest.mark.parametrize("mp", [2, 4])
@pytest.mark.parametrize("name", list(PRODUCTS))
def test_product_and_gradients_match_the_plain_product(eight_devices, name, mp):
    mesh = build_mesh(MeshConfig(dp=8 // mp, mp=mp))
    x, w, dims = _operands(name)
    with use_mesh(mesh), nn.logical_axis_rules(
            make_rules(sequence_parallel=True)):
        dot = collective_matmul.for_kernel(PRODUCTS[name][0])
        assert dot is not None

        def loss(fn, x, w):
            y = fn(x, w, dims)
            return (y * jnp.cos(y)).sum(), y

        (_, y), grads = jax.jit(jax.value_and_grad(
            lambda x, w: loss(dot, x, w), argnums=(0, 1), has_aux=True))(x, w)
        text = jax.jit(lambda x, w: dot(x, w, dims)).lower(x, w).compile(
            ).as_text()
    (_, want), want_grads = jax.value_and_grad(
        lambda x, w: loss(lax.dot_general, x, w), argnums=(0, 1),
        has_aux=True)(x, w)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    for got, ref in zip(grads, want_grads):
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=5e-3)
    # one step a device of the ring, one shift fewer; no gather, no scatter
    assert text.count(" collective-permute(") + text.count(
        " collective-permute-start(") == mp - 1
    assert " all-gather(" not in text and " all-reduce(" not in text


@pytest.mark.parametrize("case", ["no-mesh", "mp1", "kernel-off-mp"])
def test_where_lax_dot_general_is_the_right_one(eight_devices, case):
    """``for_kernel`` hands back None, and ``nn.DenseGeneral`` then runs
    ``lax.dot_general`` as if nothing had been passed: without a mesh,
    without an ``mp`` axis to ride, and for a kernel that is whole."""
    rules = make_rules(sequence_parallel=True)
    if case == "no-mesh":
        with nn.logical_axis_rules(rules):
            assert collective_matmul.for_kernel(("embed", "mlp")) is None
        return
    layout, axes = {"mp1": (dict(dp=8), ("embed", "mlp")),
                    "kernel-off-mp": (dict(dp=4, mp=2), ("embed", "norm"))}[case]
    with use_mesh(build_mesh(MeshConfig(**layout))), nn.logical_axis_rules(
            rules):
        assert collective_matmul.for_kernel(axes) is None


@pytest.mark.parametrize("name", ["up_proj", "down_proj"])
@pytest.mark.parametrize("case", ["all-reduce-form", "context-parallel",
                                  "pipeline"])
def test_where_the_partitioner_lays_the_product_out(eight_devices, case, name):
    """With ``Model.sequence_parallel: False``, where the rows are sharded
    over ``cp`` as well, and under pipeline stages the product is the plain
    one (no step, no collective-permute of its own), a column product's
    output laid out by name: every row of the ``cp`` share, a share of the
    hidden units."""
    layout, rules = {
        "all-reduce-form": (dict(dp=4, mp=2), dict(sequence_parallel=False)),
        "context-parallel": (dict(dp=2, cp=2, mp=2), dict(
            sequence_parallel=True, context_parallel=True)),
        "pipeline": (dict(dp=2, pp=2, mp=2), dict(sequence_parallel=True)),
    }[case]
    x, w, dims = _operands(name)
    mesh = build_mesh(MeshConfig(**layout))
    with use_mesh(mesh), nn.logical_axis_rules(make_rules(**rules)):
        dot = collective_matmul.for_kernel(PRODUCTS[name][0])
        compiled = jax.jit(lambda x, w: dot(x, w, dims)).lower(x, w).compile()
        y = compiled(x, w)
    np.testing.assert_allclose(y, lax.dot_general(x, w, dims), rtol=1e-5,
                               atol=1e-5)
    assert "collective-permute" not in compiled.as_text()
    if name == "up_proj":
        rows = "cp" if case == "context-parallel" else None
        assert tuple(y.sharding.spec)[1:] == (rows, "mp"), y.sharding


def test_rows_that_do_not_divide_take_the_plain_product(eight_devices):
    """A decode tick's one row, or a batch the data axes do not divide."""
    mesh = build_mesh(MeshConfig(dp=4, mp=2))
    w = jnp.ones((32, 64), jnp.float32)
    dims = (((2,), (0,)), ((), ()))
    with use_mesh(mesh), nn.logical_axis_rules(
            make_rules(sequence_parallel=True)):
        dot = collective_matmul.for_kernel(("embed", "mlp"))
        for shape in ((4, 1, 32), (3, 16, 32)):
            x = jnp.ones(shape, jnp.float32)
            text = jax.jit(lambda x, w: dot(x, w, dims)).lower(
                x, w).compile().as_text()
            assert "collective-permute" not in text
