"""Compiles for a described TPU v5e, with no chip attached.

The TPU compiler is installed wherever JAX's TPU support is, and it
compiles for a chip that is described rather than present.  That catches
what the Pallas interpreter cannot: a kernel Mosaic refuses, or a fused
program whose kernel call fell back to the XLA path.  Covered here, at
rec_ippo's registry-default widths on smax_lite (rollout 128, 64 envs x 3
agents, hidden 64): the recurrent-scan kernel forward and its gradient,
and the fused anakin program of rec_ippo with the linear core.

The topology is described only inside the module fixture, never while a
module is imported: only one process at a time may load the TPU library,
and test collection happens in every worker.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.core.system import make_anakin
from repro.kernels.recurrent_scan import ops
from repro.systems.registry import make_pair

T, B, H = 128, 64 * 3, 64


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        from jax.experimental import topologies

        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU support in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _scan_args(sharding):
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
    reset = jax.ShapeDtypeStruct((T, B), jnp.bool_, sharding=sharding)
    return f32((T, B, H)), f32((T, B, H)), f32((B, H)), reset


def test_recurrent_scan_forward_compiles_for_v5e(one_chip):
    compiled = ops.linear_recurrent_scan.lower(
        *_scan_args(one_chip), interpret=False
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_recurrent_scan_grad_compiles_for_v5e(one_chip):
    def loss(a, b, h0, reset):
        return jnp.sum(ops.linear_recurrent_scan(a, b, h0, reset, interpret=False))

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    compiled = grad.lower(*_scan_args(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rec_ippo_linear_program_compiles_with_kernel(one_chip, monkeypatch):
    # On a TPU backend the default dispatch picks the kernel; here the CPU
    # is the backend, so steer it.  Traces cached under either choice must
    # not leak into other tests, hence the cache clears on both sides.
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    jax.clear_caches()
    try:
        _, system = make_pair("rec_ippo", "smax_lite", recurrent_core="linear")
        program = make_anakin(system, 512, 64)
        shapes = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
            jax.eval_shape(program.init_fn, jax.random.key(0)),
        )
        compiled = program.fused.lower(shapes).compile()
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in compiled.as_text()
