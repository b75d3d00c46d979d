"""The four Pallas kernels compiled ahead of time for a v5e, with no chip
attached: each must carry its own name as the name of its custom-call
instruction, which is what an `XLA Ops` event of a profiler trace is
called and what `benchmarks/metrics/kernel.flash_*_roofline.train.py`
and the ledger's `device_ops` find it by. Also a guard that the kernels
still compile for the chip at a real head size.

The topology is described inside a module-scoped fixture (one process at
a time may load libtpu: nothing here touches it at import time), and all
such compiles live in this one file.
"""
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops import attention, norms
from ray_tpu.ops.dispatch import compute_platform


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compiled_text(topo):
    """One train-like program: a rematted layer of saveable flash
    attention (GQA, 8 heads over 4 of 128) and an rms_norm, forward and
    backward, so that every kernel is in it once or more."""
    from jax.experimental.compilation_cache import compilation_cache
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v, x, w):
        def layer(q, k, v, x):
            a = attention.flash_attention_saveable(q, k, v, causal=True)
            return a.astype(jnp.float32).sum() * norms.rms_norm(x, w)
        y = jax.checkpoint(layer)(q, k, v, x)
        return y.astype(jnp.float32).sum()

    # a compile for a described chip is written to the persistent cache
    # and cannot be read back without one: keep it out
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with compute_platform("tpu"):
            step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))
            return step.trace(shape(1, 8, 512, 128), shape(1, 4, 512, 128),
                              shape(1, 4, 512, 128), shape(512, 1024),
                              shape(1024)).lower().compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


def kernel_names(text: str):
    """Instruction names of the program's Mosaic custom calls, numbering
    dropped: `%flash_fwd.3 = ... custom_call_target="tpu_custom_call"`."""
    return [re.sub(r"[.\d]+$", "", m.group(1)) for m in re.finditer(
        r"^\s*%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text, re.M)]


@pytest.mark.parametrize("name", [
    attention.KERNEL_FWD, attention.KERNEL_BWD_DKDV,
    attention.KERNEL_BWD_DQ, norms.KERNEL_RMS_FWD])
def test_kernel_is_named_where_the_trace_shows_it(compiled_text, name):
    assert name in kernel_names(compiled_text)


def test_no_kernel_is_named_after_its_enclosing_call(compiled_text):
    names = kernel_names(compiled_text)
    assert set(names) == {"flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq",
                          "rms_norm_fwd"}
    # the benchmark's label for such an event is 'kernel:<name>'
    assert not {"closed_call", "checkpoint", "rematted_computation"} \
        & set(names)
