"""The serving and tuning kernels compile for a TPU v5e chip.

Interpret mode accepts block shapes the chip's compiler refuses, so these
tests lower each kernel at serving widths for a described (not attached)
``v5e:2x2`` topology and compile it with the installed TPU compiler.
Nothing runs.  The topology is described inside a fixture, never at
import: the TPU library admits one process at a time, and every test
worker imports this file.  All compiles stay in this one file so that one
worker holds that library.
"""
import functools
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.candidate_score.kernel import affine_scores_pallas
from repro.kernels.fused_descent.kernel import (KERNEL_NAME,
                                                fused_descent_pallas,
                                                fused_descent_windows)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _descent_args(L, P, Q, sharding):
    """The kernel's arguments: (2, Q) query words, kinds (L,), the two
    int32 key-word planes and the two float32 band planes."""
    return ([_shape((2, Q), jnp.int32, sharding),
             _shape((L,), jnp.int32, sharding)]
            + [_shape((L, 1, P), jnp.int32, sharding)] * 2
            + [_shape((L, 1, P), jnp.float32, sharding)] * 2)


@pytest.mark.parametrize("L,P,Q", [(1, 128, 256), (3, 4096, 4096),
                                   (4, 1024, 65536)])
def test_fused_descent_compiles_for_v5e(one_chip, no_persistent_cache,
                                        L, P, Q):
    compiled = fused_descent_pallas.lower(
        *_descent_args(L, P, Q, one_chip), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_descent_custom_call_keeps_its_name(one_chip,
                                                  no_persistent_cache):
    """A profiler trace names the kernel's events by its custom call in the
    compiled HLO: the ``pallas_call``'s own ``name=``, whatever the jitted
    wrapper around it is called."""
    L, P, Q = 2, 1536, 1024
    args = _descent_args(L, P, Q, one_chip)

    @functools.partial(jax.jit, static_argnames=("interpret",))
    def renamed_wrapper(*a, interpret):
        return fused_descent_pallas.__wrapped__(*a, interpret=interpret)

    text = renamed_wrapper.lower(*args, interpret=False).compile().as_text()
    calls = [ln.split(" = ", 1)[0].split()[-1] for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1, calls
    assert re.match(rf"^%{KERNEL_NAME}\b", calls[0]), calls


@pytest.mark.parametrize("L,P,Q", [(1, 256, 1024), (2, 1536, 2048),
                                   (1, 512, 4096)])
def test_fused_descent_batch_entry_is_one_kernel(one_chip,
                                                 no_persistent_cache, L, P, Q):
    """The serving engine's one compiled call a batch (the two-word
    queries, kernel, covering entry and both ends stacked) holds exactly
    one custom call, under the kernel's name, so a profiler trace still
    shows one kernel event a call."""
    lowered = fused_descent_windows.lower(*_descent_args(L, P, Q, one_chip),
                                          interpret=False)
    assert lowered.out_info.shape == (3, L, Q)
    text = lowered.compile().as_text()
    calls = [ln.split(" = ", 1)[0].split()[-1] for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1, calls
    assert re.match(rf"^%{KERNEL_NAME}\b", calls[0]), calls


def test_candidate_score_compiles_for_v5e(one_chip, no_persistent_cache):
    C, S = 64, 1024
    compiled = affine_scores_pallas.lower(
        _shape((C, S), jnp.float32, one_chip),
        _shape((S,), jnp.float32, one_chip),
        ell=1e-4, inv_bw=1e-9, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
