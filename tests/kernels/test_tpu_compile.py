"""The flush kernels compile for a TPU v5e at xlstm-350m's padded P.

Interpret mode (test_hybrid_aggregate.py) checks the kernels' values;
only the chip's own compiler refuses what a real chip would (tile
alignment, VMEM limits, HBM that a program does not fit in).  The TPU
compiler is installed without a chip, so each case compiles for a
*described* v5e chip and asserts the kernel was lowered as a Mosaic
custom call.  The arguments are shapes only, at the slab length the
cluster server stages for ``zoo:xlstm`` at ``zoo_scale=1.0``, in both
operand forms: a stacked ``(K, P)`` matrix (the SPMD merge) and K
separate ``(P,)`` rows (the server's held slabs); the moment-carrying
kernels donate what the server donates.

The topology is described inside a fixture (never at import): one
process at a time may load the TPU library, and every pytest worker
imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.hybrid_aggregate import (flush_adamw_pallas,
                                            flush_momentum_pallas,
                                            flush_pallas)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def padded_p():
    from repro.core.slab import slab_codec
    from repro.models import model as M
    from repro.models.zoo import zoo_config
    cfg = zoo_config("xlstm", 1.0)
    params = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    return slab_codec(params).padded_size


@pytest.fixture(scope="module")
def no_compile_cache():
    # a chip-targeted compile is written to a persistent cache but
    # cannot be read back without the chip: keep these out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _flush(rows, w):
    return flush_pallas(rows, w, out_dtype=jnp.float32)


def _momentum(rows, w, mu):
    return flush_momentum_pallas(rows, w, mu, 0.9, out_dtype=jnp.float32)


def _adamw(rows, w, p, mu, nu, bc1, bc2, scale):
    return flush_adamw_pallas(rows, w, p, mu, nu, bc1, bc2, scale,
                              b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)


# kernel -> (fn, row dtype, extra (P,) f32 slabs, scalars, donated args).
# The moment kernels stage bf16 rows: with f32 rows at K=4, AdamW's
# rows, params, moments and their fresh outputs need 16.4 GB, over the
# chip's 15.75 GB
KERNELS = {
    "flush_f32": (_flush, jnp.float32, 0, 0, ()),
    "flush_bf16": (_flush, jnp.bfloat16, 0, 0, ()),
    "momentum": (_momentum, jnp.bfloat16, 1, 0, (2,)),
    "adamw": (_adamw, jnp.bfloat16, 3, 3, (2, 3, 4)),
}


def _compiles(kernel, K, one_chip, padded_p, rows_form):
    fn, dtype, n_slabs, n_scalars, donate = KERNELS[kernel]
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    rows = [sds((padded_p,), dtype)] * K if rows_form \
        else sds((K, padded_p), dtype)
    args = ([rows, sds((K,), jnp.float32)]
            + [sds((padded_p,), jnp.float32)] * n_slabs
            + [sds((), jnp.float32)] * n_scalars)
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("K", [1, 2, 4])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_flush_kernel_compiles_for_v5e(kernel, K, one_chip, padded_p,
                                       no_compile_cache):
    _compiles(kernel, K, one_chip, padded_p, rows_form=False)


@pytest.mark.parametrize("K", [1, 2, 4])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_flush_kernel_rows_form_compiles_for_v5e(kernel, K, one_chip,
                                                 padded_p,
                                                 no_compile_cache):
    _compiles(kernel, K, one_chip, padded_p, rows_form=True)
