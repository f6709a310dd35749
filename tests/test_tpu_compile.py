"""Compiles for a described TPU v5e, no chip attached.

The TPU compiler is installed wherever jaxlib is, and it compiles for a
chip that is only described.  These tests compile the programs of the main
path at the widths the chip runs them, so what the chip's compiler would
refuse (a block that breaks the (8, 128) tiling, a dynamic VMEM scalar
load, too much fast memory) fails here.  They never run anything.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: a compile for a described chip is written to the cache but
    can never be read back without one."""
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype,weighted", [
    (jnp.float32, False), (jnp.float32, True), (jnp.bfloat16, False)])
def test_embedding_bag_compiles_at_mtwnd_full_width(one_chip, dtype,
                                                    weighted):
    from repro.kernels import ops
    from repro.models.paper_models import MTWND_PRESETS

    cfg = MTWND_PRESETS["full"]
    idx = _on(one_chip, (32, cfg["bag"]), jnp.int32)
    table = _on(one_chip, (cfg["vocab"], cfg["emb"]), dtype)
    if weighted:
        w = _on(one_chip, (32, cfg["bag"]), dtype)
        lowered = jax.jit(ops.embedding_bag).lower(idx, table, w)
    else:
        lowered = jax.jit(ops.embedding_bag).lower(idx, table)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mtwnd_forward_compiles_at_full_width(one_chip):
    """One full-width MT-WND step at batch 32 through the Pallas kernel,
    with its 8 x 200,000 x 64 tables in the chip's memory."""
    from repro.models.paper_models import (mtwnd_apply, mtwnd_init,
                                           mtwnd_input_spec)

    def place(s):
        return _on(one_chip, s.shape, s.dtype)

    params = jax.tree_util.tree_map(place, jax.eval_shape(
        partial(mtwnd_init, preset="full"), jax.random.PRNGKey(0)))
    batch = jax.tree_util.tree_map(place, mtwnd_input_spec("full", 32))
    compiled = jax.jit(partial(mtwnd_apply, use_kernel=True)).lower(
        params, batch).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 8 * 200_000 * 64 * 4
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 16 * 1024 ** 3)


def test_grid_counts_compile_at_wide_lane_shapes(one_chip):
    """The fused grid-count scan at the wide-lane shapes: W=4 load levels
    of the 1500-query mtwnd stream x B=32 configs, 32 slots."""
    from repro.serving import simulator as sim

    w, nq, b, s, n_types = 4, 1500, 32, 32, 3
    args = (_on(one_chip, (w, nq), jnp.float32),
            _on(one_chip, (nq, n_types), jnp.float32),
            _on(one_chip, (b, s), jnp.int32),
            _on(one_chip, (s,), jnp.float32),
            _on(one_chip, (b, s), jnp.float32),
            _on(one_chip, (s,), jnp.int32),
            _on(one_chip, (), jnp.float32))
    sim._grid_counts_jit.lower(*args).compile()


def test_spec_chunk_compiles_under_x64(one_chip):
    """The on-device workload chunk divides in float64, which the TPU
    emulates; it compiles only with x64 on, as ``generate_chunk`` runs it."""
    from repro.serving.workload import _spec_chunk

    f32 = partial(_on, one_chip, (), jnp.float32)
    with jax.enable_x64(True):
        args = (_on(one_chip, (2,), jnp.uint32), _on(one_chip, (2,),
                                                     jnp.uint32),
                _on(one_chip, (), jnp.int64), f32(), f32(), f32(), f32(),
                f32(), f32())
        compiled = _spec_chunk.lower(*args, chunk=4096,
                                     dist="lognormal").compile()
    assert compiled.memory_analysis() is not None


def test_gp_posterior_compiles(one_chip):
    """The GP fit and predict at the optimizer's padded shapes: 192
    observation slots over the mtwnd search lattice."""
    from repro.core.gp import gp_posterior
    from repro.serving.pool import DEFAULT_BOUNDS

    bounds = DEFAULT_BOUNDS["mtwnd"]
    n_lattice = int(np.prod([m + 1 for m in bounds]))
    args = (_on(one_chip, (192, 3), jnp.float32),
            _on(one_chip, (192,), jnp.float32),
            _on(one_chip, (192,), jnp.float32),
            _on(one_chip, (n_lattice, 3), jnp.float32),
            _on(one_chip, (3,), jnp.float32))
    gp_posterior.lower(*args).compile()
