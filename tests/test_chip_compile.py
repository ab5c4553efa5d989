"""The replay kernels compile for a TPU v5e at the chip smoke's geometries.

No chip is needed: the TPU compiler compiles for a described v5e:2x2
topology.  Interpret-mode tests cannot catch what this does - block shapes
that break the (8, 128) tiling rule, SMEM loads of whole arrays, blocks
that outgrow VMEM.  The topology is described inside a module fixture
(never at import time), so every test worker collects the same tests and
only the worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.jaxsim import make_live_carry, packed_init_carry
from repro.kernels import fitscore as fk

F32, I32 = jnp.float32, jnp.int32
D = 5


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_select(sharding, L, n, policy):
    Np, dpad, _, _ = fk.select_pad_geometry(n, D)
    args = ([_spec((L, Np, dpad), F32, sharding)] +
            [_spec((L, Np), I32, sharding)] * 4 +
            [_spec((L, Np), F32, sharding), _spec((L, dpad), F32, sharding),
             _spec((L,), F32, sharding), _spec((L,), F32, sharding),
             _spec((L, dpad), F32, sharding), _spec((L, Np), I32, sharding)])
    fn = jax.jit(lambda *a: fk.fitscore_select_batch_padded(
        *a, policy=policy, n=n))
    return fn.lower(*args).compile()


def _compile_block(sharding, carry, family, n, T, C, migrate=False):
    carry = {k: _spec(v.shape, v.dtype, sharding) for k, v in carry.items()}
    L, _, dpad = carry["loads"].shape
    ev_i = {nm: _spec((L, C), I32, sharding)
            for nm in ("kind", "item") + fk.REPLAY_EV_I[family]}
    ev_f = {nm: _spec((L, C), F32, sharding)
            for nm in ("t", "pdep") + fk.REPLAY_EV_F[family]}
    fn = jax.jit(lambda c, a, b, s, m: fk.fitscore_replay_chunk(
        c, a, b, s, m, block_events=T, family=family,
        policy="best_fit_linf" if family == "score" else "first_fit",
        n=n, d=D, migrate=migrate))
    return fn.lower(carry, ev_i, ev_f, _spec((L, C, dpad), F32, sharding),
                    _spec((L, dpad), F32, sharding)).compile()


@pytest.mark.parametrize("L,policy", [(4, "nrt_prioritized"),
                                      (28, "best_fit_linf")])
def test_select_compiles_for_v5e(one_chip, L, policy):
    """The per-event select with more than one lane: the default sweep path
    on TPU (``block_events=0``)."""
    compiled = _compile_select(one_chip, L, 256, policy)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("family", ["score", "rcp", "hybrid"])
def test_replay_block_compiles_at_stream_pool(one_chip, family):
    """The streamed-replay pool: one lane, 4096 item rows, 512 slots,
    256-event blocks (hybrid's ``hagg`` and every family's ``itemi`` are
    the largest VMEM blocks)."""
    carry = jax.eval_shape(lambda: packed_init_carry(family, 1, 4096, 512,
                                                     D))
    compiled = _compile_block(one_chip, carry, family, 512, 256, 4096,
                              migrate=family == "rcp")
    assert "tpu_custom_call" in compiled.as_text()


def test_replay_block_compiles_for_serving_live_carry(one_chip):
    """The serving live carry at every default block geometry."""
    from repro.serving.dispatch import DEFAULT_GEOMETRIES
    carry = jax.eval_shape(lambda: make_live_carry("cbd", 64, D, 1024))
    for T in DEFAULT_GEOMETRIES:
        compiled = _compile_block(one_chip, carry, "cbd", 64, T, T)
        assert "tpu_custom_call" in compiled.as_text()


def test_replay_block_rejects_geometry_past_vmem():
    """A pool too large for VMEM is refused up front, naming the geometry,
    instead of failing inside the compiler."""
    carry = jax.eval_shape(lambda: packed_init_carry("hybrid", 1, 200_000,
                                                     64, D))
    T = 8
    dpad = carry["loads"].shape[2]
    ev_i = {nm: jax.ShapeDtypeStruct((1, T), I32)
            for nm in ("kind", "item") + fk.REPLAY_EV_I["hybrid"]}
    ev_f = {nm: jax.ShapeDtypeStruct((1, T), F32)
            for nm in ("t", "pdep") + fk.REPLAY_EV_F["hybrid"]}
    with pytest.raises(ValueError, match="item_rows=200000"):
        jax.eval_shape(lambda c, a, b, s, m: fk.fitscore_replay_block(
            c, a, b, s, m, family="hybrid", policy="first_fit", n=64, d=D),
            carry, ev_i, ev_f, jax.ShapeDtypeStruct((1, T, dpad), F32),
            jax.ShapeDtypeStruct((1, dpad), F32))
