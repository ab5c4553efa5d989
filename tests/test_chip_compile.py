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


def _compile_select(sharding, L, n, policy, cmask=True, d=D):
    Np, dsub = fk.select_event_geometry(n, d)
    args = ([_spec((L, dsub, Np), F32, sharding)] +
            [_spec((L, Np), I32, sharding)] * 4 +
            [_spec((L, Np), F32, sharding),
             _spec((L, dsub, 1), F32, sharding),
             _spec((L,), F32, sharding), _spec((L,), F32, sharding),
             _spec((L, dsub, 1), F32, sharding)] +
            ([_spec((L, Np), I32, sharding)] if cmask else []))
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


@pytest.mark.parametrize("L,n", [(56, 2048), (1, 512), (28, 2048),
                                 (1, 65536)])
def test_select_compiles_at_cell_geometries(one_chip, L, n):
    """The per-event select at the sweep cell's (56 lanes, 2048 slots) and
    the stream cell's (1, 512) geometries, one block of 28 lanes, and one
    lane at the 65,536-slot cap (2 MiB of loads: the block budget's
    edge)."""
    Np, dsub = fk.select_event_geometry(n, D)
    assert fk.select_lanes_per_block(L, Np, dsub) == (8 if L == 56 else L)
    compiled = _compile_select(one_chip, L, n, "best_fit_linf", cmask=False)
    assert "tpu_custom_call" in compiled.as_text()


def test_select_compiles_at_hybrid_cell_geometry(one_chip):
    """The select of the Huawei-like hybrid fleets
    (``bench/configs/huawei_east1.json``, ``sweep.hybrid``): 18 lanes (not
    a multiple of 8) in one block, a 64-slot pool (half a lane tile), d = 2 (six of the
    eight sublanes padding), first fit within the item's category
    (``cmask``)."""
    Np, dsub = fk.select_event_geometry(64, 2)
    assert (Np, dsub) == (128, 8)
    assert fk.select_lanes_per_block(18, 128, 8) == 18
    compiled = _compile_select(one_chip, 18, 64, "first_fit", cmask=True,
                               d=2)
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


def test_replay_block_compiles_at_picked_stream_geometry(one_chip):
    """The block size ``replay_stream`` picks for the stream cell's
    geometry (one lane, 512 slots, d = 5, 8,192 item rows, 2,048-event
    chunks) compiles for a v5e."""
    from repro.core.jaxsim import replay_block_events
    T = replay_block_events("best_fit_linf", backend="pallas", L=1,
                            max_bins=512, d=D, item_rows=8192,
                            chunk_events=2048)
    assert T > 1
    carry = jax.eval_shape(lambda: packed_init_carry("score", 1, 8192, 512,
                                                     D))
    compiled = _compile_block(one_chip, carry, "score", 512, T, 2048)
    assert "tpu_custom_call" in compiled.as_text()
