"""Compile the Pallas kernels for one TPU v5e chip, at the sizes the chip
path runs (no chip needed: the TPU compiler targets a described topology).

Interpret-mode tests cannot see what Mosaic refuses: blocks off the
(8, 128) tiling, scalar stores to VMEM, over-budget VMEM or SMEM. These
compiles can. The topology is described inside a fixture, so importing
this file never loads the TPU library (a test worker that is not given
this file never touches it).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.costmodel import choose_tiles
from repro.kernels import bsr_spmv, decode_attend, gamma_score, tsne_force


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n,nbr,f", [
    (1 << 20, 16, 1),          # a charge vector at SIFT1M scale
    (1 << 20, 16, 128),        # a 128-charge block
    (1 << 18, 160, 1),         # the chip smoke's plan: wide ELL rows
    (1 << 18, 160, 128),
    (1 << 17, 158, 128),       # the interact-f128 cell's plan
])
def test_bsr_spmv_batched_compiles(one_chip, n, nbr, f):
    bs = 32
    n_rb = n // bs
    rbs, chunk, fc = choose_tiles((n, bs, 8, n_rb, n_rb, nbr), f)
    xshape = (1, n) if f == 1 else (1, n, f)

    def run(v, i, x):
        return bsr_spmv.bsr_spmv_batched(v, i, x, rbs=rbs, chunk=chunk,
                                         fc=fc)

    compiled = jax.jit(run).lower(
        _spec((1, n_rb, nbr, bs, bs), jnp.float32, one_chip),
        _spec((1, n_rb, nbr), jnp.int32, one_chip),
        _spec(xshape, jnp.float32, one_chip)).compile()
    assert _has_kernel(compiled)


def test_single_plan_spmv_copies_tiles_twice(one_chip, monkeypatch):
    """The pallas backend's program at the interact-f128 cell's plan
    makes two full copies of the tiles on their way to the panel layout
    (a swap of two major dims, then the transpose), and no third (a pad
    of the tiles before the transpose would be one)."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    n, bs, nbr = 1 << 17, 32, 158
    n_rb = n // bs

    def run(v, i, x):
        return ops.bsr_spmv(v, i, x, n, shape_key=(n, bs, 8, n_rb, n_rb,
                                                   nbr))

    compiled = jax.jit(run).lower(
        _spec((n_rb, nbr, bs, bs), jnp.float32, one_chip),
        _spec((n_rb, nbr), jnp.int32, one_chip),
        _spec((n, 128), jnp.float32, one_chip)).compile()
    tiles = n_rb * nbr * bs * bs
    made = [line.strip() for line in compiled.as_text().splitlines()
            if _writes_f32(line) >= tiles and "parameter(" not in line
            and " bitcast(" not in line]
    assert _has_kernel(compiled)
    assert len(made) == 2, made


def _writes_f32(line: str) -> int:
    """Elements of the float32 array an HLO instruction line defines."""
    if " = f32[" not in line:
        return 0
    out = 1
    for d in line.split(" = f32[", 1)[1].split("]", 1)[0].split(","):
        out *= int(d)
    return out


@pytest.mark.parametrize("dtype,n_sel,has_self", [
    (jnp.bfloat16, 16, True),  # the decode service's default budget
    (jnp.float32, 256, False),  # full coverage of a 32k cache
])
def test_decode_attend_fused_compiles(one_chip, dtype, n_sel, has_self):
    b, hq, hkv, dh, s, bk = 4, 14, 2, 64, 32768, 128   # qwen2-0.5b heads

    def run(q, k, v, p, ix, qp, ks, vs):
        return decode_attend.decode_attend_fused(
            q, k, v, p, ix, qp, ks, vs, bk=bk, has_self=has_self)

    compiled = jax.jit(run).lower(
        _spec((b, hq, dh), dtype, one_chip),
        _spec((b, hkv, s, dh), dtype, one_chip),
        _spec((b, hkv, s, dh), dtype, one_chip),
        _spec((b, hkv, s), jnp.int32, one_chip),
        _spec((b, hkv, n_sel), jnp.int32, one_chip),
        _spec((b,), jnp.int32, one_chip),
        _spec((b, hkv, dh), dtype, one_chip),
        _spec((b, hkv, dh), dtype, one_chip)).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("symmetric", [True, False])
def test_gamma_pairs_compiles(one_chip, symmetric):
    nnz = 1 << 16

    def run(c, w):
        return gamma_score.gamma_pairs(c, 7.0, 256, weights=w,
                                       symmetric=symmetric)

    compiled = jax.jit(run).lower(
        _spec((nnz, 2), jnp.float32, one_chip),
        _spec((nnz,), jnp.float32, one_chip)).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("bs,d", [(32, 2), (16, 3)])
def test_tsne_force_compiles(one_chip, bs, d):
    n, nbr = 1 << 14, 24
    n_rb = n // bs
    compiled = jax.jit(tsne_force.tsne_force).lower(
        _spec((n_rb, nbr, bs, bs), jnp.float32, one_chip),
        _spec((n_rb, nbr), jnp.int32, one_chip),
        _spec((n, d), jnp.float32, one_chip)).compile()
    assert _has_kernel(compiled)
