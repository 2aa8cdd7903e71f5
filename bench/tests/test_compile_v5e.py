"""Each cell's main programs compiled for one described TPU v5e at the
cell's real shapes: no chip needed, and what the chip's compiler refuses
(tiling, VMEM, HBM over 16 GB) shows here. The topology is described in
a fixture, never while a module is imported."""
import os
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

N, D, K, BS, SB = 1 << 17, 128, 16, 32, 8
# the widest ELL row of the gauss64-128d plan (data_seed 0), as the plan
# built on the chip reports it: the cell's real tile shape
MAX_NBR = 158


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled) -> int:
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9
    return used


def test_interact_matvec_compiles(one_chip, monkeypatch):
    """``plan.matvec`` of the interact cell as the plan runs it: permute,
    the program's own ``pallas`` backend on a 128-charge block, unpermute.
    The CPU backend would interpret the kernel, so the test asks for the
    compiled one, as a TPU backend does."""
    from repro.core.registry import get_backend
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    n_rb = N // BS
    spec = SimpleNamespace(shape_key=(N, BS, SB, n_rb, n_rb, MAX_NBR))

    def matvec(vals, col_idx, pi, inv, x):
        plan = SimpleNamespace(bsr=SimpleNamespace(vals=vals,
                                                   col_idx=col_idx),
                               spec=spec, n=N)
        y = get_backend("pallas")(plan, jnp.take(x, pi, axis=0))
        return jnp.take(y, inv, axis=0)

    compiled = jax.jit(matvec).lower(
        _spec((n_rb, MAX_NBR, BS, BS), jnp.float32, one_chip),
        _spec((n_rb, MAX_NBR), jnp.int32, one_chip),
        _spec((N,), jnp.int32, one_chip), _spec((N,), jnp.int32, one_chip),
        _spec((N, 128), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_build_knn_compiles(one_chip):
    """The plan build's device part: the exact kNN over 2^17 points."""
    from repro.core import knn

    compiled = knn.knn_graph.lower(
        _spec((N, D), jnp.float32, one_chip),
        _spec((N, D), jnp.float32, one_chip), K, exclude_self=True).compile()
    _fits(compiled)


def test_reference_candidates_compile(one_chip):
    """The reference's candidate pass at the cells' size."""
    from bench.reference import knn_spmv

    compiled = knn_spmv._candidates.lower(
        _spec((N, D), jnp.float32, one_chip), m=K + 16, block=512).compile()
    _fits(compiled)
