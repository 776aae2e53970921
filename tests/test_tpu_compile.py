"""Ahead-of-time compiles for a described TPU v5e, no chip attached.

Interpret mode accepts block shapes, scalar-memory sizes and VMEM
footprints that Mosaic refuses, so these tests lower the main path with
`interpret=False` for a `v5e:2x2` topology described inside a fixture and
compile it at the widths `chip_smoke.py` runs: a scale-15 R-MAT graph
(32,768 vertices) blocked at Vb=64 — B_N=512 blocks, P=72,034 block
pairs, K_max=498 ELL slots — with 64 concurrent jobs.  A compile that
passes says the program is accepted and fits one chip; it says nothing
about results or times.

The topology is described only inside the `topo` fixture, never while a
module is imported, so every test worker collects the same tests and
only the worker running this file loads the TPU compiler.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

J, N, VB, P, K_MAX = 64, 32768, 64, 72034, 498
BN = N // VB
HBM_BYTES = 16 * 10**9                  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_interpret(monkeypatch):
    """Lower Pallas kernels for Mosaic although the process runs on CPU."""
    from repro.kernels import common
    monkeypatch.setattr(common, "default_interpret", lambda: False)


def _fits_one_chip(compiled) -> int:
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 1e9:.2f} GB does not fit one chip"
    return total


@pytest.mark.parametrize("semiring", ["plus_times", "min_plus"])
def test_fused_superstep_compiles_for_v5e(one_chip, semiring):
    """The fused kernel at phase-A widths, at the job chunk the wrapper
    picks: block shapes, the chained SMEM pair chunks and the VMEM
    footprint are all accepted by Mosaic."""
    from repro.kernels.fused_superstep.kernel import PAIR_CHUNK, _fused_jit
    from repro.kernels.fused_superstep.ops import _pick_job_block

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    meta = spec((P,), jnp.int32)
    state = spec((J, BN, VB))
    jb = _pick_job_block(J, VB, semiring)
    assert jb == J                      # 64 jobs fit one grid cell
    compiled = _fused_jit.lower(
        meta, meta, meta, meta, state, state, spec((P, VB, VB)),
        state if semiring == "min_plus" else None, semiring=semiring,
        job_block=jb, interpret=False).compile()
    # one Mosaic kernel per PAIR_CHUNK pairs: the [P] scalar-prefetch
    # metadata of one call must fit scalar memory
    assert compiled.as_text().count("tpu_custom_call") == -(-P // PAIR_CHUNK)
    _fits_one_chip(compiled)


def _device_step_shapes(sess, one_chip):
    """The session's device-superstep arguments as shapes on the
    described chip, with the view's adjacency at phase-A size."""
    from repro.core.policy import device_step_args
    from repro.graph.structure import BlockPairs

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = jax.tree.map(lambda x: spec(x.shape, x.dtype),
                        device_step_args(sess, 1000))
    state, scales, _, _, ovs, _, max_steps, key = args
    meta = spec((P,), jnp.int32)
    pairs = BlockPairs(num_pairs=P, block_size=VB, num_blocks=BN,
                       src=meta, dst=meta, slot=meta, first=meta, last=meta,
                       src_nnz=spec((BN,), jnp.int32),
                       tiles=spec((P, VB, VB), jnp.float32))
    return (state, scales, (spec((BN, K_MAX, VB, VB), jnp.float32),),
            (spec((BN, K_MAX), jnp.int32),), ovs, (pairs,), max_steps, key)


@pytest.mark.parametrize("alg,use_pallas", [
    ("sssp", False), ("sssp", True), ("pagerank", True)])
def test_device_superstep_compiles_for_v5e(one_chip, no_interpret, alg,
                                           use_pallas):
    """The whole jitted device superstep of `TwoLevel(backend="device",
    steps_per_sync=inf)` with 64 jobs on the phase-A view: the Pallas
    legs hold the kernel, the jnp leg does not, and each fits one chip."""
    from repro.algorithms import SSSP, PageRank
    from repro.core import Fused, GraphSession
    from repro.graph import CSRGraph

    v = np.arange(N)
    ring = CSRGraph.from_edges(N, v, (v + 1) % N)   # B_N=512, tiny tiles
    sess = GraphSession(ring, VB, capacity=J, use_pallas=use_pallas)
    sess.submit(SSSP(source=0) if alg == "sssp" else PageRank())
    step = sess._device_step_fn(Fused())
    compiled = step.lower(*_device_step_shapes(sess, one_chip)).compile()
    kernels = compiled.as_text().count("tpu_custom_call")
    assert (kernels > 0) == use_pallas
    total = _fits_one_chip(compiled)
    # the view's adjacency is what the step holds: ELL tiles on the jnp
    # leg, pair tiles on the kernel leg
    view = (P if use_pallas else BN * K_MAX) * VB * VB * 4
    assert total >= view
