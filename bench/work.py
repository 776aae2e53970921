"""The work that the fused superstep kernel is asked to do.

Counted from the schedule and the shapes, whatever implements it: the
adjacency tiles of the block pairs the schedule selected
(`RunMetrics.tile_pair_loads`, pairs whose source block was staged), and
the job state the kernel reads and writes once per superstep.  A kernel
that streams every pair whatever was selected moves more than this, and
reads as a lower share of its roofline.

  plus-times  reads the masked deltas and the consumed base, writes the
              new deltas: 3 state arrays; 2 * J * Vb**2 flops per pair
              (a [J, Vb] @ [Vb, Vb] product).
  min-plus    reads deltas, values and base, writes values and deltas:
              5 state arrays; no MXU work (add and min on the VPU).
"""

from __future__ import annotations

import re

F32 = 4
STATE_ARRAYS = {"plus_times": 3, "min_plus": 5}


def kernel_bytes(pair_loads: int, supersteps: int, *, jobs: int,
                 num_blocks: int, block: int, semiring: str) -> int:
    tiles = pair_loads * block * block * F32
    state = supersteps * STATE_ARRAYS[semiring] * jobs * num_blocks \
        * block * F32
    return int(tiles + state)


def kernel_flops(pair_loads: int, *, jobs: int, block: int,
                 semiring: str) -> int:
    if semiring != "plus_times":
        return 0
    return int(2 * jobs * block * block * pair_loads)


def roofline_seconds(nbytes: float, flops: float, peaks: dict) -> tuple:
    """(least time, "bytes" or "flops"): the larger of the two bounds."""
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    t_flops = flops / peaks["flops_per_s_bf16"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")


#: the kernel's operations in a reduced trace (`bench.xplane.short_name`):
#: its `pallas_call`s carry no `name=` yet, so each shows as a
#: `tpu_custom_call` named after the jitted wrapper, "%_fused_jit.<n>"
KERNEL_OPS = re.compile(r"^_fused_jit$")


def kernel_seconds(ops: dict) -> float:
    """Device seconds of the kernel's operations in a reduced trace."""
    return sum(t for name, t in ops.items() if KERNEL_OPS.search(name))
