"""Compiled-artifact contract checker.

Lowers the REAL device superstep (`core.policy.build_device_step`) for a
policy on a live session — the same program `_run_device` dispatches —
and asserts contracts on the compiled HLO and the run it drives:

  one-sync        the inf-cadence program is one fused while-loop with no
                  host callbacks (infeed/outfeed/send/recv, host
                  custom-calls): a whole run costs exactly ONE blocking
                  device->host transfer, and a real run's
                  RunMetrics.host_syncs confirms it.
  no-f64          nothing in the program (or the host-backend pairs/counts
                  reductions) promotes to f64 — x64 is off, so an f64 in
                  the HLO means someone flipped it on and doubled traffic.
  vmem-budget     the Pallas per-grid-cell footprints (`mj_spmm`: tile +
                  temp + 2 job stripes; `fused_superstep`: pair tile +
                  per-job state stripes + pair counters;
                  `priority_pairs`: one Vb stripe + counters) fit the
                  shared `kernels.common.VMEM_BUDGET` and the ~16 MB/core
                  hardware ceiling for every view's Vb.
  tile-bytes      a measured run's `RunMetrics.tile_pair_loads` — real
                  nonzero (src, dst) block pairs moved, priced at Vb^2
                  fp32 each — never exceeds the HBM traffic the compiled
                  artifact can account for: the static body estimate
                  (hlo_analysis.estimate_hbm_bytes) scaled by supersteps
                  executed, since the convergence loop's trip count is a
                  runtime argument the estimate cannot see.
  push-flops      the plus-times push is MXU-shaped: the lowered program
                  carries real dot flops (parse_dot_flops > 0), i.e. the
                  semiring product did not degrade to scalar gathers.

`check_all()` builds a small canonical session (one plus-times + one
min-plus view, the same shape the regression tests pin) and sweeps the
policy matrix; the CLI exposes it as ``python -m repro.analysis
--contracts`` and tests/test_analysis_contracts.py locks the checker
itself (including that a deliberately broken 1-sync program is flagged).
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import List

from repro.launch import hlo_analysis as H

_HOST_CALLBACK_RE = re.compile(
    r"\b(infeed|outfeed|send(?:-done)?|recv(?:-done)?)\(|"
    r"custom-call[^\n]*(?:xla_python_cpu_callback|HostCompute|"
    r"annotate_device_placement[^\n]*host)")

#: hardware ceiling per core (pallas guide: ~16 MB VMEM on current TPUs)
VMEM_HW_LIMIT = 16 * 2**20


@dataclasses.dataclass
class ContractResult:
    name: str
    ok: bool
    detail: str

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def lower_device_superstep(sess, policy, max_steps: int = 1024):
    """Lower the exact program `_run_device` would dispatch for `policy`
    on `sess` (with the driver's own `device_step_args`); returns
    (compiled, hlo_text)."""
    from repro.core.policy import build_device_step, device_step_args

    lowered = build_device_step(policy, sess).lower(
        *device_step_args(sess, max_steps))
    compiled = lowered.compile()
    return compiled, compiled.as_text()


def host_callback_sites(hlo: str) -> List[str]:
    return [m.group(0) for m in _HOST_CALLBACK_RE.finditer(hlo)]


def check_one_sync(hlo: str, *, expect_while: bool = True
                   ) -> ContractResult:
    """Static half of the 1-sync invariant: the inf-cadence program keeps
    the whole run inside one while-loop and surfaces NO mid-flight host
    hops — the only transfer left is the driver's single device_get of
    the result tuple."""
    sites = host_callback_sites(hlo)
    has_while = " while(" in hlo or "=while(" in hlo.replace(" ", "")
    if sites:
        return ContractResult(
            "one-sync", False,
            f"{len(sites)} host-callback site(s) in the superstep HLO "
            f"(first: {sites[0][:60]!r}) — each is a hidden sync")
    if expect_while and not has_while:
        return ContractResult(
            "one-sync", False,
            "inf-cadence program lowered without a while-loop: the "
            "convergence loop fell back to the host (one sync per "
            "superstep)")
    return ContractResult(
        "one-sync", True,
        "single fused while-loop, zero host callbacks" if expect_while
        else "zero host callbacks")


def check_no_f64(hlo: str, label: str = "superstep") -> ContractResult:
    n = hlo.count("f64[")
    if n:
        line = next(ln for ln in hlo.splitlines() if "f64[" in ln)
        return ContractResult(
            "no-f64", False,
            f"{n} f64 tensor(s) in the {label} HLO (first: "
            f"{line.strip()[:80]!r})")
    return ContractResult("no-f64", True, f"no f64 tensors in {label}")


def mj_spmm_vmem_bytes(capacity: int, vb: int) -> int:
    """Per-grid-cell VMEM for the mj_spmm kernel at job count `capacity`:
    tile [Vb,Vb] + min-plus temp [Vb,Vb] + in/out job stripes [Jb,Vb],
    fp32 — the same arithmetic `_pick_job_block` budgets against."""
    from repro.kernels.mj_spmm.ops import _pick_job_block
    jb = _pick_job_block(capacity, vb)
    return 2 * vb * vb * 4 + 2 * jb * vb * 4


def mj_spmm_hbm_fetch_bytes(q: int, k: int, capacity: int, vb: int) -> int:
    """Input HBM bytes one mj_spmm dispatch actually fetches, counted per
    grid step.  Grid (q, K, J/Jb) with jt INNERMOST: the adjacency tile's
    index (i, kk) is unchanged across the inner jt sweep (one fetch per
    (i, k) — the CAJS revisit), but the d-chunk's index (i, jt) changes
    at every grid step, so d is re-fetched K times per job chunk — q * K
    * (J/Jb) fetches, NOT one per (i, jt).  Only the J/Jb == 1 degenerate
    grid keeps d resident across k (its index is then constant per i)."""
    from repro.kernels.mj_spmm.ops import _pick_job_block
    jb = _pick_job_block(capacity, vb)
    jt = capacity // jb
    d_fetches = q * jt if jt == 1 else q * k * jt
    tile_fetches = q * k
    return d_fetches * jb * vb * 4 + tile_fetches * vb * vb * 4


def fused_superstep_vmem_bytes(capacity: int, vb: int,
                               semiring: str) -> int:
    """Per-grid-cell VMEM for the fused megakernel at the job chunk its
    `_pick_job_block` chooses for `capacity` jobs (`block_vmem_bytes`:
    padded, double-buffered pair tile and [Jb, Vb] state stripes)."""
    from repro.kernels.fused_superstep.ops import (_pick_job_block,
                                                   block_vmem_bytes)
    return block_vmem_bytes(_pick_job_block(capacity, vb, semiring), vb,
                            semiring)


def priority_pairs_vmem_bytes(vb: int) -> int:
    """Per-cell footprint of the priority_pairs kernel: one [Vb] priority
    stripe plus the (node_un, p_sum) accumulator pair, fp32."""
    return (vb + 2) * 4


def check_vmem_budget(sess) -> List[ContractResult]:
    from repro.kernels.common import VMEM_BUDGET
    out: List[ContractResult] = []
    for g in sess.view_groups():
        vb = g.graph.block_size
        spmm = mj_spmm_vmem_bytes(g.capacity, vb)
        pairs = priority_pairs_vmem_bytes(vb)
        sem = getattr(g, "semiring", None) or "plus_times"
        if sem not in ("plus_times", "min_plus"):
            sem = "plus_times"
        fused = fused_superstep_vmem_bytes(g.capacity, vb, sem)
        budget = min(VMEM_BUDGET, VMEM_HW_LIMIT)
        ok = spmm <= budget and pairs <= budget and fused <= budget
        out.append(ContractResult(
            "vmem-budget", ok,
            f"view {g.key!r} Vb={vb}: mj_spmm {spmm} B, fused_superstep "
            f"{fused} B, priority_pairs {pairs} B vs budget {budget} B"))
    return out


def check_tile_bytes(hlo: str, metrics, vb: int) -> ContractResult:
    """Cross-check the measured schedule against the compiled artifact:
    the real adjacency bytes the run moved — RunMetrics.tile_pair_loads
    nonzero (src, dst) block pairs at Vb^2 fp32 each, the sparse
    BlockPairs accounting — must fit within the HBM traffic the HLO can
    generate.  The lowered superstep's convergence while-loop has a
    DYNAMIC trip count (max_steps is a runtime argument), so the static
    estimate counts the loop body — one superstep — once; the program's
    accountable traffic is therefore body-estimate x supersteps
    executed.  (For finite-K cadences whose constant-trip scan is
    already folded into the estimate this over-allows by K, which only
    loosens an upper bound.)  Falls back to the coarser tile_loads /
    host_syncs for metrics predating the pair accounting."""
    n = int(getattr(metrics, "tile_pair_loads", 0) or metrics.tile_loads)
    staged = n * vb * vb * 4
    steps = int(getattr(metrics, "supersteps", 0) or metrics.host_syncs)
    capacity = H.estimate_hbm_bytes(hlo) * max(1, steps)
    ok = staged <= capacity
    return ContractResult(
        "tile-bytes", ok,
        f"measured pair loads={n} -> {staged} B real adjacency bytes "
        f"staged vs {capacity} B HLO-accountable HBM traffic "
        f"({steps} supersteps)")


def check_push_flops(hlo: str) -> ContractResult:
    flops = H.parse_dot_flops(hlo)
    ok = flops > 0
    return ContractResult(
        "push-flops", ok,
        f"{flops:.3g} dot flops in the lowered superstep"
        + ("" if ok else " — the plus-times push lost its dot (gather/"
                         "scalar fallback)"))


def _canonical_session(seed: int = 0, use_pallas: bool = False):
    """Small two-view session (plus-times PageRank + min-plus SSSP) — the
    same canonical shape the regression suites pin.  use_pallas=True
    routes the push through the fused superstep megakernel (interpret
    mode off-TPU), lowering the Pallas path into the checked program."""
    from repro.algorithms import PageRank, SSSP
    from repro.core import GraphSession
    from repro.graph import rmat_graph
    sess = GraphSession(rmat_graph(200, 5, seed=7), 32, capacity=2,
                        seed=seed, use_pallas=use_pallas)
    sess.submit(PageRank())
    sess.submit(SSSP(source=0))
    return sess


def check_device_contracts(sess=None, policy=None,
                           run_budget: int = 2000) -> List[ContractResult]:
    """The inf-cadence device contract bundle for one session/policy."""
    from repro.core import TwoLevel
    if sess is None:
        sess = _canonical_session()
    if policy is None:
        policy = TwoLevel(backend="device", steps_per_sync=math.inf)
    expect_while = policy.steps_per_sync == math.inf
    _, hlo = lower_device_superstep(sess, policy)
    results = [check_one_sync(hlo, expect_while=expect_while),
               check_no_f64(hlo)]
    results.extend(check_vmem_budget(sess))
    results.append(check_push_flops(hlo))
    m = sess.run(policy, run_budget)
    vb = sess.view_groups()[0].graph.block_size
    results.append(check_tile_bytes(hlo, m, vb))
    if expect_while:
        ok = m.converged and m.host_syncs == 1
        results.append(ContractResult(
            "one-sync-runtime", ok,
            f"run: converged={m.converged} host_syncs={m.host_syncs} "
            f"(contract: converged with exactly 1)"))
    return results


def check_host_programs(sess=None) -> List[ContractResult]:
    """Host-backend contracts: the per-group pairs/counts reductions the
    host driver dispatches each superstep carry no f64 and no host
    callbacks (they are pure device reductions; the driver's device_get
    of their outputs is the one sanctioned sync)."""
    if sess is None:
        sess = _canonical_session()
    out: List[ContractResult] = []
    for g in sess.view_groups():
        for label, fn in (("pairs", sess._pairs_fn(g)),
                          ("counts", sess._counts_fn(g))):
            hlo = fn.lower(g.values, g.deltas).compile().as_text()
            out.append(check_no_f64(hlo, f"{label}[{g.key!r}]"))
            sites = host_callback_sites(hlo)
            out.append(ContractResult(
                f"host-{label}-pure", not sites,
                f"view {g.key!r}: {len(sites)} host-callback site(s)"))
    return out


def check_all() -> List[ContractResult]:
    """The CI sweep: device inf-cadence + K=4 cadence + host programs,
    then the same inf-cadence bundle with use_pallas=True — the fused
    superstep megakernel lowered into the one-while-loop program (VMEM
    budget, zero host callbacks, pair-based tile bytes)."""
    from repro.core import TwoLevel
    results: List[ContractResult] = []
    sess = _canonical_session()
    results += check_device_contracts(
        sess, TwoLevel(backend="device", steps_per_sync=math.inf))
    sess2 = _canonical_session()
    results += check_device_contracts(
        sess2, TwoLevel(backend="device", steps_per_sync=4))
    results += check_host_programs(_canonical_session())
    results += check_device_contracts(
        _canonical_session(use_pallas=True),
        TwoLevel(backend="device", steps_per_sync=math.inf))
    return results
