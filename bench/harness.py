"""One run of one cell: set-up, the measured window, the check, the metrics.

A cell is an entry of `BENCHMARK.json`'s `workloads`.  Everything it names
is found by name under the benchmark's root:

  configs[...]["file"]          the deployment: graph, engine, algorithm,
                                the limits of its comparison
  bench/traffic/<mix>.json      the traffic; its `kind` names the driver
  bench/drivers/<kind>.py       `Driver`: set-up, window, queries
  bench/algorithms/<alg>.py     the job, its reference and its numbers
  bench/metrics/<metric>.py     `read(run)`: one metric from the run record;
                                a name `<base>.<part>` without a file of its
                                own is read by `bench/metrics/<base>.py`

so a later cell is new files and new entries, never an edit here.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import pathlib
import resource
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from bench import graph500, xplane

ROOT = pathlib.Path(__file__).resolve().parents[1]
#: how long past the window's close a request may still complete before it
#: counts as failed
DRAIN_S = 60.0


class ChipMissing(RuntimeError):
    """No accelerator of the kind the cell needs (no result is printed)."""


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise ValueError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return Cell(
        name=name,
        config=json.loads((root / cfg["file"]).read_text()),
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json")
            .read_text()),
        chips=int(w["chips"]),
        end_to_end=[m for m in spec["end_to_end"] if _in_cell(m, name)],
        per_layer=[m for m in spec["per_layer"] if _in_cell(m, name)])


def load_module(root: pathlib.Path, kind: str, name: str):
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(root: pathlib.Path, name: str):
    """The reader of metric `name`: its own file, else its base's (a
    quantity split by the end-to-end metric it moves, such as
    `device_idle_share.batch` and `.serve`, shares one reader)."""
    if not (root / "bench" / "metrics" / f"{name}.py").is_file():
        name = name.split(".")[0]
    return load_module(root, "metrics", name)


def load_peaks(root: pathlib.Path, kind: str) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table["kinds"]:
        raise ChipMissing(f"device kind {kind!r} is not in bench/peaks.json "
                          f"(have {sorted(table['kinds'])})")
    return table["kinds"][kind]


# ---------------------------------------------------------------------------
# what a run records, and what the metric readers read
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Query:
    source: int
    due_s: float                       # window clock
    submit_s: Optional[float] = None   # window clock, when submitted
    done_s: Optional[float] = None     # window clock, result read back
    result: Optional[np.ndarray] = None


class Recorder:
    """Host spans (also written into the profiler trace as `bench.<name>`,
    so idle gaps can be charged to them) and the program's counters,
    summed over the runs made while `counting` is on."""

    def __init__(self):
        self.spans: Dict[str, List[float]] = {}
        self.counters: Dict[str, float] = {}
        self.batches: List[dict] = []
        self.counting = False

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.spans.setdefault(name, []).append(
                    time.perf_counter() - t0)

    def add(self, m) -> None:
        """Sum one `RunMetrics` into the window's counters."""
        if not self.counting:
            return
        for k in ("supersteps", "tile_loads", "tile_pair_loads",
                  "job_block_pushes", "host_syncs"):
            self.counters[k] = self.counters.get(k, 0) + getattr(m, k)


@dataclasses.dataclass
class Run:
    """The record a metric reader gets (`bench/metrics/<name>.py`)."""

    cell: Cell
    setup_s: float
    window_s: float          # window open to the last result read back
    queries: List[Query]
    recorder: Recorder
    shapes: dict             # jobs, num_blocks, block, num_pairs, semiring
                             # (from the graph and the traffic)
    peaks: dict
    trace: Optional[dict]    # bench.xplane reduction, --trace 1 only

    @property
    def completed(self) -> List[Query]:
        return [q for q in self.queries if q.done_s is not None]

    @property
    def latencies_s(self) -> List[float]:
        """Due to read back, per query; +inf for one never completed."""
        return [q.done_s - q.due_s if q.done_s is not None else math.inf
                for q in self.queries]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


class CompileCounter:
    """Programs compiled, or loaded from the persistent cache, while open
    (JAX's monitoring events)."""

    def __enter__(self):
        import jax
        self.compiles = self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._compile)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _compile(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._compile)
        jax.monitoring.unregister_event_listener(self._event)
        return False


def check_device(cell: Cell, root: pathlib.Path):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise ChipMissing(f"needs a TPU; JAX found {devs[0].platform} "
                          f"({devs[0].device_kind})")
    if len(devs) < cell.chips:
        raise ChipMissing(f"cell {cell.name} needs {cell.chips} chips; "
                          f"JAX sees {len(devs)}")
    return load_peaks(root, devs[0].device_kind)


def make_graph(config: dict, rng: np.random.Generator) -> graph500.Graph:
    """The configuration's Graph500 graph and search keys (both from its
    `graph_seed`), re-labelled for this run by `graph500.reblock`.  The
    generator makes the spec's undirected graph with permuted labels only,
    so a configuration stating otherwise is refused."""
    for key in ("undirected", "permuted"):
        if config[key] is not True:
            raise ValueError(f"{key}: {config[key]!r}; bench.graph500 "
                             f"makes {key} graphs only")
    seed = int(config["graph_seed"])
    g = graph500.graph500(config["scale"], config["edge_factor"],
                          config["a"], config["b"], config["c"], seed=seed)
    keys = graph500.search_keys(g, int(config["search_keys"]),
                                np.random.default_rng(seed))
    return graph500.reblock(dataclasses.replace(g, keys=keys),
                            int(config["engine"]["block_size"]), rng)


def make_session(graph: graph500.Graph, config: dict, capacity: int,
                 rng: np.random.Generator):
    """The program's `GraphSession` over the graph, as the configuration
    runs it (weights or unit weights, block size, Pallas kernel), with
    a fixed job capacity so that the session never grows in the window."""
    from repro.core import GraphSession
    from repro.graph.structure import CSRGraph
    w = (graph.weights if config["weighted"]
         else np.ones_like(graph.weights))
    csr = CSRGraph(n=graph.n, indptr=graph.indptr, indices=graph.indices,
                   weights=w)
    eng = config["engine"]
    return GraphSession(csr, int(eng["block_size"]), capacity=capacity,
                        seed=int(rng.integers(2**31 - 1)),
                        use_pallas=bool(eng["use_pallas"]))


def compare(cell: Cell, graph, queries: List[Query], alg,
            rng: np.random.Generator, control: bool = False
            ) -> Dict[str, dict]:
    """Each number compared, with its limit, over a seeded sample of the
    completed queries.  With `control`, the algorithm's control (its
    reference one precision down) answers the sampled queries in the
    program's place, which the limits have to refuse."""
    done = [q for q in queries if q.done_s is not None]
    limits = cell.config["limits"]
    if not done:
        return {name: {"value": None, "limit": lim}
                for name, lim in limits.items()}
    k = min(alg.CHECK_SAMPLE, len(done))
    pick = sorted(rng.choice(len(done), size=k, replace=False))
    sources = [done[i].source for i in pick]
    got = (alg.control_results(graph, sources, cell.config) if control
           else np.stack([done[i].result for i in pick]))
    ref = alg.reference_results(graph, sources, cell.config)
    nums = alg.numbers(ref, got)
    # a number that is not finite (NaN in a result) is reported as null
    return {name: {"value": nums[name] if math.isfinite(nums[name])
                   else None, "limit": limits[name]} for name in limits}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _proc_seconds() -> tuple:
    """(seconds this thread waited runnable for a CPU, seconds the
    hypervisor took from all of the machine's CPUs), each None where the
    OS does not say."""
    wait = steal = None
    with contextlib.suppress(OSError, ValueError, IndexError):
        wait = int(pathlib.Path("/proc/thread-self/schedstat")
                   .read_text().split()[1]) / 1e9
    with contextlib.suppress(OSError, ValueError, IndexError):
        steal = (int(pathlib.Path("/proc/stat").read_text().split()[8])
                 / os.sysconf("SC_CLK_TCK"))
    return wait, steal


def _secs(v: Optional[float]) -> str:
    return "unknown" if v is None else f"{v:.4f} s"


class HostWatch:
    """What the host did while open, to tell a stall of the host loop
    apart from slow program work: garbage-collector passes (count, total
    and longest pause), the process's CPU time against the wall clock,
    the times the OS took the CPU away (involuntary context switches),
    how long this thread waited runnable for a CPU, and the time the
    hypervisor stole from the machine's CPUs."""

    def __enter__(self):
        self.pauses: List[tuple] = []
        self._t = 0.0
        gc.callbacks.append(self._gc)
        self._ru = resource.getrusage(resource.RUSAGE_SELF)
        self._proc = _proc_seconds()
        self._cpu, self._wall = time.process_time(), time.perf_counter()
        return self

    def _gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((time.perf_counter() - self._t,
                                info["generation"]))

    def __exit__(self, *exc):
        gc.callbacks.remove(self._gc)
        ru = resource.getrusage(resource.RUSAGE_SELF)
        self.cpu_s = time.process_time() - self._cpu
        self.wall_s = time.perf_counter() - self._wall
        self.preempted = ru.ru_nivcsw - self._ru.ru_nivcsw
        self.waited_s, self.stolen_s = (
            None if a is None or b is None else b - a
            for a, b in zip(self._proc, _proc_seconds()))
        return False

    def summary(self) -> str:
        longest = max(self.pauses, default=(0.0, -1))
        return (f"gc {len(self.pauses)} passes, "
                f"{sum(p for p, _ in self.pauses):.4f} s, longest "
                f"{longest[0]:.4f} s (generation {longest[1]}); process "
                f"CPU {self.cpu_s:.2f} s of {self.wall_s:.2f} s; "
                f"{self.preempted} involuntary context switches; waited "
                f"runnable {_secs(self.waited_s)}; stolen by the "
                f"hypervisor {_secs(self.stolen_s)}")


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             root: pathlib.Path = ROOT, t_start: Optional[float] = None,
             control: bool = False) -> dict:
    """One run; returns the result object the entry point prints.
    `control` puts the algorithm's control in the program's place in the
    comparison (`bench/controls.py`; the benchmark's runs never do)."""
    import jax
    t_start = time.perf_counter() if t_start is None else t_start
    marks = {"start": time.perf_counter() - t_start}
    cell = load_cell(cell_name, root)
    peaks = check_device(cell, root)
    marks["device"] = time.perf_counter() - t_start
    graph_rng, driver_rng, check_rng = (
        np.random.default_rng(s)
        for s in np.random.SeedSequence(seed).spawn(3))
    graph = make_graph(cell.config, graph_rng)
    marks["graph"] = time.perf_counter() - t_start
    alg = load_module(root, "algorithms", cell.config["algorithm"])
    driver_mod = load_module(root, "drivers", cell.traffic["kind"])
    rec = Recorder()
    driver = driver_mod.Driver(graph, cell.config, cell.traffic, alg,
                               driver_rng, rec)
    with CompileCounter() as setup_compiles:
        driver.set_up()
    # what set-up left behind lives to the end of the run: out of the
    # collector's way, so that the window's passes scan only the window's
    # own objects
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s: imports {marks['start']:.3f}, device "
        f"{marks['device'] - marks['start']:.3f}, graph "
        f"{marks['graph'] - marks['device']:.3f}, " + ", ".join(
            f"{k} {sum(v):.3f}" for k, v in rec.spans.items())
        + f"; {setup_compiles.compiles} compiles, "
        f"{setup_compiles.cache_hits} from the cache")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    with CompileCounter() as window_compiles:
        if trace:
            # no Python function tracer and only the critical host
            # events, the bench.* spans among them: more slows the host
            # loop and fills the trace
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        before = {k: len(v) for k, v in rec.spans.items()}
        rec.counting = True
        with HostWatch() as host, rec.span("window"):
            window_s = driver.run_window(seconds)
        rec.counting = False
        gc.unfreeze()
        if trace:
            jax.profiler.stop_trace()
    memory_peak = int(max(s.get("peak_bytes_in_use", 0) for s in
                          (d.memory_stats() or {} for d in
                           jax.devices()[:cell.chips])))
    queries = driver.queries()
    block = int(cell.config["engine"]["block_size"])
    shapes = {"jobs": driver.jobs, "num_blocks": -(-graph.n // block),
              "block": block, "num_pairs": graph500.block_pairs(graph, block),
              "semiring": alg.SEMIRING}
    driver.close()
    del driver
    gc.collect()

    reduced = None
    if trace:
        reduced = xplane.reduce_file(xplane.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"window {window_s:.3f} s, {len(queries)} queries, "
        f"{sum(q.done_s is not None for q in queries)} completed, "
        f"compiles in the window {window_compiles.compiles}, "
        f"{window_compiles.cache_hits} from the cache")
    log("longest in the window: " + ", ".join(
        f"{k} {max(v[before.get(k, 0):]):.4f} s"
        for k, v in rec.spans.items() if len(v) > before.get(k, 0)
        and k != "window") + "; " + host.summary())

    checked = compare(cell, graph, queries, alg, check_rng, control)
    run = Run(cell=cell, setup_s=setup_s, window_s=window_s,
              queries=queries, recorder=rec, shapes=shapes, peaks=peaks,
              trace=reduced)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_metric(root, m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    attempted = len(queries)
    failed = attempted - len(run.completed)
    correct = (attempted > 0 and failed == 0
               and all(c["value"] is not None and c["value"] <= c["limit"]
                       for c in checked.values()))
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": xplane.top(reduced["ops"]),
                            "idle_gaps": xplane.top(reduced["idle_gaps"])}
    out["compared"] = checked
    return out
