"""Chip benchmark: one cell of ``BENCHMARK.json`` on the chip it finds.

    python3 -m benchmarks.chip.run --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything is found by name. The cell (an entry of ``workloads``) names
its configuration, whose ``file`` holds the graph's sizes, and its
traffic, ``traffic/<name>.json``, which names the job kind
(``jobs/<job>.py``) and its arguments. End-to-end metrics are read by
``e2e/<metric>.py`` and per-layer metrics by ``metrics/<metric>.py``.
A new configuration, cell or metric is a new file and a new entry.

A run: set-up (JAX on the chip, the graph from ``--seed``, one whole job
that compiles or loads every program from the cache), then whole jobs
back to back until ``--seconds`` have passed, each a fresh call of the
program's public entry point from the edge list to host results. With
``--trace 1`` the window runs under the profiler and the per-layer
metrics are printed in place of the end-to-end ones. After the window
the device's peak memory is read, then the plain reference runs on the
host and every job's answer is compared with it.

Standard output ends with one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: each number compared with its limit. Standard error
ends with the same checks, one per line. Without a TPU, or with fewer
chips than the cell asks for, the run prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
JOB_SPAN = "bench.job"  # the host span around each job of the window
COMPILE_EVENTS = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


class NoChip(RuntimeError):
    """The machine lacks what the cell needs; the run prints no result."""


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under this directory, imported by path (a
    metric's name may hold dots)."""
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.{kind}.{name.replace('.', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_spec() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def cell_plan(spec: dict, workload: str) -> dict:
    """Everything the spec says about one cell: the cell, its
    configuration, its traffic and the metrics it reports."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(ROOT / conf_entry["file"]) as f:
        config = json.load(f)
    with open(HERE / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)

    def mine(metrics):
        return [m for m in metrics if workload in m.get("workloads", [workload])]

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": mine(spec["end_to_end"]),
            "per_layer": mine(spec["per_layer"])}


@dataclasses.dataclass
class Run:
    """What one run saw, handed to every metric reader."""

    device_kind: str
    setup_s: float = 0.0
    setup_compile_s: float = 0.0
    # process-clock seconds at the end of each set-up phase
    setup_phases: dict = dataclasses.field(default_factory=dict)
    window_s: float = 0.0
    job_s: list = dataclasses.field(default_factory=list)
    jobs: int = 0
    window_compiles: int = 0
    programs: list = dataclasses.field(default_factory=list)
    trace: Any = None  # trace.Reduced of the window, --trace 1 only
    reference: Any = None


def _name(program) -> str:
    return getattr(program, "__name__", str(program))


def _compile_listener(totals: dict):
    def on_duration(event: str, secs: float, **_kw) -> None:
        if event in COMPILE_EVENTS:
            totals["s"] += secs
            totals["n"] += 1

    return on_duration


def _import_program():
    """Import the program from this checkout's ``src`` and nowhere else."""
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro.core
    except ImportError as e:
        raise NoChip(f"the program is not in this checkout: {e}") from e
    where = Path(repro.core.__file__).resolve()
    if ROOT / "src" not in where.parents:
        raise NoChip(f"repro imported from {where}, outside {ROOT}")


def _configure_jax(cache_dir: Path):
    """Global x64 and the checkout's compile cache, set before the
    program is imported: importing it compiles, and JAX fixes its cache
    directory at the first compile."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    import jax

    jax.config.update("jax_enable_x64", True)  # global: counts are int64
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def _devices(jax, chips: int, require_accelerator: bool):
    devices = jax.devices()
    if require_accelerator:
        if devices[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX sees {devices[0].platform} devices")
        if len(devices) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def run_cell(plan: dict, seed: int, seconds: float, trace: bool,
             require_accelerator: bool = True, trace_dir: Optional[str] = None,
             cache_dir: Path = CACHE_DIR):
    """Set up, run the window, check, and read the metrics. Returns the
    result dict (the JSON line) and the :class:`Run`. The tests skip
    the look for a chip (``require_accelerator=False``)."""
    cell, config, traffic = plan["cell"], plan["config"], plan["traffic"]
    phases = {}

    def phase(name):
        phases[name] = time.perf_counter() - T_START

    jax = _configure_jax(cache_dir)
    phase("jax_import")
    _import_program()
    phase("program_import")
    devices = _devices(jax, cell["chips"], require_accelerator)
    phase("devices")
    from benchmarks.chip import checks, graphs
    from benchmarks.chip import trace as trace_mod
    from repro.core.graph import BipartiteGraph
    from repro.core.pipeline import record_programs

    compiles = {"s": 0.0, "n": 0}
    jax.monitoring.register_event_duration_secs_listener(_compile_listener(compiles))
    job = load_module("jobs", traffic["job"])
    run = Run(devices[0].device_kind)

    edges = graphs.build_edges(config, seed)
    g = BipartiteGraph(config["n_u"], config["n_v"], edges,
                       on_duplicate="raise")
    phase("graph")
    answers, bad_path = [], 0
    with record_programs() as programs:  # the warm-up job: compiles
        answers.append(job.run(g, traffic["args"]))
    run.programs = [(_name(p), a, k) for p, a, k in programs]
    warm_problem = checks.off_path(
        answers[0], [n for n, _, _ in run.programs], traffic["path"])
    if warm_problem:
        print(f"warm-up job off its path: {warm_problem}", file=sys.stderr)
    run.setup_compile_s = compiles["s"]
    n_compiles0 = compiles["n"]
    phase("warm_up_job")
    gc.collect()  # what compiling and the warm-up left is freed in set-up

    if trace:
        tdir = trace_dir or tempfile.mkdtemp(prefix="chip-trace-")
        jax.profiler.start_trace(tdir)
    t0 = time.perf_counter()
    run.setup_s = t0 - T_START
    run.setup_phases = phases
    t_end = t0
    while True:  # at least one job; the last one ends the window
        with record_programs() as programs:
            with jax.profiler.TraceAnnotation(JOB_SPAN):
                answers.append(job.run(g, traffic["args"]))
        run.job_s.append(time.perf_counter() - t_end)
        t_end = time.perf_counter()
        problem = checks.off_path(
            answers[-1], [_name(p) for p, _, _ in programs], traffic["path"])
        del programs
        if problem:
            bad_path += 1
            print(f"job {len(answers) - 1} off its path: {problem}", file=sys.stderr)
        if t_end - t0 >= seconds:
            break
    run.window_s = t_end - t0
    run.jobs = len(answers) - 1
    run.window_compiles = compiles["n"] - n_compiles0
    if trace:
        jax.profiler.stop_trace()
        run.trace = trace_mod.reduce_dir(tdir, JOB_SPAN, len(devices))
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    run.reference = job.reference(config, edges)
    compared = checks.compare(job, answers, run.reference)
    correct = checks.correct(compared)

    metrics = {}
    wanted = plan["per_layer"] if trace else plan["end_to_end"]
    for m in wanted:
        value = load_module("metrics" if trace else "e2e", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": run.jobs,
              "failed": bad_path, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    result["checks"] = compared
    return result, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--graph-seed", type=int, default=None,
                    help="draw another graph than the configuration's "
                         "(a check of the comparison on a second structure; "
                         "its shapes compile anew)")
    args = ap.parse_args(argv)
    plan = cell_plan(load_spec(), args.workload)
    if args.graph_seed is not None:
        plan["config"] = dict(plan["config"], graph_seed=args.graph_seed)
    try:
        result, run = run_cell(plan, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"benchmark not run: {e}", file=sys.stderr)
        return 2
    print(f"setup_s {run.setup_s} window_s {run.window_s} jobs {run.jobs}",
          flush=True)
    print(f"window_compiles {run.window_compiles}", flush=True)
    print("setup_phases " + json.dumps(run.setup_phases), file=sys.stderr)
    print("job_s " + json.dumps(run.job_s), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
