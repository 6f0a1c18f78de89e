"""On-chip benchmark of the mining runtime: one cell, one run.

    python3 benchmarks/chip/run.py --workload mico-1pct.motifs3 \
        --seed 1234 --seconds 20 --trace 0

A cell of ``BENCHMARK.json`` names a configuration (a graph deployment,
``configs/<name>.json``) and a traffic mix (a mining job,
``traffic/<name>.json``, which names its app: ``apps/<app>.py`` builds the
program's app, reads its answer, and holds its reference, comparison and
control). A run:

1. refuses to run unless JAX finds a TPU with as many chips as the cell
   asks for (exit 3, no result);
2. sets up: generates the cell's graph from ``--seed`` (``graphs.py``),
   turns on the persistent compile cache at a fixed path in the checkout,
   and runs one warm-up job (the cost model calibrates inside it, on
   every run alike);
3. measures: jobs back to back, one client in a closed loop, for
   ``--seconds``; each job goes through ``repro.core.run`` (the serial
   superstep runtime) from the host graph to the mined result on the host;
4. checks every job of the window against the numpy reference
   (``reference.py``), computed after the window;
5. prints earlier JSON lines (graph, compiles, decisions, window) and, last,
   one JSON line with ``correct``, ``attempted``, ``failed``, ``metrics``,
   ``device`` (and ``breakdown`` with ``--trace 1``) and ``check``.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer metrics, each read by ``layers/<metric>.py``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import graphs  # noqa: E402
import reference  # noqa: E402
import roofline  # noqa: E402
import tracereduce  # noqa: E402

#: where the run keeps what it builds: compiled programs and the
#: profiler's trace (listed in .gitignore)
CACHE = os.path.join(ROOT, ".bench_cache")


class Refused(Exception):
    """The run cannot measure what the cell asks for; no result."""


# ---------------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

def _read_json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_cell(name: str, bench_path: str = None) -> SimpleNamespace:
    bench = _read_json(bench_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = _read_json(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    job = SimpleNamespace(name=traffic["app"], params=traffic["params"],
                          module=load_module("apps", traffic["app"]))

    def applies(metric):
        return "workloads" not in metric or name in metric["workloads"]

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    layers = [m for m in bench["per_layer"]
              if applies(m) and m["moves"] in e2e_names]
    return SimpleNamespace(name=name, chips=int(w["chips"]), config=config,
                           job=job, end_to_end=e2e, per_layer=layers)


def load_module(kind: str, name: str):
    """The module ``<kind>/<name>.py`` of the benchmark's directory."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise Refused(f"no {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    """``layers/<metric>.py``'s ``read(ctx)``."""
    return load_module("layers", metric).read


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def use_caches(jax) -> dict:
    """Persistent compile cache (``JAX_COMPILATION_CACHE_DIR`` where the
    environment sets it, else a fixed directory in the checkout) and the
    cost model's table directory, both at fixed paths."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        CACHE, "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return {"compile_cache": path}


class CompileClock:
    """Counts JAX's traces, backend compiles (with their seconds, also by
    program name) and persistent-cache hits."""

    EVENTS = {
        "/jax/core/compile/backend_compile_duration": "compiles",
        "/jax/core/compile/jaxpr_trace_duration": "traces",
    }

    def __init__(self, jax):
        self.n = {"compiles": 0, "traces": 0, "cache_hits": 0}
        self.compile_s = 0.0
        self.by_program = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, fun_name=None, **_):
        key = self.EVENTS.get(event)
        if key:
            self.n[key] += 1
            if key == "compiles":
                self.compile_s += duration
                n, s = self.by_program.get(fun_name, (0, 0.0))
                self.by_program[fun_name] = (n + 1, s + duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.n["cache_hits"] += 1

    def snapshot(self):
        return dict(self.n, compile_s=self.compile_s,
                    by_program=dict(self.by_program))

    @staticmethod
    def since(a, b, top: int = 12):
        out = {k: b[k] - a[k] for k in a if k != "by_program"}
        # a program loaded from the persistent cache is counted as a
        # compile and as a cache hit; the rest were compiled anew
        out["compiled_anew"] = out["compiles"] - out["cache_hits"]
        grew = []
        for name, (n, s) in b["by_program"].items():
            n0, s0 = a["by_program"].get(name, (0, 0.0))
            if n > n0:
                grew.append([name, n - n0, s - s0])
        out["top_compiles"] = sorted(grew, key=lambda r: -r[2])[:top]
        return out


def default_mine(graph, job, config, traced):
    """One job through the normal entry: ``repro.core.run``, which is
    ``SuperstepRuntime(graph, app, config, SerialBackend()).run()``, with
    the program's app that ``apps/<app>.py`` built (``job.app``). A traced
    job builds that runtime itself to keep its phase spans."""
    from repro.core import run
    from repro.core.runtime import SerialBackend, SuperstepRuntime

    if not traced:
        return run(graph, job.app, config), None
    rt = SuperstepRuntime(graph, job.app, config, SerialBackend())
    res = rt.run()
    tracer = rt.observer.tracer
    spans = [(s.name, tracer.epoch + s.ts * 1e-6, s.dur * 1e-6, s.depth)
             for s in tracer.spans]
    return res, spans


def job_record(res, wall):
    steps = res.stats.steps
    return {
        "wall_s": wall,
        "phase_walls": {k: sum(getattr(s, k) for s in steps) for k in (
            "t_expand", "t_aggregate", "t_canon", "t_storage")},
        "host_syncs": sum(s.n_host_syncs for s in steps),
        "bytes_to_host": sum(s.bytes_to_host for s in steps),
        "n_chunks": sum(s.n_chunks for s in steps),
        "n_steps": len(steps),
    }


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def check_answers(module, answers, want):
    """Compare every job's answer with the reference (``module.compare``,
    of the cell's app). Answers equal to one already compared share its
    verdict (the comparison is exact). Returns ``jobs_wrong`` and the
    worst job's reading of each of ``module.LIMITS``."""
    seen = []                       # (answer, verdict)
    worst = dict.fromkeys(module.LIMITS, 0)
    jobs_wrong = 0
    for got in answers:
        verdict = None
        for prev, v in seen:
            if _same(got, prev):
                verdict = v
                break
        if verdict is None:
            verdict = module.compare(got, want)
            seen.append((got, verdict))
        if any(verdict[k] > module.LIMITS[k] for k in worst):
            jobs_wrong += 1
        for k in worst:
            worst[k] = max(worst[k], verdict[k])
    return dict(jobs_wrong=jobs_wrong, **worst)


def _same(a, b):
    return a.keys() == b.keys() and all(
        a[k].shape == b[k].shape and np.array_equal(a[k], b[k]) for k in a)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def execute(cell, seed: int, seconds: float, trace: bool,
            require_tpu: bool = True, mine=default_mine,
            out=sys.stdout) -> dict:
    """Set up, measure, check; returns the result line's object."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < cell.chips:
        raise Refused(f"the cell needs {cell.chips} chips, JAX found "
                      f"{len(devices)}")
    peaks = (roofline.load_peaks(dev.device_kind) if require_tpu else {})
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        import repro.core  # noqa: F401  (turns on x64, as every entry does)
        from repro.core import Graph, RunConfig
    except ImportError as e:
        raise Refused(f"the repro package is not in the checkout ({e})")

    def emit(rec):
        print(json.dumps(rec, default=str), file=out, flush=True)

    caches = use_caches(jax)
    clock = CompileClock(jax)
    spec = cell.config["graph"]
    n = int(spec["vertices"])
    labels, edges = graphs.generate(spec, seed)
    graph = Graph(n=n, labels=labels, edges=edges)
    deg = graphs.degrees(n, edges)
    emit({"graph": {
        "n": n, "m": int(len(edges)), "labels": int(spec["labels"]),
        "max_degree": int(deg.max()),
        "lane_fill": float(2 * len(edges) / (n * deg.max())),
        "sum_deg2": int((deg * deg).sum())}, "seed": seed, **caches})

    job = cell.job
    job.app = job.module.make(job.params)
    config = RunConfig(**cell.config["run_config"], trace=trace)

    # ---- set-up: one warm-up job compiles every program the window runs
    c0 = clock.snapshot()
    res, _ = mine(graph, job, config, False)
    warm_answer = job.module.answer(res)
    setup_s = time.perf_counter() - T_START
    c1 = clock.snapshot()
    decisions = dict(res.stats.cost_model)
    emit({"setup": {"setup_s": setup_s, **CompileClock.since(c0, c1)},
          "decisions": decisions,
          "kernel_routes": res.stats.kernel_routes,
          "embeddings_per_job": int(res.stats.total_embeddings)})
    del res

    # ---- the window: one client, jobs back to back ----------------------
    trace_dir = os.path.join(CACHE, "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    answers, jobs, marks, job_spans = [], [], [], []
    failed = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    t_end = t0
    while not jobs or time.perf_counter() < deadline:
        tj = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.job"):
                mark = time.perf_counter()
                res, spans = mine(graph, job, config, trace)
                got = job.module.answer(res)
        except Exception:           # a job that raises is a failed job
            traceback.print_exc()
            failed += 1
            break
        t_end = time.perf_counter()
        jobs.append(job_record(res, t_end - tj))
        answers.append(got)
        marks.append(mark)
        job_spans.append(spans or [])
        del res
    window_s = t_end - t0
    if trace:
        jax.profiler.stop_trace()
    c2 = clock.snapshot()
    mem = dev.memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    emit({"window": {"jobs": len(jobs), "window_s": window_s,
                     "job_walls_s": [j["wall_s"] for j in jobs],
                     **CompileClock.since(c1, c2)},
          "peak_bytes_in_use": peak,
          "bytes_limit": int(mem.get("bytes_limit", 0))})

    # ---- the check, after the window -----------------------------------
    t_ref = time.perf_counter()
    hg = reference.HostGraph(n, labels, edges)
    want, parents = job.module.reference_answer(hg, job.params)
    numbers = check_answers(job.module, answers, want)
    wrong_in_window = numbers["jobs_wrong"]
    warm = check_answers(job.module, [warm_answer], want)
    numbers = {k: max(v, warm[k]) for k, v in numbers.items()}
    emit({"reference": {"seconds": time.perf_counter() - t_ref,
                        "keys": len(want),
                        "jobs_checked": len(answers) + 1}})

    # ---- metrics ---------------------------------------------------------
    metrics, breakdown, device = {}, None, {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices), "memory_peak_bytes": peak}
    if not trace:
        walls = [j["wall_s"] for j in jobs]
        values = {"setup_s": setup_s,
                  "job_s": window_s / max(len(jobs), 1),
                  "job_p95_s": (float(np.percentile(walls, 95))
                                if walls else None)}
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        red, path = None, _xplane(trace_dir)
        if path is not None:
            data = tracereduce.extract(
                jax.profiler.ProfileData.from_file(path), marks, job_spans)
            red = tracereduce.reduce(data)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = SimpleNamespace(
            app=job.name, jobs=jobs, trace=red, peaks=peaks,
            min_bytes=roofline.expansion_bytes(deg, parents),
            decisions=decisions)
        ctx.metric = lambda name: load_reader(name)(ctx)
        for m in cell.per_layer:
            v = ctx.metric(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red is not None:
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
            emit({"trace": {k: red[k] for k in (
                "n_jobs", "window_s", "busy_s", "n_gaps", "program_s")},
                "min_bytes_per_job": ctx.min_bytes})

    limits = dict(jobs_wrong=0, **job.module.LIMITS)
    correct = (failed == 0 and len(jobs) > 0
               and all(numbers[k] <= limits[k] for k in limits))
    result = {"correct": correct, "attempted": len(jobs) + failed,
              "failed": failed + wrong_in_window,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {k: {"value": numbers[k], "limit": limits[k]}
                       for k in limits}
    return result


def _xplane(trace_dir):
    """The profiler's trace file of this run, or None."""
    for dirpath, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(dirpath, f)
    return None


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        result = execute(cell, args.seed, args.seconds, bool(args.trace))
    except (Refused, KeyError, OSError, ValueError) as e:
        print(f"run.py: {type(e).__name__}: {e}; nothing measured",
              file=sys.stderr)
        return 3
    for k, v in result["check"].items():
        print(f"check: {k} {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
