"""Runs one cell once and prints the contract's result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name: `BENCHMARK.json` names the cell, its
configuration file and traffic file; the configuration's `entry`
names the module under `benchmark/entries/` that drives the program;
each per-layer metric is the module `benchmark/metrics/<name>.py`.
"""

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A workload of BENCHMARK.json with its configuration and traffic
    loaded, and the metrics that apply to it."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        found = [w for w in self.bench["workloads"] if w["name"] == name]
        if not found:
            raise SystemExit("unknown workload %r (known: %s)" % (
                name, ", ".join(w["name"] for w in self.bench["workloads"])))
        self.workload = found[0]
        self.name = name
        self.chips = int(self.workload["chips"])
        cfg = [c for c in self.bench["configs"]
               if c["name"] == self.workload["config"]][0]
        self.config = load_json(os.path.join(root, cfg["file"]))
        bench_dir = os.path.join(root, self.bench["paths"][0])
        self.traffic = load_json(os.path.join(
            bench_dir, "traffic", self.workload["traffic"] + ".json"))
        self.entry_path = os.path.join(bench_dir, "entries",
                                       self.config["entry"] + ".py")
        self.metrics_dir = os.path.join(bench_dir, "metrics")

    def _applies(self, metric: dict) -> bool:
        listed = metric.get("workloads")
        if listed is not None:
            return self.name in listed
        return True

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"] if self._applies(m)]

    def per_layer(self) -> list:
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if self._applies(m) and m["moves"] in e2e]


class Guards:
    """Counts programs built and compiled, and catches the events that
    mean the path did not run as resolved: `selection.fallback` and
    tier demotions (PR 21's `chip_smoke._Guards`, with the programs
    named). Its telemetry sink arms the program's whole record path, so
    it listens only outside the measured window; the tiers resolve
    during set-up, where a fallback would show."""

    def __init__(self):
        import jax

        from gelly_streaming_tpu.utils import resilience, telemetry

        self.compiled = []   # backend compiles (persistent-cache misses)
        self.built = []      # every program lowered in this process
        self.fallbacks = []
        self.listening = True
        self._resilience = resilience

        def on_duration(name, *_a, fun_name=None, **_k):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiled.append(fun_name)
            elif name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                self.built.append(fun_name)

        def on_record(rec):
            if rec.get("name") == "selection.fallback":
                self.fallbacks.append({k: rec.get(k) for k in
                                       ("component", "fallback", "error")})

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        telemetry.register_sink(on_record, lambda: self.listening)

    def mark(self) -> tuple:
        return (len(self.built), len(self.compiled))

    def since(self, mark: tuple) -> dict:
        return {"built": self.built[mark[0]:],
                "compiled": self.compiled[mark[1]:]}

    def demotions(self) -> list:
        return [{k: str(v)[:200] for k, v in d.items()}
                for d in self._resilience.demotion_events()]


class Run:
    """What an entry gets: the cell, the run's arguments, a temporary
    directory, the guards and the trace switch; and what it fills in."""

    def __init__(self, cell: Cell, args, t_start: float, tmp: str):
        self.cell = cell
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.fault = args.fault
        self.t_start = t_start
        self.tmp = tmp
        self.guards = None
        self.devices = []
        self.trace_dir = os.path.join(tmp, "trace")
        # filled by the entry
        self.setup_s = None
        self.values = {}       # end-to-end metric -> value
        self.counters = {}     # per-layer inputs the program counted
        self.checks = {}       # name -> (value, limit)
        self.attempted = 0
        self.failed = 0
        self.info = []         # dicts printed as earlier lines
        self.memory_peak = None

    def note(self, **kv) -> None:
        self.info.append(kv)
        print(json.dumps(kv, default=str), file=sys.stderr, flush=True)

    def first_edge(self) -> None:
        """The moment the first timed edge goes: set-up ends here."""
        self.setup_s = time.monotonic() - self.t_start

    @contextlib.contextmanager
    def window(self):
        """The measured window: traced when --trace 1, and watched for
        programs built inside it."""
        import jax

        mark = self.guards.mark()
        self.guards.listening = False
        if self.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            if self.trace:
                jax.profiler.stop_trace()
            self.guards.listening = True
            inside = self.guards.since(mark)
            self.counters["programs_built_in_window"] = len(inside["built"])
            self.note(guard="window", **inside)

    def read_memory(self) -> None:
        """Peak device bytes on the fullest chip used; read after the
        window and before the reference runs."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices[:self.cell.chips]]
        self.memory_peak = max(peaks) if peaks else 0


def _devices(cpu: bool, chips: int) -> list:
    import jax

    devs = jax.devices()
    want = "cpu" if cpu else "tpu"
    if devs[0].platform != want:
        raise NoChip("JAX found %s devices, not %s" % (devs[0].platform,
                                                        want))
    if len(devs) < chips:
        raise NoChip("the cell asks for %d chips, JAX sees %d"
                     % (chips, len(devs)))
    return devs


def _compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _per_layer(run: Run, trace) -> dict:
    out = {}
    for m in run.cell.per_layer():
        reader = load_module(os.path.join(run.cell.metrics_dir,
                                          m["name"] + ".py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(run, trace)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu", action="store_true",
                    help="rehearsal on JAX's CPU backend; its line names "
                         "the CPU and is never a measurement")
    ap.add_argument("--fault", default=None,
                    help="break the timed path (control and fault tests)")
    return ap.parse_args(argv)


def main(argv=None, t_start=None, root: str = ROOT) -> int:
    t_start = time.monotonic() if t_start is None else t_start
    args = parse(argv)
    cell = Cell(args.workload, root)
    tmp = tempfile.mkdtemp(prefix="gsbench-")
    env = {"GS_TUNE_CACHE": os.path.join(tmp, "tune")}   # empty every run
    if args.cpu:
        env["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_"
                                "device_count=%d" % cell.chips).strip()
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    run = Run(cell, args, t_start, tmp)
    try:
        try:
            run.devices = _devices(args.cpu, cell.chips)
        except NoChip as e:
            print("benchmark: %s; no result" % e, file=sys.stderr)
            return 3
        if not args.cpu:
            run.note(compile_cache=_compile_cache())
        run.guards = Guards()
        entry = load_module(cell.entry_path, "bench_entry")
        entry.run(run)
        trace = None
        if run.trace:
            from benchmark import trace as trace_mod

            trace = trace_mod.Trace(trace_mod.find_xplane(run.trace_dir))
        return _report(run, trace)
    finally:
        if run.guards is not None:
            run.guards.listening = False
        shutil.rmtree(tmp, ignore_errors=True)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _report(run: Run, trace) -> int:
    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(run.devices), "memory_peak_bytes": run.memory_peak}
    run.note(guard="process", demotions=run.guards.demotions(),
             fallbacks=run.guards.fallbacks)
    if trace is None:
        metrics = {}
        values = dict(run.values, setup_s=run.setup_s)
        for m in run.cell.end_to_end():
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
        breakdown = None
    else:
        metrics = _per_layer(run, trace)
        device["busy_s"] = trace.busy_s(run.cell.chips)
        device["window_s"] = trace.window_s
        breakdown = {"device_ops": trace.top_ops(),
                     "idle_gaps": trace.idle_gaps()}
    checks = {k: {"value": v, "limit": lim}
              for k, (v, lim) in run.checks.items()}
    correct = bool(checks) and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    for k, c in checks.items():
        print("check %s = %s (limit %s)" % (k, c["value"], c["limit"]),
              file=sys.stderr)
    line = {"correct": correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
