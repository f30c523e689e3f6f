"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json``; its configuration file,
its traffic mix (``bench/traffic/<mix>.json``, which names its loop in
``bench/loops/``), its correctness limits (``bench/limits/<cell>.json``)
and its per-layer metric readers (``bench/metrics/<metric>.py``) are
found by name too. With ``--trace 0`` the result holds the cell's
end-to-end metrics; with ``--trace 1`` the last seconds of the window
are traced and the result holds its per-layer metrics. The last line of
standard output is one JSON object; the last lines of standard error
give each number that decided ``correct`` beside its limit. Without a
TPU, or with fewer chips than the cell asks for, it exits with code 3
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUNS_DIR = os.path.join(ROOT, ".bench_runs")
NO_CHIP = 3
# the TPU runtime logs to a fixed directory under /tmp unless told not to
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _paths() -> None:
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def named(items: list[dict], name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def process_age() -> float:
    """Seconds since this process started."""
    try:
        import psutil

        return time.time() - psutil.Process().create_time()
    except (ImportError, OSError):
        return time.perf_counter() - T_START


class CompileCount:
    """Programs lowered and compiled by the backend, from JAX's events."""

    def __init__(self) -> None:
        import jax

        self.lowered = 0
        self.compiled = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1
            self.compile_s += duration


class Tracer:
    """Profiles the window from ``start`` to ``stop`` seconds after it
    opens, inside a ``bench.traced`` span."""

    def __init__(self, log_dir: str, start: float, stop: float):
        self.log_dir, self.start, self.stop = log_dir, start, stop
        self.t0 = self.t1 = None
        self._ann = None

    def tick(self, now: float) -> None:
        import jax

        if self.t0 is None and now >= self.start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation("bench.traced")
            self._ann.__enter__()
            self.t0 = time.perf_counter()
        elif self.t0 is not None and self.t1 is None and now >= self.stop:
            self.close()

    def close(self) -> None:
        import jax

        if self.t0 is not None and self.t1 is None:
            self.t1 = time.perf_counter()
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()


class Context:
    """What a loop is given, and what it reports back through."""

    def __init__(self, workload: str, seed: int, seconds: float, trace: bool,
                 config: dict | None = None, mix: dict | None = None):
        spec = load_json("BENCHMARK.json")
        self.spec = spec
        self.cell = named(spec["workloads"], workload, "workload")
        entry = named(spec["configs"], self.cell["config"], "config")
        self.config = config if config is not None else load_json(entry["file"])
        self.mix = mix if mix is not None else load_json("bench", "traffic", f"{self.cell['traffic']}.json")
        self.limits = load_json("bench", "limits", f"{workload}.json")
        self.seed, self.seconds, self.trace = int(seed), float(seconds), bool(trace)
        self.compiles = CompileCount()
        self.setup_s = None
        self.window_compiles = None
        self.memory_peak = 0
        self.the_tracer = None
        self.excluded = 0.0
        self.control = None  # "fp8": also read the lower-precision control (bench/control.py)

    def note(self, msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    def phase(self, name: str) -> None:
        """Note how far into the process set-up has come."""
        self.note(f"set-up: {name} at {process_age() - self.excluded:.3f} s")

    def exclude(self, seconds: float) -> None:
        """Leave ``seconds`` of correctness readings out of ``setup_s``."""
        self.excluded += seconds

    def window_opens(self) -> None:
        self.setup_s = process_age() - self.excluded
        self._at_open = (self.compiles.lowered, self.compiles.compiled)

    def window_closed(self) -> None:
        self.window_compiles = (self.compiles.lowered - self._at_open[0],
                                self.compiles.compiled - self._at_open[1])

    def tracer(self, start: float, stop: float) -> Tracer:
        log_dir = os.path.join(RUNS_DIR, "trace")
        shutil.rmtree(log_dir, ignore_errors=True)
        self.the_tracer = Tracer(log_dir, start, stop)
        return self.the_tracer

    def read_memory(self) -> None:
        import jax

        self.memory_peak = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.local_devices())


def cache_on() -> None:
    """The program's persistent compilation cache, keeping every program,
    so that only a cell's first run in a checkout compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def require_chip(chips: int) -> dict:
    """The device as JAX reports it; exits unless it is ``chips`` TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"[bench] needs {chips} TPU chip(s); JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(NO_CHIP)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": chips}


def _finite(x: float):
    return x if math.isfinite(x) else None


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` (every number within its limit; one that is not finite
    is not), and each number beside its limit."""
    correct = all(values[k] <= limits[k] for k in values)
    return correct, {k: {"value": _finite(v), "limit": limits[k]} for k, v in values.items()}


def per_layer(ctx: Context, out: dict, device: dict) -> tuple[dict, dict, dict]:
    """(metrics, device additions, breakdown) of a traced run."""
    from bench.lib import peaks, record
    from bench.lib import trace as tr

    t = ctx.the_tracer
    if t is None or t.t0 is None:
        raise RuntimeError("the traced run recorded no trace")
    trace = tr.load(t.log_dir)
    run = record.RunData(
        peaks=peaks.peaks(device["kind"]),
        chips=ctx.cell["chips"], spans=out["spans"], traced=(t.t0, t.t1), trace=trace,
        facts=out["facts"])
    metrics = {}
    for m in ctx.spec["per_layer"]:
        if not applies(m, ctx.cell["name"]):
            continue
        value = record.reader(ROOT, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    extra = {"busy_s": tr.busy_s(trace), "window_s": trace.window_s}
    breakdown = {"device_ops": tr.op_seconds(trace), "idle_gaps": tr.idle_gaps(trace)}
    return metrics, extra, breakdown


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             check_chip: bool = True, config: dict | None = None,
             mix: dict | None = None) -> dict:
    """One run of one cell; returns the result object."""
    _paths()
    ctx = Context(workload, seed, seconds, trace, config, mix)
    if check_chip:
        device = require_chip(ctx.cell["chips"])
    else:
        import jax

        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind, "count": jax.device_count()}
    cache_on()
    loop = importlib.import_module(f"bench.loops.{ctx.mix['loop']}")
    out = loop.run(ctx)
    lowered, compiled = ctx.window_compiles
    ctx.note(f"setup {ctx.setup_s:.3f} s; programs lowered in the window: {lowered}, "
             f"compiled: {compiled} (set-up compiled {ctx.compiles.compiled - compiled} "
             f"in {ctx.compiles.compile_s:.1f} s)")
    for k, v in {**out["facts"], **out["counts"]}.items():
        ctx.note(f"{k}: {v}")
    device["memory_peak_bytes"] = ctx.memory_peak
    breakdown = None
    if trace:
        metrics, extra, breakdown = per_layer(ctx, out, device)
        device.update(extra)
    else:
        values = dict(out["end_to_end"], setup_s=ctx.setup_s)
        metrics = {
            m["name"]: {"value": _finite(values[m["name"]]), "unit": m["unit"]}
            for m in ctx.spec["end_to_end"] if applies(m, workload)
        }
    correct, checks = judge(out["checks"], ctx.limits)
    result = {
        "correct": correct,
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
