"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  It names a
configuration (``bench/configs/<config>.json``: the catalog's shape, its
data generator and data seed, the ``BuildConfig`` and the limits of the
comparison) and a traffic mix (``bench/mixes/<traffic>.json``, whose
``kind`` picks the driver: ``drive.DRIVERS[kind]``, else ``run`` of
``bench/drivers/<kind>.py``).  A generator the configuration names is found
the same way (``bench/data.py``: ``data.GENERATORS``, else
``bench/generators/<name>.py``).  A per-layer metric ``a.b.c`` is read by
``bench/metrics/a.b.c.py``, or else by the file of its longest prefix
(``a.b.py``).  Nothing here names a cell, a traffic kind or a generator:
adding one takes new files and entries only.

The run refuses anything but a TPU with as many chips as the cell asks for,
unless ``--rehearse`` is given: that runs on whatever JAX finds (the CPU
where no chip is attached) at the tiny sizes of the files' ``rehearsal`` entries, and its
result line names the platform it ran on.

Set-up (``setup_s``) runs from the start of the process to the start of the
window: importing, making the data, building the index and running every
shape the window uses.  JAX's persistent compilation cache lives in the
checkout (``repro.launch.cache``), so only a cell's first run compiles.
With ``--trace 1`` the window runs under the profiler and the line carries
the per-layer metrics, ``busy_s``/``window_s`` and a ``breakdown``; with
``--trace 0`` it carries the end-to-end metrics.  Its last key, ``checks``,
holds each number compared with the reference beside its limit; they are
also the last lines on standard error.
"""

from __future__ import annotations

T_START = __import__("time").perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over``'s keys set; dict values merge one level down."""
    out = dict(base)
    for k, v in over.items():
        out[k] = {**out[k], **v} if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def resolve(spec: dict, workload: str, rehearse: bool) -> tuple[dict, dict, dict]:
    """(cell entry, configuration, mix) of a workload, found by name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf_file = {c["name"]: c["file"] for c in spec["configs"]}[cell["config"]]
    config = load_json(os.path.join(ROOT, conf_file))
    mix = load_json(os.path.join(BENCH, "mixes", cell["traffic"] + ".json"))
    if rehearse:
        config = merged(config, config.get("rehearsal", {}))
        mix = merged(mix, mix.get("rehearsal", {}))
    return cell, config, mix


class CompileClock:
    """Counts JAX's trace, lower and compile events, as JAX reports them."""

    def __init__(self, jax):
        self.counts: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.counts[name] = self.counts.get(name, 0) + 1

    def snapshot(self) -> dict:
        return dict(self.counts)

    def since(self, before: dict) -> dict:
        return {k.rsplit("/", 1)[-1]: v - before.get(k, 0) for k, v in self.counts.items()
                if v - before.get(k, 0)}


def reader(name: str):
    """The module that reads per-layer metric ``name``: the file of the name
    or of its longest dotted prefix under ``bench/metrics``."""
    from bench import plugins

    parts = name.split(".")
    for i in range(len(parts), 0, -1):
        prefix = ".".join(parts[:i])
        if os.path.exists(plugins.path("metrics", prefix)):
            return plugins.load("metrics", prefix)
    raise FileNotFoundError(f"no reader for per-layer metric {name!r} under bench/metrics")


def driver(kind: str):
    """The driver of a traffic kind: ``drive.DRIVERS[kind]``, else ``run`` of
    ``bench/drivers/<kind>.py``; ``FileNotFoundError`` for neither."""
    from bench import drive, plugins

    return drive.DRIVERS.get(kind) or plugins.load("drivers", kind).run


def reports(metric: dict, workload: str, e2e_names: set | None = None) -> bool:
    """Whether a metric belongs in this cell's line: the cells its
    ``workloads`` lists; without the key, every cell for an end-to-end
    metric, and every cell that reports its ``moves`` for a per-layer one."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def memory_peak(jax, devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run(args) -> dict:
    """Run the cell once; returns the result line's object."""
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry, config, mix = resolve(spec, args.workload, args.rehearse)

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    import jax

    # keep the small eager programs too, so a warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if not args.rehearse and (platform != "tpu" or len(devices) < entry["chips"]):
        print(f"refusing to run: JAX found {len(devices)} {platform} device(s) "
              f"({kind}); the cell needs {entry['chips']} TPU chip(s)", file=sys.stderr)
        raise SystemExit(3)
    used = devices[: entry["chips"]] if platform == "tpu" else devices[:1]

    from bench import drive

    clock = CompileClock(jax)
    cell = drive.Cell(name=args.workload, config=config, mix=mix, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      program=not getattr(args, "control", False))
    marks: dict = {}
    trace_dir = os.path.join(args.trace_dir or TRACE_DIR, f"{args.workload}.{args.seed}")

    def ready():
        if cell.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host spans from TraceMe only: cheap
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        marks["setup_s"] = time.perf_counter() - T_START
        marks["compiles"] = clock.snapshot()

    outcome = driver(mix["kind"])(cell, ready)
    window_compiles = clock.since(marks["compiles"])
    if cell.trace:
        jax.profiler.stop_trace()
    peak = memory_peak(jax, used)

    device = {"platform": platform, "kind": kind, "count": len(used),
              "memory_peak_bytes": peak}
    e2e = [m for m in spec["end_to_end"] if reports(m, args.workload)]
    e2e_names = {m["name"] for m in e2e}
    result_metrics: dict = {}
    breakdown = None
    if not cell.trace:
        for m in e2e:
            value = marks["setup_s"] if m["name"] == "setup_s" else outcome.metrics[m["name"]]
            result_metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        from bench import trace as trace_lib

        trace = None
        if platform == "tpu":
            trace = trace_lib.load(trace_dir)
            busy, win = trace_lib.busy_share(trace)
            device.update(busy_s=busy, window_s=win)
            breakdown = {"device_ops": trace_lib.top_ops(trace),
                         "idle_gaps": trace_lib.idle_gaps(trace)}
        ctx = Readings(trace=trace, device_kind=kind, config=config, mix=mix,
                       outcome=outcome)
        for m in spec["per_layer"]:
            if not reports(m, args.workload, e2e_names):
                continue
            try:
                value = reader(m["name"]).read(ctx)
            except KeyError as e:  # a name the trace lacks: no reading
                print(f"per-layer {m['name']}: nothing to read ({e})", file=sys.stderr)
                value = None
            if value is not None:
                result_metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)

    line = {
        "correct": all(drive.passes(*c) for c in outcome.checks.values()),
        "attempted": int(outcome.attempted),
        "failed": int(outcome.failed),
        "metrics": result_metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {
        name: {"value": float(v), "op": op, "limit": float(lim)}
        for name, (v, op, lim) in outcome.checks.items()
    }
    extra = {k: v for k, v in outcome.readings.items() if isinstance(v, (int, float))}
    print(f"window_compiles={sum(window_compiles.values())} detail={window_compiles} "
          f"setup_s={marks['setup_s']:.3f} readings={extra}", flush=True)
    return line


class Readings:
    """What a per-layer reader gets: the reduced trace (None off the chip),
    the chip's kind, the run's configuration and mix, and the driver's
    ``Outcome`` (its ``readings`` dict holds the program's own counts)."""

    def __init__(self, trace, device_kind, config, mix, outcome):
        self.trace = trace
        self.device_kind = device_kind
        self.config = config
        self.mix = mix
        self.outcome = outcome
        self.readings = outcome.readings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on any platform at the files' rehearsal sizes")
    ap.add_argument("--trace-dir", default=None,
                    help=f"where the profiler writes, and its files are removed (default {TRACE_DIR})")
    args = ap.parse_args(argv)
    line = run(args)
    from bench.drive import passes

    for name, c in line["checks"].items():
        ok = passes(c["value"], c["op"], c["limit"])
        print(f"check {name}: {c['value']!r} {c['op']} {c['limit']!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
