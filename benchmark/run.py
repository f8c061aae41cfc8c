"""The benchmark's command: one process, one cell.

    python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``:
``configs/<config>.json``, ``traffic/<traffic>.json``, ``workloads/<cell>.json``,
``engines/<engine>.py``, ``layer_metrics/<metric>.json`` and its
``readers/<reader>.py``. The last line of standard output is the result object;
every other finding is printed on earlier lines.
"""

from __future__ import annotations

import time

_IMPORTED_MONO = time.monotonic()
_IMPORTED_BOOT = time.clock_gettime(time.CLOCK_BOOTTIME)

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def seconds_before_import() -> float:
    """Process start to this module's import (interpreter start-up), from the
    kernel's record of when the process began."""
    try:
        ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19])
        return max(0.0, _IMPORTED_BOOT - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


@dataclass
class Job:
    """What a driver is given: the cell's files, the seed, the devices."""

    name: str
    cell: dict
    cfg: dict
    traffic: dict
    seed: int
    trace: bool
    devices: list = field(default_factory=list)
    checks: object = None
    say: object = say


class Tracer:
    """The profiler around a few rounds; Python-frame tracing off (it slows the
    host and swells the file), host annotations and device operations on."""

    def __init__(self, directory: Path) -> None:
        self.directory = directory
        shutil.rmtree(directory, ignore_errors=True)
        self.ran = False

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(self.directory), profiler_options=options)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()
        self.ran = True

    def load(self) -> dict:
        from benchmark import trace_reduce

        files = sorted(self.directory.glob("plugins/profile/*/*.xplane.pb"))
        if not self.ran or not files:
            raise SystemExit("benchmark: the traced run wrote no trace")
        return trace_reduce.load_xplane(str(files[-1]))


def resolve(benchmark: dict, name: str, rehearsal: bool = False) -> tuple[dict, dict, dict, dict]:
    """(BENCHMARK.json entry, cell file, configuration file, traffic file)."""
    entries = {w["name"]: w for w in benchmark["workloads"]}
    if name not in entries:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json (has: {sorted(entries)})")
    entry = entries[name]
    cell = load_json(HERE / "workloads" / f"{name}.json")
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise SystemExit(f"benchmark: workloads/{name}.json and BENCHMARK.json disagree on {key!r}")
    configs = {c["name"]: c for c in benchmark["configs"]}
    cfg = load_json(ROOT / configs[entry["config"]]["file"])
    traffic = load_json(HERE / "traffic" / f"{entry['traffic']}.json")
    if rehearsal:  # the CPU rehearsal's tiny sizes sit in each file under "rehearsal"
        for body in (cfg, traffic, cell):
            body.update(body.get("rehearsal", {}))
    return entry, cell, cfg, traffic


def metrics_of(benchmark: dict, group: str, cell: str) -> list[dict]:
    return [m for m in benchmark[group] if cell in m.get("workloads", [cell])]


def quantiles(xs: list[float]) -> dict:
    xs = sorted(xs)
    pick = lambda q: xs[min(len(xs) - 1, int(q * len(xs)))]  # noqa: E731
    return {"n": len(xs), "p10": pick(0.1), "median": statistics.median(xs), "p90": pick(0.9)}


def execute(name: str, seed: int, seconds: float, trace: bool, rehearsal: bool = False) -> dict | None:
    benchmark = load_json(ROOT / "BENCHMARK.json")
    entry, cell, cfg, traffic = resolve(benchmark, name, rehearsal)

    from p2pfl_tpu.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    from benchmark import checks as ck
    from benchmark import engines, flops
    from benchmark.compile_clock import CompileClock

    clock = CompileClock()
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if not rehearsal:
        if platform != "tpu":
            raise SystemExit(f"benchmark: needs a TPU, found platform {platform!r}")
        if len(devices) != entry["chips"]:
            raise SystemExit(f"benchmark: cell {name!r} needs {entry['chips']} chip(s), found {len(devices)}")
        peak = flops.peaks(kind)  # KeyError on a device_kind the table does not know
    else:
        peak = {"bf16_flops_per_s": float("nan")}
    say(
        f"cell {name}: config {entry['config']}, traffic {entry['traffic']}, engine {cell['engine']}, "
        f"seed {seed}, {seconds}s, trace={int(trace)}; platform={platform} kind={kind!r} devices={len(devices)} "
        f"jax={jax.__version__}; compile cache {cache_dir}"
    )

    job = Job(name, cell, cfg, traffic, seed, trace, devices=devices, checks=ck.Checks())
    engine = engines.load(cell["engine"])
    marks = {"start": time.monotonic()}

    def mark(stage: str) -> None:
        marks[stage] = time.monotonic()
        stats = devices[0].memory_stats() or {}
        say(f"after {stage}: device 0 memory " + json.dumps({
            k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use", "largest_free_block_bytes", "bytes_limit")
        }))

    state = engine.build(job)
    mark("built")
    engine.check(job, state)
    mark("checked")
    engine.warm(job, state)
    mark("warmed")
    engine.reset(job, state)
    setup_split = clock.since()
    in_window = clock.mark()
    tracer = Tracer(HERE / "out" / "trace" / name) if trace else None
    window = engine.measure(job, state, seconds, tracer)
    compiled = clock.since(in_window)
    window_start = window["completions"][0]
    setup_s = seconds_before_import() + (window_start - _IMPORTED_MONO)
    job.checks.add(
        "window.compiled_nothing", compiled["backend_n"] == 0 and compiled["cache_misses"] == 0,
        backend_n=compiled["backend_n"], cache_misses=compiled["cache_misses"],
        programs=clock.backend_names(in_window),
    )
    engine.finish(job, state, window)
    shapes = engine.describe(job, state)

    intervals = window["intervals"]
    losses = window["losses"]
    say(f"rounds: {len(losses)} finished; interval quantiles (s): {json.dumps(quantiles(intervals)) if intervals else 'none'}")
    say(f"loss curve: {json.dumps([round(x, 5) for x in losses])}")
    say(
        "set-up split (s): "
        + json.dumps({
            "before_import": round(seconds_before_import(), 3),
            "imports_and_devices": round(marks["start"] - _IMPORTED_MONO, 3),
            "build": round(marks["built"] - marks["start"], 3),
            "reference_check": round(marks["checked"] - marks["built"], 3),
            "warm_up": round(marks["warmed"] - marks["checked"], 3),
            "reset": round(window_start - marks["warmed"], 3),
            **{k: round(v, 3) for k, v in setup_split.items()},
        })
    )
    say(f"shapes: {json.dumps(shapes, default=str)}")
    for row in job.checks.rows:
        say(f"check: {json.dumps(row)}")

    peaks_bytes = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    say(f"peak_bytes_in_use per device: {peaks_bytes}")
    device = {
        "platform": platform, "kind": kind, "count": len(devices),
        "memory_peak_bytes": max((b for b in peaks_bytes if b is not None), default=0),
    }
    k = cell["k"]
    if len(losses) < k or not intervals:
        raise SystemExit(f"benchmark: the window held {len(losses)} round(s); the cell needs {k} and an interval")
    values: dict[str, float] = {}
    result = {"correct": job.checks.ok, "attempted": window["attempted"], "failed": window["failed"]}
    if not trace:
        values = {
            "setup_s": setup_s,
            "loss_at_k": losses[k - 1],
            cell["interval_metric"]: statistics.median(intervals),
        }
        wanted = metrics_of(benchmark, "end_to_end", name)
    else:
        reduced = tracer.load()
        from benchmark import trace_reduce

        if rehearsal and not reduced["devices"]:
            say(f"REHEARSAL on {platform}: the trace has no device plane here ({len(reduced['host'])} host spans); "
                "the reduction runs on the chip only")
            return None
        busy_s, window_s = trace_reduce.busy_seconds(reduced)
        device.update(busy_s=busy_s, window_s=window_s)
        result["breakdown"] = {
            "device_ops": trace_reduce.top_device_ops(reduced),
            "idle_gaps": trace_reduce.longest_idle_gaps(reduced),
        }
        context = {
            "job": job, "trace": reduced, "window": window, "shapes": shapes, "peak": peak,
            "setup_split": setup_split, "device": device, "intervals": intervals,
        }
        wanted = metrics_of(benchmark, "per_layer", name)
        for metric in wanted:
            spec = load_json(HERE / "layer_metrics" / f"{metric['name']}.json")
            reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
            value = reader.read(context, **spec.get("args", {}))
            if value is not None:  # a reader that finds nothing to read returns nothing
                values[metric["name"]] = value
        say(f"traced program: {trace_reduce.main_module(reduced)}; window {window_s:.4f}s busy {busy_s:.4f}s")
    units = {m["name"]: m["unit"] for m in wanted}
    result["metrics"] = {n: {"value": v, "unit": units[n]} for n, v in values.items() if n in units}
    result["device"] = device
    if rehearsal:
        say("REHEARSAL on " + platform + ": control flow only, no result line. Would have printed:")
        say(json.dumps(result))
        return None
    return result


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)
    result = execute(args.workload, args.seed, args.seconds, bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
