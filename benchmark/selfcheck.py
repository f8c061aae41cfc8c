"""Checks of the yardstick itself, on the CPU, in a few seconds:

    JAX_PLATFORMS=cpu python -m benchmark.selfcheck

1. ``trace_reduce`` on the recorded trace ``fixtures/small_trace.xplane.pb``
   (TPU v5 lite, PR 22: three executions of one program — a 2048^2 bf16 matmul
   and one flash-attention Mosaic kernel — 12.4 ms apart): busy/idle, gaps and
   Mosaic sums against a brute-force rasterisation and against the numbers read
   by hand from the trace's dump;
2. ``flops.py`` against hand counts for one Mistral-7B layer and one
   bottleneck block;
3. every file ``BENCHMARK.json`` names resolves: configurations, cells,
   traffic mixes, engines, per-layer metrics and their readers; metric names,
   units and cells agree between ``BENCHMARK.json`` and the files.

A script, not a tier-1 test: it exits non-zero on the first failure.
"""

from __future__ import annotations

import importlib
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def expect(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"selfcheck failed: {msg}")
    print(f"ok: {msg}")


def near(got: float, want: float, rel: float = 1e-9) -> bool:
    return abs(got - want) <= rel * max(abs(want), 1e-30)


def check_trace() -> None:
    from benchmark import trace_reduce as tr

    trace = tr.load_xplane(str(HERE / "fixtures" / "small_trace.xplane.pb"))
    expect(sorted(trace["devices"]) == [0], "one device plane")
    dev = trace["devices"][0]
    expect(len(dev["ops"]) == 18 and len(dev["modules"]) == 3, "18 leaf ops in 3 program executions")
    # read by hand from the dump: the Mosaic calls took 26353, 26357 and 26353 ns
    seconds, calls = tr.mosaic_seconds(trace)
    expect(calls == 3 and near(seconds, (26353 + 26357 + 26353) / 1e9), "Mosaic calls: 3, 79.063 us")
    # brute force: rasterise every op at 1 ns into a set-free boolean timeline
    lo = min(op[3] for op in dev["ops"])
    hi = max(op[3] + op[4] for op in dev["ops"])
    line = bytearray(hi - lo)
    for _, _, _, s, d, _ in dev["ops"]:
        line[s - lo:s + d - lo] = b"\x01" * d
    busy_s, window_s = tr.busy_seconds(trace)
    expect(near(busy_s, sum(line) / 1e9) and near(window_s, (hi - lo) / 1e9), f"busy {busy_s * 1e6:.3f} us of {window_s * 1e3:.3f} ms")
    runs = tr.module_runs(dev, tr.main_module(trace))
    expect(tr.main_module(trace) == "jit_f" and len(runs) == 3, "main program jit_f, 3 executions")
    gaps = tr.gaps_between_runs(trace, "jit_f")
    brute = [(b[0] - a[1] - sum(line[a[1] - lo:b[0] - lo])) / 1e9 for a, b in zip(runs, runs[1:])]
    expect(len(gaps) == 2 and all(near(g, b) for g, b in zip(gaps, brute)), f"gaps between executions {[round(g * 1e3, 3) for g in gaps]} ms")
    inside = tr.busy_in_runs(trace, "jit_f")
    brute = [sum(line[max(s, lo) - lo:min(e, hi) - lo]) / 1e9 for s, e in runs]
    expect(all(near(g, b) for g, b in zip(inside, brute)), "busy time inside each execution")
    total, exposed, n = tr.collective_seconds(trace)
    expect((total, exposed, n) == (0.0, 0.0, 0), "no collective on one chip")
    # 3 host annotations p2pfl:round; idle outside them, by rasterising too
    spans = [(s, s + d) for name, s, d in trace["host"] if name.startswith("p2pfl:")]
    expect(len(spans) == 3, "3 p2pfl: host annotations")
    covered = bytearray(hi - lo)
    for s, e in spans:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            covered[s - lo:e - lo] = b"\x01" * (e - s)
    want = sum(1 for b, c in zip(line, covered) if not b and not c) / (hi - lo)
    expect(near(tr.idle_outside(trace, "p2pfl:"), want), f"idle outside p2pfl: spans {100 * want:.2f} %")
    top = tr.top_device_ops(trace)
    expect(
        top[0][0] == "convolution_reduce_fusion (fusion kOutput -> bf16[])"
        and top[1][0].startswith("f (mosaic custom-call -> (bf16[1,4,1024,128]"),
        f"top device ops labelled from the trace's own text: {top[0][0]!r}, {top[1][0]!r}",
    )
    name, opcode, mosaic = tr.parse_hlo(
        '%ar.1 = (f32[8]{0}, f32[8]{0}) all-reduce-start(f32[8]{0:T(128)S(1)} %x), replica_groups={}'
    )
    expect((name, opcode, mosaic) == ("ar.1", "all-reduce-start", False) and tr.is_collective(opcode), "HLO text parsing")


def check_flops() -> None:
    from benchmark import flops

    cfg = json.loads((HERE / "configs" / "mistral7b_lora.json").read_text())
    # one Mistral-7B layer by hand: q,o 4096x4096; k,v 4096x1024; three 4096x14336
    by_hand = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    expect(flops.lm_layer_params(cfg) == by_hand == 218103808, "Mistral layer: 218,103,808 parameters")
    one = dict(cfg, num_hidden_layers=1)
    step = flops.lora_step_flops(one, 4096, rank=8, lora_mlp=True)
    expect(step["base"] == 4.0 * by_hand * 4096, "frozen base: forward + dX = 4 x params x tokens")
    expect(step["attention"] == 6 * 2.0 * 4096 * 4096 * 128 * 32 * 0.5, "causal attention fwd+bwd = 6 matmuls x T^2 D H, halved")
    expect(step["head"] == 4.0 * 4096 * 32768 * 4096, "tied head: forward + dX")
    expect(flops.flash_executed_flops(one, 4096) == 8 * 2.0 * 4096 * 4096 * 128 * 32 * 0.5, "flash under mlp_qkv: 2 fwd + 1 bwd")
    res = json.loads((HERE / "configs" / "resnet50_cifar100.json").read_text())
    # stage-1 first block on 32x32x64: 1x1 64->64, 3x3 64->64, 1x1 64->256, projection 1x1 64->256
    by_hand = 2 * 1024 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    expect(flops.bottleneck_flops(32, 32, 64, 64, 1)[0] == by_hand == 150994944, "bottleneck block: 150,994,944 FLOP")
    expect(flops.resnet_params(res) == res["parameters"] == 23705252, "ResNet-50/CIFAR-100: 23,705,252 parameters")
    expect(near(flops.resnet_forward_flops(res), 2596028416.0), "ResNet-50 at 32x32: 2.596 GFLOP forward")


def check_files() -> None:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        body = json.loads((ROOT / c["file"]).read_text())
        expect(body["source"] == c["source"] and body["reduced"] == c["reduced"], f"config {c['name']}: file agrees on source and reduced")
    cells = set()
    for w in bench["workloads"]:
        cell = json.loads((HERE / "workloads" / f"{w['name']}.json").read_text())
        expect(all(cell[k] == w[k] for k in ("config", "traffic", "chips", "why")), f"cell {w['name']}: file agrees")
        expect(w["config"] in configs and (HERE / "traffic" / f"{w['traffic']}.json").is_file(), f"cell {w['name']}: config and traffic files exist")
        importlib.import_module(f"benchmark.engines.{cell['engine']}")
        cells.add(w["name"])
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        spec = json.loads((HERE / "layer_metrics" / f"{m['name']}.json").read_text())
        expect(all(spec[k] == m[k] for k in ("layer", "unit", "better", "source", "moves")), f"metric {m['name']}: file agrees")
        expect(hasattr(importlib.import_module(f"benchmark.readers.{spec['reader']}"), "read"), f"metric {m['name']}: reader {spec['reader']}")
        expect(m["moves"] in end_to_end and set(m.get("workloads", cells)) <= cells, f"metric {m['name']}: moves and cells known")
    for w in bench["workloads"]:
        named = [m["name"] for g in ("end_to_end", "per_layer") for m in bench[g] if w["name"] in m.get("workloads", cells)]
        expect("setup_s" in named and len(named) >= 3, f"cell {w['name']}: {len(named)} metrics")
    peaks = json.loads((HERE / "peaks.json").read_text())
    expect(all("source" in v for v in peaks.values()), "every peak names its source")


def main() -> int:
    check_trace()
    check_flops()
    check_files()
    print("selfcheck passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
