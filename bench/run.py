#!/usr/bin/env python3
"""The chip benchmark of the online OSAFL trainer.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine whose JAX finds a TPU (with as many
chips as the cell asks for); without one it exits 1 and prints no result.

Everything a cell is made of is data found by name: the cell in
``BENCHMARK.json``, its workload file ``bench/workloads/<cell>.json`` (driver,
rounds per dispatch, the limits of the comparison), its configuration
``bench/configs/<config>.json`` with the plain model reference beside it
(``<config>.py``), its driver ``bench/drivers/<driver>.py``, and one reader
per per-layer metric ``bench/metrics/<metric>.py``.

A run: the driver's set-up (its first rounds compile or load from JAX's
persistent cache) and measured window, the peak device memory, then the
driver's comparison of the run's first rounds with the plain reference.
A driver module has ``numbers(workload)`` (the names of the numbers it
compares), ``run(ctx)``, ``compare_reference(ctx, res)`` and
``trace_view(ctx, res, trace, tz)``. With ``--trace 0`` the result carries
the cell's end-to-end metrics; with ``--trace 1`` a profiler trace of a
short window gives its per-layer metrics. The last line on standard output
is one JSON object; the numbers compared, each beside its limit, are the
last lines on standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json")


def cell_spec(name: str) -> SimpleNamespace:
    """The cell, its configuration, model reference and workload file."""
    manifest = load_json(ROOT / "BENCHMARK.json")
    cell = find(manifest["workloads"], name, "workload")
    conf = find(manifest["configs"], cell["config"], "config")
    cfg_path = ROOT / conf["file"]
    return SimpleNamespace(
        manifest=manifest, cell=cell, cfg=load_json(cfg_path),
        model=load_module(cfg_path.with_suffix(".py")),
        workload=load_json(BENCH / "workloads" / f"{name}.json"))


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return table[kind]


class CompileCounter:
    """Counts JAX's compile and compile-cache events while armed."""

    def __init__(self):
        import jax.monitoring
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.armed and (event.startswith("/jax/core/compile")
                           or event.startswith("/jax/compilation_cache")):
            self.count += 1


def per_layer(spec, tctx) -> dict:
    """Each per-layer metric of this cell that its reader finds."""
    out = {}
    for m in spec.manifest["per_layer"]:
        if "workloads" in m and spec.cell["name"] not in m["workloads"]:
            continue
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(tctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def traced_numbers(spec, driver, ctx, res, tr, peaks) -> tuple:
    """Per-layer metrics, busy and window seconds, and the breakdown."""
    from bench.yardstick import trace as tz

    chips = spec.cell["chips"]
    view = driver.trace_view(ctx, res, tr, tz)
    if view is None:
        raise SystemExit("bench: the trace holds no window to read")
    lo, hi = view.lo, view.hi
    ops = tr["devices"][:chips]
    tctx = SimpleNamespace(trace=tr, ops=ops, lo=lo, hi=hi,
                           rounds=view.rounds, flops=view.flops,
                           plane_rows=view.plane_rows // chips,
                           n_params=spec.cfg["n_params"],
                           request_gen_s=view.request_gen_s,
                           chips=chips, peaks=peaks, tz=tz)
    busy_s = sum(tz.busy_ns(o, lo, hi) for o in ops) / len(ops) / 1e9
    own = {}
    for o in ops:
        for k, v in tz.own_times(o, lo, hi).items():
            own[k] = own.get(k, 0.0) + v / len(ops) / 1e9
    gaps = sorted(((label, ns / 1e9) for o in ops
                   for label, ns in tz.idle_gaps(tr, o, lo, hi)),
                  key=lambda g: -g[1])
    breakdown = {
        "device_ops": [[k, v] for k, v in sorted(
            own.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[k, v] for k, v in gaps[:10]]}
    return per_layer(spec, tctx), busy_s, (hi - lo) / 1e9, breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = cell_spec(args.workload)
    chips = spec.cell["chips"]

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    kind = devices[0].device_kind
    peaks = peaks_for(kind)
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return run_cell(spec, args, devices[:chips], peaks, kind, cache_dir)


def run_cell(spec, args, devices, peaks, kind, cache_dir) -> int:
    """Drive the cell, compare, and print the result."""
    import jax
    from bench.yardstick import compare
    from bench.yardstick import trace as tz

    wl = spec.workload
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    seconds = (min(args.seconds, wl["trace_seconds"]) if args.trace
               else args.seconds)

    def trace_on():
        if trace_dir:
            jax.profiler.start_trace(trace_dir)

    def trace_off():
        if trace_dir:
            jax.profiler.stop_trace()

    ctx = SimpleNamespace(cfg=spec.cfg, workload=wl, model=spec.model,
                          seed=args.seed, seconds=seconds, t_start=T_START,
                          counter=CompileCounter(), trace_on=trace_on,
                          trace_off=trace_off)
    driver = load_module(BENCH / "drivers" / f"{wl['driver']}.py")
    res = driver.run(ctx)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    nums = driver.compare_reference(ctx, res)
    limits = wl["limits"]
    correct = compare.judge(nums, limits) and res.failed == 0

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": res.attempted,
              "failed": res.failed}
    print(f"bench: cell={spec.cell['name']} seed={args.seed} "
          f"setup_s={res.setup_s:.3f} window_s={res.window_s:.3f} "
          f"rounds={res.rounds} compiles_in_window={res.compiles_in_window} "
          f"compile_cache={cache_dir} {res.note}", file=sys.stderr)
    if args.trace:
        path = next(Path(trace_dir).rglob("*.xplane.pb"))
        tr = tz.load(str(path))
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics, busy_s, window_s, breakdown = traced_numbers(
            spec, driver, ctx, res, tr, peaks)
        device.update(busy_s=busy_s, window_s=window_s)
        result.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        names = {m["name"] for m in spec.manifest["end_to_end"]
                 if spec.cell["name"] in m.get("workloads",
                                               [spec.cell["name"]])}
        e2e = {"round_ms": {"value": 1000.0 * res.window_s / res.rounds,
                            "unit": "ms"},
               "peak_hbm_gb": {"value": peak / 1e9, "unit": "GB"},
               "setup_s": {"value": res.setup_s, "unit": "s"}}
        result.update(metrics={k: v for k, v in e2e.items() if k in names},
                      device=device)
    result["checks"] = {k: {"value": nums[k], "limit": limits[k]}
                        for k in limits}
    for k in limits:
        print(f"check {k}={nums[k]!r} limit={limits[k]!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
