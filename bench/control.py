#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from. Not part of a
benchmark run.

    python3 bench/control.py --workload <cell> --variant <v> --seeds a,b,c

For every seed it prints the numbers the comparison computes
(``yardstick/compare.py``) between the plain reference at the configuration's
precision and:

* ``program``: the program's first rounds, driven as a benchmark run drives
  them (the lower readings);
* ``bf16``: the reference itself in the program's place, computed in
  bfloat16, the precision below the configuration's float32 (the control);
* ``half_batch``: the reference in the program's place with local SGD on
  half of each batch (a fault);
* ``label``: the reference in the program's place with every new request's
  label off by one where the request model makes it (a fault);
* ``frozen``: the reference in the program's place with weights that never
  change (a fault; the host-driven round's comparison may read no weights,
  so it needs a reading there);
* ``no_exchange``: the reference in the program's place with the exchange
  between chips left out (a fault, for a cell on a mesh).

The last line is a JSON object with every reading. Runs on the machine it is
started on (the chip, for the readings ``PERF.md`` gives).
"""
import argparse
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH.parent)]

from bench.run import cell_spec, load_module  # noqa: E402


def readings(spec, variant: str, seed: int) -> dict:
    from bench.run import CompileCounter

    wl = spec.workload
    driver = load_module(BENCH / "drivers" / f"{wl['driver']}.py")
    ctx = SimpleNamespace(cfg=spec.cfg, workload=wl, model=spec.model,
                          seed=seed, seconds=0.0,
                          t_start=time.perf_counter(),
                          counter=CompileCounter(),
                          trace_on=lambda: None, trace_off=lambda: None)
    res = driver.run(ctx) if variant == "program" else None
    return driver.compare_reference(
        ctx, res, None if variant == "program" else variant)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variant", required=True,
                    choices=("program", "bf16", "half_batch", "label",
                             "frozen", "no_exchange"))
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args()
    spec = cell_spec(args.workload)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    out = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        nums = readings(spec, args.variant, seed)
        out[seed] = nums
        print(f"reading cell={args.workload} variant={args.variant} "
              f"seed={seed} device={jax.devices()[0].device_kind} "
              + " ".join(f"{k}={v!r}" for k, v in nums.items()), flush=True)
    print(json.dumps({"cell": args.workload, "variant": args.variant,
                      "readings": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
