#!/usr/bin/env python3
"""Read a cell's compared numbers for the program and for its control, on
several seeds in one process (the chip is held once):

    python3 benchmarks/chip/control.py --workload nell2.cpals \
        --seeds 101,102,103 --seconds 3

The control is the plain reference computed one precision below what the
configuration states, put where the program's output was (see each
runner's ``check``). Each seed runs the cell's set-up and a short window at
the cell's own size; the program's readings and the control's are printed
per seed and, last, as one JSON line. The control's numbers go through
the harness's own comparison against the cell's limits, so each seed
also says whether the control came out correct; it has to come out
false. The limits in the configuration files were set from these
readings (PERF.md gives them).
"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()
    jax = harness._jax_setup()
    devices = jax.devices()
    spec = harness.Spec(args.workload)
    if devices[0].platform != "tpu" or len(devices) < spec.chips:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 1
    sys.path.insert(0, str(harness.SRC))
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = harness.Context(spec, seed, args.seconds, False, devices)
        ctx.control = harness.Checks()
        harness.finish(ctx)
        row = {"seed": seed, "correct": ctx.correct,
               "control_correct": ctx.control.correct,
               "program": {k: v for k, (v, _) in ctx.compared.items()},
               "control": {k: v for k, (v, _)
                           in ctx.control.compared.items()}}
        print(f"[control] {json.dumps(row)}", flush=True)
        rows.append(row)
    print(json.dumps({"workload": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
