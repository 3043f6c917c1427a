"""``python benchmark/control.py --workload <cell> --steps <k> --seeds <n>
[<n> ...]``: the control of the comparison that decides ``correct``, on
the chip at the cell's own size.  The benchmark's runs never call it.

For each seed it builds the cell's state and batches as a run does, drives
the program's step ``--steps`` times over the resident batches (as many
as the cell's window holds: the state a run compares on is the one its
window left), unloads the step as a run does, and makes the cell's
comparison twice: the program's forward pass beside the plain reference
— the reading sound runs give — and the reference computed in the
nearest precision below the configuration's
(``reference/compare.py::NEXT_LOWER``) put in the program's place — the
reading a tolerance has to refuse.  One JSON line a seed, and the two
readings' ranges last.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def readings(root: str, workload: str, seed: int, steps: int) -> dict:
    from benchmark import harness, spec
    from benchmark.reference import compare

    cell = spec.load_cell(root, workload)
    job = spec.load_plugin(root, "builders", cell.builder).build(cell, seed)
    if job.reference_check is None:
        raise ValueError(f"{workload}: the configuration's file gives no "
                         "'reference' tolerance, so nothing is compared")
    for i in range(steps):
        job.state, _ = job.step(job.state, *job.batches[i % len(job.batches)])
    harness.drop_step(job)
    return {"seed": seed, "steps": steps,
            "program": job.reference_check(job.state),
            "control": job.reference_check(
                job.state, compare.rounded_to(cell.config["precision"]))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--steps", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from stochastic_gradient_push_tpu.utils.compile_cache import (
        place_compile_cache)

    place_compile_cache()
    rows = []
    for seed in args.seeds:
        rows.append(readings(ROOT, args.workload, seed, args.steps))
        print(json.dumps(rows[-1]), flush=True)
    summary = {"workload": args.workload, "steps": args.steps}
    for side in ("program", "control"):
        for number in ("logit_error", "loss_error"):
            values = [r[side][number] for r in rows]
            summary[f"{side}_{number}"] = [min(values), max(values)]
    summary["control_refused_on_every_seed"] = not any(
        r["control"]["ok"] for r in rows)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
