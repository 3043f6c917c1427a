"""``python benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell on the TPU chips it asks for.

One process, no child.  Prints human-readable lines and, last, one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
and, traced, ``breakdown``).  Without the cell's chips it exits non-zero
and prints no result.
"""

import time

PROCESS_START = time.time()   # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import harness, spec
    from stochastic_gradient_push_tpu.utils.compile_cache import (
        place_compile_cache)

    chips = spec.load_cell(ROOT, args.workload).chips
    cache = place_compile_cache()

    import jax

    platform, found = jax.default_backend(), jax.device_count()
    if platform != "tpu" or found < chips:
        print(f"benchmark: {args.workload} needs {chips} TPU chip(s); the "
              f"backend is {platform!r} with {found} device(s). The "
              "benchmark measures the chip and has no other path.",
              file=sys.stderr)
        return 1
    print(f"benchmark: {args.workload} seed {args.seed} on {found} x "
          f"{jax.devices()[0].device_kind}; compile cache at {cache}")
    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              bool(args.trace), PROCESS_START)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
