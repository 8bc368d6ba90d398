#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/onchip/run.py --workload <name> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

from the root of a checkout that holds ``BENCHMARK.json`` and ``src/repro``.
The cell (its configuration, traffic mix and per-layer metrics) is looked up
by name in ``BENCHMARK.json``.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``, and last
``check``, each compared number beside its limit.  The same numbers end
standard error.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for; it never falls back to the CPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.getcwd()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # JAX's persistent compilation cache at a fixed path inside the
    # checkout, whatever the environment says: JAX reads the variable when
    # it is imported, and the program's enable_compile_cache() takes it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from harness import cell as C
    bench = C.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, entry, spec, per_layer = C.find_cell(bench, ROOT, args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < int(cell["chips"]):
        print(f"run.py: cell {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    C.enable_cache()
    result = C.run(cell, entry, spec, per_layer, seed=args.seed,
                   seconds=args.seconds, trace_on=bool(args.trace),
                   t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
