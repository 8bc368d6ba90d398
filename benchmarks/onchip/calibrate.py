#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 benchmarks/onchip/calibrate.py --workload <name> \\
        --seeds 1,2,...,12 [--control-seeds 1,2,3] [--fault-seeds 1,2,3] \\
        [--batches 2]

For each seed, in one process: the cell's program collects ``--batches``
batches of its traffic (the first seed's also compile), its state is freed,
and the rows that a run's check would sample are compared with the float32
reference (the program's reading of ``lp_gap``).  For the control seeds
(each also among ``--seeds``) the control, the same reference computed with
float8 e4m3 operands in every linear layer, is compared with the float32
reference at the same tokens (the control's reading).  For the fault seeds each fault of
``harness/faults.py`` is planted in the program and the seed is read again.
Every reading goes through the harness's own ``check.verdict`` with the
cell's limits, and its line says whether it came out ``correct``.  One JSON
line per reading on standard output.

The benchmark's own runs never run this.  Exits 2 off a TPU.
"""
import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.getcwd()


def seeds_of(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--batches", type=int, default=2)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from harness import cell as C
    from harness import check, faults
    bench = C.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    _, entry, spec, _ = C.find_cell(bench, ROOT, args.workload)
    limits = C.limits_for(entry)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 2
    C.enable_cache()
    controls = set(seeds_of(args.control_seeds))

    def reading(seed, kind):
        t0 = time.perf_counter()
        s = C.Setup(entry, spec, seed)
        recs = [C.collect(s.collector, s.params, s.traffic.batch(i),
                          time.perf_counter) for i in range(args.batches)]
        ref, sizes, wkey, traffic = s.ref, s.sizes, s.wkey, s.traffic
        del s
        gc.collect()
        values, rows, ref_lp = C.reference_check(
            ref, sizes, wkey, recs, spec, traffic, seed, entry["config"],
            lambda m: print(m, file=sys.stderr))
        out = [{"seed": seed, "kind": kind, **values,
                "correct": check.verdict(values, limits)}]
        if kind == "program" and seed in controls:
            _, ctl_lp = C.reference_logprobs(ref, sizes, wkey, rows, traffic,
                                             "control")
            ctl = dict(values, lp_gap=check.lp_gap(ctl_lp, ref_lp))
            out.append({"seed": seed, "kind": "control", **ctl,
                        "correct": check.verdict(ctl, limits)})
        for o in out:
            o.update(limits={k: limits[k] for k in limits},
                     wall_s=time.perf_counter() - t0)
            print(json.dumps(o), flush=True)

    for seed in seeds_of(args.seeds):
        reading(seed, "program")
    for name, plant in faults.FAULTS.items():
        if not args.fault_seeds:
            break
        with plant():
            for seed in seeds_of(args.fault_seeds):
                reading(seed, name)
    return 0


if __name__ == "__main__":
    sys.exit(main())
