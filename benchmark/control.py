#!/usr/bin/env python3
"""python3 benchmark/control.py --workload <name> --seed <n> [--jobs 40]

The controls of "how `correct` is decided": the configuration's plain
reference put in the program's place at the cell's own size, once as it
is (`sound`, which has to pass), once computed in bfloat16, the step
below the float32 the configuration states (`control`), and once with
its argmax blind to the better half of the nodes (`half_hidden`: right
scores, wrong choice), all held to the same comparison as a run.  Needs
no chip and starts no agent; numpy only.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(workload: str, seed: int, jobs: int, n_nodes=None) -> dict:
    from benchmark import harness, traffic
    cell = next(w for w in harness.load_benchmark()["workloads"]
                if w["name"] == workload)
    cfg = harness.load_config(cell["config"])
    ref = harness.world_module(cfg, "reference")
    cl = harness.world_module(cfg, "cluster").Cluster(cfg, seed, n_nodes)
    mix = traffic.load(cell["traffic"])
    order = traffic.shape_order(mix, seed)
    specs = []
    for k in range(jobs):
        name, ns = next(order)
        specs.append(ref.JobSpec(f"c{k:05d}-{name}", ns,
                                 mix["shapes"][name]))
    return {name: {"correct": v["correct"],
                   **{k: c["value"] for k, c in v["compared"].items()}}
            for name, v in ref.controls(cl, specs).items()}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--jobs", type=int, default=40)
    args = p.parse_args()
    sys.path.insert(0, ROOT)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      **readings(args.workload, args.seed, args.jobs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
