"""Readings that the limits in `limits/<cell>.json` are set from.

    python benchmarks/proof.py --workload <cell> --seeds 1,2,3,... --controls 3

One process, one staged graph: for every seed the program's first three
steps against the plain reference (the lower readings); for the first
`--controls` seeds also the control — the reference held in bfloat16, put
in the program's place — and the planted fault — half of the batch left
out, the mean taken over the rest (the upper readings). A step that
leaves its state unchanged needs no run: the change's gap reads 1.
One JSON line per seed; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import run as harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    st = harness.stage(args.workload, args.rehearse)
    config, mix, built, spec, train = st["config"], st["mix"], st["built"], st["spec"], st["train"]
    tables, loss_fn = st["reference"].make(config, mix, st["graph"])
    lr = config["optimizer"]["learning_rate"]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        est = harness.make_estimator(built, config, mix, spec, seed)
        got = harness.program_first_steps(est, spec, seed)
        est.params = est.opt_state = None
        del est
        gc.collect()
        want = train.first_steps(loss_fn, tables, spec, seed, lr)
        row = {"seed": seed, "program": train.compare(got, want),
               "loss": want["loss"], "program_loss": got["loss"]}
        if i < args.controls:
            ctrl = train.first_steps(loss_fn, tables, spec, seed, lr, dtype=jnp.bfloat16)
            row["control_bf16"] = train.compare(ctrl, want)
            half = train.first_steps(loss_fn, tables, spec, seed, lr, fault="half_batch")
            row["fault_half_batch"] = train.compare(half, want)
            row["leaf_gaps"] = {
                k: [got["grad_norm"][k], want["grad_norm"][k],
                    got["change_norm"][k], want["change_norm"][k]]
                for k in want["grad_norm"]
            }
        print(json.dumps(row), flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"memory_peak_bytes": stats.get("peak_bytes_in_use", 0)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
