"""Readings that the limits in `limits/<cell>.json` are set from.

    python benchmarks/proof.py --workload <cell> --seeds 1,2,3,... --controls 3
        [--weights-seeds a,b,c]

One process, one staged graph: for every seed the program's first three
steps against the plain reference (the lower readings); for the first
`--controls` seeds also the control — the reference held in bfloat16, put
in the program's place — and the planted fault — half of the batch left
out, the mean taken over the rest (the upper readings). A step that
leaves its state unchanged needs no run: the change's gap reads 1.
`--seeds` are the batches' seeds, whatever the configuration fixes for
its own runs. The weights are made from the configuration's
`model.run_seed`, else from each seed's own; `--weights-seeds` runs the
loop over `--seeds` once for each weights seed given. So a configuration
that fixes its runs' seed (`weights.run_seed`) still has its limits read
over many models and many batches. One JSON line per (weights seed,
seed); the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import sys

import run as harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--weights-seeds", default="",
                    help="run the loop once for each of these weights seeds")
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    import weights

    st = harness.stage(args.workload, args.rehearse)
    config, mix, built, spec, train = st["config"], st["mix"], st["built"], st["spec"], st["train"]
    tables, loss_fn = st["reference"].make(config, mix, st["graph"])
    lr = config["optimizer"]["learning_rate"]
    seeds = [int(x) for x in args.seeds.split(",")]
    models = [int(x) for x in args.weights_seeds.split(",") if x] or [None]
    for model in models:
        for i, seed in enumerate(seeds):
            wseed = weights.run_seed(config, seed) if model is None else model
            reference_steps = functools.partial(
                train.first_steps, loss_fn, tables, spec, seed, lr, weights_seed=wseed
            )
            est = harness.make_estimator(built, config, mix, spec, seed, wseed)
            got = harness.program_first_steps(est, spec, wseed)
            est.params = est.opt_state = None
            del est
            gc.collect()
            want = reference_steps()
            row = {"seed": seed, "weights_seed": wseed,
                   "program": train.compare(got, want),
                   "loss": want["loss"], "program_loss": got["loss"]}
            if i < args.controls:
                ctrl = reference_steps(dtype=jnp.bfloat16)
                row["control_bf16"] = train.compare(ctrl, want)
                half = reference_steps(fault="half_batch")
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
