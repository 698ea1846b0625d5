"""What the router does with the harness's weights, by seed: one forward
of a cell's model over the first batch its seed gives (the same weights and
ids a run of the cell starts from; no training step, no window), on
whatever device JAX has, and the counters the expert layer sows.

    python3 benchmarks/held_share.py --workload zaya1_8b_train_s4096 \\
        --seeds 2147496001,2147496002 [--plain] [--layers 4]

One JSON line a seed: per layer the share of the rows whose expert is held
here, the largest ÷ mean load over the held experts and the rows each held
expert got. A family without such counters prints nothing to read.
``--plain`` takes the configuration's bias on the selection out: this is
how PERF.md's table of held shares under the plain argmax was made (PR 28).
A run of the cell reads the same counters from its telemetry rows."""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--plain", action="store_true",
                    help="the plain top-k, without the configuration's "
                         "bias on the selection")
    ap.add_argument("--layers", type=int, default=None,
                    help="fewer layers than the configuration's (CPU runs)")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import numpy as np

    from benchmarks import cell, weights
    from tpudist import mesh as mesh_lib

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    spec, config, traffic = cell.load_cell(bench, args.workload, ROOT)
    if args.plain:
        config["recipe"].pop("selection_bias", None)
    if args.layers:
        config["num_hidden_layers"] = args.layers
    family = importlib.import_module(f"benchmarks.families.{config['family']}")
    mesh = mesh_lib.create_mesh(devices=jax.devices()[:spec["chips"]])
    built = family.build(config, traffic, mesh)
    model = built["model"]
    make_stream = family.make_stream(config, traffic, spec["chips"])

    @jax.jit
    def counters(params, tokens):
        _, sown = model.apply({"params": params}, tokens, train=False,
                              return_hidden=True, mutable=["moe_stats"])
        return sown.get("moe_stats", {})

    for seed in (int(s) for s in args.seeds.split(",")):
        params = weights.generate(built["param_shapes"], seed)
        batch = make_stream(np.random.Generator(np.random.PCG64(seed)))()
        sown = jax.device_get(counters(params, batch["tokens"]))
        layers = {name: {k: np.asarray(v[0]).tolist() for k, v in c.items()}
                  for name, c in sown.items()}
        shares = [c["held_share"] for c in layers.values()]
        print(json.dumps({
            "seed": seed, "device": jax.devices()[0].platform,
            "held_share": sum(shares) / len(shares) if shares else None,
            "layers": layers,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
