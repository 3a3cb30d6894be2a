#!/usr/bin/env python3
"""The control of a cell's check, on the chip: the reference put in the
program's place, computed one precision below what the configuration
states (``reference.control_forward``, float32 matmuls at ``HIGH``), on
the inputs a run with the same seed makes, compared by the same numbers
against the same float64 reference.  Its readings have to exceed the
cell's limits; they set the limits' upper end.

    python3 bench/control.py --workload <cell> --seeds 11,12,13

The benchmark's own runs never run this.  ``--platform cpu`` runs it in
the CPU rehearsal sizes (what ``tests/test_bench.py`` does).
"""

import argparse
import json
import math
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def batch_readings(config, traffic, seed, log) -> dict:
    import inputs
    import reference
    from drivers import batch

    worst = {"fwd_rel_l2": 0.0, "inv_rel_l2": 0.0}
    for j, p in enumerate(batch.problems(config, traffic)):
        real = not p.complex_input
        x = batch.make_input(seed, j, p)
        rows = inputs.check_rows(seed, j, p.batch, math.prod(p.extents))
        xs = x[rows]
        del x
        xr = np.asarray(xs)
        spec = reference.control_forward(xs, p.rank, real)
        fwd = float(reference.rel_l2_rows(
            np.asarray(spec), reference.forward(xr, p.rank, real)).max())
        back = reference.control_inverse(spec, p.extents, real)
        inv = float(reference.rel_l2_rows(np.asarray(back), xr).max())
        log(f"control {p.signature()} rows={len(rows)} fwd_rel_l2={fwd!r} "
            f"inv_rel_l2={inv!r}")
        worst["fwd_rel_l2"] = max(worst["fwd_rel_l2"], fwd)
        worst["inv_rel_l2"] = max(worst["inv_rel_l2"], inv)
    return worst


def serve_readings(config, traffic, seed, log) -> dict:
    import jax.numpy as jnp

    import reference
    import traffic_tape
    from drivers import serve

    worst = 0.0
    for i, (ext, kind) in enumerate(traffic_tape.mix(traffic)):
        real = kind.endswith("Real")
        pool = serve.make_pool(seed, i, ext, kind, traffic["payload_pool"])
        got = reference.control_forward(jnp.asarray(pool), len(ext), real)
        r = float(reference.rel_l2_rows(
            np.asarray(got), reference.forward(pool, len(ext), real)).max())
        log(f"control {'x'.join(map(str, ext))}/{kind} payloads={len(pool)} "
            f"serve_rel_l2={r!r}")
        worst = max(worst, r)
    return {"serve_rel_l2": worst}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--platform", default="tpu")
    args = p.parse_args(argv)
    sys.path.insert(0, HERE)
    import harness

    config, traffic = harness.load_cell(args.workload,
                                        rehearsal=args.platform != "tpu")
    devices = harness.open_devices(args.platform, 1)
    tag = f"[{devices[0].platform} {devices[0].device_kind}]"

    def log(msg):
        print(f"control {tag} {msg}", file=sys.stderr, flush=True)

    readings = serve_readings if config["driver"] == "serve" \
        else batch_readings
    out = {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        r = readings(config, traffic, seed, log)
        out[str(seed)] = {k: {"value": v, "limit": config["limits"][k],
                              "fails": v > config["limits"][k]}
                          for k, v in r.items()}
        log(f"seed={seed} " + " ".join(f"{k}={v!r}" for k, v in r.items()))
    fails = all(any(c["fails"] for c in r.values()) for r in out.values())
    print(json.dumps({"workload": args.workload, "control_fails": fails,
                      "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind},
                      "seeds": out}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
