#!/usr/bin/env python3
"""Find the FFT service's knee on the chip: the highest offered rate at
which the backlog does not grow over the window.

    python3 bench/knee.py --workload fft-service.zipf-steady \\
        --rates 200,400,800 --seconds 10 --seed N

One process, one service: set-up as in a run of the cell, then one window
per rate, lowest first.  Each prints the backlog half way and at the last
arrival (requests submitted and not answered), the latencies and the
batches.  The rate a mix file states is read off this once, by hand; the
benchmark's runs never search for one.
"""

import argparse
import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", default="fft-service.zipf-steady")
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--platform", default="tpu")
    args = p.parse_args(argv)
    sys.path.insert(0, HERE)
    import harness
    from drivers import serve

    rehearsal = args.platform != "tpu"
    config, traffic = harness.load_cell(args.workload, rehearsal)
    devices = harness.open_devices(args.platform, int(config["chips"]))
    tag = f"[{devices[0].platform} {devices[0].device_kind} x{len(devices)}]"

    def log(msg):
        print(f"knee {tag} {msg}", file=sys.stderr, flush=True)

    cell = serve.Cell(config, traffic, args.seed, devices, log)
    try:
        for rate in [float(r) for r in args.rates.split(",")]:
            cell.traffic = dict(traffic, rate_hz=rate)
            w = cell.window(args.seconds,
                            lambda name: contextlib.nullcontext())
            print(f"knee {tag} rate_hz={rate!r} requests={w['attempted']} "
                  f"failed={w['failed']} "
                  f"p50_ms={w['metrics']['serve_p50_ms']!r} "
                  f"p95_ms={w['metrics']['serve_p95_ms']!r} "
                  f"backlog_mid={w['backlog_mid']} "
                  f"backlog_close={w['backlog_close']} "
                  f"batches={w['batches']} completed={w['completed']}",
                  flush=True)
    finally:
        cell.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
