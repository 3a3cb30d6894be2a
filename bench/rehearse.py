#!/usr/bin/env python3
"""The CPU rehearsal of a cell: the same run as ``run.py`` at the tiny
sizes under each config's and mix's ``rehearsal`` key, on the CPU with the
Pallas kernels interpreted (four virtual devices for a four-chip cell).
It reports platform ``cpu``; none of its numbers is a device measurement.

    python3 bench/rehearse.py --workload <cell> --seed N --seconds S \\
        --trace 0|1 [--fault NAME]

``--fault`` breaks the program's timed path underneath (see
``faults.py``), for the tests that see ``correct`` come out false.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv) -> int:
    fault = None
    if "--fault" in argv:
        i = argv.index("--fault")
        fault = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    sys.path.insert(0, HERE)
    import harness

    workload = argv[argv.index("--workload") + 1]
    config, _ = harness.load_cell(workload, rehearsal=True)
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={config['chips']}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    if fault:
        harness.import_program()
        import faults

        faults.install(fault)
    return harness.main(argv, t_start=T_START, platform="cpu",
                        rehearsal=True)


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
