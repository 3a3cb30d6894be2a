#!/usr/bin/env python3
"""One traced run of a cell, as ``run.py --trace 1`` does, that also reads
the program's own trace events (``program_trace.py``) before the harness
reduces the trace, and prints what it finds on log lines:

* ``clock_offset_ms=`` with its bracket, device seconds per executable,
  and the idle gaps split by program span;
* ``program launch_ms.fft= sync_ms.fft= hbm_share.xla_plans=
  hbm_share.pallas_plans=``.

    python3 bench/program_window.py --workload <cell> --seed N \\
        --seconds S [--keep FILE] [--rehearsal]

It wraps ``tracing.reduce_trace`` because the harness deletes the trace
once its own reduction is done.  ``--keep`` copies the window's
``.xplane.pb`` to FILE.  ``--rehearsal`` runs the CPU rehearsal's sizes
on the CPU, as ``rehearse.py`` does.  The result line is the harness's
own.
"""

import time

T_START = time.perf_counter()

import glob  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv) -> int:
    keep = None
    if "--keep" in argv:
        i = argv.index("--keep")
        keep = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    rehearsal = "--rehearsal" in argv
    argv = [a for a in argv if a != "--rehearsal"] + ["--trace", "1"]
    sys.path.insert(0, HERE)
    import harness
    import tracing

    if rehearsal:
        workload = argv[argv.index("--workload") + 1]
        config, _ = harness.load_cell(workload, rehearsal=True)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={config['chips']}")
    reduce_trace = tracing.reduce_trace

    def reduce_with_program(trace_dir, chips, span_names):
        summary = reduce_trace(trace_dir, chips, span_names)
        import jax
        from jax.profiler import ProfileData

        import program_trace
        from repro.core.trace import SPANS
        from yardstick import peaks

        (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True)
        if keep:
            os.makedirs(os.path.dirname(os.path.abspath(keep)), exist_ok=True)
            shutil.copy(path, keep)
        t = program_trace.summarize(ProfileData.from_file(path), set(SPANS))
        hbm = peaks(jax.devices()[0].device_kind,
                    harness.REHEARSAL_PEAKS if rehearsal
                    else harness.PEAKS)["hbm_bytes_per_s"]
        values = {
            "launch_ms.fft": t.launch_ms(), "sync_ms.fft": t.sync_ms(),
            "hbm_share.xla_plans": t.hbm_share("fft_xla_", hbm),
            "hbm_share.pallas_plans": t.hbm_share("fft_pallas_", hbm)}
        for line in program_trace.log_lines(t) + [
                "program " + " ".join(f"{k}={v!r}"
                                      for k, v in values.items())]:
            print(f"bench program +{time.perf_counter() - T_START:.1f}s "
                  f"{line}", file=sys.stderr, flush=True)
        return summary

    tracing.reduce_trace = reduce_with_program
    return harness.main(argv, t_start=T_START,
                        platform="cpu" if rehearsal else "tpu",
                        rehearsal=rehearsal)


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
