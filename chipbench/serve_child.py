"""The process that holds the chip in a serve cell: a thin wrapper that calls
``paddle_tpu.cli``'s ``serve`` entry in its main thread, unchanged, for traced
and untraced runs alike.

Around it, and only from here: the device is named (a CPU is refused outside
a rehearsal), JAX's compile events are logged with their wall times, and
with ``--trace_seconds`` a side thread starts ``jax.profiler`` when the
parent creates ``<run_dir>/trace.go`` (the window has begun) and stops it
that many seconds later. At exit ``<run_dir>/child_report.json`` carries
the device, its peak memory, the compile log and the trace's directory.

    python -m chipbench.serve_child --run_dir D [--trace_seconds S]
        [--rehearsal] -- serve --config chipbench/serve_model.py ...
"""

import argparse
import json
import os
import sys
import threading
import time


def _tracer(run_dir, seconds, report):
    import jax
    go = os.path.join(run_dir, "trace.go")
    while not os.path.exists(go):
        time.sleep(0.05)
    trace_dir = os.path.join(run_dir, "trace")
    t0 = time.time()
    from chipbench import device as dev
    jax.profiler.start_trace(trace_dir, profiler_options=dev.trace_options())
    time.sleep(seconds)
    jax.profiler.stop_trace()
    report["trace"] = {"dir": trace_dir, "span": [t0, time.time()]}


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    split = argv.index("--")
    ap = argparse.ArgumentParser()
    ap.add_argument("--run_dir", required=True)
    ap.add_argument("--trace_seconds", type=float, default=0.0)
    ap.add_argument("--rehearsal", action="store_true")
    a = ap.parse_args(argv[:split])

    from chipbench import device as dev
    device, runtime_up_s = dev.start_runtime(a.rehearsal)
    compiles = dev.CompileLog().install()
    report = {"device": device, "trace": None, "runtime_up_s": runtime_up_s}
    print(f"chipbench serve child: device {device}; accelerator runtime "
          f"came up in {runtime_up_s:.2f}s", flush=True)
    tracer = None
    if a.trace_seconds > 0:
        tracer = threading.Thread(target=_tracer, daemon=True,
                                  args=(a.run_dir, a.trace_seconds, report))
        tracer.start()
    from paddle_tpu import cli
    rc = 1
    try:
        rc = cli.main(argv[split + 1:])
    finally:
        if tracer is not None and os.path.exists(
                os.path.join(a.run_dir, "trace.go")):
            tracer.join(timeout=120)    # let the profiler write its file
        report.update(rc=rc, memory_peak_bytes=dev.memory_peak_bytes(),
                      compile_events=compiles.events,
                      cache_line=compiles.cache_line())
        tmp = os.path.join(a.run_dir, "child_report.json.tmp")
        with open(tmp, "w") as f:
            json.dump(report, f)
        os.replace(tmp, os.path.join(a.run_dir, "child_report.json"))
    return rc


if __name__ == "__main__":
    sys.exit(main())
