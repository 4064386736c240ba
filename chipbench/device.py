"""What every process that holds the chip does first and last: name the
device as JAX reports it, refuse a CPU outside a rehearsal, count the
compilations JAX makes, and read the chip's peak memory."""

import time


class CompileLog:
    """Every backend-compile event with the wall time it ended at.
    A backend compile inside the measured window fails the run: a shape was
    not warmed up. The persistent cache's hits and misses are
    counted beside them: a second run of a cell in a checkout should miss
    nothing."""

    def __init__(self):
        self.events = []
        self.cache = {"compile_requests_use_cache": 0, "cache_hits": 0,
                      "cache_misses": 0}

    def install(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._count)
        return self

    def _count(self, event, **kw):
        name = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and name in self.cache:
            self.cache[name] += 1

    def cache_line(self):
        c = self.cache
        return (f"persistent compile cache: {c['compile_requests_use_cache']}"
                f" requests, {c['cache_hits']} hits, {c['cache_misses']} "
                "misses")

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((event.rsplit("/", 1)[-1], time.time(),
                                float(duration)))

    def between(self, t0, t1, what="backend_compile_duration"):
        return [e for e in self.events if e[0] == what and t0 <= e[1] <= t1]

    def seconds(self, what="backend_compile_duration"):
        return sum(e[2] for e in self.events if e[0] == what)


def describe(rehearsal=False, chips=1):
    """{"platform", "kind", "count"} of this process's devices. Raises
    SystemExit(3) when JAX found no accelerator, or fewer chips than the
    cell asks for — a measurement path never falls back to the CPU."""
    import jax
    d = jax.devices()
    device = {"platform": d[0].platform, "kind": d[0].device_kind,
              "count": len(d)}
    if not rehearsal and (device["platform"] == "cpu"
                          or device["count"] < chips):
        print(f"chipbench: JAX reports {device}; the cell needs {chips} "
              "accelerator chip(s). No result.", flush=True)
        raise SystemExit(3)
    return device


def start_runtime(rehearsal=False, chips=1):
    """``describe()`` and the seconds it took: the first ``jax.devices()`` of
    a process is the accelerator runtime's own start. On a v5e that is 8-13 s
    of a run, moves by 5 s between two runs that do nothing else, and nothing
    in this repository is in it (PERF.md, PR 23), so every mode takes it out
    of ``setup_s`` and prints it on a line of its own."""
    import jax  # noqa: F401  (the import is set-up; the runtime's start is not)
    t = time.time()
    device = describe(rehearsal, chips)
    return device, time.time() - t


def memory_peak_bytes():
    """Peak bytes in use on the fullest chip (0 where the backend does not
    report it, as the CPU does not)."""
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def trace_options():
    """Profiler options of every traced run: device events only. The Python
    tracer (tens of thousands of host events a second, under the GIL the
    daemon's scheduler needs) and all but the coarsest host events are off;
    what the host was doing comes from the program's own spans."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts
