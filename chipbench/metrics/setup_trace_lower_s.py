"""Seconds of set-up the chip-holding process spent tracing functions to
jaxprs and lowering them to MLIR — the part of building a program that a
persistent compile cache does not save: the union, thread by thread, of the
``jax.trace`` / ``jax.lower`` instants' intervals ``[ts - duration_secs,
ts]`` that ended before the window opened (a traced function that calls
jitted ones reports their traces inside its own, so a plain sum would count
them twice). A note names the functions that took longest."""

from chipbench.metrics._span_tree import instants, union_seconds


def read(ctx):
    by_tid, by_fun = {}, {}
    for tid, t, args in instants(ctx, ("jax.trace", "jax.lower")):
        if t > ctx["window"][0] or "duration_secs" not in args:
            continue
        d = float(args["duration_secs"])
        by_tid.setdefault(tid, []).append((t - d, t))
        fun = str(args.get("fun_name", "?"))
        by_fun[fun] = by_fun.get(fun, 0.0) + d
    if not by_tid:
        return None
    top = sorted(by_fun.items(), key=lambda kv: -kv[1])[:4]
    ctx.setdefault("notes", []).append(
        "set-up, trace + lower: longest " + ", ".join(
            f"{f} {s:.2f}s" for f, s in top))
    return sum(union_seconds(iv) for iv in by_tid.values())
