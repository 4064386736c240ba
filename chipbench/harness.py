"""Discovery and arithmetic shared by every mode.

Everything here is found by listing directories under ``chipbench/``: a later
PR adds ``configs/<name>.json``, ``workloads/<cell>.json``,
``traffic/<mix>.json``, ``generators/<name>.py``, ``modes/<name>.py`` or
``metrics/<name>.py`` and an entry in ``BENCHMARK.json``; no table in code
names them.
"""

import importlib.util
import json
import math
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchError(RuntimeError):
    """A run that cannot produce a result line (no chip, bad cell, ...)."""


def _listing(sub, ext, base=None):
    d = os.path.join(base or HERE, sub)
    return {f[:-len(ext)]: os.path.join(d, f) for f in sorted(os.listdir(d))
            if f.endswith(ext) and not f.startswith("_")}


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_named(sub, name, base=None):
    """``<sub>/<name>.json`` as a dict; unknown names list what exists."""
    found = _listing(sub, ".json", base)
    if name not in found:
        raise BenchError(f"no {sub}/{name}.json (there: {sorted(found)})")
    return load_json(found[name])


def load_module(sub, name, base=None):
    """``<sub>/<name>.py`` imported from its file, whatever directory a
    later PR (or a test's temp copy) put it in."""
    found = _listing(sub, ".py", base)
    if name not in found:
        raise BenchError(f"no {sub}/{name}.py (there: {sorted(found)})")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{sub}_{name.replace('.', '_').replace('-', '_')}",
        found[name])
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root=None):
    return load_json(os.path.join(root or ROOT, "BENCHMARK.json"))


def load_cell(name, root=None):
    """Everything one cell needs, gathered by name: the BENCHMARK.json entry,
    the cell file, its configuration, its traffic mix, and the metrics it has
    to report."""
    root = root or ROOT
    base = os.path.join(root, "chipbench")
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchError(f"BENCHMARK.json has no workload {name!r} (there: "
                         f"{[w['name'] for w in bench['workloads']]})")
    cell = load_named("workloads", name, base)
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise BenchError(f"workloads/{name}.json {key}={cell[key]!r} but "
                             f"BENCHMARK.json says {entry[key]!r}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_named("traffic", cell["traffic"], base)

    def applies(m):
        return name in m.get("workloads", [name])
    e2e = [m for m in bench["end_to_end"] if applies(m)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if applies(m) and m["moves"] in reported]
    return {"name": name, "cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layer, "base": base, "root": root,
            "run_seconds": bench["run_seconds"]}


def generator_for(loaded):
    return load_module("generators", loaded["traffic"]["generator"],
                       loaded["base"])


def mode_for(loaded):
    return load_module("modes", loaded["cell"]["mode"], loaded["base"])


def read_layer_metrics(loaded, ctx, log=print):
    """Each per-layer metric of the cell through its own reader
    ``metrics/<name>.py:read(ctx)``; a reader that finds nothing to read
    returns None and the metric is left out of the line."""
    out = {}
    for m in loaded["per_layer"]:
        try:
            value = load_module("metrics", m["name"], loaded["base"]).read(ctx)
        except BenchError:
            raise
        except Exception as e:  # one bad reader must not lose the others
            log(f"per-layer metric {m['name']}: reader failed: {e!r}")
            value = None
        if value is None:
            log(f"per-layer metric {m['name']}: nothing to read "
                "(not measured)")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    for note in ctx.get("notes", []):
        log(note)
    return out


def peaks_for(device_kind, base=None):
    """The chip's published peaks; a device that is not in the table is an
    error, never a default."""
    table = load_json(os.path.join(base or HERE, "peaks.json"))
    if device_kind not in table or device_kind.startswith("_"):
        raise BenchError(f"device_kind {device_kind!r} is not in peaks.json "
                         f"(there: {[k for k in table if k[0] != '_']})")
    return table[device_kind]


def run_reference(spec, work_dir, root, rehearsal, timeout, tag="ref"):
    """Run chipbench/ref_child.py on ``spec`` in a process of its own and
    return what it wrote. The caller must not hold the chip meanwhile."""
    import subprocess
    import sys
    spec_path = os.path.join(work_dir, f"{tag}_spec.json")
    out_path = os.path.join(work_dir, f"{tag}_out.json")
    with open(spec_path, "w") as f:
        json.dump(dict(spec, rehearsal=rehearsal), f)
    env = dict(os.environ, PYTHONPATH=root)
    env.pop("JAX_PLATFORMS", None)
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-m", "chipbench.ref_child",
                        spec_path, out_path], cwd=root, env=env,
                       timeout=timeout)
    if r.returncode == 3:       # the child found no chip and said so
        raise SystemExit(3)
    if r.returncode != 0:
        raise BenchError(f"the reference child ended with {r.returncode}")
    return load_json(out_path)


def pin_compile_cache():
    """The compile cache of every process of a run: a fixed directory
    inside THIS checkout, never trimmed. (The chip machines come with a
    192 MiB cap in JAX_COMPILATION_CACHE_MAX_SIZE; a 36-layer program is
    tens of MB, so a capped cache evicts the first while the ninth is
    written and the next run compiles everything again.) The program's own
    rule, ``paddle_tpu.enable_compile_cache()``, takes the directory from
    this environment variable."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


# -- arithmetic -------------------------------------------------------------

def percentile(values, q):
    """Linear-interpolated percentile (numpy's default) of a plain list."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    if xs[lo] == xs[hi]:            # also inf next to inf (two misses)
        return xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def program_seed(seed):
    """--seed is any whole number up to a little over 2**31; fold it into
    what numpy's RandomState and a PRNGKey both take."""
    return int(seed) % (2 ** 32 - 1)
