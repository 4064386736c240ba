"""mode ``serve_mimo_v2``: chipbench/modes/serve.py's run — the same daemon
child, warm-up plan, open loop, sampling and comparison — for a cell whose
configuration is of the ``mimo_v2_flash`` family.

chipbench/configs/README.md says why a family brings a mode. This one adds
no code of its own to the run: modes/serve_lfm2.py's ``Daemon`` (the cell's
own flag list, ``--prompt_buckets`` and ``--no_prefix_cache`` among them: a
ring's rows belong to a slot, not to a prefix) and its ``run_reference`` are
used as they are, under this family's two names (modes/serve_afmoe.py's
swap): ``paddle_tpu serve`` is started on chipbench/serve_model_mimo_v2.py
and the reference is chipbench/ref_child_mimo_v2.py. The cell's
``control_operand`` LISTS its controls (a lower precision, a forgotten
sink); under ``CHIPBENCH_CONTROL`` the child reads them all and ``run`` logs
each under its own name, beside the cell's limits.

The knee sweep of a cell of this mode:

    python -m chipbench.modes.serve_mimo_v2 --workload <cell> \
        --rates 0.1,0.2,0.3,0.4,0.5,0.6 --seconds 50 --seed 1 [--out sweep.json]

is chipbench/sweep.py under the same names: the knee is the highest rate
whose backlog does not grow.
"""

import contextlib
import sys
from unittest import mock

from chipbench import harness
from chipbench.modes import serve, serve_lfm2

MODEL_SCRIPT = "serve_model_mimo_v2.py"
REF_CHILD = "chipbench.ref_child_mimo_v2"


@contextlib.contextmanager
def family():
    """serve.py's two family-bound names, for as long as it runs."""
    with mock.patch.object(serve_lfm2, "MODEL_SCRIPT", MODEL_SCRIPT), \
            mock.patch.object(serve_lfm2, "REF_CHILD", REF_CHILD), \
            serve_lfm2.family():
        yield


def run(loaded, args, log=print, **kw):
    limits = loaded["cell"]["limits"]

    def run_reference(*a, **k):
        out = serve_lfm2.run_reference(*a, **k)
        for name in (out["rows"] or [{}])[0].get("controls", ()):
            gaps = [g for row in out["rows"] for g in row["controls"][name]]
            for n, v in (("served_gap_mean", sum(gaps) / len(gaps)),
                         ("served_gap_widest", max(gaps))):
                log(f"control[{name}] {n} = {v:.6g}  limit {limits[n]:.6g}  "
                    f"{'passes' if v <= limits[n] else 'fails'}")
        return out
    with family(), mock.patch.object(harness, "run_reference",
                                     run_reference):
        return serve.run(loaded, args, log=log, **kw)


def sweep(argv=None):
    from chipbench import sweep as sweep_mod
    with family():
        return sweep_mod.main(argv)


if __name__ == "__main__":
    sys.exit(sweep())
