"""mode ``serve_keye_vl2``: chipbench/modes/serve.py's run — the same daemon
child, warm-up plan, open loop, sampling and comparison — for a cell whose
configuration is of the ``keye_vl2`` family.

chipbench/configs/README.md says why a family brings a mode. This one adds
no code of its own to the run (modes/serve_afmoe.py's two-name swap):
modes/serve_lfm2.py's ``Daemon`` (the cell's own flag list,
``--prompt_buckets`` and ``--no_prefix_cache`` among them: KeyeSparseLM has
no suffix admission, so the pool refuses a prefix index) and its
``run_reference`` are used as they are, under this family's two names:
``paddle_tpu serve`` is started on chipbench/serve_model_keye_vl2.py and
the reference is chipbench/ref_child_keye_vl2.py. While ``serve.run`` (or
the knee sweep) runs, the names it looks up are these.

The knee sweep of a cell of this mode:

    python -m chipbench.modes.serve_keye_vl2 --workload <cell> \
        --rates 0.3,0.4,0.5,0.6 --seconds 50 --seed 1 [--out sweep.json]

is chipbench/sweep.py under the same names: the knee is the highest rate
whose backlog does not grow.
"""

import contextlib
import sys
from unittest import mock

from chipbench.modes import serve, serve_lfm2

MODEL_SCRIPT = "serve_model_keye_vl2.py"
REF_CHILD = "chipbench.ref_child_keye_vl2"


@contextlib.contextmanager
def family():
    """serve.py's two family-bound names, for as long as it runs."""
    with mock.patch.object(serve_lfm2, "MODEL_SCRIPT", MODEL_SCRIPT), \
            mock.patch.object(serve_lfm2, "REF_CHILD", REF_CHILD), \
            serve_lfm2.family():
        yield


def run(loaded, args, log=print, **kw):
    with family():
        return serve.run(loaded, args, log=log, **kw)


def sweep(argv=None):
    from chipbench import sweep as sweep_mod
    with family():
        return sweep_mod.main(argv)


if __name__ == "__main__":
    sys.exit(sweep())
