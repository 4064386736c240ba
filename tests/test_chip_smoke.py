"""chip_smoke.py's parent process: argument parsing and the rule that it
never touches JAX (a parent that has initialised a backend holds the chip and
its children then fail or hang). The chip run itself is `python chip_smoke.py`
through the builder's chip tool; here only what a CPU can show."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*argv, **env_over):
    env = dict(os.environ, **env_over)
    return subprocess.run([sys.executable, *argv], capture_output=True,
                          text=True, env=env, cwd=REPO, timeout=300)


def test_parent_module_imports_without_jax():
    """Importing the parent module and parsing its arguments pulls in
    neither jax nor paddle_tpu."""
    r = _run("-c", (
        "import sys; sys.path.insert(0, %r)\n"
        "import chip_smoke\n"
        "a = chip_smoke.parse_args(['--chips', '4', '--seed', '3'])\n"
        "assert (a.chips, a.seed, a.tiny, a.child) == (4, 3, False, None)\n"
        "assert chip_smoke.parse_args([]).chips == 1\n"
        "assert 'jax' not in sys.modules, 'parent imported jax'\n"
        "assert 'paddle_tpu' not in sys.modules\n"
        "print('CLEAN')\n") % REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "CLEAN" in r.stdout


def test_help_lists_the_options():
    r = _run(SMOKE, "--help")
    assert r.returncode == 0
    for opt in ("--chips", "--seed", "--tiny"):
        assert opt in r.stdout
    assert "--child" not in r.stdout          # internal


def test_serving_client_import_creates_no_backend():
    """The parent imports paddle_tpu.serving for ServingClient while the
    daemon child holds the chip: that import must not start a backend."""
    r = _run("-c", (
        "import paddle_tpu.serving\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
        "print('NO_BACKEND')\n"))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "NO_BACKEND" in r.stdout


def test_no_accelerator_is_a_failure_not_a_cpu_result():
    """With JAX held to the CPU the script exits non-zero and its last line
    says ok:false — it never reports a CPU as the chip."""
    r = _run(SMOKE, JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"] is None
    assert '"ok": true' not in r.stdout
