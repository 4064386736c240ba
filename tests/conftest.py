"""Test bootstrap: force an 8-device virtual CPU mesh before jax import.

Mirrors the reference's strategy of testing distributed logic in-process
(SURVEY.md §4.3: pserver tests on localhost, MultiGradientMachine with threads):
sharding/collective tests run on 8 virtual CPU devices so no TPU pod is needed.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
prev = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (prev + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _session_compile_cache():
    """Persistent XLA compile cache for the suite, by the repo's one rule
    (paddle_tpu.enable_compile_cache): $JAX_COMPILATION_CACHE_DIR if set,
    else the fixed in-checkout ``.jax_cache/`` — never a temp name, so a
    second run of the suite (and every xdist worker of this one) hits what
    the first compiled.

    Many tests trace structurally-identical small programs into FRESH jit
    closures (every Executor/Trainer instantiation mints new callables),
    so jax's in-memory cache never hits across tests — the persistent
    cache keys on the serialized computation and does. The directory and
    the zeroed floors are exported as JAX's own environment variables so
    subprocess-spawning tests inherit the cache.
    """
    import paddle_tpu
    os.environ["JAX_COMPILATION_CACHE_DIR"] = \
        paddle_tpu.enable_compile_cache()
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    yield


@pytest.fixture(scope="session")
def paged_model_and_params():
    """ONE TransformerLM (the shared serving dims: VOCAB=97, D=32, H=4,
    L=2, MAX_LEN=128) for the paged/prefix serving suites — ROADMAP
    item 5's shared-executable fixture. PagePool shares its jitted
    admission/segment programs PER MODEL INSTANCE
    (serving/paged.py _SHARED_FNS), so a session-scoped model means each
    shape family traces once for the whole suite instead of once per
    test, and the model's own generate/prefill jit caches carry the solo
    references across files too."""
    from paddle_tpu.models import TransformerLM
    model = TransformerLM(97, d_model=32, n_heads=4, n_layers=2,
                          max_len=128)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


#: tests whose assertion counts the benchmark AS IT WAS when they were
#: written, in files of the benchmark's own (``BENCHMARK.json`` ``paths``)
#: that only a ``benchmark`` PR may edit: they fail from the PR that adds
#: the next cell on, and STRICTLY — the entry has to go with the lines that
#: count. Every other line of such a test is asserted again, of the same
#: cell and with no count of the day, where the reason names.
_COUNTS_THE_BENCHMARK_OF_ITS_DAY = {
    "test_chipbench_keye_vl2.py::"
    "test_cell_is_found_by_name_with_its_mode_traffic_and_readers":
        "PR 41's test ends with `len(workloads) == 8 and len(configs) == 7` "
        "and its own cell last; PR 47 added the ninth cell and may not edit "
        "the file (tests/chipbench_tests/test_chipbench_mimo_v2.py "
        "test_the_cells_before_this_one_are_found_as_they_were asserts "
        "every other line of it; PERF.md section 7 asks a benchmark PR to "
        "drop the two lines and this entry)",
}


def pytest_collection_modifyitems(items):
    for item in items:
        for tail, why in _COUNTS_THE_BENCHMARK_OF_ITS_DAY.items():
            if item.nodeid.endswith(tail):
                item.add_marker(pytest.mark.xfail(reason=why, strict=True))


_MP_CPU_PROBE = None

_MP_PROBE_SRC = r"""
import os, sys
port, pid = sys.argv[1], int(sys.argv[2])
import jax
jax.distributed.initialize(f"localhost:{port}", num_processes=2,
                           process_id=pid)
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
mesh = Mesh(np.array(jax.devices()), ("d",))
x = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("d")), np.ones((1,), np.float32))
assert float(jax.jit(lambda a: a.sum())(x)) == 2.0
print("MP_OK")
"""


def multiprocess_cpu_support():
    """(supported, reason): can this jaxlib run a COMPILED computation
    across two CPU processes? ``jax.distributed.initialize`` succeeding is
    NOT enough — some jaxlib builds join the job fine and then fail every
    cross-process computation with 'Multiprocess computations aren't
    implemented on the CPU backend'. The probe runs the real thing (a
    2-process 1-float reduction over a global mesh) once per session, so
    the multiprocess-on-CPU tests skip with the actual backend error as
    the reason instead of failing red on a capability the environment
    never had."""
    global _MP_CPU_PROBE
    if _MP_CPU_PROBE is not None:
        return _MP_CPU_PROBE
    import socket
    import subprocess
    import sys
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    procs = [subprocess.Popen(
        [sys.executable, "-c", _MP_PROBE_SRC, str(port), str(i)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for i in range(2)]
    outs, ok = [], True
    try:
        for p in procs:
            out, _ = p.communicate(timeout=90)
            outs.append(out.decode(errors="replace"))
            ok = ok and p.returncode == 0
    except subprocess.TimeoutExpired:
        ok = False
        outs.append("probe timed out after 90s")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    if ok:
        _MP_CPU_PROBE = (True, "")
    else:
        tail = [ln for o in outs for ln in o.strip().splitlines()
                if ln.strip()]
        reason = tail[-1] if tail else "probe subprocess failed"
        _MP_CPU_PROBE = (False, reason[:300])
    return _MP_CPU_PROBE


def require_multiprocess_cpu():
    """Capability gate for tests that need REAL cross-process collectives
    on the CPU backend (tests/test_multiprocess_dp.py + the launcher's
    training e2es). A skip here always names the backend's own error, so
    a red tier-1 run means a genuine regression, never a missing
    environment capability."""
    ok, reason = multiprocess_cpu_support()
    if not ok:
        pytest.skip("multiprocess-on-CPU collectives unavailable in this "
                    f"environment: {reason}")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "chaos: deterministic fault-injection tests "
        "(tests/test_faults.py); tier-1, no real sleeps, <60s total")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 `-m 'not slow'` run")
    config.addinivalue_line(
        "markers", "obs: observability-plane tests (tests/test_obs.py); "
        "tier-1, fake clocks, no real sleeps")
    config.addinivalue_line(
        "markers", "perf: wall-clock budget tests (generous bounds; "
        "override via PADDLE_TPU_VERIFY_BUDGET_S)")


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture
def np_rng():
    return np.random.RandomState(0)
