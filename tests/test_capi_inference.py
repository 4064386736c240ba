"""C inference ABI (native/capi_inference.cc — capi/gradient_machine.h:36-88
analog): create from the merged inference bundle, forward-only, callable from
plain C (driven here via ctypes), multi-thread safe (the reference's
multi_thread example)."""

import ctypes
import os
import threading

import numpy as np
import pytest

import paddle_tpu.fluid as fluid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB_PATH = os.path.join(REPO, "native", "libpaddle_tpu_capi.so")


@pytest.fixture(autouse=True)
def fresh_programs():
    fluid.reset_default_programs()
    yield


def _load():
    if not os.path.exists(LIB_PATH):
        pytest.skip("capi library not built (make -C native)")
    lib = ctypes.CDLL(LIB_PATH)
    lib.pti_create.restype = ctypes.c_void_p
    lib.pti_create.argtypes = [ctypes.c_char_p]
    lib.pti_forward.restype = ctypes.c_int
    lib.pti_forward.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_void_p),      # inputs
        ctypes.POINTER(ctypes.c_longlong),    # shapes (concatenated)
        ctypes.POINTER(ctypes.c_int),         # ndims
        ctypes.POINTER(ctypes.c_int),         # dtypes
        ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_float), ctypes.c_longlong,
        ctypes.POINTER(ctypes.c_longlong), ctypes.POINTER(ctypes.c_int)]
    lib.pti_destroy.argtypes = [ctypes.c_void_p]
    lib.pti_last_error.restype = ctypes.c_char_p
    return lib


def _export_model(tmp_path):
    x = fluid.layers.data("x", shape=(4,))
    h = fluid.layers.fc(x, 8, act="tanh")
    out = fluid.layers.fc(h, 2)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / "model")
    fluid.io.export_inference_model(d, ["x"], [out], exe)
    xs = np.random.RandomState(0).randn(3, 4).astype(np.float32)
    ref = np.asarray(exe.run(fluid.default_main_program(), feed={"x": xs},
                             fetch_list=[out])[0])
    return d, xs, ref


def _forward(lib, h, xs, out_elems=64):
    buf = np.ascontiguousarray(xs)
    inputs = (ctypes.c_void_p * 1)(buf.ctypes.data)
    shapes = (ctypes.c_longlong * 2)(*buf.shape)
    ndims = (ctypes.c_int * 1)(2)
    dtypes = (ctypes.c_int * 1)(0)
    out = np.zeros(out_elems, np.float32)
    out_shape = (ctypes.c_longlong * 8)()
    out_ndim = ctypes.c_int(0)
    rc = lib.pti_forward(
        h, inputs, shapes, ndims, dtypes, 1, 0,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out_elems, out_shape, ctypes.byref(out_ndim))
    assert rc >= 0, lib.pti_last_error().decode()
    shape = tuple(out_shape[i] for i in range(out_ndim.value))
    return out[:rc].reshape(shape)


def test_capi_create_forward_destroy(tmp_path):
    lib = _load()
    d, xs, ref = _export_model(tmp_path)
    h = lib.pti_create(d.encode())
    assert h, lib.pti_last_error().decode()
    got = _forward(lib, h, xs)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    lib.pti_destroy(h)


def test_capi_create_bad_dir_reports_error():
    lib = _load()
    h = lib.pti_create(b"/nonexistent/model/dir")
    assert not h
    assert lib.pti_last_error()


def test_capi_multi_thread(tmp_path):
    """capi/examples/model_inference/multi_thread analog: concurrent
    forwards on one handle must all produce correct results."""
    lib = _load()
    d, xs, ref = _export_model(tmp_path)
    h = lib.pti_create(d.encode())
    assert h, lib.pti_last_error().decode()
    errs = []

    def worker():
        try:
            for _ in range(5):
                got = _forward(lib, h, xs)
                np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
        except Exception as e:   # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    lib.pti_destroy(h)


def test_capi_small_buffer_reports_size(tmp_path):
    lib = _load()
    d, xs, _ = _export_model(tmp_path)
    h = lib.pti_create(d.encode())
    buf = np.ascontiguousarray(xs)
    inputs = (ctypes.c_void_p * 1)(buf.ctypes.data)
    shapes = (ctypes.c_longlong * 2)(*buf.shape)
    ndims = (ctypes.c_int * 1)(2)
    dtypes = (ctypes.c_int * 1)(0)
    out = np.zeros(1, np.float32)
    out_shape = (ctypes.c_longlong * 8)()
    out_ndim = ctypes.c_int(0)
    rc = lib.pti_forward(
        h, inputs, shapes, ndims, dtypes, 1, 0,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), 1,
        out_shape, ctypes.byref(out_ndim))
    assert rc == -2          # too small; shape still reported for retry
    assert tuple(out_shape[i] for i in range(out_ndim.value)) == (3, 2)
    lib.pti_destroy(h)


def _build_and_run_c_example(tmp_path, name, argv, extra_cc=()):
    """Compile native/examples/<name>.c against the capi .so and run it as
    its own process (its own embedded-CPython init — ensure_python's cold
    path). Skips when the toolchain or library is missing."""
    import shutil
    import subprocess

    _load()   # skip if lib not built
    if shutil.which("gcc") is None:
        pytest.skip("no C toolchain")
    src = os.path.join(REPO, "native", "examples", name + ".c")
    exe = str(tmp_path / name)
    lib_dir = os.path.join(REPO, "native")
    cc = subprocess.run(
        ["gcc", src, "-o", exe, *extra_cc, "-L" + lib_dir,
         "-lpaddle_tpu_capi"],
        capture_output=True, text=True)
    assert cc.returncode == 0, cc.stderr
    env = dict(os.environ)
    env["LD_LIBRARY_PATH"] = lib_dir + ":" + env.get("LD_LIBRARY_PATH", "")
    env["PYTHONPATH"] = REPO + ":" + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([exe, *argv], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)


def test_c_example_program_standalone(tmp_path):
    """capi/examples/model_inference/dense analog: a REAL C program compiled
    with gcc, linked against the capi .so, output compared to the in-process
    executor."""
    d, _, _ = _export_model(tmp_path)
    n, dim = 3, 4
    out = _build_and_run_c_example(tmp_path, "infer_dense",
                                   [d, str(n), str(dim)])
    assert out.returncode == 0, out.stdout + out.stderr
    rows = [list(map(float, line.split()))
            for line in out.stdout.strip().splitlines()]
    assert len(rows) == n and len(rows[0]) == 2

    # compare against the same inputs through the Python host. The C
    # program's embedded interpreter inherits JAX_PLATFORMS=cpu from this
    # process (conftest.py), so both sides run the CPU backend; tolerances
    # stay the cross-backend matmul kind (TensorCheck tiering, SURVEY §7)
    # so the example also passes where it is pointed at a chip.
    from paddle_tpu.runtime.capi_host import InferenceHost
    x = (np.arange(n * dim) % 7).astype(np.float32) * 0.1 - 0.3
    ref = InferenceHost(d).run([x.reshape(n, dim)])
    np.testing.assert_allclose(np.asarray(rows), ref, rtol=5e-2, atol=5e-3)


def _export_sequence_model(tmp_path, vocab=40, emb=8, max_len=6):
    """Lengths-carrying text classifier: embedding -> masked average pool
    (padding ids must NOT leak into the pool) -> fc. The lengths slot is the
    second feed, as an i32 vector — the TPU-native LoD encoding."""
    ids = fluid.layers.data("ids", shape=(max_len,), dtype="int32")
    lens = fluid.layers.data("lens", shape=(), dtype="int32")
    emb_out = fluid.layers.embedding(ids, size=(vocab, emb))
    pooled = fluid.layers.sequence_pool(emb_out, lens, pool_type="average")
    out = fluid.layers.fc(pooled, 3)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / "seq_model")
    fluid.io.export_inference_model(d, ["ids", "lens"], [out], exe)
    return d


def test_c_example_sequence(tmp_path):
    """capi/examples/model_inference/sequence analog: ragged int32 sequences
    with a true-lengths slot through the C ABI; results must match the
    in-process executor on identical inputs (so the padded tail is provably
    masked)."""
    batch, max_len, vocab = 3, 6, 40
    d = _export_sequence_model(tmp_path, vocab=vocab, max_len=max_len)
    out = _build_and_run_c_example(tmp_path, "infer_sequence",
                                   [d, str(batch), str(max_len), str(vocab)])
    assert out.returncode == 0, out.stdout + out.stderr
    rows = [list(map(float, line.split()))
            for line in out.stdout.strip().splitlines()]
    assert len(rows) == batch and len(rows[0]) == 3

    # same deterministic inputs as the C program builds
    ids = np.zeros((batch, max_len), np.int32)
    lens = np.zeros((batch,), np.int32)
    for b in range(batch):
        n = max(1, max_len - b)
        lens[b] = n
        for t in range(n):
            ids[b, t] = (b * 31 + t * 7) % vocab
    from paddle_tpu.runtime.capi_host import InferenceHost
    ref = InferenceHost(d).run([ids, lens])
    # cross-backend tolerance: the C process runs on the default platform
    np.testing.assert_allclose(np.asarray(rows), ref, rtol=5e-2, atol=5e-3)


def _export_sparse_binary_model(tmp_path, dim=50, emb=6, max_nnz=5):
    """Multi-hot classifier: active-feature ids + nnz counts -> embedded
    row SUM (the weighted-row-sum sparse-fc path) -> fc."""
    ids = fluid.layers.data("ids", shape=(max_nnz,), dtype="int32")
    counts = fluid.layers.data("counts", shape=(), dtype="int32")
    emb_out = fluid.layers.embedding(ids, size=(dim, emb))
    summed = fluid.layers.sequence_pool(emb_out, counts, pool_type="sum")
    out = fluid.layers.fc(summed, 2)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / "sb_model")
    fluid.io.export_inference_model(d, ["ids", "counts"], [out], exe)
    return d


def test_c_example_sparse_binary(tmp_path):
    """capi/examples/model_inference/sparse_binary analog: multi-hot rows
    as padded index lists + counts through the C ABI; results must match
    the in-process executor (padding indices provably masked)."""
    batch, max_nnz, dim = 4, 5, 50
    d = _export_sparse_binary_model(tmp_path, dim=dim, max_nnz=max_nnz)
    out = _build_and_run_c_example(
        tmp_path, "infer_sparse_binary",
        [d, str(batch), str(max_nnz), str(dim)])
    assert out.returncode == 0, out.stdout + out.stderr
    rows = [list(map(float, line.split()))
            for line in out.stdout.strip().splitlines()]
    assert len(rows) == batch and len(rows[0]) == 2

    ids = np.zeros((batch, max_nnz), np.int32)
    counts = np.zeros((batch,), np.int32)
    for b in range(batch):
        nnz = max_nnz - (b % max_nnz)
        counts[b] = nnz
        for j in range(nnz):
            ids[b, j] = (b * 13 + j * 5) % dim
    from paddle_tpu.runtime.capi_host import InferenceHost
    ref = InferenceHost(d).run([ids, counts])
    np.testing.assert_allclose(np.asarray(rows), ref, rtol=5e-2, atol=5e-3)


def test_c_example_multi_thread(tmp_path):
    """capi/examples/model_inference/multi_thread analog: a REAL pthread C
    program — 4 threads x 5 forwards on one shared handle must all bit-match
    the single-threaded reference forward."""
    d, _, _ = _export_model(tmp_path)
    out = _build_and_run_c_example(
        tmp_path, "infer_multi_thread", [d, "4", "5", "3", "4"],
        extra_cc=("-pthread",))
    assert out.returncode == 0, out.stdout + out.stderr
    assert out.stdout.strip().splitlines()[-1] == "OK 4x5"
