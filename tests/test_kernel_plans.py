"""Which kernel plan runs is decided beside the kernel, from what the code
can observe: the platform, the lengths, the shapes.

* the decode route's whole decision table (platform x length x forced route);
* no file and no environment variable steers any of the five decisions that
  once consulted ``~/.paddle_tpu/autotune.json``;
* the fused-RNN kernels give the scan's outputs and gradients under any
  legal (block_b, chunk_t), so the plan is a matter of speed alone;
* the plan heuristic stays inside the VMEM budget it is given.
"""

import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.ops import rnn as R

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the decode route -------------------------------------------------------

_LENGTHS = (1, 255, 256, 8192)


@pytest.mark.parametrize("on_tpu,L,forced", [
    (on_tpu, L, None) for on_tpu in (False, True) for L in _LENGTHS
] + [(None, None, "dense"), (None, None, "kernel")])
def test_decode_route_is_a_function_of_platform_length_and_route(
        monkeypatch, on_tpu, L, forced):
    assert pk.SHORT_SEQ_DENSE == 256
    if forced is None:
        monkeypatch.setattr(pk, "_on_tpu", lambda: on_tpu)
        want = "kernel" if on_tpu and L >= 256 else "dense"
        assert pk.decode_route(L) == pk.decode_route(L, None) == want
        return
    # a forced route wins on either platform at every length
    for tpu in (False, True):
        monkeypatch.setattr(pk, "_on_tpu", lambda v=tpu: v)
        assert {pk.decode_route(n, forced) for n in _LENGTHS} == {forced}


# -- nothing outside the program steers a plan ------------------------------

# A well-formed cache in the schema the autotuning plane read (version 1,
# the space hashes of its last tree, device_kind "cpu"), holding for every
# former consult site a winner that differs from the constant.
_STEERING_CACHE = {"schema_version": 1, "entries": {
    "decode_route|decode_attention|cpu|default": {
        "space": "decode_route", "kernel": "decode_attention",
        "device_kind": "cpu", "family": "default",
        "plan": {"kernel_min_len": 1}, "space_hash": "d550f2acb69e",
        "methodology": "measured"},
    "fused_rnn|lstm_sequence_fused|cpu|g4_t12_h8_b8": {
        "space": "fused_rnn", "kernel": "lstm_sequence_fused",
        "device_kind": "cpu", "family": "g4_t12_h8_b8",
        "plan": [8, 8], "space_hash": "6a81f7b21c77",
        "methodology": "measured"},
    "page_block|paged_decode_attention|cpu|default": {
        "space": "page_block", "kernel": "paged_decode_attention",
        "device_kind": "cpu", "family": "default",
        "plan": {"page_block": 32}, "space_hash": "99ceb79a9847",
        "methodology": "measured"},
    "bucket_grid|prefill_dispatch|cpu|prompt": {
        "space": "bucket_grid", "kernel": "prefill_dispatch",
        "device_kind": "cpu", "family": "prompt",
        "plan": {"buckets": [16, 48]}, "space_hash": "7b11bc47a7ff",
        "methodology": "measured"},
    "bucket_grid|prefill_dispatch|cpu|cache": {
        "space": "bucket_grid", "kernel": "prefill_dispatch",
        "device_kind": "cpu", "family": "cache",
        "plan": {"buckets": [64, 128]}, "space_hash": "7b11bc47a7ff",
        "methodology": "measured"},
}}

_DECISIONS_SCRIPT = r"""
import json
import jax, jax.numpy as jnp
from paddle_tpu.data.feeder import BucketSpec
from paddle_tpu.models import TransformerLM
from paddle_tpu.ops import pallas_kernels as pk, rnn as R
from paddle_tpu.serving.paged import PagePool

out = {"decode_route": pk.decode_route(32)}

plans, plan = [], R._fused_plan
R._fused_plan = lambda *a, **k: plans.append(plan(*a, **k)) or plans[-1]
R.lstm(jnp.zeros((8, 12, 5)), None, jnp.zeros((5, 32)), jnp.zeros((8, 32)))
out["fused_plan"] = plans[0]

model = TransformerLM(97, d_model=32, n_heads=4, n_layers=2, max_len=128)
pool = PagePool(model, model.init(jax.random.PRNGKey(0)), slots=2)
out["page_block"] = pool.bs
out["bucket_grids"] = [pool.cache_bucket, list(pool.prompt_buckets)]

try:
    out["bucket_spec"] = list(BucketSpec({"words": "tuned"}).spec["words"][1])
except ValueError:
    out["bucket_spec"] = "refused"
print("DECISIONS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def steered_decisions(tmp_path_factory):
    """The five decisions as a fresh process makes them with the steering
    cache at ``~/.paddle_tpu/autotune.json`` AND at
    ``$PADDLE_TPU_AUTOTUNE_CACHE``."""
    home = tmp_path_factory.mktemp("home")
    (home / ".paddle_tpu").mkdir()
    for path in (home / ".paddle_tpu" / "autotune.json",
                 home / "named.json"):
        path.write_text(json.dumps(_STEERING_CACHE))
    env = dict(os.environ, HOME=str(home), JAX_PLATFORMS="cpu",
               PADDLE_TPU_AUTOTUNE_CACHE=str(home / "named.json"),
               PYTHONPATH=REPO)
    env.pop("PADDLE_TPU_AUTOTUNE", None)
    r = subprocess.run([sys.executable, "-c", _DECISIONS_SCRIPT], env=env,
                       cwd=str(home), capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("DECISIONS ")][-1]
    return json.loads(line[len("DECISIONS "):])


@pytest.mark.parametrize("site,constant", [
    ("decode_route", "dense"),              # 32 rows, off the TPU
    ("fused_plan", [8, 12]),                # T 12 fits whole at batch 8
    ("page_block", 64),
    ("bucket_grids", [256, [32, 64, 128, 256, 512]]),
    ("bucket_spec", "refused"),             # no spelling reaches a file
])
def test_no_file_and_no_environment_steers_a_plan(steered_decisions, site,
                                                  constant):
    assert steered_decisions[site] == constant


# -- the fused-RNN plan changes speed, never results ------------------------

_B, _T, _D, _H = 16, 12, 5, 8


def _rnn_inputs(gates):
    rs = np.random.RandomState(gates)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return dict(
        x=f32(rs.randn(_B, _T, _D) * 0.3),
        lens=jnp.asarray(rs.randint(1, _T + 1, _B), jnp.int32),
        w=f32(rs.randn(_D, gates * _H) * 0.3),
        u=f32(rs.randn(_H, gates * _H) * 0.3),
        b=f32(rs.randn(gates * _H) * 0.1),
        h0=f32(rs.randn(_B, _H) * 0.2),
        wo=f32(rs.randn(_B, _T, _H)), wh=f32(rs.randn(_B, _H)))


@functools.lru_cache(maxsize=None)
def _rnn_outputs_and_grads(cell, plan):
    """(out, hT) and d(loss)/d(x, h0) of one cell; ``plan`` None = scan."""
    a = _rnn_inputs(4 if cell == "lstm" else 3)
    lens, w, u, b = a["lens"], a["w"], a["u"], a["b"]

    def run(x, h0):
        if cell == "lstm" and plan is None:
            out, st = R.lstm(x, lens, w, u, b, h0=h0, c0=h0,
                             forget_bias=1.0, fused=False)
            return out, st.h
        if cell == "lstm":
            out, ht, _ = R._lstm_fused(x, lens, w, u, b, h0, h0, 1.0, *plan)
            return out, ht
        if plan is None:
            return R.gru(x, lens, w, u, b, h0=h0, fused=False)
        return R._gru_fused(x, lens, w, u, b, h0, *plan)

    def loss(x, h0):
        out, ht = run(x, h0)
        return jnp.sum(out * a["wo"]) + jnp.sum(ht * a["wh"])

    outs = run(a["x"], a["h0"])
    grads = jax.grad(loss, argnums=(0, 1))(a["x"], a["h0"])
    return [np.asarray(v) for v in outs + grads]


@pytest.mark.parametrize("cell", ["lstm", "gru"])
@pytest.mark.parametrize("plan", [
    (8, 4),       # two programs of an 8-row tile, three time chunks
    (8, 12),      # two programs, the sequence whole
    (16, 5),      # the widest tile: one program, a ragged last chunk
])
def test_fused_rnn_outputs_do_not_depend_on_the_plan(cell, plan):
    got = _rnn_outputs_and_grads(cell, plan)
    for g, want in zip(got, _rnn_outputs_and_grads(cell, None)):
        np.testing.assert_allclose(g, want, rtol=2e-5, atol=2e-5)
    # against another launch geometry: the same numbers to the bit
    for g, other in zip(got, _rnn_outputs_and_grads(cell, (16, 12))):
        np.testing.assert_array_equal(g, other)


# -- the heuristic stays inside its budget ----------------------------------

@pytest.mark.parametrize("T,H,gates,batch,fits", [
    (100, 256, 4, 64, True),      # LSTM text classifier (benchmarks/)
    (32, 512, 3, 64, True),       # GRU translation encoder / decoder
    (1024, 512, 4, 64, True),     # long sequence: the chunk shrinks
    (100, 256, 4, 5, True),       # batch < 8: one exact-width program
    (100, 256, 3, 8, True),       # batch 8: one program, single-buffered
    (100, 1280, 4, 64, False),    # u alone is 26 MB: the scan
])
def test_fused_plan_heuristic_is_legal_for_the_shapes_we_ship(
        T, H, gates, batch, fits):
    budget = 15_500_000
    for units, always in ((gates + 2, False), (2 * gates + 3, True)):
        plan = R._fused_plan(T, H, gates, units, batch, budget,
                             double_buffer_always=always)
        if not fits:
            assert plan is None
            continue
        blk, chunk = plan
        one_program = blk == batch
        # Mosaic's batch-tile rule
        assert blk <= batch and (blk % 8 == 0 or one_program)
        assert 1 <= chunk <= T
        # resident bytes: u and its accumulator + the [chunk, blk, units*H]
        # f32 tile, twice over when Pallas double-buffers it
        tile = chunk * blk * units * H * 4
        if always or not one_program:
            tile *= 2
        assert 2 * H * gates * H * 4 + tile <= budget
