"""Fused decode step (ISSUE 7): CPU ``interpret=True`` parity for the
decode-attention kernel, the quantized-KV numerics contract, the
one-dispatch-per-token obs evidence, the multi-token verify step, and the
widened fused-RNN coverage (reverse direction + wide batch tiles).

The contract under test (docs/design/kernels.md): route choice — dense
reference math vs the Pallas kernel, full-precision vs int8 cache reads —
NEVER changes which greedy token comes out; int8 changes logits only
through the documented quantize-dequant of cache reads, identically on
every route."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import obs
from paddle_tpu.models import TransformerLM
from paddle_tpu.ops import pallas_kernels as pk

VOCAB, D, H, L, MAX_LEN = 97, 32, 4, 2, 512


@pytest.fixture(scope="module")
def model_and_params():
    model = TransformerLM(VOCAB, d_model=D, n_heads=H, n_layers=L,
                          max_len=MAX_LEN)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _prompt(b=2, t=7, seed=0):
    rs = np.random.RandomState(seed)
    return jnp.asarray(rs.randint(0, VOCAB, (b, t)), jnp.int32)


# -- the auto-routing entry point -----------------------------------------


@pytest.mark.parametrize("quant", [False, True])
def test_decode_attention_kernel_matches_dense(quant):
    """The Pallas decode kernel (interpret=True on CPU) and the dense
    reference route share one masked-softmax formulation: same output to
    float tolerance on identical inputs, and exact masking — rows past
    pos contribute nothing on either route."""
    rs = np.random.RandomState(3)
    B, Lc, Hh, Dh = 3, 64, 4, 8
    q = jnp.asarray(rs.randn(B, Hh, Dh), jnp.float32)
    pos = jnp.asarray([5, 0, 63], jnp.int32)
    if quant:
        kf = rs.randn(B, Lc, Hh, Dh).astype(np.float32)
        vf = rs.randn(B, Lc, Hh, Dh).astype(np.float32)
        k, ks = pk.quantize_kv(jnp.asarray(kf))
        v, vs = pk.quantize_kv(jnp.asarray(vf))
    else:
        k = jnp.asarray(rs.randn(B, Lc, Hh, Dh), jnp.float32)
        v = jnp.asarray(rs.randn(B, Lc, Hh, Dh), jnp.float32)
        ks = vs = None
    dense = pk.decode_attention(q, k, v, pos, k_scale=ks, v_scale=vs,
                                route="dense")
    kern = pk.decode_attention(q, k, v, pos, k_scale=ks, v_scale=vs,
                               route="kernel", interpret=True)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)
    # masking: zeroing every row PAST pos must not change the output
    j = np.arange(Lc)
    live = jnp.asarray((j[None, :] <= np.asarray(pos)[:, None]))
    kz = jnp.where(live[..., None, None], k, jnp.zeros((), k.dtype))
    vz = jnp.where(live[..., None, None], v, jnp.zeros((), v.dtype))
    kern_z = pk.decode_attention(q, kz, vz, pos, k_scale=ks, v_scale=vs,
                                 route="kernel", interpret=True)
    np.testing.assert_allclose(np.asarray(kern_z), np.asarray(kern),
                               rtol=1e-6, atol=1e-6)


def test_quantize_kv_roundtrip_bound():
    """Symmetric int8: per-row max-abs scale bounds the dequant error at
    scale/2 per element (half a code step)."""
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(4, 16, 3, 8) * 3.0, jnp.float32)
    q, s = pk.quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == x.shape[:-1]
    err = np.abs(np.asarray(q, np.float32) * np.asarray(s)[..., None]
                 - np.asarray(x))
    assert (err <= np.asarray(s)[..., None] * 0.5 + 1e-6).all()


# -- the fused decode step -------------------------------------------------


@pytest.mark.parametrize("bucket", [None, 32])
def test_generate_fused_greedy_matches_cached(model_and_params, bucket):
    """Greedy parity of the fused single-dispatch-per-token loop against
    the reference generate_cached scan, bucketed and not."""
    model, params = model_and_params
    prompt = _prompt()
    want = np.asarray(model.generate_cached(params, prompt, steps=12,
                                            bucket=bucket))
    got = np.asarray(model.generate_fused(params, prompt, steps=12,
                                          bucket=bucket))
    np.testing.assert_array_equal(got, want)


def test_generate_fused_kernel_route_matches_dense(model_and_params):
    """Forcing the Pallas kernel route (interpret on CPU) through the whole
    model must leave greedy tokens identical — the auto-routing contract."""
    model, params = model_and_params
    prompt = _prompt(seed=2)
    want = np.asarray(model.generate_fused(params, prompt, steps=8,
                                           attn_route="dense"))
    got = np.asarray(model.generate_fused(params, prompt, steps=8,
                                          attn_route="kernel"))
    np.testing.assert_array_equal(got, want)


def test_generate_fused_int8_routes_agree(model_and_params):
    """int8 numerics contract: the quantization error is the MODEL's
    (introduced by quantize_kv at append), not the kernel's — dense and
    kernel routes over the same int8 cache emit identical tokens."""
    model, params = model_and_params
    prompt = _prompt(seed=3)
    a = np.asarray(model.generate_fused(params, prompt, steps=10,
                                        kv_dtype="int8",
                                        attn_route="dense"))
    b = np.asarray(model.generate_fused(params, prompt, steps=10,
                                        kv_dtype="int8",
                                        attn_route="kernel"))
    np.testing.assert_array_equal(a, b)


def test_generate_fused_dispatch_counter(model_and_params):
    """THE acceptance assert: one compiled dispatch per generated token —
    1 prefill (emits the first token) + steps-1 fused steps — visible on
    decode.dispatches_total; tokens_total counts every emitted token."""
    model, params = model_and_params
    prompt = _prompt(b=3)
    steps = 9
    r = obs.MetricsRegistry()
    with obs.ObsSession(registry=r).installed():
        model.generate_fused(params, prompt, steps=steps)
    disp = {s["labels"]["route"]: s["value"] for s in r.collect()
            if s["name"] == "decode.dispatches_total"}
    assert disp == {"prefill": 1, "step": steps - 1}
    toks = [s["value"] for s in r.collect()
            if s["name"] == "decode.tokens_total"]
    assert toks == [3 * steps]
    # the modeled kernel bytes rode along
    assert any(s["name"] == "kernels.bytes_total"
               and s["labels"]["kernel"] == "decode_attention"
               and s["value"] > 0 for s in r.collect())


def test_generate_fused_topk_sampling(model_and_params):
    """top-k sampling: deterministic under a fixed key, tokens stay inside
    the top-k set of the reference logits at every step."""
    model, params = model_and_params
    prompt = _prompt(b=1, seed=4)
    key = jax.random.PRNGKey(11)
    a = np.asarray(model.generate_fused(params, prompt, steps=6,
                                        sample="topk", top_k=5, key=key))
    b = np.asarray(model.generate_fused(params, prompt, steps=6,
                                        sample="topk", top_k=5, key=key))
    np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="top_k and key"):
        model.generate_fused(params, prompt, steps=2, sample="topk")


# -- verify step (speculative building block) ------------------------------


def test_verify_step_bit_exact_vs_sequential(model_and_params):
    """verify_step's span must reproduce running decode_step sequentially
    over the same tokens — the exactness speculative decoding inherits
    (serving.SpeculativeDecoder). The contract (docs/design/kernels.md,
    "verify_step"): greedy tokens are EQUAL, logits agree within 2 ulp of
    the largest logit. Bit-equal logits are out of reach: the span's p·v
    contraction is a matmul with M = S where the single step's is a
    matrix-vector product, and XLA's CPU backend sums the two in a
    different order (scores and projections do agree to the bit)."""
    model, params = model_and_params
    prompt = _prompt(seed=6)
    cell, last = model.prefill(params, prompt)
    cur = jnp.argmax(last, -1).astype(prompt.dtype)
    toks, logits, c = [cur], [], dict(cell)
    for _ in range(6):
        lg, c = model.decode_step(params, c, toks[-1])
        logits.append(lg)
        toks.append(jnp.argmax(lg, -1).astype(prompt.dtype))
    span = jnp.stack(toks[:6], axis=1)
    vlg, c2 = model.verify_step(params, cell, span)
    seq = np.stack([np.asarray(lg) for lg in logits], axis=1)
    np.testing.assert_array_equal(np.argmax(np.asarray(vlg), -1),
                                  np.argmax(seq, -1))
    np.testing.assert_allclose(np.asarray(vlg), seq, rtol=0,
                               atol=2 * np.spacing(np.abs(seq).max()))
    np.testing.assert_array_equal(np.asarray(c2["pos"]),
                                  np.asarray(cell["pos"]) + 6)


def test_verify_step_int8_matches_sequential_int8(model_and_params):
    """Same check on an int8 cell: append-quantize + dequant-read agree
    between the span and sequential paths (greedy tokens identical)."""
    model, params = model_and_params
    prompt = _prompt(seed=7)
    cell, last = model.prefill(params, prompt, kv_dtype="int8")
    cur = jnp.argmax(last, -1).astype(prompt.dtype)
    toks, c = [cur], dict(cell)
    for _ in range(5):
        lg, c = model.decode_step(params, c, toks[-1])
        toks.append(jnp.argmax(lg, -1).astype(prompt.dtype))
    span = jnp.stack(toks[:5], axis=1)
    vlg, _ = model.verify_step(params, cell, span)
    t = np.asarray(jnp.argmax(vlg, -1))
    np.testing.assert_array_equal(
        t, np.stack([np.asarray(x) for x in toks[1:6]], axis=1))


# -- widened fused-RNN coverage --------------------------------------------


def _lstm_inputs(seed, B=5, T=9, D=4, Hh=6):
    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(B, T, D), jnp.float32)
    lens = jnp.asarray(rs.randint(1, T + 1, B), jnp.int32)
    w = jnp.asarray(rs.randn(D, 4 * Hh) * 0.3, jnp.float32)
    u = jnp.asarray(rs.randn(Hh, 4 * Hh) * 0.3, jnp.float32)
    b = jnp.asarray(rs.randn(4 * Hh) * 0.1, jnp.float32)
    return x, lens, w, u, b


def test_reverse_within_length_roundtrip():
    from paddle_tpu.ops import rnn as R
    rs = np.random.RandomState(8)
    x = jnp.asarray(rs.randn(3, 5, 2), jnp.float32)
    lens = jnp.asarray([5, 3, 1], jnp.int32)
    y = R._reverse_within_length(x, lens)
    # sample 1 (len 3): first three steps flipped, tail zero
    np.testing.assert_array_equal(np.asarray(y[1, :3]),
                                  np.asarray(x[1, :3][::-1]))
    assert (np.asarray(y[1, 3:]) == 0).all()
    # flipping twice restores the live prefix
    z = R._reverse_within_length(y, lens)
    np.testing.assert_array_equal(np.asarray(z[1, :3]),
                                  np.asarray(x[1, :3]))


def test_fused_lstm_reverse_matches_scan():
    """reverse=True through the fused kernel (within-length flip around the
    forward kernel) vs the masked reverse scan: outputs AND final state."""
    from paddle_tpu.ops import rnn as R
    x, lens, w, u, b = _lstm_inputs(9)
    B, T, _ = x.shape
    Hh = u.shape[0]
    ref_out, ref_state = R.lstm(x, lens, w, u, b, reverse=True, fused=False,
                                forget_bias=1.0)
    h0 = jnp.zeros((B, Hh), x.dtype)
    xk = R._reverse_within_length(x, lens)
    out, ht, ct = R._lstm_fused(xk, lens, w, u, b, h0, h0, 1.0, 5, 3)
    out = R._reverse_within_length(out, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ht), np.asarray(ref_state.h),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ct), np.asarray(ref_state.c),
                               rtol=1e-5, atol=1e-6)


def test_fused_lstm_reverse_grads_match_scan():
    """Gradients flow through the flip gathers around the fused kernel's
    custom VJP: reverse-direction training parity (the bidirectional
    textcls/NMT encoder case)."""
    from paddle_tpu.ops import rnn as R
    x, lens, w, u, b = _lstm_inputs(10)
    B, T, _ = x.shape
    Hh = u.shape[0]
    wo = jnp.asarray(np.random.RandomState(1).randn(B, T, Hh), jnp.float32)
    h0 = jnp.zeros((B, Hh), x.dtype)

    def ref(x, w, u, b):
        out, _ = R.lstm(x, lens, w, u, b, reverse=True, fused=False,
                        forget_bias=1.0)
        return jnp.sum(out * wo)

    def fused(x, w, u, b):
        xk = R._reverse_within_length(x, lens)
        out, ht, ct = R._lstm_fused(xk, lens, w, u, b, h0, h0, 1.0, 5, 4)
        return jnp.sum(R._reverse_within_length(out, lens) * wo)

    g_ref = jax.grad(ref, argnums=(0, 1, 2, 3))(x, w, u, b)
    g_fused = jax.grad(fused, argnums=(0, 1, 2, 3))(x, w, u, b)
    for name, a, bb in zip("x w u b".split(), g_ref, g_fused):
        np.testing.assert_allclose(np.asarray(bb), np.asarray(a),
                                   rtol=2e-5, atol=2e-5, err_msg=name)


def test_fused_lstm_multichunk_backward_matches_scan(monkeypatch):
    """Force a small backward time-chunk so the multi-launch reverse
    recurrence (boundary state from the saved out/c sequences) is
    exercised at test scale — at real scale it engages for long T."""
    from paddle_tpu.ops import rnn as R
    x, lens, w, u, b = _lstm_inputs(11)
    B, T, _ = x.shape
    Hh = u.shape[0]
    monkeypatch.setattr(R, "_fused_bwd_plan",
                        lambda *a, **k: (B, 3))
    wo = jnp.asarray(np.random.RandomState(2).randn(B, T, Hh), jnp.float32)
    h0 = jnp.zeros((B, Hh), x.dtype)

    def ref(x, w, u, b):
        out, _ = R.lstm(x, lens, w, u, b, fused=False, forget_bias=1.0)
        return jnp.sum(out * wo)

    def fused(x, w, u, b):
        out, ht, ct = R._lstm_fused(x, lens, w, u, b, h0, h0, 1.0, B, None)
        return jnp.sum(out * wo)

    g_ref = jax.grad(ref, argnums=(0, 1, 2, 3))(x, w, u, b)
    g_fused = jax.grad(fused, argnums=(0, 1, 2, 3))(x, w, u, b)
    for name, a, bb in zip("x w u b".split(), g_ref, g_fused):
        np.testing.assert_allclose(np.asarray(bb), np.asarray(a),
                                   rtol=2e-5, atol=2e-5, err_msg=name)


def test_fused_gru_reverse_matches_scan():
    """Same flip construction for the GRU — the seq2seq NMT encoder's
    backward direction."""
    from paddle_tpu.ops import rnn as R
    rs = np.random.RandomState(12)
    B, T, D, Hh = 4, 8, 3, 6
    x = jnp.asarray(rs.randn(B, T, D), jnp.float32)
    lens = jnp.asarray(rs.randint(1, T + 1, B), jnp.int32)
    w = jnp.asarray(rs.randn(D, 3 * Hh) * 0.3, jnp.float32)
    u = jnp.asarray(rs.randn(Hh, 3 * Hh) * 0.3, jnp.float32)
    b = jnp.asarray(rs.randn(3 * Hh) * 0.1, jnp.float32)
    ref_out, ref_h = R.gru(x, lens, w, u, b, reverse=True, fused=False)
    h0 = jnp.zeros((B, Hh), x.dtype)
    xk = R._reverse_within_length(x, lens)
    out, ht = R._gru_fused(xk, lens, w, u, b, h0, 4, 3)
    out = R._reverse_within_length(out, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ht), np.asarray(ref_h),
                               rtol=1e-5, atol=1e-6)
