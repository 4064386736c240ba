"""The plain reference against the system under test at a tiny size, and the
output checks' power: they pass at the stated precision and fail below it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights
from chipbench.modes import train
from chipbench.reference import gpt2 as ref
from chipbench.ref_child import gaps_for, train_readings

CFG = {"vocab_size": 128, "n_positions": 64, "n_embd": 32, "n_head": 4,
       "n_layer": 2, "n_inner": 128}


@pytest.fixture(scope="module")
def tiny():
    model, shapes = weights.model_and_shapes(CFG)
    return model, shapes, weights.make(shapes, 7)


def test_weights_are_a_function_of_the_seed_and_published_init(tiny):
    _, shapes, params = tiny
    again = weights.make(shapes, 7)
    other = weights.make(shapes, 2**31 + 5)
    w = params["blocks_0"]["qkv"]["w"]
    assert (w == again["blocks_0"]["qkv"]["w"]).all()
    assert not (w == other["blocks_0"]["qkv"]["w"]).all()
    assert float(jnp.std(params["embed"]["w"])) == pytest.approx(0.02,
                                                                 rel=0.1)
    assert (params["ln_f"]["gamma"] == 1).all()
    assert (params["blocks_1"]["mlp_in"]["b"] == 0).all()


def test_reference_logits_and_loss_match_transformer_lm(tiny):
    model, _, params = tiny
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 128, (3, 48)),
                      jnp.int32)
    np.testing.assert_allclose(ref.forward(params, ids, CFG["n_head"]),
                               model(params, ids), rtol=2e-4, atol=2e-5)
    assert float(ref.lm_loss(params, ids, CFG["n_head"])) == pytest.approx(
        float(model.loss(params, ids)), rel=1e-5)


def test_reference_gradient_and_adam_match_the_programs_step(tiny):
    model, _, params = tiny
    from paddle_tpu.optimizer import Adam
    ids = np.random.RandomState(1).randint(0, 128, (2, 32)).astype(np.int32)
    losses, gnorm, dnorm = ref.train_reference(params, [ids, ids],
                                               CFG["n_head"], 3e-4)
    opt = Adam(3e-4)
    p, st = params, opt.init(params)
    for step in range(2):
        loss, g = jax.value_and_grad(model.loss)(p, jnp.asarray(ids))
        if step == 0:
            got_g = [float(x) for x in ref.leaf_norms(g)]
        p, st = opt.update(g, st, p)
        assert float(loss) == pytest.approx(losses[step], rel=1e-5)
    np.testing.assert_allclose(got_g, gnorm, rtol=1e-3, atol=1e-9)
    np.testing.assert_allclose(
        [float(x) for x in ref.leaf_diff_norms(p, params)], dnorm,
        rtol=1e-3)


def _readings(params, batches, operand):
    return train_readings(params, CFG, batches, 3e-4, operand)


def test_train_comparison_passes_sound_and_fails_the_fp8_control(tiny):
    _, _, params = tiny
    rs = np.random.RandomState(3)
    batches = [rs.randint(0, 128, (2, 48)).astype(np.int32)
               for _ in range(3)]
    want = _readings(params, batches, None)
    limits = {"loss_rel_gap": 1e-3, "grad_norm_gap": 0.05,
              "update_norm_gap": 0.5}
    assert all(ok for *_, ok in train.compare(want, want, limits))
    control = train.compare(_readings(params, batches, "fp8"), want, limits)
    assert not all(ok for *_, ok in control), control
    # a step that returns its state unchanged: no update at all
    stuck = dict(want, update_norms=[0.0] * len(want["update_norms"]))
    rows = {n: ok for n, _, _, ok in train.compare(stuck, want, limits)}
    assert rows["update_norm_gap"] is False
    # a part of the batch left out moves the loss
    half = _readings(params, [b[:1] for b in batches], None)
    rows = {n: ok for n, _, _, ok in train.compare(half, want, limits)}
    assert rows["loss_rel_gap"] is False


def test_worst_leaf_gap_is_held_to_the_median_leaf():
    want = [1.0, 2.0, 1e-9, 3.0]
    # the median leaf's norm is 1.5: a leaf below it is held to 1.5
    assert train.worst_leaf_gap([1.1, 2.0, 2e-9, 3.0], want) == \
        pytest.approx(0.1 / 1.5)
    assert train.worst_leaf_gap([1.0, 2.0, 1e-9, 3.6], want) == \
        pytest.approx(0.2)
    assert train.worst_leaf_gap([1.0, 2.0, 0.5, 3.0], want) == \
        pytest.approx(0.5 / 1.5)


def test_served_token_gap_passes_f32_and_fails_lower_precisions(tiny):
    model, _, params = tiny
    # at two layers of 0.02-weights the tied head just echoes the input
    # token with a wide lead and no precision flips anything; eightfold
    # block matrices let the blocks speak, as 36 layers do at full size
    params = {k: (jax.tree_util.tree_map(
        lambda a: a * 8 if a.ndim == 2 else a, v)
        if k.startswith("blocks_") else v) for k, v in params.items()}
    rs = np.random.RandomState(5)
    rows = []
    for _ in range(6):
        prompt = rs.randint(0, 128, 12).astype(np.int32)
        out = np.asarray(model.generate_cached(params, prompt[None], 40))[0]
        rows.append({"prompt": prompt.tolist(),
                     "tokens": out[12:].tolist()})
    got = gaps_for(params, CFG, rows, control="bf16", batch=3)
    limit = 1e-4                # between the two readings below
    assert max(g for r in got for g in r["gaps"]) < limit
    assert max(g for r in got for g in r["control_gaps"]) > 10 * limit
    # a token altered where it is produced
    rows[0]["tokens"][5] = (rows[0]["tokens"][5] + 1) % 128
    altered = gaps_for(params, CFG, rows, batch=3)
    assert max(g for r in altered for g in r["gaps"]) > 10 * limit
