"""The plain reference of the ``mimo_v2_flash`` family in a process of its
own, which has the chip after the daemon has exited: chipbench/ref_child.py's
``kind: "serve"`` contract over chipbench/reference/mimo_v2.py.

    python -m chipbench.ref_child_mimo_v2 <spec.json> <out.json>

ONE full forward over each sampled prompt + served tokens, a sequence at a
time, for every served token how far its reference logit lies below the
reference's best at that position, each sequence padded to a multiple of
``PAD`` positions (a causal mask and a causal band make the padding
invisible to what is read; at most thirteen lengths to 50,176, four layer
programs a length) and only the served positions taken through the head (a
49k-token row's logits over 19,072 tokens would be 3.7 GB). It makes the
same seeded weights itself (chipbench/weights_mimo_v2.py) and takes nothing
the program made. spec: {"config", "seed", "rows": [{"prompt", "tokens"}],
"control"}; ``control`` names one control or lists several (the cell's
``control_operand``, data): "fp8" rounds every product's operands (the token
that precision puts first, held to the reference); "no_sink" holds the
SERVED tokens to a reference whose sink was taken away (PERF.md says by how
much the comparison tells a forgotten sink). A row of the result holds
``controls``: {name: gaps} — every control under its own name — and, where
ONE was named and not listed, ``control_gaps`` as chipbench/ref_child.py's
contract has it.
"""

import json
import sys
import time

import numpy as np

PAD = 4096


def _gaps_below_best(logits, tokens):
    """How far each of ``tokens``' logits lies below its position's best."""
    import jax.numpy as jnp
    at = jnp.take_along_axis(logits, tokens[..., None], -1)[..., 0]
    return np.asarray(jnp.max(logits, axis=-1) - at)


def _control_gaps(ref, params, ids, hp, control, rows, sound):
    """One control's reading over the positions ``rows``: another forward,
    and what it says of the served tokens or of ``sound``'s logits."""
    import jax.numpy as jnp
    if control == "no_sink":
        # a gap of its own: the SERVED tokens below the altered
        # reference's best
        alt = ref.forward(params, ids, dict(hp, sink=(False, False)),
                          rows=rows)
        return _gaps_below_best(alt, ids[rows[0] + 1:rows[1] + 1])
    # a lower precision's first token, below the reference's best
    low = ref.forward(params, ids, hp, control, rows=rows)
    return _gaps_below_best(sound, jnp.argmax(low, -1))


def gaps_for(params, config, rows, control=None):
    import jax.numpy as jnp

    from chipbench.reference import mimo_v2 as ref
    hp = ref.hparams(config)
    limit = config["n_positions"]
    controls = [control] if isinstance(control, str) else list(control or ())
    out = []
    for r in rows:
        seq = (list(r["prompt"]) + list(r["tokens"]))[:limit]
        T = min(-(-len(seq) // PAD) * PAD, limit)
        ids = np.zeros((T,), np.int32)
        ids[:len(seq)] = seq
        ids = jnp.asarray(ids)
        # logits at position t predict token t+1: the served tokens sit at
        # positions len(prompt) .. len(prompt)+len(tokens)-1
        lo = len(r["prompt"]) - 1
        hi = min(lo + len(r["tokens"]), T - 1)
        sound = ref.forward(params, ids, hp, rows=(lo, hi))
        row = {"gaps": _gaps_below_best(sound, ids[lo + 1:hi + 1]).tolist()}
        if controls:
            row["controls"] = {
                c: _control_gaps(ref, params, ids, hp, c, (lo, hi),
                                 sound).tolist() for c in controls}
        if isinstance(control, str):
            row["control_gaps"] = row["controls"][control]
        out.append(row)
    return out


def main(argv=None):
    spec_path, out_path = (sys.argv[1:] if argv is None else argv)
    with open(spec_path) as f:
        spec = json.load(f)
    t0 = time.time()
    from chipbench import device as dev
    from chipbench import weights_mimo_v2 as weights
    device = dev.describe(spec.get("rehearsal", False))
    import jax

    import paddle_tpu
    paddle_tpu.enable_compile_cache()
    _, shapes = weights.model_and_shapes(spec["config"])
    params = weights.make(shapes, spec["seed"],
                          weights.sink_mean(spec["config"]))
    control = spec.get("control")
    with jax.default_matmul_precision("highest"):
        rows = gaps_for(params, spec["config"], spec["rows"], control)
    out = {"device": device, "rows": rows, "seconds": time.time() - t0,
           "control": control,
           "memory_peak_bytes": dev.memory_peak_bytes()}
    with open(out_path, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
