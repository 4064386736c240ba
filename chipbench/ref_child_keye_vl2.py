"""The plain reference of the ``keye_vl2`` family in a process of its own,
which has the chip after the daemon has exited: chipbench/ref_child.py's
``kind: "serve"`` contract over chipbench/reference/keye_vl2.py.

    python -m chipbench.ref_child_keye_vl2 <spec.json> <out.json>

ONE full forward over each sampled prompt + served tokens, a sequence at a
time, for every served token how far its reference logit lies below the
reference's best at that position, each sequence padded to a multiple of
``PAD`` positions (a causal mask makes the padding invisible to what is
read; at most nine lengths to 33,792, and ONE layer program a length: the
layers are all of one kind). It makes the same seeded weights itself
(chipbench/weights_keye_vl2.py) and takes nothing the program made. spec:
{"config", "seed", "rows": [{"prompt", "tokens"}], "control"}; ``control``
"fp8" rounds every product's operands (the token that precision puts first,
held to the reference); "all" and "recent" hold the SERVED tokens to a
reference whose selection was taken away, or replaced by the 2,048 most
recent keys (PERF.md says by how much the comparison tells a forgotten
selection). ``CHIPBENCH_KEYE_CONTROL`` names a control where the cell's
``control_operand`` does not (the builder's runs of the other two).
"""

import json
import os
import sys
import time

import numpy as np

PAD = 4096


def _token_gaps(ref, params, ids, hp, control):
    if control in (None, "fp8"):
        return ref.token_gaps(params, ids, hp, control)
    best, served, _ = ref.token_gaps(params, ids, hp)
    alt_best, alt_served, _ = ref.token_gaps(params, ids,
                                             dict(hp, select=control))
    # the control's reading is a gap of its own: the served tokens below
    # the ALTERED reference's best (best - pick below = alt_best - alt_served)
    return best, served, best - (alt_best - alt_served)


def gaps_for(params, config, rows, control=None):
    import jax.numpy as jnp

    from chipbench.reference import keye_vl2 as ref
    hp = ref.hparams(config)
    limit = config["n_positions"]
    out = []
    for r in rows:
        seq = (list(r["prompt"]) + list(r["tokens"]))[:limit]
        T = min(-(-len(seq) // PAD) * PAD, limit)
        ids = np.zeros((T,), np.int32)
        ids[:len(seq)] = seq
        best, served, pick = _token_gaps(ref, params, jnp.asarray(ids), hp,
                                         control)
        # logits at position t predict token t+1: the served tokens sit at
        # positions len(prompt) .. len(prompt)+len(tokens)-1
        lo = len(r["prompt"]) - 1
        hi = min(lo + len(r["tokens"]), T - 1)
        row = {"gaps": (np.asarray(best)[lo:hi]
                        - np.asarray(served)[lo:hi]).tolist()}
        if pick is not None:
            row["control_gaps"] = (np.asarray(best)[lo:hi]
                                   - np.asarray(pick)[lo:hi]).tolist()
        out.append(row)
    return out


def main(argv=None):
    spec_path, out_path = (sys.argv[1:] if argv is None else argv)
    with open(spec_path) as f:
        spec = json.load(f)
    t0 = time.time()
    from chipbench import device as dev
    from chipbench import weights_keye_vl2 as weights
    device = dev.describe(spec.get("rehearsal", False))
    import jax

    import paddle_tpu
    paddle_tpu.enable_compile_cache()
    _, shapes = weights.model_and_shapes(spec["config"])
    params = weights.make(shapes, spec["seed"])
    control = spec.get("control") and (
        os.environ.get("CHIPBENCH_KEYE_CONTROL") or spec["control"])
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
