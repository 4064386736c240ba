"""The plain reference in a process of its own, which has the chip while the
program does not (before the Trainer's process touches it; after the daemon
has exited), so ``memory_peak_bytes`` stays the program's. It makes the same
seeded weights itself and takes nothing the program made.

    python -m chipbench.ref_child <spec.json> <out.json>

``kind: "serve"`` — ONE full forward over each sampled prompt + served tokens
(rows padded to the configuration's positions; causal attention makes the
padding invisible to what is read); for every served token, how far its
reference logit lies below the reference's best at that position.
spec: {"config", "seed", "rows": [{"prompt", "tokens"}], "control", ...}

``kind: "train"`` — follows the first Adam steps over ``batches``: each
step's loss, the per-leaf norms of the first gradient and of the parameters'
change. spec: {"config", "seed", "batches", "lr", "control", ...}
"""

import json
import sys
import time

import numpy as np


def gaps_for(params, config, rows, control=None, batch=8):
    """Per row: the gaps of its served tokens (and of the control's picks)."""
    import jax.numpy as jnp

    from chipbench.reference import gpt2 as ref
    T = config["n_positions"]
    out = []
    for i in range(0, len(rows), batch):
        chunk = rows[i:i + batch]
        ids = np.zeros((batch, T), np.int32)
        for j, r in enumerate(chunk):
            seq = list(r["prompt"]) + list(r["tokens"])
            ids[j, :len(seq)] = seq[:T]
        best, served, pick = ref.token_gaps(params, jnp.asarray(ids),
                                            config["n_head"], control)
        best, served = np.asarray(best), np.asarray(served)
        pick = None if pick is None else np.asarray(pick)
        for j, r in enumerate(chunk):
            # logits at position t predict token t+1: the served tokens sit
            # at positions len(prompt) .. len(prompt)+len(tokens)-1
            lo = len(r["prompt"]) - 1
            hi = min(lo + len(r["tokens"]), T - 1)
            row = {"gaps": (best[j, lo:hi] - served[j, lo:hi]).tolist()}
            if pick is not None:
                row["control_gaps"] = (best[j, lo:hi]
                                       - pick[j, lo:hi]).tolist()
            out.append(row)
    return out


def train_readings(params, config, batches, lr, operand=None):
    from chipbench.reference import gpt2 as ref
    losses, g, d = ref.train_reference(
        params, [np.asarray(b, np.int32) for b in batches], config["n_head"],
        lr, operand)
    return {"losses": losses, "grad_norms": g, "update_norms": d}


def main(argv=None):
    spec_path, out_path = (sys.argv[1:] if argv is None else argv)
    with open(spec_path) as f:
        spec = json.load(f)
    t0 = time.time()
    from chipbench import device as dev
    from chipbench import weights
    device = dev.describe(spec.get("rehearsal", False))
    import paddle_tpu
    paddle_tpu.enable_compile_cache()
    _, shapes = weights.model_and_shapes(spec["config"])
    params = weights.make(shapes, spec["seed"])
    out = {"device": device}
    if spec.get("kind", "serve") == "train":
        out["reference"] = train_readings(params, spec["config"],
                                          spec["batches"], spec["lr"])
        if spec.get("control"):
            out["control"] = train_readings(params, spec["config"],
                                            spec["batches"], spec["lr"],
                                            spec["control"])
    else:
        out["rows"] = gaps_for(params, spec["config"], spec["rows"],
                               spec.get("control"))
    out.update(seconds=time.time() - t0,
               memory_peak_bytes=dev.memory_peak_bytes())
    with open(out_path, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
