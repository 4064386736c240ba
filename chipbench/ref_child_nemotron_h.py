"""The plain reference of the ``nemotron_h`` family in a process of its own,
which has the chip after the daemon has exited: chipbench/ref_child.py's
``kind: "serve"`` contract over chipbench/reference/nemotron_h.py.

    python -m chipbench.ref_child_nemotron_h <spec.json> <out.json>

ONE full forward over each sampled prompt + served tokens, a sequence at a
time, padded to a multiple of 512 positions (causal attention, a causal
convolution and a recurrence that runs forward make the padding invisible
to what is read; at most six lengths, so a handful of compiled programs);
for every served token, how far its reference logit lies below the
reference's best at that position. It makes the same seeded weights itself
(chipbench/weights_nemotron_h.py) and takes nothing the program made. spec:
{"config", "seed", "rows": [{"prompt", "tokens"}], "control"}
"""

import json
import sys
import time

import numpy as np

PAD = 512


def gaps_for(params, config, rows, control=None):
    import jax.numpy as jnp

    from chipbench.reference import nemotron_h as ref
    hp = ref.hparams(config)
    limit = config["n_positions"]
    out = []
    for r in rows:
        seq = (list(r["prompt"]) + list(r["tokens"]))[:limit]
        T = min(-(-len(seq) // PAD) * PAD, limit)
        ids = np.zeros((T,), np.int32)
        ids[:len(seq)] = seq
        best, served, pick = ref.token_gaps(params, jnp.asarray(ids), hp,
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
    from chipbench import weights_nemotron_h as weights
    device = dev.describe(spec.get("rehearsal", False))
    import jax

    import paddle_tpu
    paddle_tpu.enable_compile_cache()
    _, shapes = weights.model_and_shapes(spec["config"])
    params = weights.make(shapes, spec["seed"], spec["config"])
    with jax.default_matmul_precision("highest"):
        rows = gaps_for(params, spec["config"], spec["rows"],
                        spec.get("control"))
    out = {"device": device, "rows": rows, "seconds": time.time() - t0,
           "memory_peak_bytes": dev.memory_peak_bytes()}
    with open(out_path, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
