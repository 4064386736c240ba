"""The plain reference of the ``afmoe`` family in a process of its own, which
has the chip after the daemon has exited: chipbench/ref_child.py's ``kind:
"serve"`` contract over chipbench/reference/afmoe.py.

    python -m chipbench.ref_child_afmoe <spec.json> <out.json>

ONE full forward over each sampled prompt + served tokens, a sequence at a
time, for every served token how far its reference logit lies below the
reference's best at that position (chipbench/ref_child_nemotron_h.py's
``gaps_for`` over this family's reference), each sequence padded to a
multiple of 1,024 positions (a causal mask and a causal band make the
padding invisible to what is read; at most nine lengths to 8,960, so a
handful of compiled programs). It makes the same seeded weights itself
(chipbench/weights_afmoe.py) and takes nothing the program made. spec:
{"config", "seed", "rows": [{"prompt", "tokens"}], "control"}; ``control``
"fp8" rounds every product's operands, "no_window" reads every layer's whole
context (PERF.md says by how much the comparison tells a forgotten window).
"""

import json
import sys
import time

import numpy as np

PAD = 1024


def _token_gaps(ref, params, ids, hp, control):
    if control != "no_window":
        return ref.token_gaps(params, ids, hp, control)
    full = ref.forward(params, ids, hp)[:-1]
    low = ref.forward(params, ids, dict(hp, window=None))[:-1]
    return ref._gaps(full, low, ids[1:])


def gaps_for(params, config, rows, control=None):
    import jax.numpy as jnp

    from chipbench.reference import afmoe as ref
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
    from chipbench import weights_afmoe as weights
    device = dev.describe(spec.get("rehearsal", False))
    import jax

    import paddle_tpu
    paddle_tpu.enable_compile_cache()
    _, shapes = weights.model_and_shapes(spec["config"])
    params = weights.make(shapes, spec["seed"])
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
