"""Open-loop request traffic from a data file: arrivals of a renewal process
(gamma inter-arrival gaps; cv 1 is Poisson, cv > 1 is bursty) at a FIXED mean
rate, prompt and output lengths from clipped distributions, random token ids
(so no two prompts share a prefix).

Every seed gets the SAME schedule — arrival times and lengths drawn once from
the mix's own ``set_seed`` — and other token ids (and, in the modes, other
weights): the work of a run does not depend on the seed. An earlier version
gave every seed the same lengths and gaps in another ORDER; on the chip the
order alone moved the 95th percentile of time-to-first-token between 650 and
1250 ms and the tokens delivered in the window by 3 % (PERF.md, PR 23), far
more than two runs of one seed differ, so the arrangement is now part of the
mix, not of the seed.
"""

import numpy as np


def _lengths(rs, spec, n):
    if spec["dist"] == "lognormal":
        x = rs.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        x = rs.uniform(spec["low"], spec["high"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), spec["low"], spec["high"]).astype(np.int64)


def generate(traffic, seed, seconds, vocab):
    """Requests due inside [0, seconds): a list of dicts ``due_s`` (float,
    ascending), ``prompt`` (int32 array) and ``max_new`` (int)."""
    arr = traffic["arrivals"]
    n = max(1, int(round(arr["rate_per_s"] * seconds)))
    base = np.random.RandomState(traffic["set_seed"])
    shape = 1.0 / float(arr.get("cv", 1.0)) ** 2
    gaps = base.gamma(shape, 1.0, n)
    # the n arrivals span the window whatever the draw: the last is due
    # half a mean gap before its end
    gaps *= (seconds - 0.5 * seconds / n) / gaps.sum()
    prompts = _lengths(base, traffic["prompt"], n)
    outputs = _lengths(base, traffic["output"], n)

    due = np.cumsum(gaps)
    outputs = np.minimum(outputs, traffic["max_total"] - prompts)
    rs = np.random.RandomState(seed)
    # no two prompts of a run start with the same token: the engine's prefix
    # index counts ONE shared leading token as a hit (a program of its own,
    # which no warm-up of unshared traffic reaches), so "unshared" is exact
    firsts = distinct_first_tokens(rs, vocab, n)
    out = []
    for i in range(n):
        prompt = rs.randint(0, vocab, int(prompts[i])).astype(np.int32)
        prompt[0] = firsts[i]
        out.append({"due_s": float(due[i]), "prompt": prompt,
                    "max_new": int(outputs[i])})
    return out


def distinct_first_tokens(rs, vocab, n, taken=()):
    """``n`` different token ids, none of them in ``taken``."""
    free = np.setdiff1d(np.arange(vocab), np.asarray(list(taken), np.int64))
    if n > free.size:
        raise ValueError(f"{n} prompts cannot start with different tokens "
                         f"of a vocabulary with {free.size} ids free")
    return rs.permutation(free)[:n]


def length_range(traffic):
    """(shortest prompt, longest prompt, longest prompt + output) the mix
    can send — what the warm-up has to cover."""
    p, o = traffic["prompt"], traffic["output"]
    return p["low"], p["high"], min(p["high"] + o["high"],
                                    traffic["max_total"])
