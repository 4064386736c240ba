"""Share of the positions the window's admissions ran through the depth that
were tokens of an admitted prompt: sum of ``prompt_tokens`` over sum of
``positions`` on the window's ``serving.prefill`` spans. The rest is padding
the program computed for nobody: a row's tail up to its prompt bucket, the
rows of the pool's width that hold no prompt (a model whose ``prefill`` runs
every row), the rows that fill up the last chunk (a model that walks the live
rows a chunk at a time). A prefix hit's shared part is not run and is in
neither sum. A note says how many rows a span held and names the most common
``(rows, positions)`` shapes."""

from collections import Counter

from chipbench.metrics._iteration_account import admissions, share


def read(ctx):
    ran = admissions(ctx)
    positions = sum(p for _, _, p in ran)
    if not positions:
        return None
    tokens = sum(t for _, t, _ in ran)
    shapes = ", ".join(f"{r} rows in {p} positions x{n}" for (r, p), n
                       in Counter((r, p) for r, _, p in ran).most_common(3))
    ctx.setdefault("notes", []).append(
        f"admissions: {len(ran)} ran {positions} positions for {tokens} "
        f"prompt tokens, {sum(r for r, _, _ in ran) / len(ran):.2f} rows a "
        f"span; most common: {shapes}")
    return share(tokens, positions)
