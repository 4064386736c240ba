"""Share of the slot-steps the window's decode segments ran (slots x segment
steps, every one computed) that delivered a token to a request: sum of
``emitted`` over sum of ``slot_steps`` on the ``serving.emit`` spans behind
the window's segments. A note splits the rest into ``overshoot`` — a live
slot's steps past its request's last token, and the re-emitted first token —
and ``idle`` — slots that held no request — so that this is not
``slots_live_mean`` (which counts overshoot as live) under another name."""

from chipbench.metrics._iteration_account import segments, share


def read(ctx):
    ran = segments(ctx)
    slot_steps = sum(s for s, _, _ in ran)
    if not slot_steps:
        return None
    live = sum(l for _, l, _ in ran)
    emitted = sum(e for _, _, e in ran)
    ctx.setdefault("notes", []).append(
        f"segments: {len(ran)} ran {slot_steps} slot-steps: emitted "
        f"{share(emitted, slot_steps):.1f} %, overshoot "
        f"{share(live - emitted, slot_steps):.1f} % "
        f"({share(live - emitted, max(live, 1)):.1f} % of the live steps), "
        f"idle {share(slot_steps - live, slot_steps):.1f} %")
    return share(emitted, slot_steps)
