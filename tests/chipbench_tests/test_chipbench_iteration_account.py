"""The four readers of the serve iteration's accounts (``prefill_fill``,
``segment_fill``, ``tpot_admission_ms``, ``tpot_decode_ms``), each over a
hand-made obs dump: the value from the window's own spans and requests (the
warm-up's are not read), nothing from a dump of a program that keeps no such
account — as the parent of the PR that added it does; and all four in the
traced line of a serve rehearsal at tiny sizes."""

import pytest

from chipbench import harness, run

ORIGIN = 1_000_000.0
WINDOW = (ORIGIN + 10.0, ORIGIN + 60.0)
NEW = ("prefill_fill", "segment_fill", "tpot_admission_ms", "tpot_decode_ms")
SERVE_CELLS = ["gpt2l-serve-chat", "gpt2l-serve-longprompt",
               "gigachat-ep16-serve-longout", "lfm2-serve-rag",
               "nemotron3-ep8-serve-chatburst"]


def _ctx(account=True):
    """A window of three admissions (and one adoption, which ran nothing),
    two segments and three finished requests, behind a warm-up that has
    larger numbers of everything."""
    events = []

    def span(name, ts, dur, **args):
        events.append({"name": name, "ts": ts, "dur": dur, "tid": 1,
                       "id": len(events) + 1, "parent": None,
                       "args": args if account else
                       {k: v for k, v in args.items()
                        if k in ("batch", "after", "phase")}})
    span("serving.prefill", 2.0, 0.5, batch=4, rows=4, prompt_tokens=2000,
         positions=2048)
    span("serving.emit", 3.0, 0.01, after="segment", slot_steps=512,
         live_steps=512, emitted=512)
    for ts, rows, tokens, positions in ((12.0, 1, 100, 2048),
                                        (13.0, 2, 300, 2048),
                                        (14.0, 1, 50, 4096),
                                        (15.0, 0, 0, 0)):
        span("serving.prefill", ts, 0.1, batch=max(rows, 1), rows=rows,
             prompt_tokens=tokens, positions=positions)
        span("serving.emit", ts + 0.1, 0.001, after="prefill")
    for ts, live, emitted in ((12.5, 256, 200), (13.5, 128, 100)):
        span("serving.emit", ts, 0.002, after="segment", slot_steps=512,
             live_steps=live, emitted=emitted)
    # the scheduler's own spans inside w-0's decode life [20, 21]: 50 ms of
    # scheduling and 30 of the 40 ms of an emit that ends after it
    span("serving.schedule", 20.2, 0.05, phase="admit")
    span("serving.emit", 20.97, 0.04, after="prefill")

    def request(key, first, done, **extra):
        return {"key": key, "origin": ORIGIN, "events": [
            {"phase": "first_token", "t": first, "dur": 0.0},
            dict({"phase": "done", "t": done, "dur": 0.0, "reason": "length"},
                 **(extra if account else
                    {"tokens": extra["tokens"]}))]}
    requests = [
        request("warmup", 1.0, 9.0, tokens=5, decode_s=7.0, stalled_s=1.0),
        request("w-0", 20.0, 21.0, tokens=11, decode_s=0.6, stalled_s=0.3),
        request("w-1", 25.0, 25.0, tokens=1, decode_s=0.0, stalled_s=0.0),
        request("w-2", 30.0, 32.0, tokens=21, decode_s=1.2, stalled_s=0.4)]
    return {"obs": {"meta": {"clock_origin_unix": ORIGIN}, "metrics": [],
                    "events": events, "requests": requests},
            "window": WINDOW, "values": {"tpot_p50_ms": 110.0},
            "records": [{"key": f"w-{i}"} for i in range(3)]}


def _read(name, ctx):
    return harness.load_module("metrics", name).read(ctx)


@pytest.mark.parametrize("name,want", [
    ("prefill_fill", 100 * 450 / 8192),
    ("segment_fill", 100 * 300 / 1024),
    # w-0: 300 ms over 10 gaps, w-2: 400 ms over 20; w-1 has one token
    ("tpot_admission_ms", (30.0 + 20.0) / 2),
    ("tpot_decode_ms", 60.0)])
def test_reader_on_a_hand_made_dump(name, want):
    ctx = _ctx()
    assert _read(name, ctx) == pytest.approx(want)
    assert _read(name, _ctx(account=False)) is None
    note = " ".join(ctx.get("notes", []))
    if name == "prefill_fill":
        assert "1.33 rows a span" in note
        assert "1 rows in 2048 positions x1" in note
    if name == "segment_fill":
        # 1024 slot-steps: 300 emitted, 84 of the 384 live ones overshoot
        assert "overshoot 8.2 % (21.9 % of the live steps)" in note
        assert "idle 62.5 %" in note
    if name == "tpot_decode_ms":
        # medians of (60, 60) + (30, 20) + host (8, 0): 60 + 25 + 4 = 89
        # against the daemon's own (100, 100), beside the client's 110
        assert "decode 60.000 + admissions 25.000 + host 4.000 = 89.000" \
            in note
        assert "the daemon's own TPOT 100.000 (-11.0 %" in note
        assert "means: 60.000 + 25.000 + 4.000 = 89.000 of 100.000" in note
        assert "delivery +10.000" in note


def test_benchmark_entries_name_the_five_serve_cells():
    per_layer = {m["name"]: m for m in harness.load_benchmark()["per_layer"]}
    for name in NEW:
        m = per_layer[name]
        assert m["source"] == "program_span"
        assert m["moves"] == "tpot_p50_ms"
        assert m["workloads"] == SERVE_CELLS
    assert per_layer["prefill_fill"]["layer"] == "prefill step"
    assert per_layer["segment_fill"]["layer"] == "decode step"
    assert {per_layer[n]["layer"] for n in NEW[2:]} == {"engine"}
    train = harness.load_cell("gpt2m-train-1k")
    assert not set(NEW) & {m["name"] for m in train["per_layer"]}


def test_rehearsal_line_reports_the_four():
    """The chat cell at its tiny sizes (prefix cache on, so an admission
    may run both admit programs): the traced line holds the four metrics,
    and they hang together."""
    line, raw = run.run_cell("gpt2l-serve-chat", 2**31 + 11, 2.0, 1,
                             rehearsal=True, log=lambda m: None)
    assert line["failed"] == 0
    got = {n: line["metrics"][n]["value"] for n in NEW}
    assert 0 < got["prefill_fill"] <= 100
    assert 0 < got["segment_fill"] <= 100
    assert got["tpot_decode_ms"] > 0 and got["tpot_admission_ms"] >= 0
    # an emitted token is a live slot's step, a live step a slot's
    emits = [e["args"] for e in raw["ctx"]["obs"]["events"]
             if e.get("name") == "serving.emit"
             and e["args"].get("after") == "segment"]
    assert emits and all(a["emitted"] <= a["live_steps"] <= a["slot_steps"]
                         for a in emits)
    notes = " ".join(raw["ctx"]["notes"])
    assert "a token's time" in notes and "most common" in notes
