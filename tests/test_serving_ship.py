"""KV-page shipping (serving/ship.py + PagePool.export_slot/adopt_slot):
the disaggregation wire contract. Serialization round-trips are BIT-exact
for f32 and int8 (scales included), damage — flipped payload bytes, bad
chunk CRCs, truncation, a chaos-injected ``srv.ship`` corrupt — is refused
structurally (ShipError, never adopted), and the end-to-end two-pool path
(prefill pool admits -> export -> chunks -> reassemble -> decode engine
adopts) streams wire-greedy tokens equal to solo decode."""

import numpy as np
import pytest

from paddle_tpu import faults
from paddle_tpu.serving import ShipError
from paddle_tpu.serving import ship

VOCAB, D, H, L, MAX_LEN = 97, 32, 4, 2, 128


def _arrays(kv_dtype=None, seed=0):
    """A plausible slot shipment: per-layer k/v page rows (+ int8 scales)."""
    rs = np.random.RandomState(seed)
    out = {}
    for i in range(L):
        for nm in (f"k{i}", f"v{i}"):
            if kv_dtype == "int8":
                out[nm] = rs.randint(-128, 128, (3, 8, H, D // H),
                                     dtype=np.int8)
                out[f"{nm}_scale"] = rs.rand(3, 8, H).astype(np.float32)
            else:
                out[nm] = rs.randn(3, 8, H, D // H).astype(np.float32)
    return out


# -- serialization: pure, no native runtime needed --------------------------

@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_pack_unpack_round_trip_bit_exact(kv_dtype):
    arrays = _arrays(kv_dtype)
    manifest, payload = ship.pack(arrays, plen=17, first=42, page_block=8,
                                  kv_dtype=kv_dtype)
    assert manifest["version"] == ship.SHIP_VERSION
    assert manifest["plen"] == 17 and manifest["first"] == 42
    got = ship.unpack(manifest, payload)
    assert set(got) == set(arrays)
    for nm in arrays:
        assert got[nm].dtype == arrays[nm].dtype
        # bit-exact, not allclose: adoption scatters these bytes into a
        # live pool and wire-greedy parity rides on identity
        assert np.array_equal(got[nm], arrays[nm])


def test_chunk_round_trip_and_idempotent_retry():
    arrays = _arrays()
    manifest, payload = ship.pack(arrays, plen=9, first=1, page_block=8,
                                  kv_dtype=None)
    frames = list(ship.iter_chunks(payload, chunk_bytes=1024))
    assert len(frames) > 1                       # actually chunked
    asm = ship.ChunkAssembler(frames[0][1])
    for seq, _total, fr in frames:
        asm.add(seq, fr["data"], fr["crc"])
    # at-least-once transport: a retried chunk re-verifies, no corruption
    asm.add(frames[0][0], frames[0][2]["data"], frames[0][2]["crc"])
    assert asm.complete
    got = ship.unpack(manifest, asm.payload())
    for nm in arrays:
        assert np.array_equal(got[nm], arrays[nm])


def test_corrupted_payload_refused_structurally():
    arrays = _arrays()
    manifest, payload = ship.pack(arrays, plen=9, first=1, page_block=8,
                                  kv_dtype=None)
    bad = bytearray(payload)
    bad[len(bad) // 2] ^= 0x40
    with pytest.raises(ShipError, match="CRC"):
        ship.unpack(manifest, bytes(bad))
    with pytest.raises(ShipError, match="truncated|lost"):
        ship.unpack(manifest, payload[:-4])
    with pytest.raises(ShipError, match="version"):
        ship.unpack(dict(manifest, version=99), payload)
    # an entry-level lie is caught even though the payload CRC still holds
    m2 = dict(manifest, entries=[dict(manifest["entries"][0],
                                      nbytes=manifest["entries"][0]["nbytes"]
                                      - 1)])
    with pytest.raises(ShipError, match="disagrees"):
        ship.unpack(m2, payload)


def test_chunk_corruption_refused_at_arrival():
    payload = b"x" * 4096
    frames = list(ship.iter_chunks(payload, chunk_bytes=1024))
    asm = ship.ChunkAssembler(frames[0][1])
    seq, _t, fr = frames[1]
    with pytest.raises(ShipError, match="CRC"):
        asm.add(seq, fr["data"], fr["crc"] ^ 0x1)
    with pytest.raises(ShipError, match="base64"):
        asm.add(seq, "!!! not base64 !!!", fr["crc"])
    with pytest.raises(ShipError, match="outside"):
        asm.add(99, fr["data"], fr["crc"])
    with pytest.raises(ShipError, match="incomplete"):
        asm.payload()


def test_chaos_srv_ship_corrupt_caught_by_chunk_crc():
    """The ``srv.ship`` fault site filters each raw chunk AFTER its CRC
    was stamped — injected corruption is exactly wire damage, and the
    receiver refuses the chunk instead of assembling a poisoned payload."""
    payload = bytes(range(256)) * 64
    plan = faults.FaultPlan(seed=7).add("srv.ship", "corrupt", nth=2)
    with plan.installed():
        frames = list(ship.iter_chunks(payload, chunk_bytes=4096))
    asm = ship.ChunkAssembler(frames[0][1])
    refused = 0
    for seq, _t, fr in frames:
        try:
            asm.add(seq, fr["data"], fr["crc"])
        except ShipError:
            refused += 1
    assert refused == 1                      # exactly the injected hit
    assert not asm.complete                  # damage never adopted
    with pytest.raises(ShipError, match="incomplete"):
        asm.payload()


def test_chaos_srv_ship_truncate_caught():
    payload = b"\xab" * 8192
    plan = faults.FaultPlan(seed=7).add("srv.ship", "truncate", nth=1,
                                        truncate_frac=0.5)
    with plan.installed():
        frames = list(ship.iter_chunks(payload, chunk_bytes=4096))
    asm = ship.ChunkAssembler(frames[0][1])
    with pytest.raises(ShipError, match="CRC"):
        for seq, _t, fr in frames:
            asm.add(seq, fr["data"], fr["crc"])


# -- two-pool end-to-end: prefill pool -> wire -> decode engine -------------

@pytest.fixture(scope="module")
def model_and_params():
    import jax
    from paddle_tpu.models import TransformerLM
    model = TransformerLM(VOCAB, d_model=D, n_heads=H, n_layers=L,
                          max_len=MAX_LEN)
    return model, model.init(jax.random.PRNGKey(0))


def _ship_over_wire(pool, prompt, max_new):
    """Prefill-worker half: admit into ``pool``, export the slot, push the
    payload through the real chunk framing, reassemble, unpack. Returns
    (manifest, arrays) as the decode side would see them."""
    from paddle_tpu.serving.batcher import Request
    r = Request(-1, np.asarray(prompt, np.int32), int(max_new))
    pool.validate(r)
    left = pool.effective_budget(int(r.prompt.size), int(max_new))
    plan = pool.plan_admission(r.prompt, left)
    assert pool.evict_for(plan.need_pages, 0, protect=[plan])
    first = int(pool.admit([(0, plan)])[0])
    manifest, payload = pool.export_slot(0, first)
    pool.free_slot(0)
    frames = list(ship.iter_chunks(payload, chunk_bytes=8192))
    asm = ship.ChunkAssembler(frames[0][1])
    for seq, _t, fr in frames:
        asm.add(seq, fr["data"], fr["crc"])
    return manifest, ship.unpack(manifest, asm.payload())


def _drain_engine(eng, rid, steps=4000):
    for _ in range(steps):
        eng.step()
        toks, done, reason = eng.poll(rid)
        if done:
            return np.asarray(toks, np.int32), reason
    raise AssertionError("engine never finished the adopted request")


def test_shipped_decode_equals_solo_decode_f32(model_and_params):
    """The acceptance bar: tokens decoded from ADOPTED pages (prefill in
    one pool, decode in another, payload through the real chunked wire
    format) bit-equal solo single-engine greedy decode."""
    import jax.numpy as jnp
    from paddle_tpu.serving import PagePool, ServingEngine
    model, params = model_and_params
    rs = np.random.RandomState(11)
    prompt, max_new = rs.randint(0, VOCAB, 13), 24
    pre = PagePool(model, params, slots=2, segment=8, page_block=8,
                   cache_bucket=32)
    manifest, arrays = _ship_over_wire(pre, prompt, max_new)
    eng = ServingEngine(model, params, slots=2, segment=8, page_block=8,
                        cache_bucket=32)
    rid = eng.submit_prefilled(manifest["plen"], manifest["first"], arrays,
                               max_new=max_new)
    got, reason = _drain_engine(eng, rid)
    assert reason == "length"
    want = np.asarray(model.generate_cached(
        params, jnp.asarray(prompt[None]), steps=max_new))[0, prompt.size:]
    np.testing.assert_array_equal(got, want)


def test_shipped_decode_equals_solo_decode_int8(model_and_params):
    """Same bar for quantized KV: the int8 rows AND their f32 scale planes
    ship; parity target is a solo int8-KV engine (int8 changes numerics,
    so the reference must share the dtype)."""
    from paddle_tpu.serving import PagePool, ServingEngine
    model, params = model_and_params
    rs = np.random.RandomState(12)
    prompt, max_new = rs.randint(0, VOCAB, 11), 20

    solo = ServingEngine(model, params, slots=2, segment=8, page_block=8,
                         cache_bucket=32, kv_dtype="int8")
    srid = solo.submit(np.asarray(prompt, np.int32), max_new)
    want, wreason = _drain_engine(solo, srid)

    pre = PagePool(model, params, slots=2, segment=8, page_block=8,
                   cache_bucket=32, kv_dtype="int8")
    manifest, arrays = _ship_over_wire(pre, prompt, max_new)
    assert any(nm.endswith("_scale") for nm in arrays)   # scales shipped
    assert manifest["kv_dtype"] == "int8"
    eng = ServingEngine(model, params, slots=2, segment=8, page_block=8,
                        cache_bucket=32, kv_dtype="int8")
    rid = eng.submit_prefilled(manifest["plen"], manifest["first"], arrays,
                               max_new=max_new)
    got, reason = _drain_engine(eng, rid)
    assert reason == wreason
    np.testing.assert_array_equal(got, want)


def test_adopt_refuses_geometry_and_name_mismatch(model_and_params):
    """A shipment whose arrays disagree with the receiving pool (missing
    planes, wrong dtype) is refused before any page is touched."""
    from paddle_tpu.serving import PagePool, ServingEngine
    model, params = model_and_params
    rs = np.random.RandomState(13)
    prompt, max_new = rs.randint(0, VOCAB, 9), 8
    pre = PagePool(model, params, slots=2, segment=8, page_block=8,
                   cache_bucket=32)
    manifest, arrays = _ship_over_wire(pre, prompt, max_new)
    eng = ServingEngine(model, params, slots=2, segment=8, page_block=8,
                        cache_bucket=32)
    missing = dict(arrays)
    missing.pop("k0")
    # refused at SUBMIT time (structured ValueError -> the daemon's
    # invalid_argument reply), never on the scheduler thread mid-adoption
    with pytest.raises(ValueError, match="disagree"):
        eng.submit_prefilled(manifest["plen"], manifest["first"], missing,
                             max_new=max_new)
    f64 = {nm: a.astype(np.float64) if not nm.endswith("_scale") else a
           for nm, a in arrays.items()}
    with pytest.raises(ValueError, match="lossy cast"):
        eng.submit_prefilled(manifest["plen"], manifest["first"], f64,
                             max_new=max_new)
