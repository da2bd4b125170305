"""Fleet-shared KV tier (ISSUE 20): wire-blob bit-identity, peer
directory codec + registry retraction, cross-replica prefix export/import
with greedy parity, and live stream blob migration splice parity.

Engine tests reuse test_paged's pool shape (12 blocks x 8 tokens, chunk
16, ctx 128) so the paged executables compile once per model and are
shared across modules — the tier-1 suite is timeout-capped."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.fleet.kvshare import (KVBlobMismatch, MAGIC, VERSION,
                                    decode_blob, encode_blob,
                                    encode_directory, parse_directory)
from cake_tpu.fleet.registry import MembershipPolicy, Replica
from cake_tpu.models import TextModel, tiny_config
from cake_tpu.ops.sampling import SamplingConfig
from cake_tpu.serve import ServeEngine

GREEDY = SamplingConfig(temperature=0.0)
CTX = 128
CHUNK = 16
BT = 8
BLOCKS = 12


# ---------------------------------------------------------------------------
# wire format: pure codec, no model
# ---------------------------------------------------------------------------


def _sample_payload():
    header = {"kind": "prefix", "units": 2, "flag": True}
    arrays = {
        "tokens": np.arange(32, dtype=np.int32),
        "layers/0/k": np.linspace(-1, 1, 96).astype(np.float32)
                        .reshape(4, 8, 3),
        "layers/0/pos": np.arange(32, dtype=np.int32).reshape(4, 8),
        "snap/0/0": np.ones((2, 5), np.float64) * 0.25,
    }
    return header, arrays


def test_blob_roundtrip_bit_identity():
    header, arrays = _sample_payload()
    data = encode_blob(header, arrays)
    assert data.startswith(MAGIC) and data[len(MAGIC)] == VERSION
    h2, a2 = decode_blob(data)
    for k in ("kind", "units", "flag"):
        assert h2[k] == header[k]
    assert set(a2) == set(arrays)
    for k, a in arrays.items():
        assert a2[k].dtype == a.dtype and a2[k].shape == a.shape
        assert a2[k].tobytes() == a.tobytes()       # bit identity


def test_blob_rejects_every_corruption_mode():
    header, arrays = _sample_payload()
    data = bytearray(encode_blob(header, arrays))
    with pytest.raises(KVBlobMismatch):
        decode_blob(bytes(data[:40]))               # truncated
    bad = bytes(data[:-1]) + bytes([data[-1] ^ 0x40])
    with pytest.raises(KVBlobMismatch):
        decode_blob(bad)                            # payload bit flip
    bad = b"X" + bytes(data[1:])
    with pytest.raises(KVBlobMismatch):
        decode_blob(bad)                            # magic
    bad = bytes(data[:len(MAGIC)]) + bytes([VERSION + 1]) \
        + bytes(data[len(MAGIC) + 1:])
    with pytest.raises(KVBlobMismatch):
        decode_blob(bad)                            # version skew


# ---------------------------------------------------------------------------
# peer directory: codec + registry mirror/retraction
# ---------------------------------------------------------------------------


def test_directory_codec_roundtrip_and_malformed():
    hdr = encode_directory([("http://a:1", ["aa", "bb"]),
                            ("http://b:2", ("cc",)),
                            ("http://c:3", []),     # nothing to advertise
                            ("", ["dd"])])          # no url
    peers = parse_directory(hdr)
    assert [(u, sorted(ks)) for u, ks in peers] == \
        [("http://a:1", ["aa", "bb"]), ("http://b:2", ["cc"])]
    assert "dd" not in {k for _, ks in peers for k in ks}
    assert encode_directory([]) is None
    assert encode_directory([("http://c:3", [])]) is None
    assert parse_directory("not json") == []
    assert parse_directory('{"p": "nope"}') == []


def test_registry_mirrors_and_retracts_inventory():
    rep = Replica("r0", "http://h:1", MembershipPolicy())
    assert rep.kv_inventory() == ()
    body = {"engine": {"alive": True, "slots": 2,
                       "kvshare": {"chains": ["aa", "bb", 7]}}}
    rep.observe_health(200, body)
    assert rep.kv_inventory() == ("aa", "bb")       # non-str dropped
    # stale probe: inventory retracted with the probe state — a peer
    # directory must never point a fetch at an unknown cache
    rep.observe_health(None, None)
    assert rep.kv_inventory() == ()
    rep.observe_health(200, body)
    assert rep.kv_inventory() == ("aa", "bb")
    rep.observe_health(200, {"engine": {"alive": False}})   # sick verdict
    assert rep.kv_inventory() == ()


# ---------------------------------------------------------------------------
# cross-replica prefix export/import + stream migration (tiny CPU llama)
# ---------------------------------------------------------------------------


_MODEL = None


def _model():
    global _MODEL
    if _MODEL is None:
        _MODEL = TextModel(tiny_config("llama"), dtype=jnp.float32,
                           max_cache_len=CTX)
    return _MODEL


@pytest.fixture(scope="module")
def model():
    return _model()


def _engine(model, **kw):
    from cake_tpu.fleet.kvshare import KVShareReplica
    kw.setdefault("slots", 2)
    kw.setdefault("max_queue", 8)
    kw.setdefault("ctx_len", CTX)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("kv_blocks", BLOCKS)
    kw.setdefault("kv_block_tokens", BT)
    kw.setdefault("prefix_cache_mb", 8)
    eng = ServeEngine(model, **kw)
    eng.kv_share = KVShareReplica(eng)
    return eng


@pytest.fixture()
def engines(model):
    a, b = _engine(model), _engine(model)
    yield a, b
    a.close()
    b.close()


def _ref(model, prompt, n):
    toks, _ = model.generate(list(prompt), max_new_tokens=n,
                             sampling=GREEDY)
    return toks


SYS = [3 + (i * 7) % 200 for i in range(40)]        # 2 full share units


def test_prefix_blob_cross_replica_greedy_parity(model, engines):
    """Warm replica A, export its SYS chain, install into cold replica B:
    B's next admission splices the fetched blocks (prefix_hit_tokens) and
    the greedy body is bit-identical to the sequential reference — a
    fetched chain is indistinguishable from a locally-computed one."""
    eng_a, eng_b = engines
    ks_a, ks_b = eng_a.kv_share, eng_b.kv_share
    pa, pb = SYS + [9, 11], SYS + [77, 31]
    ra = eng_a.submit(pa, max_new_tokens=6, sampling=GREEDY)
    assert ra.wait(180)
    assert ra.result["tokens"] == _ref(model, pa, 6)
    # inventory mirror follows the cache version on the scheduler thread
    eng_a._wake.set()
    deadline = time.monotonic() + 10
    while not ks_a.health_view()["chains"] \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    chains = ks_a.health_view()["chains"]
    assert len(chains) == 2                 # 2 SYS units, newest first
    blob = ks_a.submit_job("export_prefix", chains[0], 30)
    assert blob is not None
    header, _ = decode_blob(blob)
    assert header["units"] == 2 and header["has_snap"] is False
    # unknown chain: honest None (the route answers 404)
    assert ks_a.submit_job("export_prefix", "ab" * 16, 30) is None
    res = ks_b.submit_job("import_prefix", blob, 30)
    assert res == {"installed_units": 2, "tokens": 32}
    # re-import dedupes instead of re-pinning
    res2 = ks_b.submit_job("import_prefix", blob, 30)
    assert res2["tokens"] == 32
    assert eng_b.prefix_cache.pinned == 2 * eng_b.prefix_cache.bpu
    rb = eng_b.submit(pb, max_new_tokens=6, sampling=GREEDY)
    assert rb.wait(180)
    assert rb.stats["prefix_hit_tokens"] == 32, \
        "imported chain did not splice"
    assert rb.result["tokens"] == _ref(model, pb, 6)
    eng_b.paged.alloc.check()               # allocator invariants hold


def test_prefix_import_rejects_foreign_pool(model, engines):
    """A blob whose pool signature does not match the importing replica
    raises the typed KVBlobMismatch (the route's 422) and leaves the
    cache untouched."""
    eng_a, eng_b = engines
    ra = eng_a.submit(SYS + [5], max_new_tokens=4, sampling=GREEDY)
    assert ra.wait(180)
    eng_a._wake.set()
    deadline = time.monotonic() + 10
    while not eng_a.kv_share.health_view()["chains"] \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    chain = eng_a.kv_share.health_view()["chains"][0]
    blob = eng_a.kv_share.submit_job("export_prefix", chain, 30)
    header, arrays = decode_blob(blob)
    header["pool"] = {"layers": "somewhere-else"}
    forged = encode_blob(header, arrays)
    with pytest.raises(KVBlobMismatch):
        eng_b.kv_share.submit_job("import_prefix", forged, 30)
    assert len(eng_b.prefix_cache._blocks) == 0
    with pytest.raises(KVBlobMismatch):
        eng_b.kv_share.submit_job("import_prefix", b"garbage", 30)


def test_stream_blob_migration_splice_parity(model, engines):
    """Park a live decode on A mid-stream (the fetch IS the migration
    signal), ship the blob to B, adopt: the continued stream finishes
    with exactly the sequential reference's tokens — the generated
    record, KV bytes, and decode carries all rode the blob."""
    from cake_tpu.fleet.kvshare import StreamMigrated
    eng_a, eng_b = engines
    prompt = [3, 17, 42, 99, 7]
    # long enough that the stream cannot end between its 4th token and the
    # export (a 12-token stream did, on a fast scheduler: export_stream
    # then finds nothing to park), and inside the pool: 5 + 64 tokens are
    # 9 of its 12 blocks
    n = 64
    ref = _ref(model, prompt, n)
    assert len(ref) == n, "the reference stream ends early"
    req = eng_a.submit(prompt, max_new_tokens=n, sampling=GREEDY)
    deadline = time.monotonic() + 60
    while len(req.tokens) < 4 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert len(req.tokens) >= 4, "stream never started decoding"
    blob = eng_a.kv_share.export_stream(req.id, 30)
    assert blob is not None
    # the source request failed TYPED: the SSE handler severs the socket
    # so the router sees a broken leg, never a clean finish
    assert req.wait(30)
    assert isinstance(req.result.get("error"), StreamMigrated)
    # parked blobs re-export from host memory (drain teardown path)
    assert eng_a.kv_share.export_stream(req.id, 30) == blob
    staged = eng_b.kv_share.store_inbound(req.id, blob)
    assert staged["rid"] == req.id and staged["gen_tokens"] >= 4
    req2 = eng_b.kv_share.submit_job(
        "adopt", {"rid": req.id, "sampling": GREEDY}, 30)
    assert req2 is not None
    assert req2.wait(180)
    assert "error" not in req2.result, req2.result.get("error")
    assert req2.result["tokens"] == ref, \
        "migrated stream diverged from the uninterrupted reference"
    assert req2.stats.get("kv_migrated") is True
    # adopting twice is a miss (inbound is consumed), not a crash
    assert eng_b.kv_share.submit_job(
        "adopt", {"rid": req.id, "sampling": GREEDY}, 30) is None
    eng_b.paged.alloc.check()


# ---------------------------------------------------------------------------
# GDN (qwen3_5): row-snapshot layout through the same wire format
# ---------------------------------------------------------------------------


@pytest.mark.slow      # tier-2 covers it; tier-1 runs under the 870s cap
def test_prefix_blob_gdn_rows_roundtrip():
    """The second KV layout: GDN's per-slot linear rows ride the blob as
    per-unit boundary snapshots, and the imported chain's splice restores
    them — greedy parity cold vs fetched."""
    gdn = TextModel(tiny_config("qwen3_5"), dtype=jnp.float32,
                    max_cache_len=CTX)
    eng_a, eng_b = _engine(gdn), _engine(gdn)
    try:
        pa, pb = SYS + [9, 11], SYS + [77, 31]
        ra = eng_a.submit(pa, max_new_tokens=6, sampling=GREEDY)
        assert ra.wait(600)
        assert ra.result["tokens"] == _ref(gdn, pa, 6)
        eng_a._wake.set()
        deadline = time.monotonic() + 10
        while not eng_a.kv_share.health_view()["chains"] \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        chain = eng_a.kv_share.health_view()["chains"][0]
        blob = eng_a.kv_share.submit_job("export_prefix", chain, 60)
        header, _ = decode_blob(blob)
        assert header["has_snap"] is True
        res = eng_b.kv_share.submit_job("import_prefix", blob, 60)
        assert res["tokens"] == 32
        rb = eng_b.submit(pb, max_new_tokens=6, sampling=GREEDY)
        assert rb.wait(600)
        assert rb.stats["prefix_hit_tokens"] == 32
        assert rb.result["tokens"] == _ref(gdn, pb, 6)
    finally:
        eng_a.close()
        eng_b.close()
