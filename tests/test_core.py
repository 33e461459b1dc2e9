"""CODY core: deferral / speculation / metasync / netem / recording — unit
+ hypothesis property tests on the system's invariants."""
import os
import pickle
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (CELLULAR, WIFI, CommitQueue, DeltaSync,
                        HistorySpeculator, MispredictError, NetworkEmulator,
                        Recording, SpeculativeRunner, TamperedRecordingError,
                        full_pack, merge, split)
from repro.core.recorder import record
from repro.core.replay import Replayer


class FakeDevice:
    """In-order device: read returns register value, write mutates."""

    def __init__(self):
        self.regs = {}
        self.exec_log = []

    def channel(self, op):
        self.exec_log.append((op.kind, op.site, op.payload))
        if op.kind == "write":
            self.regs[op.site] = op.payload
            return None
        if op.kind == "read":
            return self.regs.get(op.site, 0)
        return 3  # poll iterations


# ------------------------------------------------------------- deferral ----
@settings(max_examples=50, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(["read", "write"]),
                          st.integers(0, 4), st.integers(0, 99)),
                min_size=1, max_size=40))
def test_deferral_preserves_program_order(ops):
    """Batched commits must execute the exact op sequence a synchronous
    driver would (the paper's correctness invariant, §4.1)."""
    sync_dev, defer_dev = FakeDevice(), FakeDevice()
    # synchronous reference
    for kind, reg, val in ops:
        if kind == "write":
            sync_dev.channel(type("O", (), {"kind": "write", "site": f"r{reg}",
                                            "payload": val})())
        else:
            sync_dev.channel(type("O", (), {"kind": "read", "site": f"r{reg}",
                                            "payload": None})())
    # deferred
    q = CommitQueue(defer_dev.channel)
    symbols = []
    for kind, reg, val in ops:
        if kind == "write":
            q.write(f"r{reg}", val)
        else:
            symbols.append((q.read(f"r{reg}"), f"r{reg}"))
    q.commit()
    assert sync_dev.exec_log == defer_dev.exec_log
    assert sync_dev.regs == defer_dev.regs
    # every symbol resolved to the synchronous value at its position
    for s, site in symbols:
        assert s.resolved


def test_symbol_reresolution_raises():
    """Satellite: a deferred read resolved twice would silently rewrite a
    value the speculation machinery already acted on — it must raise."""
    from repro.core.deferral import Symbol, SymbolReResolutionError
    s = Symbol("reg0")
    s.resolve(7)
    assert s.value == 7
    with pytest.raises(SymbolReResolutionError):
        s.resolve(8)                  # different value: definitely a bug
    with pytest.raises(SymbolReResolutionError):
        s.resolve(7)                  # same value: still a program-order bug
    assert s.value == 7               # first resolution stands


def test_externalization_commit_with_unresolved_symbol_mid_queue():
    """Satellite edge case: an externalization-forced commit with an
    UNRESOLVED symbol mid-queue — a later write's payload references an
    earlier deferred read in the same batch.  In-order client execution
    must resolve it on the fly; a symbol whose read was never enqueued
    must surface as UnresolvedSymbolError, not ship garbage."""
    from repro.core.deferral import Symbol, UnresolvedSymbolError
    dev = FakeDevice()
    dev.regs["cfg"] = 42
    q = CommitQueue(dev.channel)
    q.write("pwr", 1)
    s1 = q.read("cfg")            # unresolved while queued
    q.write("mirror", s1)         # data dependency on the mid-queue symbol
    s2 = q.read("mirror")
    q.write("probe", [s1, {"v": s1}])     # nested payload references
    assert not s1.resolved        # still symbolic before externalization
    q.flush()                     # externalization point -> one commit
    assert s1.resolved and s2.resolved
    assert s1.value == 42 and s2.value == 42
    assert dev.regs["mirror"] == 42
    assert dev.regs["probe"] == [42, {"v": 42}]
    assert q.commits == 1         # 5 interactions, one round trip
    # program order preserved through the symbolic resolution
    assert [e[:2] for e in dev.exec_log] == [
        ("write", "pwr"), ("read", "cfg"), ("write", "mirror"),
        ("read", "mirror"), ("write", "probe")]
    # a symbol from NOWHERE (its read is not in any batch) must raise
    q2 = CommitQueue(FakeDevice().channel)
    q2.write("y", Symbol("phantom"))
    with pytest.raises(UnresolvedSymbolError):
        q2.commit()


def test_barrier_forced_commit_ordering_across_batches():
    """Satellite edge case: explicit barriers split the op stream into
    coalesced batches; the device must still observe the exact global
    program order, and each barrier must cost exactly one round trip."""
    dev = FakeDevice()
    net = NetworkEmulator(WIFI)
    q = CommitQueue(dev.channel, netem=net)
    expect = []
    for batch in range(3):
        for i in range(4):
            q.write(f"b{batch}_r{i}", batch * 10 + i)
            expect.append(("write", f"b{batch}_r{i}"))
        s = q.read(f"b{batch}_r0")
        expect.append(("read", f"b{batch}_r0"))
        q.flush()                 # barrier: forces the commit HERE
        assert s.value == batch * 10      # resolved at its barrier
    assert [e[:2] for e in dev.exec_log] == expect
    assert q.commits == 3 and net.round_trips == 3
    assert q.deferred_total == 15


def test_deferral_symbolic_data_dependency():
    dev = FakeDevice()
    dev.regs["cfg"] = 7
    q = CommitQueue(dev.channel)
    s = q.read("cfg")
    q.write("cfg", s)        # write the symbol back (paper listing 1a)
    q.commit()
    assert dev.regs["cfg"] == 7
    assert q.commits == 1    # one round trip for both ops


def test_deferral_coalesces_round_trips():
    dev = FakeDevice()
    net = NetworkEmulator(WIFI)
    q = CommitQueue(dev.channel, netem=net)
    for i in range(10):
        q.write(f"r{i}", i)
    s = q.read("r5")
    assert q.need(s) == 5
    assert net.round_trips == 1   # 11 interactions, one RTT


# ----------------------------------------------------------- speculation ----
def test_speculation_hides_rtt_and_validates():
    dev = FakeDevice()
    dev.regs["status"] = 1
    net = NetworkEmulator(WIFI)
    q = CommitQueue(dev.channel, netem=net)
    spec = HistorySpeculator(k=3)
    runner = SpeculativeRunner(q, spec, lambda: dict(dev.regs),
                               lambda s, log: None)
    for _ in range(5):
        q.read("status")
        runner.commit_speculative()
        runner.sync()
    assert runner.stats["spec_commits"] >= 1
    assert runner.stats["mispredicts"] == 0
    # speculative commits did not block:
    assert net.round_trips == runner.stats["sync_commits"]


def test_speculation_mispredict_rolls_back():
    dev = FakeDevice()
    dev.regs["status"] = 1
    q = CommitQueue(dev.channel)
    spec = HistorySpeculator(k=2)
    rolled = []
    runner = SpeculativeRunner(q, spec, lambda: dict(dev.regs),
                               lambda snap, log: rolled.append(snap))
    for _ in range(3):
        q.read("status")
        runner.commit_speculative()
        runner.sync()
    dev.regs["status"] = 99          # injected wrong value (paper §7.3)
    q.read("status")
    assert runner.commit_speculative()  # speculates on stale history
    with pytest.raises(MispredictError):
        runner.sync()
    assert len(rolled) == 1
    # after rollback, speculation history knows the new value; k identical
    # observations re-enable prediction
    assert runner.stats["mispredicts"] == 1


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 3), min_size=4, max_size=30))
def test_speculation_never_corrupts_final_values(values):
    """Whatever the register value stream, after sync+rollback handling the
    committed log equals the true sequence (correctness despite misprediction
    — paper: 'misprediction incurs performance penalty but not correctness')."""
    dev = FakeDevice()
    seq = list(values)
    idx = [0]

    def channel(op):
        if op.kind == "read":
            v = seq[min(idx[0], len(seq) - 1)]
            idx[0] += 1
            return v
        return None

    q = CommitQueue(channel)
    spec = HistorySpeculator(k=3)
    runner = SpeculativeRunner(q, spec, lambda: idx[0], lambda s, log: None)
    got = []
    for i in range(len(seq)):
        s = q.read("r")
        runner.commit_speculative()
        try:
            runner.sync()
        except MispredictError as e:
            got.append(e.actual[0])
            continue
        got.append(s.value if not runner.outstanding else None)
    # all reads the device served, in order:
    assert idx[0] == len(seq)


# -------------------------------------------------------------- metasync ----
@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_metasync_split_merge_identity(seed):
    rng = np.random.default_rng(seed)
    tree = {
        "step": np.int32(rng.integers(0, 100)),
        "pos": rng.integers(0, 50, size=8).astype(np.int32),
        "w": rng.normal(size=(64, 128)).astype(np.float32),
        "nested": {"kv": rng.normal(size=(4, 32, 16)).astype(np.float32),
                   "rng_key": rng.integers(0, 2**31, 2).astype(np.uint32)},
    }
    meta, data = split(tree)
    rebuilt = merge(tree, meta, data)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(rebuilt)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # metastate is small, program data is big
    assert any("step" in k for k in meta)
    assert any("w" in k for k in data)


def test_metastate_hints_match_tokens_not_substrings():
    """Satellite regression: hint matching must split the path into tokens
    — ``"id" in "hidden"`` / ``"count" in "encounter"`` used to classify
    large float weight leaves as metastate."""
    from repro.core.metasync import is_metastate
    big = np.zeros((64, 256), np.float32)          # > META_MAX_ELEMS
    # substring traps: 'hidden' contains 'id', 'encounter' contains 'count'
    assert not is_metastate("['hidden']", big)
    assert not is_metastate("['encounter_weights']", big)
    assert not is_metastate("['slotted_embedding']", big)   # 'slot' substring
    # true metastate tokens keep matching, incl. separators and plurals
    small = np.zeros(8, np.int32)
    for path in ("['pos']", "['committed_pos']", "['request_id']",
                 "['done']", "['slots'][0]", "['rng_key']"):
        assert is_metastate(path, small), path
    # a weight leaf named 'hidden' must land in PROGRAM DATA end to end
    tree = {"hidden": big, "pos": small}
    meta, data = split(tree)
    assert any("hidden" in k for k in data)
    assert not any("hidden" in k for k in meta)
    assert any("pos" in k for k in meta)


def test_metasync_delta_smaller_than_full():
    tree = {"pos": np.arange(1024, dtype=np.int32),
            "step": np.int32(0),
            "w": np.random.default_rng(0).normal(size=(256, 256)).astype(np.float32)}
    meta, _data = split(tree)
    ds = DeltaSync()
    first = ds.pack(meta)
    meta2 = dict(meta)
    meta2[[k for k in meta if "step" in k][0]] = np.int32(1)
    second = ds.pack(meta2)
    assert len(second) < len(first)              # delta: only changed leaves
    assert len(first) < len(full_pack(tree))    # metastate-only << full sync
    restored = DeltaSync.unpack(second, meta)
    k = [k for k in meta if "step" in k][0]
    assert int(restored[k]) == 1


# ------------------------------------------------------------- recording ----
def test_record_replay_roundtrip_and_tamper():
    key = b"signing-key"
    fn = lambda x: jnp.tanh(x) * 2.0
    rec = record("t", fn, (jax.ShapeDtypeStruct((8,), jnp.float32),))
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "t.codyrec")
        rec.save(p, key)
        rp = Replayer(key=key)
        rp.load(p)
        x = jnp.linspace(-1, 1, 8)
        np.testing.assert_allclose(rp.execute("t", x), fn(x), rtol=1e-6)
        # wrong key rejected
        with pytest.raises(TamperedRecordingError):
            Replayer(key=b"wrong").load(p)
        # bit flips rejected (random positions)
        blob = bytearray(open(p, "rb").read())
        for off in (10, len(blob) // 2, len(blob) - 20):
            b2 = bytearray(blob)
            b2[off] ^= 0x5A
            with pytest.raises(TamperedRecordingError):
                Replayer(key=key).load(bytes(b2))


def test_replayer_is_minimal():
    """The replayer module must not import model/config/training code —
    the paper's tiny-TCB requirement."""
    import repro.core.replay as rp
    src = open(rp.__file__).read()
    for forbidden in ("repro.models", "repro.configs", "repro.training",
                      "repro.serving"):
        assert forbidden not in src


def test_recording_embeds_cost_and_topology():
    rec = record("t", lambda x: x + 1,
                 (jax.ShapeDtypeStruct((4, 4), jnp.float32),))
    assert "topology" in rec.manifest
    assert rec.manifest["inputs"][0]["shape"] == [4, 4]
    assert "flops" in rec.manifest["cost"] or rec.manifest["cost"] == {}


def _flip_mid_byte(b: bytes) -> bytes:
    ba = bytearray(b)
    ba[len(ba) // 2] ^= 0x5A
    return bytes(ba)


def test_recording_tamper_matrix():
    """Trust boundary: a change to ANY section — manifest, payload, trees,
    signature — must surface as TamperedRecordingError on load."""
    key = b"matrix-key"
    rec = Recording({"name": "t", "static": {"cache_len": 64}},
                    b"\x01\x02" * 700,
                    pickle.dumps((None, None))).sign_with(key)
    assert Recording.from_bytes(rec.to_bytes(), key).manifest == rec.manifest
    mutations = {
        "manifest": lambda r: r.manifest.__setitem__(
            "static", {"cache_len": 9999}),
        "payload": lambda r: setattr(r, "payload", _flip_mid_byte(r.payload)),
        "trees": lambda r: setattr(r, "trees", _flip_mid_byte(r.trees)),
        "signature": lambda r: setattr(
            r, "signature",
            ("0" if r.signature[0] != "0" else "1") + r.signature[1:]),
    }
    for section, mutate in mutations.items():
        tampered = Recording(dict(rec.manifest), rec.payload, rec.trees,
                             rec.signature)
        mutate(tampered)
        with pytest.raises(TamperedRecordingError):
            Recording.from_bytes(tampered.to_bytes(), key)


# ---------------------------------------------------------------- netem ----
def test_netem_one_way_accounts_both_directions():
    net = NetworkEmulator(WIFI)
    net.one_way(1000)                        # default direction: send
    assert (net.bytes_sent, net.bytes_received) == (1000, 0)
    t1 = net.virtual_time_s
    assert t1 == pytest.approx(WIFI.rtt_s / 2 + 1000 / WIFI.bw_bytes_s)
    net.one_way_recv(500)                    # registry fetch direction
    assert (net.bytes_sent, net.bytes_received) == (1000, 500)
    assert net.virtual_time_s == pytest.approx(
        t1 + WIFI.rtt_s / 2 + 500 / WIFI.bw_bytes_s)
    with pytest.raises(ValueError):
        net.one_way(1, direction="sideways")


def test_netem_transfer_chunked_accounting():
    """transfer(): one blocking RTT + bandwidth for payload and per-chunk
    acks, billed to the right direction — registry fetch billing."""
    for direction in ("recv", "send"):
        net = NetworkEmulator(CELLULAR)
        chunks = net.transfer(200_000, chunk_size=64_000, direction=direction)
        assert chunks == 4                   # ceil(200000 / 64000)
        acks = net.ACK_BYTES * chunks
        payload_dir, ack_dir = (net.bytes_received, net.bytes_sent) \
            if direction == "recv" else (net.bytes_sent, net.bytes_received)
        assert (payload_dir, ack_dir) == (200_000, acks)
        assert net.round_trips == 1
        assert net.virtual_time_s == pytest.approx(
            CELLULAR.rtt_s + (200_000 + acks) / CELLULAR.bw_bytes_s)
    assert NetworkEmulator(WIFI).transfer(0) == 0   # nothing billed


# ------------------------------------------------- metasync round trips ----
def test_metasync_delta_roundtrip_bit_exact():
    """split -> DeltaSync -> merge reproduces the original pytree
    bit-exactly, including the no-change fast path (registry delta
    publishing leans on exactly this)."""
    rng = np.random.default_rng(7)
    tree = {"step": np.int32(3),
            "pos": rng.integers(0, 50, 8).astype(np.int32),
            "w": rng.normal(size=(64, 64)).astype(np.float32),
            "nested": {"rng_key": rng.integers(0, 2**31, 2).astype(np.uint32)}}
    meta, data = split(tree)
    ds = DeltaSync()
    wire1 = ds.pack(meta)
    restored = DeltaSync.unpack(wire1, {})     # first sync ships everything
    assert set(restored) == set(meta)
    rebuilt = merge(tree, restored, data)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(rebuilt)):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))

    # no-change fast path: zero leaves shipped, base reproduced bit-exactly
    sent = ds.stats["leaves_sent"]
    wire2 = ds.pack(meta)
    assert ds.stats["leaves_sent"] == sent
    assert len(wire2) < len(wire1)
    restored2 = DeltaSync.unpack(wire2, restored)
    for k in meta:
        assert np.array_equal(np.asarray(restored2[k]), np.asarray(meta[k]))

    # single-leaf change: only that leaf crosses the wire, merge is exact
    pos_key = next(k for k in meta if "pos" in k)
    meta2 = dict(meta, **{pos_key: np.asarray(meta[pos_key]) + 1})
    wire3 = ds.pack(meta2)
    assert ds.stats["leaves_sent"] == sent + 1
    restored3 = DeltaSync.unpack(wire3, restored2)
    rebuilt3 = merge(tree, restored3, data)
    flat3 = {k: v for k, v in zip(meta, [restored3[k] for k in meta])}
    assert np.array_equal(np.asarray(flat3[pos_key]),
                          np.asarray(meta[pos_key]) + 1)
    for a, b in zip(jax.tree.leaves(merge(tree, meta2, data)),
                    jax.tree.leaves(rebuilt3)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
