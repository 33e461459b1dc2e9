"""repro.api contract stability: golden registry-key derivation through
``Workload``, backward-compat of the ``launch.record``/``launch.serve``
shims (byte-identical recordings, identical serve stats), and the misuse
errors that keep unverified bytes away from ``pickle.loads``."""
import importlib
import os
import pickle
import subprocess
import sys
import tempfile

import jax
import numpy as np
import pytest

from repro.api import Workspace, static_meta_for
from repro.configs import get_config, smoke_shrink
from repro.core.netem import WIFI
from repro.core.recording import Recording, TamperedRecordingError
from repro.record import RecordingSession
from repro.registry import key_for
from repro.registry.service import recording_to_parts

KEY = b"api-test-key"
SHAPES = dict(cache_len=64, block_k=4, batch=2, prefill_batch=1, seq=8)


# ----------------------------------------------------- key derivation ----
def test_key_for_golden_values_pinned():
    """The pure derivation must not drift across refactors: these literal
    keys were produced by the PR-5 ``key_for`` (fingerprint over the
    static-meta dict + mesh fingerprint, 16 hex chars).  If this test
    fails, published registries and replayer caches stop key-matching —
    do NOT update the golden without a migration story."""
    assert key_for("qwen2.5-3b", "decode",
                   {"kind": "decode", "cache_len": 128, "block_k": 8,
                    "batch": 4, "config_fp": "cfgfp"},
                   "meshfp") == "qwen2.5-3b/decode/c7bd577923f2d89f"
    assert key_for("qwen2.5-3b", "prefill",
                   {"kind": "prefill", "cache_len": 128, "block_k": 8,
                    "batch": 1, "seq": 16, "config_fp": "cfgfp"},
                   "meshfp") == "qwen2.5-3b/prefill/65e8b35e1789427b"


def test_workload_key_composition_contract():
    """``Workload.key`` must be exactly ``key_for(arch, kind,
    {**static_meta, config_fp}, mesh_fp)`` — the contract the record CLI
    publishes under and the serve CLI fetches by."""
    ws = Workspace(key=KEY)
    wl = ws.workload("qwen2.5-3b", **SHAPES)
    for kind in ("prefill", "decode"):
        batch = SHAPES["prefill_batch"] if kind == "prefill" \
            else SHAPES["batch"]
        static = static_meta_for(kind, cache_len=SHAPES["cache_len"],
                                 block_k=SHAPES["block_k"], batch=batch,
                                 seq=SHAPES["seq"])
        assert wl.key(kind) == key_for(
            wl.cfg.name, kind, {**static, "config_fp": wl.cfg.fingerprint()},
            wl.mesh_fp)
    # smoke suffix is identity-irrelevant; derivation is deterministic
    assert wl.cfg.name.endswith("-smoke")
    assert wl.key("decode").startswith("qwen2.5-3b/decode/")
    wl2 = Workspace(key=KEY).workload("qwen2.5-3b", **SHAPES)
    assert wl2.key("prefill") == wl.key("prefill")
    assert wl2.key("decode") == wl.key("decode")
    # decode identity excludes seq: a decode recording serves any prompt
    wl3 = Workspace(key=KEY).workload("qwen2.5-3b",
                                      **dict(SHAPES, seq=32))
    assert wl3.key("decode") == wl.key("decode")
    assert wl3.key("prefill") != wl.key("prefill")


# ------------------------------------------------------- shim compat ----
def test_api_record_bit_exact_vs_legacy_session():
    """``Workload.record(artifact=...)`` must produce byte-identical
    recordings to the legacy path (hand-built RecordingSession over the
    same compiled artifact) — manifest, payload, trees, and signature."""
    ws = Workspace(key=KEY, net="wifi")
    wl = ws.workload("cody-mnist", **SHAPES)
    base = wl.compile("prefill")
    api_rec = wl.record("prefill", artifact=base)
    legacy = RecordingSession.for_profile(WIFI).finalize(
        Recording(dict(base.manifest), base.payload, base.trees))
    assert api_rec.payload == legacy.payload == base.payload
    assert api_rec.trees == legacy.trees
    assert api_rec.manifest == legacy.manifest
    api_signed = Recording(dict(api_rec.manifest), api_rec.payload,
                           api_rec.trees).sign_with(KEY)
    legacy_signed = Recording(dict(legacy.manifest), legacy.payload,
                              legacy.trees).sign_with(KEY)
    assert api_signed.to_bytes() == legacy_signed.to_bytes()
    # and the session accounting went into both manifests identically
    assert api_rec.manifest["record_virtual_s"] > 0
    assert ws.report()["sessions"][0]["virtual_time_s"] == \
        api_rec.manifest["record_virtual_s"]


def test_record_cli_shim_publishes_the_api_keys():
    """The record CLI (now a shim) must keep writing the flat file AND
    publishing under the canonical API key: an API workspace with the
    same shapes fetches the exact bytes the CLI saved."""
    from repro.launch.record import main as record_main
    with tempfile.TemporaryDirectory() as d:
        record_main(["--arch", "cody-mnist", "--kinds", "prefill",
                     "--out", d, "--key", KEY.decode(), "--cache-len", "64",
                     "--block-k", "4", "--batch", "2", "--seq", "8",
                     "--net", "wifi"])
        with open(os.path.join(d, "cody-mnist_prefill.codyrec"), "rb") as f:
            flat = f.read()
        Recording.from_bytes(flat, KEY)              # flat file verifies
        ws = Workspace(registry=os.path.join(d, "registry"), key=KEY,
                       net="wifi")
        wl = ws.workload("cody-mnist", **SHAPES)
        assert wl.fetch("prefill") == flat           # same key, same bytes


def test_serve_shim_identical_stats_vs_api():
    """``build_engine`` (now a shim) must behave exactly like driving the
    API directly: same tokens, same engine stats, stream for stream."""
    from repro.launch.serve import REC_SEQ, build_engine
    cfg = smoke_shrink(get_config("cody-mnist"))
    params_key = jax.random.PRNGKey(0)
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(3, cfg.vocab_size, 6)) for _ in range(4)]

    from repro.models import model as M
    params = M.init_params(cfg, params_key)
    shim_eng = build_engine(cfg, n_slots=2, cache_len=64, block_k=4,
                            eos_id=2, params=params)
    wl = Workspace().workload(cfg, cache_len=64, block_k=4, batch=2,
                              prefill_batch=1, seq=REC_SEQ)
    api_eng = wl.engine(params=params)
    outs = {}
    for label, eng in (("shim", shim_eng), ("api", api_eng)):
        for p in prompts:
            eng.submit(p, max_new=8)
        outs[label] = eng.run()
    assert outs["shim"] == outs["api"]
    assert dict(shim_eng.stats) == dict(api_eng.stats)


# ------------------------------------------------------ misuse errors ----
SIDE_EFFECTS = []


class _Evil:
    def __reduce__(self):
        return (SIDE_EFFECTS.append, ("pwned",))


def test_workspace_registry_requires_key():
    """A keyless registry workspace could never verify fetched bytes —
    refuse at construction, long before any fetch."""
    with pytest.raises(ValueError, match="signing key"):
        Workspace(registry=":memory:", key=b"")
    with pytest.raises(ValueError, match="signing key"):
        Workspace(registry="/tmp/somewhere")


def test_fetch_without_registry_is_an_error():
    ws = Workspace(key=KEY)
    wl = ws.workload("cody-mnist", **SHAPES)
    with pytest.raises(RuntimeError, match="no registry"):
        wl.fetch("prefill")


def test_unsigned_fetch_rejected_before_any_unpickle():
    """A recording signed under the WRONG key, smuggled into the store
    with a malicious pickle in its trees, must be rejected by the HMAC
    check before ``pickle.loads`` can run."""
    SIDE_EFFECTS.clear()
    ws = Workspace(registry=":memory:", key=KEY, net="wifi")
    wl = ws.workload("cody-mnist", **SHAPES)
    evil = Recording({"name": "evil", "static": {}}, b"payload",
                     pickle.dumps(_Evil())).sign_with(b"attacker-key")
    # the service refuses to publish a foreign-signed recording at all...
    with pytest.raises(TamperedRecordingError):
        wl.publish(evil, key=wl.key("prefill"))
    # ...so smuggle it straight into the store, bypassing the service
    ws.store.put(wl.key("prefill"),
                 recording_to_parts(evil, ws.store.chunk_size), meta={})
    with pytest.raises(TamperedRecordingError):
        wl.fetch("prefill")
    assert SIDE_EFFECTS == []                 # the pickle never executed


# ---------------------------------------------------- entry points ----
@pytest.mark.parametrize("cli", ["serve", "record", "train", "fanout"])
def test_cli_smoke_flag_reaches_published_widths(cli):
    """``--smoke`` is the default and ``--no-smoke`` turns it off, so the
    command-line entry points can run a config at its published widths."""
    parser = importlib.import_module(f"repro.launch.{cli}").build_parser()
    assert parser.parse_args([]).smoke is True
    assert parser.parse_args(["--smoke"]).smoke is True
    assert parser.parse_args(["--no-smoke"]).smoke is False


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_directory(tmp_path, env_dir):
    """The entry points' compile cache lives where
    ``JAX_COMPILATION_CACHE_DIR`` says, else at ``<checkout>/.jax_cache``
    — a fixed path, so a second run finds the first run's entries.
    Checked in a child: the cache is process-global and tests keep it
    off."""
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(repo, ".jax_cache")
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    code = ("import sys, jax\n"
            f"sys.path.insert(0, {os.path.join(repo, 'src')!r})\n"
            "from repro.launch.cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [want, want]
