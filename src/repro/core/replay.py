"""The replayer — CODY's in-TEE component.

Deliberately minimal: it imports NO model code, NO configs, NO training
machinery (tests assert this).  It loads a signed recording, verifies
(signature, format, topology), deserializes the executable, and executes it
on new inputs.  There is no tracing, no compilation, no Python model in the
TCB — the executable *is* the recorded interaction script.

Mirrors the paper's replayer obligations:
  * verify authenticity (cloud signature)            -> HMAC check
  * match recording to the exact hardware (§2.4)     -> topology fingerprint
  * reset/clean state around replay (§3.2)           -> fresh buffers, no
    state escapes except declared outputs (donation honored by XLA)

Executables are cached by ``(name, input-avals)``: several recordings of
the same workload at different shapes (e.g. prefill shape buckets) can
share a logical name, and ``execute`` dispatches on the argument avals.
The aval signature is computed from the manifest ONCE at ``load``; the
per-call check is a tuple build + dict lookup, and a mismatch raises a
clear ``ReplayArgumentError`` instead of an XLA crash deep in the TEE
path.  ``warm`` runs a loaded executable once on zero inputs so the first
real block of the serving pipeline pays no allocation/cold-start cost.

The steady-state FAST PATH: once a sole-variant name has validated one
call, the resolved executable is pinned and every later ``execute`` for
that name dispatches directly — no ``jax.tree.leaves`` walk, no signature
tuple build, no variant-dict probing.  On the decode hot path (thousands
of identical-aval calls per stream) the signature build is ~half the
Python dispatch cost, so this is what lets replay dispatch match native
jit dispatch.  The pin is dropped the moment a second aval variant loads
under the name (multi-variant names always dispatch by signature —
correctness over speed).  ``stats['fast_hits']`` / ``stats['slow_
validations']`` count the two paths; the serving stack reads them through
``Workspace.report()``.
"""
from __future__ import annotations

import pickle
from typing import Any, Optional

import jax
import numpy as np
from jax.experimental import serialize_executable as se

from repro.core.attest import (TamperedRecordingError, TopologyMismatchError,
                               UnverifiedRecordingError, fingerprint)
from repro.core.recording import Recording


class ReplayArgumentError(TypeError):
    """Replay arguments do not match any recorded executable."""


def _topology_fingerprint() -> str:
    devs = jax.devices()
    return fingerprint(sorted(str(d.device_kind) for d in devs), len(devs))


def _execution_devices(manifest: dict):
    """The recorded device assignment (None: every visible device, for
    recordings that predate the field)."""
    ids = manifest.get("devices")
    if ids is None:
        return None
    by_id = {d.id: d for d in jax.devices()}
    missing = [i for i in ids if i not in by_id]
    if missing:
        raise TopologyMismatchError(
            f"recording runs on devices {ids}; {missing} not present")
    return [by_id[i] for i in ids]


def _aval_signature(leaves) -> tuple:
    return tuple((tuple(getattr(a, "shape", ())),
                  str(getattr(a, "dtype", ""))) for a in leaves)


class Replayer:
    def __init__(self, key: Optional[bytes] = None,
                 enforce_topology: bool = True,
                 allow_unsigned: bool = False):
        if key is None and not allow_unsigned:
            raise UnverifiedRecordingError(
                "Replayer without a signing key would pickle.loads "
                "unverified recordings; pass key=... or opt in with "
                "allow_unsigned=True")
        self._key = key
        self._allow_unsigned = allow_unsigned
        self._enforce_topology = enforce_topology
        self._loaded = {}   # name -> {aval_sig: (exe, manifest, in_tree)}
        self._fast = {}     # name -> exe, sole-variant names only, pinned
        #                     after the first validated execute()
        self.stats = {"loads": 0, "executions": 0, "rejected": 0,
                      "fast_hits": 0, "slow_validations": 0}

    def load(self, path_or_bytes, name: Optional[str] = None):
        try:
            if isinstance(path_or_bytes, (bytes, bytearray)):
                rec = Recording.from_bytes(
                    bytes(path_or_bytes), self._key,
                    allow_unsigned=self._allow_unsigned)
            else:
                rec = Recording.load(path_or_bytes, self._key,
                                     allow_unsigned=self._allow_unsigned)
        except TamperedRecordingError:
            self.stats["rejected"] += 1
            raise
        if rec.manifest.get("exec_fingerprint") != fingerprint(rec.payload):
            self.stats["rejected"] += 1
            raise TamperedRecordingError("payload fingerprint mismatch")
        if self._enforce_topology and \
                rec.manifest["topology"] != _topology_fingerprint():
            self.stats["rejected"] += 1
            raise TopologyMismatchError(
                "recording was made for different hardware "
                f"({rec.manifest['topology'][:12]}... vs "
                f"{_topology_fingerprint()[:12]}...)")
        in_tree, out_tree = pickle.loads(rec.trees)
        exe = se.deserialize_and_load(
            rec.payload, in_tree, out_tree,
            execution_devices=_execution_devices(rec.manifest))
        nm = name or rec.manifest["name"]
        # manifest aval check happens HERE, once: the signature is the
        # cache key, so every execute() validates by construction
        sig = tuple((tuple(i["shape"]), i["dtype"])
                    for i in rec.manifest["inputs"])
        self._loaded.setdefault(nm, {})[sig] = (exe, rec.manifest, in_tree)
        # any load under this name invalidates the fast-path pin: the name
        # may now be multi-variant, which must dispatch by signature
        self._fast.pop(nm, None)
        self.stats["loads"] += 1
        return nm

    def preload(self, items) -> list:
        """Load many recordings up front (paths, or (path, name) pairs) so
        the serving pipeline never loads mid-decode."""
        names = []
        for it in items:
            path, name = it if isinstance(it, tuple) else (it, None)
            names.append(self.load(path, name))
        return names

    def manifest(self, name: str, signature: Optional[tuple] = None) -> dict:
        """Manifest of a loaded recording.  With one variant loaded under
        ``name`` the answer is unambiguous; with several, the caller must
        say which (``signature`` = the aval signature used as the cache
        key) — silently returning *some* variant would leak dict ordering
        into replay behavior."""
        variants = self._loaded[name]
        if signature is not None:
            try:
                return variants[signature][1]
            except KeyError:
                raise ReplayArgumentError(
                    f"no variant of '{name}' with signature "
                    f"{self._describe(signature)}") from None
        if len(variants) != 1:
            raise ReplayArgumentError(
                f"'{name}' has {len(variants)} loaded variants; pass "
                "signature=... to pick one (or use manifests())")
        return next(iter(variants.values()))[1]

    def manifests(self, name: str) -> list:
        """Manifests of every loaded variant of ``name`` (load order)."""
        return [m for _exe, m, _tree in self._loaded[name].values()]

    def execute(self, name: str, *args) -> Any:
        """Run the recorded executable on new inputs.  No retracing ever;
        the aval lookup doubles as the shape/dtype validation — and once
        a sole-variant name has validated one call, later calls take the
        pinned fast path (no leaves walk, no signature build)."""
        exe = self._fast.get(name)
        if exe is not None:
            self.stats["fast_hits"] += 1
            self.stats["executions"] += 1
            return exe(*args)
        variants = self._loaded[name]
        sig = _aval_signature(jax.tree.leaves(args))
        hit = variants.get(sig)
        if hit is None:
            known = "\n  ".join(self._diff(sig, s) for s in variants)
            raise ReplayArgumentError(
                f"replay args for '{name}' match no recorded executable.\n"
                f"got:      {self._describe(sig)}\n"
                f"recorded: {known}")
        self.stats["slow_validations"] += 1
        self.stats["executions"] += 1
        if len(variants) == 1:
            self._fast[name] = hit[0]
        return hit[0](*args)

    def warm(self, name: str):
        """Execute every variant of ``name`` once on zero-filled inputs
        (outputs discarded) so real traffic hits warm buffers."""
        for sig, (exe, _man, in_tree) in self._loaded[name].items():
            leaves = [np.zeros(shape, dtype=np.dtype(dt))
                      for shape, dt in sig]
            args, kwargs = jax.tree.unflatten(in_tree, leaves)
            jax.block_until_ready(exe(*args, **kwargs))
            self.stats["executions"] += 1
        return name

    def quote(self, keys, name: str, *, head: dict,
              recording_key: Optional[str] = None) -> dict:
        """Replay attestation quote for a LOADED recording: binds the
        registry key, the verified executable fingerprint, and how many
        executions this replayer has served, against the signed tree head
        the recording was fetched under.  (Plan-level replays quote
        through ``PlanExecutor.quote`` instead, which additionally binds
        the compacted plan and the committed write frontier.)"""
        from repro.attest.quote import build_quote
        from repro.core.attest import fingerprint as fp
        manifests = self.manifests(name)
        exec_fp = manifests[0].get("exec_fingerprint", "")
        return build_quote(
            keys, recording_key=recording_key or name,
            exec_fingerprint=exec_fp, plan_fingerprint="",
            frontier_digest=fp({"executions": self.stats["executions"],
                                "loads": self.stats["loads"]}),
            head=head,
            annotations={"variants": len(self._loaded[name])})

    @staticmethod
    def _describe(sig) -> str:
        short = [f"{dt}{list(shape)}" for shape, dt in sig[:6]]
        more = f" ... +{len(sig) - 6} leaves" if len(sig) > 6 else ""
        return ", ".join(short) + more

    @staticmethod
    def _diff(got, want) -> str:
        """Describe a recorded signature, pointing at the first leaf that
        disagrees with ``got`` (the interesting one is often past any
        truncation)."""
        if len(got) != len(want):
            return (f"{Replayer._describe(want)}  "
                    f"[{len(want)} leaves, got {len(got)}]")
        for i, (g, w) in enumerate(zip(got, want)):
            if g != w:
                return (f"{Replayer._describe(want)}  [first mismatch at "
                        f"leaf {i}: got {g[1]}{list(g[0])}, recorded "
                        f"{w[1]}{list(w[0])}]")
        return Replayer._describe(want)

    def __contains__(self, name: str) -> bool:
        return name in self._loaded
