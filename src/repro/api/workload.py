"""Workload — one (arch, shapes, mesh) driven through the full lifecycle.

A ``Workload`` derives the canonical registry identity ONCE
(``registry.key_for`` over ``static_meta_for`` + config + mesh
fingerprints) and exposes every lifecycle stage as a method: ``compile``
/ ``record`` (cloud role), ``publish`` / ``fetch`` (registry), and
``channel`` / ``engine`` (serving — live-jit, flat recordings, or
verified registry replay).  The step-building and static-meta helpers
that used to be copied between the record CLI, the serve CLI, and the
benchmarks live here, as module functions, and the CLIs re-export them.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.attest import fingerprint
from repro.core.channel import LiveChannel, NetemBilledChannel, ReplayChannel
from repro.core.recorder import (compile_artifact, mesh_descriptor, record,
                                 topology_fingerprint)
from repro.core.recording import Recording
from repro.core.replay import Replayer
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.registry import key_arch, key_for
from repro.serving.engine import Engine, cache_batch_axes_for
from repro.sharding import rules_for, shardings_for
from repro.training import steps as ST

KINDS = ("prefill", "decode")


def static_meta_for(kind: str, *, cache_len: int, block_k: int, batch: int,
                    seq: int, eos_id: int = 2) -> dict:
    """The shape/static description that parameterizes ``build_step`` —
    also the ``shapes`` component of the registry key, so record and
    serve derive identical keys from identical arguments.  ``seq`` only
    shapes prefill (decode steps one token per slot per iteration), so it
    is excluded from decode identity: a decode recording serves any
    prompt length.  ``eos_id`` is baked into the fused decode executable,
    so a NON-default value enters decode identity; the default stays out
    of the dict so existing published keys do not drift."""
    static = {"kind": kind, "cache_len": cache_len, "block_k": block_k,
              "batch": batch}
    if kind == "prefill":
        static["seq"] = seq
    elif eos_id != 2:
        static["eos_id"] = eos_id
    return static


def build_step(cfg, kind: str, rules, *, cache_len: int, block_k: int = 8,
               batch: int = 1, seq: int = 32, eos_id: int = 2):
    """Step function + abstract arg specs + donation map for one kind."""
    params = M.abstract_params(cfg)
    if kind == "prefill":
        fn = ST.make_prefill_step(cfg, rules, cache_len=cache_len)
        batch_spec = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
        return fn, (params, batch_spec), ()
    if kind == "decode":
        fn = ST.make_fused_decode_step(cfg, rules, k=block_k, eos_id=eos_id)
        caches = jax.eval_shape(lambda: M.init_cache(cfg, batch, cache_len))
        toks = jax.ShapeDtypeStruct((batch,), jnp.int32)
        pos = jax.ShapeDtypeStruct((batch,), jnp.int32)
        return fn, (params, toks, pos, caches), (3,)
    raise ValueError(kind)


def recording_name(arch: str, kind: str, extra: str = "") -> str:
    """Flat on-disk filename for a recording (identity normalization is
    shared with the registry via ``key_arch``)."""
    return f"{key_arch(arch)}_{kind}{('_' + extra) if extra else ''}.codyrec"


def stream_kwargs(cfg, *, n_slots: int, cache_len: int, block_k: int,
                  eos_id: int, speculate: bool = True,
                  pipeline_depth: int = 4) -> dict:
    """Per-stream policy for ``Scheduler.add_stream`` derived from the
    model family: recurrent state is not position-indexed, so dropped
    pipeline tails cannot be re-executed against an already-advanced
    state — the engine's metastate-only rollback is unsound there and
    speculation is forced off."""
    if cfg.family in ("ssm", "hybrid"):
        speculate = False
    return dict(n_slots=n_slots, cache_len=cache_len, block_k=block_k,
                eos_id=eos_id,
                init_caches_fn=lambda: M.init_cache(cfg, n_slots, cache_len),
                cache_batch_axes=cache_batch_axes_for(cfg),
                speculate=speculate, pipeline_depth=pipeline_depth)


def format_session_report(rep: dict) -> str:
    """One-line summary of a RecordingSession report."""
    mb = (rep["bytes_sent"] + rep["bytes_received"]) / 1e6
    passes = "+".join(rep["passes"]) or "naive"
    return (f"session[{rep['net']}|{passes}]: "
            f"{rep['virtual_time_s']:.2f}s virtual, "
            f"{rep['blocking_round_trips']} blocking / "
            f"{rep['async_round_trips']} async RTs, {mb:.2f} MB, "
            f"{rep['jobs']} jobs")


class Workload:
    """One workload's lifecycle handle.  Built by ``Workspace.workload``;
    holds the model config, the mesh/sharding rules, and the shape tuple
    (``cache_len``, ``block_k``, ``batch`` = decode batch = serving
    slots, ``prefill_batch``, ``seq`` = prefill prompt length) that —
    together with the config and mesh fingerprints — IS the recording
    identity."""

    def __init__(self, workspace, cfg, *, cache_len: int = 128,
                 block_k: int = 8, batch: int = 4, prefill_batch: int = 1,
                 seq: int = 32, eos_id: int = 2, mesh=None):
        self.ws = workspace
        self.cfg = cfg
        self.cache_len = cache_len
        self.block_k = block_k
        self.batch = batch
        self.prefill_batch = prefill_batch
        self.seq = seq
        self.eos_id = eos_id
        self.mesh = mesh if mesh is not None else make_host_mesh(model=1)
        self.rules = rules_for("serve", self.mesh.axis_names)
        self.mesh_fp = fingerprint(mesh_descriptor(self.mesh))
        self.config_fp = cfg.fingerprint()
        # the canonical identity, derived once per kind and never re-derived
        self._keys = {k: key_for(cfg.name, k,
                                 {**self.static_meta(k),
                                  "config_fp": self.config_fp},
                                 self.mesh_fp) for k in KINDS}
        self.sessions = []        # (kind, session report) per record()
        self.replays = []         # (kind, executor report) per replay()
        self.replayers = []       # every Replayer built for this workload
        self._live: Optional[LiveChannel] = None
        self._params = {}         # seed -> initialized params

    # ------------------------------------------------------------ identity --
    def static_meta(self, kind: str) -> dict:
        batch = self.prefill_batch if kind == "prefill" else self.batch
        return static_meta_for(kind, cache_len=self.cache_len,
                               block_k=self.block_k, batch=batch,
                               seq=self.seq, eos_id=self.eos_id)

    def key(self, kind: str) -> str:
        """The registry key this workload records under, publishes under,
        fetches by, and caches replay executables under."""
        return self._keys[kind]

    def step(self, kind: str):
        static = self.static_meta(kind)
        return build_step(self.cfg, kind, self.rules,
                          cache_len=self.cache_len, block_k=self.block_k,
                          batch=static["batch"], seq=self.seq,
                          eos_id=self.eos_id)

    # ----------------------------------------------------------- placement --
    def param_shardings(self):
        """Where each param leaf lives on this workload's mesh (serve
        rules: weights split over ``model`` only, so a data-parallel mesh
        holds one full copy per device)."""
        return shardings_for(M.param_axes(self.cfg),
                             M.abstract_params(self.cfg), self.mesh,
                             self.rules)

    def cache_shardings(self):
        """Where the serving slot caches live (batch over the data axes)."""
        caches = jax.eval_shape(
            lambda: M.init_cache(self.cfg, self.batch, self.cache_len))
        return shardings_for(M.cache_axes(self.cfg), caches, self.mesh,
                             self.rules)

    def in_shardings(self, kind: str) -> tuple:
        """Per-argument placement of ``kind``'s step.  Recordings are
        compiled for exactly this placement, and ``params`` / the engine's
        caches are made there, so a replayed executable never meets an
        argument it would have to reshard on every call."""
        _fn, specs, _donate = self.step(kind)
        p_axes = M.param_axes(self.cfg)
        axes = (p_axes, {"tokens": ("batch", "seq")}) if kind == "prefill" \
            else (p_axes, ("batch",), ("batch",), M.cache_axes(self.cfg))
        return shardings_for(axes, specs, self.mesh, self.rules)

    def _compile_kwargs(self, kind: str) -> dict:
        """Everything but the name that ``record`` / ``compile_artifact``
        need for ``kind``: step, abstract args, placement, identity."""
        fn, specs, donate = self.step(kind)
        return dict(fn=fn, args_abstract=specs, mesh=self.mesh,
                    in_shardings=self.in_shardings(kind),
                    donate_argnums=donate,
                    config_fingerprint=self.config_fp,
                    static_meta=self.static_meta(kind))

    def params(self, seed: int = 0):
        """Initialized model params placed on this workload's mesh,
        memoized per seed (so solo engines and scheduler streams built
        from one workload share arrays)."""
        if seed not in self._params:
            self._params[seed] = jax.device_put(
                M.init_params(self.cfg, jax.random.PRNGKey(seed)),
                self.param_shardings())
        return self._params[seed]

    # -------------------------------------------------------------- record --
    def compile(self, kind: str = "prefill") -> Recording:
        """Cloud dryrun only: lower + compile + serialize, no session
        protocol.  Use with ``record(artifact=...)`` to amortize ONE
        compile across several session variants (serialized executables
        are not byte-deterministic across recompiles)."""
        return compile_artifact(self.key(kind), **self._compile_kwargs(kind))

    def record(self, kind: str = "prefill", *, passes=None,
               artifact: Optional[Recording] = None,
               jobs: Optional[int] = None) -> Recording:
        """The paper's record phase: a distributed ``RecordingSession``
        (device proxy + cloud dryrun) over the workspace's link profile,
        with the optimization passes stacked in canonical order.  Returns
        the Recording with session accounting annotated into its manifest
        (``record_virtual_s`` / ``record_session``); the session report is
        also appended to ``self.sessions`` for ``report()``."""
        session = self.ws.session(passes=passes, jobs=jobs)
        if artifact is not None:
            # the artifact knows what it is — label the session by ITS
            # kind, not the (defaulted) argument
            kind = artifact.manifest.get("static", {}).get("kind", kind)
            rec = session.finalize(Recording(dict(artifact.manifest),
                                             artifact.payload,
                                             artifact.trees))
        else:
            rec = record(self.key(kind), session=session,
                         **self._compile_kwargs(kind))
        self.sessions.append((kind, session.report()))
        return rec

    def variants(self, *, seqs=None, kinds=KINDS):
        """Campaign work-list for this workload's shape variants:
        ``(Workload, kind)`` items covering every prefill ``seq`` bucket
        in ``seqs`` (sibling workloads sharing every other shape) plus
        the seq-independent kinds — feed to ``Workspace.campaign``.
        ``seqs=None`` keeps just this workload's own seq."""
        items = []
        for kind in kinds:
            if kind != "prefill":
                items.append((self, kind))
                continue
            for s in (seqs if seqs is not None else [self.seq]):
                wl = self if s == self.seq else self.ws.workload(
                    self.cfg, cache_len=self.cache_len,
                    block_k=self.block_k, batch=self.batch,
                    prefill_batch=self.prefill_batch, seq=s,
                    eos_id=self.eos_id, mesh=self.mesh)
                items.append((wl, "prefill"))
        return items

    # -------------------------------------------------------------- replay --
    def replay(self, kind: str = "prefill", *, passes=None,
               artifact: Optional[Recording] = None,
               jobs: Optional[int] = None) -> dict:
        """Replay-side interaction-plan execution: compact the recording's
        plan with the replay passes (``None`` -> the workspace default)
        and play it through a ``PlanExecutor`` over a fresh emulator on
        the workspace's link profile — the priced counterpart of
        ``record()``.  Returns the executor report (also appended to
        ``self.replays`` for ``report()``)."""
        from repro.core.replay_passes import PlanExecutor, plan_for
        rec = artifact if artifact is not None else self.compile(kind)
        kind = rec.manifest.get("static", {}).get("kind", kind)
        passes = self.ws.replay_passes if passes is None else passes
        plan = plan_for(rec, passes, jobs=jobs)
        rep = PlanExecutor(netem=self.ws.fresh_netem(),
                           tracer=self.ws.tracer).run(plan)
        self.replays.append((kind, rep))
        return rep

    def attested_replay(self, kind: str = "prefill", *, passes=None,
                        jobs: Optional[int] = None,
                        record_on_miss: bool = False):
        """The end-to-end attested lifecycle leg: proof-verified registry
        fetch (inclusion + consistency against the signed root), verified
        replay-plan execution, and a signed QUOTE binding what ran to
        what was published.  Returns ``(report, quote, proof_bundle)`` —
        the quote + bundle verify offline via
        ``repro.attest.verifier.verify_quote`` with no model or registry
        imports on the verifier side."""
        from repro.core.replay_passes import PlanExecutor, verified_plan
        reg_key = self.key(kind)
        record_fn = self._record_fn(kind, reg_key) if record_on_miss \
            else None
        blob = self.ws.client.fetch(reg_key, record_fn=record_fn)
        passes = self.ws.replay_passes if passes is None else passes
        plan, _rec = verified_plan(blob, self.ws.key, passes, jobs=jobs)
        ex = PlanExecutor(netem=self.ws.fresh_netem(), tracer=self.ws.tracer)
        rep = ex.run(plan)
        self.replays.append((kind, rep))
        head = self.ws.service.signed_head()
        quote = ex.quote(self.ws.keys, recording_key=reg_key, head=head)
        bundle = self.ws.service.proof_for(reg_key)
        self.ws.quotes.append(quote)
        return rep, quote, bundle

    # ------------------------------------------------------------ registry --
    def publish(self, rec: Recording, key: Optional[str] = None) -> dict:
        """Publish into the workspace registry under the canonical key
        (derived from the recording's own static meta), signing with the
        workspace key if the recording is unsigned.  Returns the
        service's wire stats (delta-published)."""
        if not rec.signature:
            rec.sign_with(self.ws.key)
        return self.ws.service.publish(key or self._key_of(rec), rec)

    def _key_of(self, rec: Recording) -> str:
        """Canonical registry key recomputed from the recording's OWN
        identity — static meta, config/mesh fingerprints, and (when the
        recording's name is itself a canonical key) its arch — NOT this
        workload's shapes, so publishing a foreign recording files it
        under its own identity instead of silently shadowing this one."""
        static = rec.manifest.get("static") or {}
        kind = static.get("kind")
        mesh = rec.manifest.get("mesh")
        name = rec.manifest.get("name", "")
        if kind not in KINDS or mesh is None:
            return name
        parts = name.split("/")
        arch = parts[0] if len(parts) == 3 and parts[1] == kind \
            else self.cfg.name
        return key_for(arch, kind,
                       {**static,
                        "config_fp": rec.manifest.get("config_fingerprint",
                                                      "")},
                       fingerprint(mesh))

    def _record_fn(self, kind: str, reg_key: str):
        """Record-on-miss closure: the service's single-flight lease
        supplies the session, so the miss records through the service's
        configured link profile with THIS workload's exact shapes."""
        def record_fn(session=None):
            return record(reg_key, session=session,
                          **self._compile_kwargs(kind))
        return record_fn

    def fetch(self, kind: str = "prefill", *, record_on_miss: bool = False,
              interrupt_after: Optional[int] = None) -> bytes:
        """Chunked/resumable fetch of this workload's recording; the
        returned bytes are HMAC-verified BEFORE they can reach any
        ``pickle.loads``.  ``record_on_miss`` records through the
        service's single-flight lease."""
        reg_key = self.key(kind)
        record_fn = self._record_fn(kind, reg_key) if record_on_miss else None
        return self.ws.client.fetch(reg_key, record_fn=record_fn,
                                    interrupt_after=interrupt_after)

    # ------------------------------------------------------------- serving --
    def _usable(self, meta: dict, static: dict, topo: str) -> bool:
        """An alternate published shape of this workload is substitutable
        iff the engine-visible shapes agree (prefill seq may differ: the
        engine adapts via fixed_prompt_len; decode ignores seq; a
        non-default eos_id is baked into the decode executable) AND it
        was recorded for this exact model config and hardware topology —
        a foreign-host or differently-sized recording would only fail
        later with TopologyMismatch/ReplayArgumentError."""
        static_meta = meta.get("static", {})
        return (all(static_meta.get(f) == static[f]
                    for f in ("batch", "cache_len", "block_k"))
                and static_meta.get("eos_id") == static.get("eos_id")
                and meta.get("config_fingerprint", "") == self.config_fp
                and meta.get("topology", "") == topo)

    def _registry_channel(self, record_on_miss: bool,
                          client=None) -> ReplayChannel:
        """Boot a ReplayChannel from the workspace registry: fetch-by-key
        (chunked, resumable, netem-billed), verify, preload + warm — a
        replica boots from a registry hit without recompiling.  On miss,
        an alternate published shape is substituted when usable, else
        ``record_on_miss`` records through the single-flight lease.

        ``client`` selects WHICH RegistryClient boots the channel: fleet
        replicas pass their own (own netem span, own stats, possibly a
        regional read-replica) so boot billing never aliases onto the
        workspace's shared client; None keeps the shared one."""
        store, service = self.ws.store, self.ws.service
        topo = topology_fingerprint()
        items = []
        for kind in KINDS:
            static = self.static_meta(kind)
            reg_key = self.key(kind)
            record_fn = None
            if not service.has(reg_key):
                found = [(store.entry(fk)["meta"], fk) for fk in
                         store.find(f"{key_arch(self.cfg.name)}/{kind}/")]
                found = [(meta.get("published_s", 0.0), fk)
                         for meta, fk in found
                         if self._usable(meta, static, topo)]
                if found:
                    # most recently published alternate wins — find()
                    # sorts by key hash, which would make it arbitrary
                    reg_key = max(found)[1]
                elif record_on_miss:
                    record_fn = self._record_fn(kind, reg_key)
            items.append((reg_key, record_fn))
        rp = Replayer(key=self.ws.key)
        self.replayers.append(rp)
        if client is None:
            client = self.ws.client
        return client.into_channel(rp, items[0], items[1], warm=True)

    def _live_channel(self) -> LiveChannel:
        """Live-jit transport, memoized: every engine/scheduler built
        from this workload shares the same compiled step functions.  Each
        step is traced and called under this workload's mesh, as the
        recorder traces it: outside a mesh the steps' sharding constraints
        are dropped, and the compiler fuses differently — on a TPU that
        alone changes the bf16 rounding, and with it greedy tokens."""
        if self._live is None:
            cfg, rules, mesh = self.cfg, self.rules, self.mesh

            def on_mesh(fn, **jit_kw):
                jitted = jax.jit(fn, **jit_kw)

                def call(*args):
                    with jax.set_mesh(mesh):
                        return jitted(*args)
                return call
            prefill_fn = on_mesh(
                ST.make_prefill_step(cfg, rules, self.cache_len))
            decode_fn = on_mesh(
                ST.make_fused_decode_step(cfg, rules, k=self.block_k,
                                          eos_id=self.eos_id),
                donate_argnums=(3,))
            # grouped right-padded admission: attention families only
            # (decode masks rows >= pos; recurrent state is not
            # position-indexed), and SWA ring layout needs true lengths
            batched_prefill = None
            if cfg.family in ("dense", "moe") and not cfg.sliding_window:
                batched_prefill = on_mesh(
                    ST.make_batched_prefill_step(cfg, rules, self.cache_len))
            self._live = LiveChannel(prefill_fn, decode_fn, batched_prefill)
        return self._live

    def channel(self, *, recordings_dir: str = "",
                record_on_miss: bool = False,
                bill_dispatches: bool = False, client=None):
        """The ExecutionChannel this workload serves through: verified
        registry replay when the workspace has a registry, flat-file
        replay when ``recordings_dir`` is given, live-jit otherwise.
        ``bill_dispatches`` wraps with the netem-billed transport;
        ``client`` boots the registry channel through a specific
        ``RegistryClient`` (a fleet replica's own) instead of the shared
        workspace client."""
        if recordings_dir and self.ws.has_registry:
            raise ValueError(
                "both a workspace registry and recordings_dir were given; "
                "recordings come from exactly one source — use a registry-"
                "less Workspace for flat-file replay")
        if client is not None and not self.ws.has_registry:
            raise ValueError("channel(client=...) requires a workspace "
                             "registry: only registry channels fetch")
        if self.ws.has_registry:
            ch = self._registry_channel(record_on_miss, client=client)
        elif recordings_dir:
            rp = Replayer(key=self.ws.key)
            self.replayers.append(rp)
            pre = rp.load(os.path.join(
                recordings_dir, recording_name(self.cfg.name, "prefill")))
            dec = rp.load(os.path.join(
                recordings_dir, recording_name(self.cfg.name, "decode")))
            rp.warm(dec)    # decode joins the async pipeline with no cold start
            ch = ReplayChannel(rp, pre, dec)
        else:
            ch = self._live_channel()
        if bill_dispatches:
            ch = NetemBilledChannel(ch, self.ws.netem)
        return ch

    def stream_kwargs(self, *, speculate: bool = True,
                      pipeline_depth: int = 4) -> dict:
        kw = stream_kwargs(self.cfg, n_slots=self.batch,
                           cache_len=self.cache_len, block_k=self.block_k,
                           eos_id=self.eos_id, speculate=speculate,
                           pipeline_depth=pipeline_depth)
        # fresh slot caches start where the decode step expects them
        init, shardings = kw["init_caches_fn"], self.cache_shardings()
        kw["init_caches_fn"] = lambda: jax.device_put(init(), shardings)
        return kw

    def engine(self, params=None, *, seed: int = 0, channel=None,
               recordings_dir: str = "", record_on_miss: bool = False,
               bill_dispatches: bool = False, speculate: bool = True,
               pipeline_depth: int = 4) -> Engine:
        """One-stream serving behind the classic ``Engine`` facade,
        wired through this workload's channel and the workspace netem."""
        if channel is None:
            channel = self.channel(recordings_dir=recordings_dir,
                                   record_on_miss=record_on_miss,
                                   bill_dispatches=bill_dispatches)
        if params is None:
            params = self.params(seed)
        eng = Engine(params, channel=channel, netem=self.ws.netem,
                     tracer=self.ws.tracer,
                     **self.stream_kwargs(speculate=speculate,
                                          pipeline_depth=pipeline_depth))
        eng.registry_client = self.ws.registry_client
        return eng

    # ----------------------------------------------------------- reporting --
    def replayer_stats(self) -> dict:
        """Summed counters over every Replayer this workload built —
        the fast-path hit/validation split the serving report surfaces."""
        totals: dict = {}
        for rp in self.replayers:
            for k, v in rp.stats.items():
                totals[k] = totals.get(k, 0) + v
        return totals

    def report(self) -> dict:
        return {"arch": self.cfg.name,
                "keys": dict(self._keys),
                "sessions": [dict(rep, kind=kind)
                             for kind, rep in self.sessions],
                "replays": [dict(rep, kind=kind)
                            for kind, rep in self.replays],
                "replayer_stats": self.replayer_stats()}
