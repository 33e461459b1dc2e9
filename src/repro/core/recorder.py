"""The recorder — CODY's "cloud dryrun service" on the JAX AOT path.

``compile_artifact()`` exercises the full framework stack (model code,
sharding rules, XLA) exactly once per (workload x shape x mesh): it lowers
and compiles the step function against abstract inputs (ShapeDtypeStructs —
the paper's dryrun needs no real data, §5 "metastate only"), serializes the
executable, and builds the signable Recording.  Replay needs none of this
machinery.

``record()`` is the paper's full record phase: it runs the compile through
an in-process degenerate ``repro.record.RecordingSession`` (device proxy and
cloud dryrun co-located, all three optimization passes on, nothing billed) —
same Recording output as ``compile_artifact``, plus the session fields
(``record_virtual_s`` and per-pass counters, zero for local records).  The
distributed record phase — device and cloud on opposite ends of an emulated
link — lives in ``repro.record`` and produces the same artifact with real
wire accounting.
"""
from __future__ import annotations

import pickle
import time
from typing import Any, Optional, Sequence

import jax
from jax.experimental import serialize_executable as se

from repro.core.attest import fingerprint
from repro.core.recording import Recording


def topology_fingerprint() -> str:
    devs = jax.devices()
    return fingerprint(sorted(str(d.device_kind) for d in devs), len(devs))


def mesh_descriptor(mesh) -> dict:
    return {"shape": list(mesh.devices.shape), "axes": list(mesh.axis_names)}


def compile_artifact(name: str, fn, args_abstract: Sequence[Any], *,
                     mesh=None, in_shardings=None, out_shardings=None,
                     donate_argnums=(), config_fingerprint: str = "",
                     static_meta: Optional[dict] = None) -> Recording:
    """Lower + compile + serialize ``fn`` into a signed-ready Recording."""
    t0 = time.time()
    kw = {}
    if in_shardings is not None:
        kw["in_shardings"] = in_shardings
    if out_shardings is not None:
        kw["out_shardings"] = out_shardings
    jitted = jax.jit(fn, donate_argnums=donate_argnums, **kw)
    if mesh is not None:
        with jax.set_mesh(mesh):
            lowered = jitted.lower(*args_abstract)
            compiled = lowered.compile()
    else:
        lowered = jitted.lower(*args_abstract)
        compiled = lowered.compile()
    payload, in_tree, out_tree = se.serialize(compiled)
    trees = pickle.dumps((in_tree, out_tree))

    flat, _ = jax.tree.flatten(args_abstract)
    manifest = {
        "name": name,
        "created_s": time.time(),
        "record_wall_s": time.time() - t0,
        "jax_version": jax.__version__,
        "topology": topology_fingerprint(),
        "mesh": mesh_descriptor(mesh) if mesh is not None else None,
        # the executable's device assignment, in order: replay loads it
        # onto exactly these devices, not onto every visible one
        "devices": [d.id for d in
                    compiled.runtime_executable().local_devices()],
        "config_fingerprint": config_fingerprint,
        "donate": list(donate_argnums),
        "inputs": [{"shape": list(getattr(a, "shape", ())),
                    "dtype": str(getattr(a, "dtype", ""))} for a in flat],
        "cost": {k: float(v)
                 for k, v in (compiled.cost_analysis() or {}).items()
                 if isinstance(v, (int, float))},
        "memory": {
            "arg_bytes": compiled.memory_analysis().argument_size_in_bytes,
            "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
            "out_bytes": compiled.memory_analysis().output_size_in_bytes,
        },
        "static": static_meta or {},
    }
    manifest["exec_fingerprint"] = fingerprint(payload)
    return Recording(manifest=manifest, payload=payload, trees=trees)


def record(name: str, fn, args_abstract: Sequence[Any], *,
           mesh=None, in_shardings=None, out_shardings=None,
           donate_argnums=(), config_fingerprint: str = "",
           static_meta: Optional[dict] = None, session=None) -> Recording:
    """Record ``fn`` through a ``RecordingSession`` (the CODY two-party
    record phase).  Without ``session`` this is the in-process degenerate
    session — LOCAL co-located device+cloud, all passes on, nothing billed
    — whose Recording is the same artifact ``compile_artifact`` builds.
    Pass a session built over a real ``NetProfile`` (see
    ``repro.record.RecordingSession.for_profile``) to bill the distributed
    record protocol into its emulator and into the manifest."""
    # lazy import: repro.record composes over this module's compile path
    from repro.record import RecordingSession
    sess = session if session is not None else RecordingSession.local()
    return sess.record(name, fn, args_abstract, mesh=mesh,
                       in_shardings=in_shardings,
                       out_shardings=out_shardings,
                       donate_argnums=donate_argnums,
                       config_fingerprint=config_fingerprint,
                       static_meta=static_meta)
