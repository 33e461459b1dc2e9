"""Whether the served path computed right: the plain reference over a
sample of the served requests, at the cell's own shapes.

The sample, drawn from the run's seed once serving has ended, comes from
the requests whose keys and values the slot caches still hold: each
slot's last request, where it was in the slot at the last decode block
(the block after a release decodes the freed slot from position 0).  It
holds the one with the most served tokens, then
others in a seeded order, until it holds ``check.tokens`` served tokens or
``check.requests`` requests.  For each, the reference runs once over the
prompt and the served tokens (teacher forcing), and these are read:

* ``kv_err``: the keys and values the served path left in the slot --
  written by the replayed prefill and the executor's scatter for the
  prompt, appended by the replayed fused decode for each served token --
  against the reference's, as a norm of the difference over the norm of
  the reference, over all layers and positions; the worst request;
* ``logit_err``: the replayed prefill's ``last_logits`` against the
  reference's logits at the last prompt position, the same way;
* ``off_share``: the share of served tokens whose logit lies more than
  ``GAP_TOL`` below the reference's best.  It sees the token choice of the
  fused decode's head, which ``kv_err`` cannot: a wrong token that is fed
  back is what the reference is teacher-forced on;
* beside them, not compared, the widest such gap: at near-ties it swings
  with the sample more than with the precision (PERF.md).

The limits lie between the largest readings of sound runs and the
smallest of the control, the reference with its weights in float8
(``bench/calibrate.py``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.traffic import rng_for

# A served token counts as off when its logit lies further below the
# reference's best than this: over twice the widest gap sound runs read
# (0.0477, at near-ties), and about the bfloat16 rounding step of logits of
# magnitude 8-16 at the top of the vocabulary.
GAP_TOL = 0.1


@dataclasses.dataclass
class Sample:
    rid: int
    tokens: np.ndarray       # prompt + served[:-1], padded to the block
    prompt_len: int
    n: int                   # positions the slot holds K/V for
    pos: np.ndarray          # position predicting each served token
    served: np.ndarray       # the served tokens
    kv: tuple                # served (keys, values) [L, n, kv x head]
    logits: Optional[np.ndarray]   # served prefill last_logits [V]


def draw(served, last_ids, seed: int, tokens: int,
         requests: int) -> List[tuple]:
    """(request, slot) pairs of the sample; ``last_ids`` holds each slot's
    request at the last decode block."""
    reqs = served.requests
    cand = sorted((rid, slot) for slot, rid in
                  served.deliveries.occupant.items()
                  if last_ids is not None and last_ids[slot] == rid
                  and reqs[rid].generated and not reqs[rid].failed)
    if not cand:
        return []
    longest = max(cand, key=lambda c: (len(reqs[c[0]].generated), -c[0]))
    rest = [c for c in cand if c != longest]
    out, n = [longest], len(reqs[longest[0]].generated)
    for j in rng_for(seed, 4).permutation(len(rest)):
        if n >= tokens or len(out) >= requests:
            break
        out.append(rest[j])
        n += len(reqs[rest[j][0]].generated)
    return out


def collect(sess, served, picks, block: int) -> List[Sample]:
    """Copy what the served path produced for the sample to the host,
    before the program's state is freed."""
    logits = {tuple(np.asarray(t)[0].tolist()): lg
              for t, lg in sess.channel.kept}
    out = []
    for rid, slot in picks:
        req = served.requests[rid]
        p, m = len(req.prompt), len(req.generated)
        toks = list(req.prompt) + list(req.generated[:-1])
        lg = logits.get(tuple(req.prompt))
        out.append(Sample(
            rid=rid,
            tokens=np.asarray(toks + [0] * ((-len(toks)) % block), np.int32),
            prompt_len=p, n=p + m - 1, pos=np.arange(p - 1, p - 1 + m),
            served=np.asarray(req.generated, np.int32),
            kv=sess.adapter.served_kv(sess.eng.caches, slot, p + m - 1),
            logits=None if lg is None else np.asarray(lg[0], np.float32)))
    return out


def rel_err(x, ref) -> float:
    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    return float(np.sqrt(((x - ref) ** 2).sum() / (ref ** 2).sum()))


@functools.partial(jax.jit)
def _gaps(logits, pos, toks):
    rows = logits[pos]
    return jnp.max(rows, -1) - jnp.take_along_axis(rows, toks[:, None],
                                                   1)[:, 0]


def _read(ref_out, s: Sample, kv, logits, tokens) -> dict:
    lg, k, v = ref_out
    kv_ref = (np.asarray(k[:, :s.n]), np.asarray(v[:, :s.n]))
    gaps = np.asarray(_gaps(lg, s.pos, np.asarray(tokens, np.int32)))
    return {"kv_err": rel_err(np.concatenate([kv[0], kv[1]]),
                              np.concatenate(kv_ref)),
            "logit_err": None if logits is None else
            rel_err(logits, np.asarray(lg[s.prompt_len - 1])),
            "gap": float(gaps.max()) if gaps.size else 0.0,
            "off": int((gaps > 0).sum()), "over": int((gaps > GAP_TOL).sum()),
            "tokens": int(gaps.size)}


def _worst(rows: List[dict]) -> dict:
    pick = lambda k: max((r[k] for r in rows if r[k] is not None),
                         default=None)
    tokens = sum(r["tokens"] for r in rows)
    return {"kv_err": pick("kv_err"), "logit_err": pick("logit_err"),
            "off_share": sum(r["over"] for r in rows) / tokens
            if tokens else None,
            "widest_gap": pick("gap"), "off": sum(r["off"] for r in rows),
            "tokens": tokens}


def _fp8(ref, config: dict, w, s: Sample) -> tuple:
    """The control's (choices, prefill logits, (keys, values)) over the
    sample's tokens: the reference with float8 weights."""
    lg, k, v = ref.forward(config, w, s.tokens, fp8=True)
    return (np.asarray(jnp.argmax(lg, -1)),
            np.asarray(lg[s.prompt_len - 1]),
            (np.asarray(k[:, :s.n]), np.asarray(v[:, :s.n])))


def compare(ref, config: dict, seed: int, samples: List[Sample],
            fp8: bool = False, others: Optional[dict] = None) -> dict:
    """The numbers of the served path against the reference made from
    ``seed``; with ``fp8``, those of the control too, and of each of
    ``others`` (name -> per sample (choices, prefill logits, (keys,
    values)) of another lower-precision path)."""
    w = ref.weights(config, seed)
    sides = {"served": [], **({"control": []} if fp8 else {}),
             **{name: [] for name in (others or {})}}
    for i, s in enumerate(samples):
        out = ref.forward(config, w, s.tokens)
        sides["served"].append(_read(out, s, s.kv, s.logits, s.served))
        got = {name: per[i] for name, per in (others or {}).items()}
        if fp8:
            got["control"] = _fp8(ref, config, w, s)
        for name, (choice, logits, kv) in got.items():
            sides[name].append(_read(out, s, kv, logits, choice[s.pos]))
        del out
    del w
    return {name: _worst(rs) for name, rs in sides.items()}


def verdict(served, numbers: dict, limits: dict) -> dict:
    """Each number compared, beside its limit; ``served`` is the window's
    record, or None for a control, which serves nothing."""
    out = {name: {"value": numbers[name], "limit": limits[name]}
           for name in ("kv_err", "logit_err", "off_share")}
    if served is not None:
        out["unserved"] = {"value": len(served.unserved), "limit": 0}
    return out


def correct(checks: dict) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
