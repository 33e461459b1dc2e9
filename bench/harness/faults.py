"""Faults planted in the served path, each one a one-chip serving cell
can have.  ``bench/tests/test_bench_faults.py`` runs a cell with each and
sees ``correct`` come out false; ``bench/calibrate.py --fault`` reads one
on the chip at a cell's own size.

Each fault is installed with a ``setattr(obj, name, value)`` (pytest's
``monkeypatch.setattr`` in tests, which undoes it) before the cell boots:
the head fault has to be in the decode executable when it is recorded.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.channel import ReplayChannel
from repro.models import model as M


def _on_decode_block(wrap):
    def install(setattr_):
        setattr_(ReplayChannel, "decode_block",
                 wrap(ReplayChannel.decode_block))
    install.__doc__ = wrap.__doc__
    return install


@_on_decode_block
def state_unchanged(orig):
    """The decode block returns its input caches: the step's state never
    moves."""
    def decode_block(self, params, tokens, pos, caches):
        keep = jax.tree.map(jnp.copy, caches)
        out, _new = orig(self, params, tokens, pos, caches)
        return out, keep
    return decode_block


@_on_decode_block
def half_batch(orig):
    """Half of the slots (the first, which admission fills first) are left
    out: they repeat their input token."""
    def decode_block(self, params, tokens, pos, caches):
        out, new = orig(self, params, tokens, pos, caches)
        h = tokens.shape[0] // 2
        toks = out["tokens"].at[:h].set(tokens[:h, None])
        return dict(out, tokens=toks), new
    return decode_block


@_on_decode_block
def token_altered(orig):
    """Each block's first token of every slot is altered where made."""
    def decode_block(self, params, tokens, pos, caches):
        out, new = orig(self, params, tokens, pos, caches)
        first = out["tokens"][:, 0]
        toks = out["tokens"].at[:, 0].set(
            jnp.where(first > 3, first - 1, first + 1))
        return dict(out, tokens=toks), new
    return decode_block


@_on_decode_block
def slot_swap(orig):
    """The first two slots' tokens are handed to each other."""
    def decode_block(self, params, tokens, pos, caches):
        out, new = orig(self, params, tokens, pos, caches)
        swap = jnp.array([1, 0])
        toks = out["tokens"].at[swap[::-1]].set(out["tokens"][swap])
        return dict(out, tokens=toks), new
    return decode_block


def wrong_head(setattr_):
    """The recorded decode's head is off by one row: every decoded token
    is the one after the best, and it is fed back, so the keys and values
    agree with the tokens served."""
    orig = M.decode_step

    def decode_step(*args, **kw):
        logits, caches = orig(*args, **kw)
        return jnp.roll(logits, 1, axis=-1), caches
    setattr_(M, "decode_step", decode_step)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "token_altered": token_altered, "slot_swap": slot_swap,
          "wrong_head": wrong_head}
