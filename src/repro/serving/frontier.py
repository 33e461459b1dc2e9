"""CommitFrontier — the ONE host<->device synchronization point (§4.2+§5).

Every decode readback in the serving stack funnels through this object:
a frontier drain materializes the metastate (tokens / done mask / pos)
of every in-flight block of ONE stream in program order — one stall no
matter how many blocks it validates — and a synchronous fallback commit
is a one-block drain.  The frontier counts these stalls (``host_syncs``),
which keeps the pipeline's "only decode transfer is the frontier"
invariant checkable.  The one other readback is admission's: both
prefill paths of ``StreamExecutor`` read the prefill's ``next_tokens``
back to seed the slot, once per prefill dispatch: that readback is
counted as ``prefill_dispatches``, and ``host_syncs`` leaves it out.

A drain's spans: ``frontier.drain`` holds one ``frontier.wait`` (the
device wait and transfer of ``materialize``) and one ``frontier.apply``
(speculation record, ``apply_block``, ``retire``) per block, then the
``frontier.commit`` loop, which stamps ``Request.first_t`` on requests
whose first token it commits.

Rollback is BY NOT APPLYING: a mispredicted block (a sequence finished
mid-pipeline) is applied with EOS honored and the speculative tail behind
it is dropped — pure metastate, no device work is redone (KV rows beyond
the committed position are inert, repro.serving.cache invariant).
"""
from __future__ import annotations

import collections
import time

import numpy as np

from repro.obs.trace import NULL, traced

ALL_RUNNING = ("all_running",)
SOME_DONE = ("some_done",)


class CommitFrontier:
    """Validates in-flight blocks; owns all host-sync accounting."""

    def __init__(self):
        self.stats = collections.Counter()
        self.tracer = NULL      # set by the Scheduler when tracing is on

    # ---------------------------------------------------------- readback --
    @staticmethod
    def materialize(out):
        """Host←device transfer of one block's metastate.  Callers never
        count this directly — ``drain``/``read_now`` account the stall."""
        return (np.asarray(out["tokens"]), np.asarray(out["done"]),
                np.asarray(out["pos"]))

    def read_now(self, stream, out):
        """Synchronous-commit readback: ONE stall for one block (the
        non-speculative fallback path)."""
        stream.stats["host_syncs"] += 1
        self.stats["host_syncs"] += 1
        return self.materialize(out)

    # ------------------------------------------------------------- drain --
    def drain(self, stream) -> bool:
        """Validate every in-flight block of ``stream`` in order with ONE
        metastate readback, then commit the generated tails.  Returns
        False when a mispredict dropped the tail of the pipeline."""
        if not stream.inflight:
            self._commit(stream)
            return True
        pipeline, stream.inflight = stream.inflight, []
        stream.stats["host_syncs"] += 1    # one stall for the drain
        self.stats["host_syncs"] += 1
        self.stats["drains"] += 1
        with traced(self.tracer, "frontier.drain", f"serve.{stream.name}",
                    blocks=len(pipeline)):
            if stream.netem is not None:
                # the paper's metastate-only sync: done masks + token tails
                n, k = stream.slots.n_slots, stream.block_k
                stream.netem.round_trip(
                    send_bytes=64, recv_bytes=len(pipeline) * n * (4 * k + 5))
            ok = self._validate(stream, pipeline)
            self._commit(stream)
        return ok

    def _validate(self, stream, pipeline) -> bool:
        track = f"serve.{stream.name}"
        for b_idx, blk in enumerate(pipeline):
            with traced(self.tracer, "frontier.wait", track, block=b_idx):
                actual = self.materialize(blk["out"])
            with traced(self.tracer, "frontier.apply", track, block=b_idx):
                outcome = SOME_DONE if actual[1].any() else ALL_RUNNING
                stream.spec.record(blk["ops"], outcome, stream=stream.name)
                if blk["pred"] != outcome:
                    dropped = len(pipeline) - b_idx - 1
                    stream.stats["mispredicts"] += 1
                    self.stats["mispredicts"] += 1
                    if self.tracer:
                        self.tracer.instant("frontier.mispredict", track,
                                            dropped=dropped)
                    stream.apply_block(actual, speculative=False)
                    stream.retire(actual)
                    stream.reset_device_chain()  # chain built on a lie
                    stream.stats["dropped_blocks"] += dropped
                    return False
                stream.apply_block(actual,
                                   speculative=outcome == ALL_RUNNING)
                stream.retire(actual)
                stream.stats["validated_blocks"] += 1
                self.stats["validated_blocks"] += 1
        return True

    def _commit(self, stream):
        """Frontier clean: commit every request's generated tail."""
        with traced(self.tracer, "frontier.commit", f"serve.{stream.name}",
                    requests=len(stream.requests)):
            now = time.perf_counter()
            for req in stream.requests.values():
                if req.generated and not req.committed:
                    req.first_t = now
                req.committed = len(req.generated)
            stream.slots.committed_pos[:] = stream.slots.pos

    def drain_all(self, streams) -> bool:
        ok = True
        for s in streams:
            ok = self.drain(s) and ok
        return ok
