"""A run with the served path broken underneath the harness comes out
not correct, for each fault a one-chip serving cell can have, on the
number that is there to catch it."""
import importlib.util
import os

import pytest

from bench.harness import spec
from bench.harness.faults import FAULTS
from bench.tests import tiny


def _run_cell(cell):
    s = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(spec.BENCH_DIR, "run.py"))
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.run_cell(cell, 31, 1.5, False, require_chip=False,
                        peak=tiny.PEAK)


@pytest.mark.parametrize("fault,fails", [
    ("state_unchanged", "kv_err"), ("half_batch", "kv_err"),
    ("token_altered", "kv_err"), ("slot_swap", "kv_err"),
    ("wrong_head", "off_share")],
    ids=["state_unchanged", "half_batch", "token_altered", "slot_swap",
         "wrong_head"])
def test_broken_decode_is_not_correct(monkeypatch, fault, fails):
    FAULTS[fault](monkeypatch.setattr)
    out = _run_cell(tiny.cell())
    assert out["correct"] is False
    c = out["checks"][fails]
    assert c["value"] > c["limit"]
