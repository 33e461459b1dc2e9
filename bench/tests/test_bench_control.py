"""The correctness check's control at a size a test run holds: on the
same served requests, the reference with float8 weights comes out not
correct by the harness's own comparison and limits, while the served
bfloat16 path comes out correct; the program's int8 weight path reads a
wider error than the served path too (``bench/calibrate.py`` reads the
same on the chip, at the cells' own sizes)."""
import importlib.util
import os

import pytest

from bench.harness import check, serve, spec
from bench.tests import tiny

SEEDS = (2, 4, 6)


@pytest.fixture(scope="module")
def rows():
    s = importlib.util.spec_from_file_location(
        "bench_calibrate", os.path.join(spec.BENCH_DIR, "calibrate.py"))
    cal = importlib.util.module_from_spec(s)
    s.loader.exec_module(cal)
    cell = tiny.cell()
    cell.shape["check"] = {"tokens": 100, "requests": 16}
    adapter, ref = spec.family("qwen2")
    sess = serve.Session(cell, adapter, ref)
    sess.boot()
    out = [cal.readings(sess, cell, seed, 1.5) for seed in SEEDS]
    sess.free()
    return out


def _numbers(row, side):
    return {k: row[f"{side}.{k}"] for k in ("kv_err", "logit_err",
                                             "off_share")}


def test_fp8_control_comes_out_not_correct(rows):
    limits = tiny.SHAPE["limits"]
    for r in rows:
        assert r["requests"] and r["unserved"] == 0
        assert check.correct(check.verdict(None, _numbers(r, "served"),
                                           limits)) is True
        assert check.correct(check.verdict(None, _numbers(r, "control"),
                                           limits)) is False
        assert r["served.correct"] is True and r["control.correct"] is False


def test_int8_control_reads_wider_errors_than_the_served_path(rows):
    for name in ("kv_err", "logit_err"):
        assert min(r[f"int8.{name}"] for r in rows) > \
            max(r[f"served.{name}"] for r in rows)
        assert min(r[f"control.{name}"] for r in rows) > \
            3 * max(r[f"served.{name}"] for r in rows)
