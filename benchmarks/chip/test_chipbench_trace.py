"""The reduction from a device trace to busy time, idle gaps and kernel
time, on a small trace kept as a fixture (worked by hand below)."""

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import devtrace  # noqa: E402


@pytest.fixture(scope="module")
def trace():
    data = json.loads((HERE / "fixtures" / "trace_small.json").read_text())
    lo, hi = data["window_ns"]
    return devtrace.DeviceTrace.from_data(data, lo, hi)


def test_union_and_gaps():
    assert devtrace.union_ns([(0, 10), (5, 20), (30, 40)], 0, 100) == 30
    assert devtrace.union_ns([(0, 10), (5, 20), (30, 40)], 8, 35) == 17
    assert devtrace.gaps_ns([(0, 10), (5, 20), (30, 40)], 0, 50) == [(20, 30), (40, 50)]
    assert devtrace.gaps_ns([], 3, 5) == [(3, 5)]


def test_busy_and_idle(trace):
    # ops clipped to [1000, 11000]: [1000,1500] [1500,2000] [2500,5500]
    # (with [4000,5500] inside) [8000,10000] [10500,11000] -> 6,500 ns busy
    assert trace.window_s == pytest.approx(10e-6)
    assert trace.busy_s() == pytest.approx(6.5e-6)
    assert trace.idle_share() == pytest.approx(0.35)


def test_kernel_time_by_pattern(trace):
    calls, seconds = trace.kernel(r"lexical_scan")
    assert calls == 2 and seconds == pytest.approx(5e-6)
    assert trace.kernel(r"tpu_custom_call") == trace.kernel(r"lexical_scan")
    assert trace.kernel(r"no_such_kernel") == (0, 0.0)


def test_top_ops_and_idle_by_host(trace):
    top = dict(trace.top_ops(3))
    assert top["lexical_scan_topk"] == pytest.approx(5e-6)  # both shapes, one name
    assert top["fusion.7"] == pytest.approx(1.5e-6)
    # gaps: [2000,2500] mid 2250 in poll; [5500,8000] mid 6750 in wait;
    # [10000,10500] mid 10250 in the second poll
    idle = dict(trace.idle_by_host())
    assert idle == pytest.approx({"chipbench.poll": 1.0e-6, "chipbench.wait": 2.5e-6})


def test_op_names_drop_the_instruction_text():
    assert devtrace.op_name("%_score_topk_jit.1 = (f32[8,1024]) custom-call(f32[8,768] %q)") == (
        "_score_topk_jit.1"
    )
    assert devtrace.op_name("fusion.7") == "fusion.7"


def test_no_device_ops_reads_nothing():
    t = devtrace.DeviceTrace.from_data({"planes": []}, 0, 10)
    assert t.busy_s() is None and t.idle_share() is None and t.kernel("x") == (0, 0.0)


def test_recorded_chip_slice_against_brute_force():
    """A slice of a real traced serve window (TPU v5e): the busy union
    matches a 1-us bitmap of the op intervals, kernel time the plain sum of
    the kernel's events, and every idle gap is named by a host span."""
    import numpy as np

    data = json.loads((HERE / "fixtures" / "trace_lex_serve_v5e.json").read_text())
    lo, hi = data["window_ns"]
    t = devtrace.DeviceTrace.from_data(data, lo, hi)
    (ops,) = t.ops.values()
    bitmap = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for _, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            bitmap[int((a - lo) // 1000): int(np.ceil((b - lo) / 1000))] = True
    assert t.busy_s() == pytest.approx(bitmap.sum() * 1e-6, abs=2e-6 * len(ops))
    kern = [d for n, s, d in ops if "lexical_scan" in n and s >= lo and s + d <= hi]
    assert len(kern) >= 3
    assert t.kernel(r"lexical_scan") == (len(kern), pytest.approx(sum(kern) * 1e-9))
    idle = t.idle_by_host()
    assert sum(v for _, v in idle) == pytest.approx(t.window_s - t.busy_s())
    assert {n for n, _ in idle} <= {"chipbench.wait", "chipbench.poll", "chipbench.submit",
                                    "host idle"}
