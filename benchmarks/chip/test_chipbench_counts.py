"""Kernel count functions and the roofline share, on hand-worked shapes."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import readers, spec  # noqa: E402

LAYOUT = spec.Layout()


def test_lexical_bytes_use_the_least_exact_token_width():
    count = LAYOUT.module("counts", "lexical_scan").per_call
    # vocab 65,536 ids + a pad value need 17 bits: 512 * 17 / 8 + 4 = 1092 B a doc
    assert count({"docs": 65536, "pad": 512, "vocab": 65536})["bytes"] == 65536 * 1092
    # 30,522 ids + pad fit 15 bits: 128 * 15 / 8 + 4 = 244 B a doc
    assert count({"docs": 2211840, "pad": 128, "vocab": 30522})["bytes"] == 2211840 * 244
    # 255 ids + pad fit one byte
    assert count({"docs": 10, "pad": 8, "vocab": 255})["bytes"] == 10 * (8 + 4)
    assert count({"docs": 10, "pad": 8, "vocab": 255})["flops"] is None


def test_dense_bytes_and_flops():
    got = LAYOUT.module("counts", "score_topk").per_call(
        {"queries": 64, "docs": 2211840, "dim": 768}
    )
    assert got["bytes"] == 4 * 768 * (2211840 + 64) == 6794969088
    assert got["flops"] == 2 * 64 * 2211840 * 768 == 217432719360


class _Trace:
    def __init__(self, calls, seconds):
        self._k = (calls, seconds)

    def kernel(self, pattern):
        return self._k


@pytest.mark.parametrize(
    "calls,seconds,want,bound",
    [
        # 2 calls of 6.79 GB at 819 GB/s = 16.593 ms least, over 20 ms
        (2, 0.020, 100 * 2 * 6794969088 / 819e9 / 0.020, "hbm_bytes"),
        (0, 0.0, None, None),
    ],
)
def test_roofline_share_and_bound(calls, seconds, want, bound):
    run = SimpleNamespace(
        device_trace=_Trace(calls, seconds), layout=LAYOUT,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
    )
    got = readers.roofline(run, "x", "score_topk", {"queries": 64, "docs": 2211840, "dim": 768})
    if want is None:
        assert got is None
    else:
        assert got["bound"] == bound
        assert got["value"] == pytest.approx(want, rel=1e-12)


def test_flop_bound_wins_when_it_is_larger():
    run = SimpleNamespace(
        device_trace=_Trace(1, 1.0), layout=LAYOUT,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
    )
    # 4,096 queries over 1,024 docs of 1,024 dims: 8.6 GFLOP vs 21 MB
    got = readers.roofline(run, "x", "score_topk", {"queries": 4096, "docs": 1024, "dim": 1024})
    assert got["bound"] == "bf16_flops"
    assert got["value"] == pytest.approx(100 * 2 * 4096 * 1024 * 1024 / 197e12)


def test_untraced_run_reads_nothing():
    run = SimpleNamespace(device_trace=None)
    assert readers.roofline(run, "x", "score_topk", {}) is None
    assert readers.idle_percent(run) is None
