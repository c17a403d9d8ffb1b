"""Due-time latency and exact quantiles: the serve window times each request
from when it was due, not from when it was submitted, and the readers take
exact quantiles of those times."""

import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import spec  # noqa: E402

LAYOUT = spec.Layout()
SERVE = LAYOUT.module("drivers", "serve")


class FakeClock:
    """``monotonic`` and ``sleep`` of a scripted timeline."""

    def __init__(self):
        self.t = 100.0

    def monotonic(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


class FakeService:
    """Dispatches everything pending in one block per poll; a block takes
    ``block_s`` on the clock."""

    def __init__(self, clock, block_s):
        self.clock, self.block_s = clock, block_s
        self.queue, self.metrics, self.next_rid = [], [], 0

    def try_submit(self, query, kind):
        rid, self.next_rid = self.next_rid, self.next_rid + 1
        self.queue.append(rid)
        return SimpleNamespace(rid=rid)

    def pending(self):
        return len(self.queue)

    def next_deadline(self):
        return None

    def poll(self, limit=None):
        if not self.queue:
            return {}
        rids, self.queue = self.queue, []
        self.clock.t += self.block_s
        self.metrics.append(SimpleNamespace(latency_s=self.block_s, n_real=len(rids), n_padded=8))
        return {r: SimpleNamespace(ids=np.zeros(1), scores=np.zeros(1)) for r in rids}


def scripted_run(monkeypatch, due, block_s):
    clock = FakeClock()
    monkeypatch.setattr(SERVE, "time", clock)
    st = SERVE.State(
        service=FakeService(clock, block_s), kind="lexical", tokens=None, lengths=None,
        vectors=None, due=np.asarray(due, float), queries=np.zeros((len(due), 8)), terms=None,
    )
    run = SimpleNamespace(
        seconds=1.0, trace=False, profiler=SimpleNamespace(stop=lambda: None),
        window_start=None,
    )
    return run, SERVE.serve_window(run, st)


def test_latency_counts_from_due_time(monkeypatch):
    # r0 due 0.00: submitted 0.00, block 0.00-0.05
    # r1 due 0.01: submitted late at 0.05 (the server was busy), block -> 0.10
    # r2 due 0.20: the loop sleeps to 0.20, block -> 0.25
    run, rec = scripted_run(monkeypatch, [0.0, 0.01, 0.20], block_s=0.05)
    assert rec["submit"] == pytest.approx([0.0, 0.05, 0.20])
    assert rec["reply"] == pytest.approx([0.05, 0.10, 0.25])
    lat = (rec["reply"] - rec["due"]) * 1e3
    assert lat == pytest.approx([50.0, 90.0, 50.0])  # r1 counts its 40 ms wait
    assert list(rec["block_of"]) == [0, 1, 2]


def _reader_run(due, reply, blocks, block_of):
    return SimpleNamespace(
        records={
            "due": np.asarray(due, float), "reply": np.asarray(reply, float),
            "blocks": np.asarray(blocks, float), "block_of": np.asarray(block_of),
        },
        window_start=0.0,
    )


def test_exact_quantiles_and_layer_readers():
    due = np.arange(20) * 0.01
    reply = due + np.arange(1, 21) * 1e-3  # latencies 1..20 ms
    blocks = [(reply[i], 0.5e-3, 1, 8) for i in range(20)]
    run = _reader_run(due, reply, blocks, np.arange(20))
    get = {n: LAYOUT.module("metrics", n).read for n in (
        "p95_ms", "p50_ms", "completed_qps", "queue_wait_ms", "dispatch_ms")}
    assert get["p50_ms"](run) == pytest.approx(10.5)  # between the 10th and 11th
    assert get["p95_ms"](run) == pytest.approx(19.05)  # linear between 19 and 20 ms
    assert get["completed_qps"](run) == pytest.approx(20 / reply[-1])
    assert get["dispatch_ms"](run) == pytest.approx(0.5)
    assert get["queue_wait_ms"](run) == pytest.approx(10.5 - 0.5)


def test_unanswered_request_lies_in_every_tail():
    run = _reader_run([0.0, 0.1], [0.01, np.nan], [(0.01, 0.01, 1, 8)], [0, -1])
    assert LAYOUT.module("metrics", "p95_ms").read(run) > 1e8
    assert LAYOUT.module("metrics", "completed_qps").read(run) == pytest.approx(1 / 0.01)


@pytest.mark.parametrize("kind", ["poisson", "burst"])
def test_arrivals_from_a_traffic_file(kind):
    from chipbench import data, schedules

    traffic = {"arrivals": kind, "rate_qps": 200.0, "burst_factor": 4, "duty": 0.25,
               "period_s": 1.0}
    a = schedules.arrivals(traffic, 3.0, data.rng_of(2**40 + 3))
    b = schedules.arrivals(traffic, 3.0, data.rng_of(2**40 + 3))
    assert np.array_equal(a, b)  # the same seed, the same schedule
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 3.0
    if kind == "poisson":
        assert len(a) == 600  # every seed offers rate x seconds
    else:
        # a quarter of each second at 4x the rate, the rest at the floor
        on = (a % 1.0) < 0.25
        assert on.sum() > 2 * (~on).sum()
