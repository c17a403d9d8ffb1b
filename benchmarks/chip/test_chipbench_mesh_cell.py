"""The four-chip cell's parts on the CPU: the mesh driver end to end on four
virtual devices at a tiny size (sound runs are correct, the control is not,
a traced run holds the sessions' spans), and the three readers of a mesh
trace on a four-chip trace made by hand, with the values worked out below."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import devtrace, harness, spec  # noqa: E402

LAYOUT = spec.Layout()
US = 1e-6


def trace_of(name):
    data = json.loads((HERE / "fixtures" / name).read_text())
    lo, hi = data.get("window_ns") or (0.0, float("inf"))
    return devtrace.DeviceTrace.from_data(data, lo, hi)


def make_run(tmp_path, trace, *, config="msmarco-passage-4chip", chips=4):
    cell = spec.Cell(
        name="mesh-test", chips=chips, config=LAYOUT.json("configs", config), traffic={},
        end_to_end=(), per_layer=(),
    )
    return harness.Run(
        cell=cell, layout=LAYOUT, seed=0, seconds=1.0, trace=True, workdir=tmp_path,
        peaks=LAYOUT.peaks("TPU v5 lite"), t_process=0.0,
        profiler=harness.Profiler(True, tmp_path), device_trace=trace,
    )


def read(name, run):
    return LAYOUT.module("metrics", name).read(run)


@pytest.fixture(scope="module")
def mesh_trace():
    return trace_of("trace_mesh_small.json")


def test_reduce_time_is_the_named_stretch_after_each_kernel_call(tmp_path, mesh_trace):
    # block A: kernel ends at 3000/3100/3300/3200 us; the all-gather starts
    # 1 us later and the last merge ends at 3320 on every chip -> 319, 219,
    # 19, 119 us. Block B: ends 10050/10000/10000/10010, last merge ends at
    # 10060 -> 9, 59, 59, 49 us. The marked op before each kernel and block
    # C (past the window) do not count: (676 + 176) / 8 = 106.5 us.
    assert read("mesh_reduce_ms", make_run(tmp_path, mesh_trace)) == pytest.approx(0.1065)


def test_shard_skew_is_the_spread_of_the_chips_kernel_ends(tmp_path, mesh_trace):
    # block A: 3300 - 3000 = 300 us; block B: 10050 - 10000 = 50 us
    assert read("shard_skew_ms", make_run(tmp_path, mesh_trace)) == pytest.approx(0.175)


def test_mesh_roofline_counts_each_chips_share(tmp_path, mesh_trace):
    run = make_run(tmp_path, mesh_trace)
    cfg = run.config
    got = read("lexical_roofline.mesh", run)
    # 8 calls (2 a chip), each over 8,847,360 / 4 docs x (128 x 15 bits + 4 bytes),
    # in 16,644 us of kernel time summed over the chips
    per_call = cfg["n_docs"] // 4 * (cfg["doc_len"][1] * 15 / 8 + 4)
    want = 100.0 * 8 * per_call / run.peaks["hbm_bytes_per_s"] / (16644 * US)
    assert got["bound"] == "hbm_bytes"
    assert got["value"] == pytest.approx(want)
    whole = read("lexical_roofline.serve", run)  # as if each call scanned all n_docs
    assert got["value"] == pytest.approx(whole["value"] / 4)


def test_mesh_readers_read_nothing_on_a_one_chip_program(tmp_path):
    run = make_run(tmp_path, trace_of("trace_lex_serve_v5e.json"), config="msmarco-passage",
                   chips=1)
    assert read("mesh_reduce_ms", run) is None  # no op carries the reduce's name
    assert read("shard_skew_ms", run) is None  # one chip: no spread
    run.device_trace = None
    for name in ("mesh_reduce_ms", "shard_skew_ms", "lexical_roofline.mesh"):
        assert read(name, run) is None


_CELL_SCRIPT = r"""
import os, sys, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
sys.path.insert(0, sys.argv[2])
from pathlib import Path
from chipbench import harness, spec, tiny

root = Path(sys.argv[3])
real = spec.Layout()
bench = real.benchmark()
cfg = real.json("configs", "msmarco-passage-4chip")
cfg.update(tiny.SMALL, name="tiny-marco-4chip", dim=128,
           compare={"candidates": 64, "sample_requests": 16})
tiny._write(root / "configs" / "tiny-marco-4chip.json", cfg)
for kind in ("lexical", "dense"):
    traffic = real.json("traffic", "lex-poisson-4chip")
    traffic.update(kind=kind, rate_qps=60.0)
    tiny._write(root / "traffic" / f"tiny-{kind}-4chip.json", traffic)
cells = {"marco-lex-poisson-4chip": "tiny-lexical-4chip", "marco-dense-poisson": "tiny-dense-4chip"}
bench["configs"] = [{"name": "tiny-marco-4chip", "source": "tiny", "reduced": [], "why": "tests",
                     "file": "configs/tiny-marco-4chip.json"}]
bench["workloads"] = [
    {"name": f"tiny-{kind}-4chip", "config": "tiny-marco-4chip", "traffic": f"tiny-{kind}-4chip",
     "chips": 4, "why": "tests"} for kind in ("lexical", "dense")
]
for group in ("end_to_end", "per_layer"):
    for m in bench[group]:
        if "workloads" in m:
            m["workloads"] = [cells[w] for w in m["workloads"] if w in cells]
tiny._write(root / "BENCHMARK.json", bench)
layout = spec.Layout(bench_file=root / "BENCHMARK.json", roots=(root, spec.BENCH_ROOT))

out = {}
for kind in ("lexical", "dense"):
    res = harness.run_cell(
        f"tiny-{kind}-4chip", seed=2**35 + 3, seconds=0.5, trace=False,
        t_process=time.monotonic(), layout=layout, require_tpu=False, control=True,
    )
    out[kind] = {k: res[k] for k in ("correct", "attempted", "failed", "checks", "control")}
    out[kind]["metrics"] = sorted(res["metrics"])
    out[kind]["count"] = res["device"]["count"]

# a traced run through serve_mesh, its spans read from the run object
driver = layout.module("drivers", "serve_mesh")
cell = spec.load_cell(layout, "tiny-lexical-4chip")
import tempfile
with tempfile.TemporaryDirectory() as tmp:
    run = harness.Run(cell=cell, layout=layout, seed=11, seconds=0.5, trace=True,
                      workdir=Path(tmp), peaks={}, t_process=time.monotonic(),
                      profiler=harness.Profiler(False, Path(tmp)))
    st = driver.setup(run)
    driver.window(run, st)
    spans = [(s.name, dict(s.attrs)) for s in run.spans if s.name.startswith("session.")]
    checks = driver.finish(run, st)
out["traced"] = {"spans": spans, "ok": all(c.ok for c in checks), "window_start": run.window_start,
                 "first_search": min(s.ts for s in run.spans if s.name == "session.mesh_search")}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def cell_runs(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny4")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _CELL_SCRIPT, str(HERE), str(HERE.parents[1] / "src"), str(root)],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("kind", ["lexical", "dense"])
def test_tiny_four_device_cell_is_correct_and_its_control_is_not(cell_runs, kind):
    res = cell_runs[kind]
    assert res["count"] == 4
    assert res["correct"], res["checks"]
    assert res["attempted"] == 30 and res["failed"] == 0
    assert res["metrics"] == sorted(["p95_ms", "p50_ms", "completed_qps", "setup_s"])
    assert not all(c["ok"] for c in res["control"].values()), res["control"]


def test_traced_run_holds_the_sessions_spans(cell_runs):
    traced = cell_runs["traced"]
    assert traced["ok"]
    by_name = {}
    for name, attrs in traced["spans"]:
        by_name.setdefault(name, []).append(attrs)
    places = {a["kind"]: a for a in by_name["session.place"]}
    # 2048 docs x (32 int32 tokens + an int32 length) over 4 chips; 2048 x 128 float32
    assert places["lexical"] == {"kind": "lexical", "shards": 4, "bytes_per_chip": 512 * 33 * 4}
    assert places["dense"] == {"kind": "dense", "shards": 4, "bytes_per_chip": 512 * 128 * 4}
    assert by_name["session.stats"] == [{"shards": 4}]
    searches = by_name["session.mesh_search"]
    assert {a["rows"] for a in searches} >= {8, 16, 32, 64}  # the warm-up's buckets
    for a in searches:  # a 2x2 mesh: two stages of 2 x rows x k (20) x 8 bytes
        assert a == {"kind": "lexical", "shards": 4, "rows": a["rows"],
                     "gather_bytes": 4 * a["rows"] * 20 * 8}
    assert traced["first_search"] < traced["window_start"]  # set-up spans kept
