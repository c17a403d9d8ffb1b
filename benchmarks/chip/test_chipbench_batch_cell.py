"""The batch cell end to end on the CPU at a tiny size (the harness's look
for a chip skipped): sound, it is correct; with the timed path broken
underneath, or with the control (the reference in bfloat16) in the
program's place, ``correct`` comes out false."""

import sys
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import harness, tiny  # noqa: E402
from repro.core import topk  # noqa: E402
from repro.experiments import runner  # noqa: E402


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    return tiny.layout(tmp_path_factory.mktemp("tiny"))


def run(layout, seed, **kw):
    return harness.run_cell(
        "tiny-batch", seed=seed, seconds=0.3, trace=False, t_process=time.monotonic(),
        layout=layout, require_tpu=False, **kw,
    )


def test_sound_run_is_correct_and_control_is_not(layout):
    result = run(layout, 2**40 + 11, control=True)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"experiment_docs_per_s", "setup_s"}
    assert not all(c["ok"] for c in result["control"].values()), result["control"]


def test_an_answer_altered_where_it_is_written(layout, monkeypatch):
    write = runner.write_run_files

    def altered(out_dir, scorers, state, *, tag_prefix):
        ids = np.array(state.ids)
        ids[:, :, 0] = ids[:, :, -1]  # each query's best answer replaced
        return write(out_dir, scorers, topk.TopKState(state.scores, ids), tag_prefix=tag_prefix)

    monkeypatch.setattr(runner, "write_run_files", altered)
    assert not run(layout, 5)["correct"]


def test_half_of_the_corpus_left_out(layout, monkeypatch):
    job = runner.run_sharded_scan_job

    def half(queries, docs, *args, **kw):
        tokens, lengths = docs
        lengths = np.where(np.arange(len(lengths)) < len(lengths) // 2, lengths, 0)
        return job(queries, (tokens, lengths.astype(np.int32)), *args, **kw)

    monkeypatch.setattr(runner, "run_sharded_scan_job", half)
    assert not run(layout, 6)["correct"]
