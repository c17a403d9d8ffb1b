"""The lexical and dense serve cells end to end on the CPU at a tiny size
(the harness's look for a chip skipped): sound, they are correct; with an
answer altered where the service produces it, with half of the resident
corpus left out of the scan, or with the control in the program's place,
``correct`` comes out false."""

import sys
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import harness, tiny  # noqa: E402
from repro.serve import service as service_mod  # noqa: E402
from repro.serve.session import DenseSession, LexicalSession  # noqa: E402

CELLS = ("tiny-lex", "tiny-dense")


@pytest.fixture(autouse=True)
def restore_matmul_precision():
    """The serve configuration sets JAX's process-wide matmul precision; a
    test gives it back to the next test in the same process."""
    import jax

    before = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", before)


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    return tiny.layout(tmp_path_factory.mktemp("tiny"))


def run(layout, cell, seed, **kw):
    return harness.run_cell(
        cell, seed=seed, seconds=0.5, trace=False, t_process=time.monotonic(),
        layout=layout, require_tpu=False, **kw,
    )


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_control_is_not(layout, cell):
    result = run(layout, cell, 2**35 + 1, control=True)
    assert result["correct"], result["checks"]
    assert result["attempted"] == 30 and result["failed"] == 0
    assert set(result["metrics"]) == {"p95_ms", "p50_ms", "completed_qps", "setup_s"}
    assert not all(c["ok"] for c in result["control"].values()), result["control"]


@pytest.mark.parametrize("cell", CELLS)
def test_an_answer_altered_where_it_is_produced(layout, cell, monkeypatch):
    def altered(arr, n_real):
        out = np.array(arr[:n_real])
        out[:, 0] = out[:, -1]  # each request's best answer replaced
        return out

    monkeypatch.setattr(service_mod, "unpad_results", altered)
    assert not run(layout, cell, 7)["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_corpus_left_out(layout, cell, monkeypatch):
    lex_init, dense_init = LexicalSession.__init__, DenseSession.__init__

    def lex_half(self, *a, **kw):
        lex_init(self, *a, **kw)
        tokens, lengths = self._docs
        self._docs = (tokens, lengths.at[lengths.shape[0] // 2:].set(0))

    def dense_half(self, *a, **kw):
        dense_init(self, *a, **kw)
        self._vectors = self._vectors.at[self._vectors.shape[0] // 2:].set(0.0)

    monkeypatch.setattr(LexicalSession, "__init__", lex_half)
    monkeypatch.setattr(DenseSession, "__init__", dense_half)
    assert not run(layout, cell, 8)["correct"]
