"""``chip_smoke.py`` at tiny widths on the CPU, kernels in interpret mode.

The script itself refuses to run without a TPU; these tests drive its
phases directly so that a change to an entry point it calls breaks here
first, not on the chip. The kernel-presence check needs the TPU compiler's
output and is left to the chip run.
"""

import importlib.util
import pathlib
import sys

import jax
import numpy as np
import pytest

from repro.kernels import ops

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve annotations through it
    spec.loader.exec_module(mod)
    yield mod
    sys.modules.pop("chip_smoke", None)


@pytest.fixture(scope="module")
def widths(smoke):
    return smoke.Widths(
        n_docs=2048, n_queries=16, vocab=2048, doc_len=32, q_len=4, k=50,
        chunk=512, dense_dim=128, dense_queries=16,
    )


def test_scan_50q_widths(smoke):
    w = smoke.Widths.scan_50q()
    assert (w.n_docs, w.n_queries, w.doc_len) == (1_048_576, 64, 128)
    assert (w.vocab, w.q_len, w.k, w.chunk, w.dense_dim) == (65_536, 8, 1000, 16384, 256)


def test_single_chip_phases(smoke, widths, tmp_path, capsys):
    check = smoke.KernelCheck(strict=False)
    coll = smoke.phase_experiment(widths, tmp_path, 0, check)
    smoke.phase_serve(widths, coll, 0, check)
    smoke.phase_dense(widths, 0, check)
    lines = capsys.readouterr().out.splitlines()
    assert [ln.split()[0] for ln in lines] == ["[experiment]", "[serve]", "[dense]"]
    assert "run_files_identical=True" in lines[0]


def test_sharded_phase(smoke, widths, tmp_path, capsys):
    smoke.phase_sharded(widths, tmp_path, 0, smoke.KernelCheck(strict=False))
    out = capsys.readouterr().out
    assert "[sharded-experiment] ok" in out and "[sharded-serve] ok" in out


def test_assert_same_is_bitwise(smoke):
    ids = np.array([[3, 1, -1]])
    ref = np.array([[2.0, 1.0, -np.inf]], np.float32)
    near = np.where(np.isfinite(ref), np.nextafter(ref, np.float32(3.0)), ref)
    smoke.assert_same("same", ids, ref.copy(), ids, ref)
    with pytest.raises(AssertionError):  # one ulp is a difference
        smoke.assert_same("ulp", ids, near, ids, ref)
    with pytest.raises(AssertionError):
        smoke.assert_same("ids", ids[:, ::-1], ref, ids, ref)


def test_refuses_without_compiled_kernels(smoke, monkeypatch, capsys):
    """Off the TPU (or with REPRO_KERNEL_BACKEND=interpret) the script
    stops before any phase and prints no result line."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""
    assert ops.kernel_backend() == "interpret"
    assert jax.default_backend() == "cpu"
