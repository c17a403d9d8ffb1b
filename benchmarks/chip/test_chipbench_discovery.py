"""A new configuration, traffic mix, cell and metric are new files and new
entries only: the harness finds them by name, with no edit anywhere else."""

import json
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import harness, spec, tiny  # noqa: E402


@pytest.fixture(autouse=True)
def restore_matmul_precision():
    """The serve configuration sets JAX's process-wide matmul precision; a
    test gives it back to the next test in the same process."""
    import jax

    before = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", before)


def test_new_files_are_found_without_an_edit(tmp_path):
    layout = tiny.layout(tmp_path)
    cfg = layout.json("configs", "tiny-marco")
    cfg["name"] = "tiny-dummy"
    (tmp_path / "configs" / "tiny-dummy.json").write_text(json.dumps(cfg))
    traffic = layout.json("traffic", "tiny-lex-poisson")
    traffic["rate_qps"] = 40.0
    (tmp_path / "traffic" / "dummy-mix.json").write_text(json.dumps(traffic))
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "dummy_offered.py").write_text(
        "def read(run):\n    return len(run.records['due'])\n"
    )
    bench = json.loads(layout.bench_file.read_text())
    bench["configs"].append(
        {"name": "tiny-dummy", "source": "tiny", "file": "x", "reduced": [], "why": "tests"}
    )
    bench["workloads"].append(
        {"name": "dummy-cell", "config": "tiny-dummy", "traffic": "dummy-mix", "chips": 1,
         "why": "tests"}
    )
    bench["end_to_end"].append(
        {"name": "dummy_offered", "unit": "req", "better": "higher", "bound": 0.01,
         "source": "host_clock", "workloads": ["dummy-cell"]}
    )
    layout.bench_file.write_text(json.dumps(bench))

    cell = spec.load_cell(layout, "dummy-cell")
    assert cell.config["name"] == "tiny-dummy" and cell.traffic["rate_qps"] == 40.0
    assert "dummy_offered" in [m["name"] for m in cell.end_to_end]
    result = harness.run_cell(
        "dummy-cell", seed=3, seconds=0.5, trace=False, t_process=time.monotonic(),
        layout=layout, require_tpu=False,
    )
    assert result["correct"], result["checks"]
    assert result["metrics"]["dummy_offered"]["value"] == result["attempted"] == 20


def test_unknown_names_are_errors(tmp_path):
    layout = tiny.layout(tmp_path)
    with pytest.raises(KeyError):
        spec.load_cell(layout, "no-such-cell")
    with pytest.raises(KeyError):
        layout.peaks("TPU v9 imaginary")
    with pytest.raises(FileNotFoundError):
        layout.module("metrics", "no_such_metric")
    with pytest.raises(ValueError):
        layout.find("metrics", "../escape", ".py")
    assert layout.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
