"""The harness end to end on the CPU at a small size: a run without a TPU
refuses to measure; the output check passes the program and fails the
control and every fault the training cells can have (bench/faults.py).
The harness's look for a chip is skipped here (``require_tpu=False``)."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import faults, run


def tiny(name):
    """The cell at a size a test run holds: two stages of 16/32 channels,
    16x16 images, a 64-wide head, 8 clients a round."""
    spec = run.load_cell(name)
    spec["model"].update(resnet_stages=[1, 1], resnet_channels=[16, 32],
                         resnet_groups=8, image_size=16, proj_dims=[64, 64])
    spec["traffic"]["dataset"].update(num_samples=600, num_classes=5)
    spec["traffic"]["clients_per_round"] = 8
    spec["cell"]["chunk_rounds"] = 2
    if spec["cell"]["cohort_chunk"]:
        spec["cell"]["cohort_chunk"] = 4
    return spec


def test_no_tpu_exits_before_measuring(capsys):
    rc = run.main(["--workload", "r14-k256-n2", "--seed", str(2**31 + 7),
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "TPU" in out.err and "{" not in out.out


def test_bare_directory_exits_nonzero(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ has no program."""
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(run.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "r14-k256-n2",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not proc.stdout.strip().endswith("}")


def _run(name, variant, seed=2**31 + 11, window=False):
    spec = tiny(name)
    return run.run_cell(spec, seed, 0.5, False, require_tpu=False,
                        window=window, log=lambda *a: None,
                        **faults.variant_kwargs(variant, spec))


def test_program_window_and_result_line():
    res = _run("r14-k256-n2", "program", window=True)
    line = run.output(res)
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) >= {"samples_per_s", "setup_s"}
    assert res["attempted"] >= 2 and res["failed"] == 0
    json.dumps(line)
    for v in res["checks"].values():
        assert 0 <= v["value"] < 0.5


@pytest.mark.parametrize("variant", ["half", "unchanged", "delta_half"])
def test_fault_is_not_correct(variant):
    assert _run("r14-k256-n2", variant)["correct"] is False


def test_kept_elements_rule():
    """Elements whose reference gradient is under a thousandth of the
    median leaf's root mean square element are left out; a leaf of them
    goes whole."""
    grad = {"a": np.ones(4), "b": np.full(4, 2.0), "c": np.ones(4),
            "d": np.array([1.0, 1e-4, 0.0, 3.0]), "e": np.full(3, 1e-5)}
    keep = run.kept_elements(grad)
    assert [k.tolist() for k in keep] == [
        [True] * 4, [True] * 4, [True] * 4, [True, False, False, True],
        [False] * 3]
    norms = run.kept_norms({"a": np.ones(4), "b": np.ones(4),
                            "c": np.ones(4), "d": np.ones(4),
                            "e": np.ones(3)}, keep)
    assert norms == [2.0, 2.0, 2.0, np.sqrt(2.0), 0.0]


def test_control_is_not_correct():
    """The reference with float8 products in the program's place."""
    res = run.run_control(tiny("r14-k256-n2"), 2**31 + 11,
                          require_tpu=False)
    assert res["correct"] is False
