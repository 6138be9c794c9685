"""The streamed cell's output check on the CPU at a small size: the
program passes, each fault fails (bench/faults.py)."""
import pytest

from bench import faults, run
from test_bench_run import tiny


def _run(variant):
    spec = tiny("r18-k256-q1to6-stream")
    return run.run_cell(spec, 2**31 + 13, 0.0, False, require_tpu=False,
                        window=False, log=lambda *a: None,
                        **faults.variant_kwargs(variant, spec))


def test_program_readings_are_small():
    for v in _run("program")["checks"].values():
        assert 0 <= v["value"] < 0.1


@pytest.mark.parametrize("variant", ["half", "unchanged", "delta_half"])
def test_fault_is_not_correct(variant):
    assert _run(variant)["correct"] is False
