"""The controls through the harness, at a size a test run holds: with the
control in the program's place in the check, a whole run must come out
not correct, and on the number the control is for."""
import pytest

from bench.tests import small
from bench.tests.cpu_cell import run_small


def failing(res) -> set:
    return {k for k, c in res["checks"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_jacobi_bf16_control_fails_the_limit(tmp_path, seed):
    res = run_small(tmp_path, "jacobi-7209.paper", config=small.JACOBI,
                    traffic=small.JACOBI_MIX, seed=seed, seconds=0.3,
                    control=True)
    assert res["correct"] is False
    assert failing(res) == {"x_err"}
    x = res["checks"]["x_err"]
    assert x["value"] > 3 * x["limit"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_qwen2_fp8_control_fails_the_limit(tmp_path, seed):
    res = run_small(tmp_path, "qwen2-1.5b.chat", config=small.QWEN2,
                    traffic=small.SERVE_MIX, seed=seed, seconds=3.0,
                    control=True)
    assert res["correct"] is False
    assert failing(res) == {"logit_gap"}
