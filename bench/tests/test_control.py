"""The control comes out not correct: the plain reference computed in int8
(``bench/check.py::int8_matmul``), put in the program's place in the
harness's comparison, fails a compared number's limit, while the program's
own numbers on the same sample stay within every limit.  At the cells'
own size on the chip this is ``python3 -m bench.control``; here at smoke
size on the CPU."""
import pytest

from bench import control
from bench.tests import smoke


@pytest.mark.parametrize("family", ["llama", "mamba2"])
def test_control_fails_a_compared_number(family):
    cell = smoke.cell(family)
    said = []
    (out,) = control.run_seeds(cell, [1], 2.0, on_chip=False,
                               log=said.append)
    checks = out["checks"]
    assert not out["correct"]
    assert checks["failed_requests"]["value"] == 0
    assert any(c["value"] > c["limit"] for c in checks.values())
    # the program's own numbers on the same sample, as the log gives them
    words = next(m for m in said if m.startswith("seed 1: program")).split()
    program = dict(zip(words[3::2], map(float, words[4::2])))
    assert program.keys() == {"widest_logit_gap", "u_rel_error"}
    assert all(program[k] <= checks[k]["limit"] for k in program)
