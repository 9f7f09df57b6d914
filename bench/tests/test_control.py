"""The control, the reference computed with float8 matmuls in the program's
place, must fail the comparison (here at the tiny sizes of test_faults.py;
on the chip at each cell's own size, by bench/calibrate.py)."""
import jax
import pytest

from bench import check, reference
from bench.system import Program

from test_faults import tiny_mamba, tiny_qwen


@pytest.mark.parametrize("make", [tiny_qwen, tiny_mamba], ids=["qwen-dp4", "mamba-1chip"])
def test_control_is_not_correct(make):
    config, workload = make()
    shapes = Program(config, workload, jax.devices(), use_pallas=False).shapes
    ref = reference.run(config, workload, shapes, 3000000017)
    ctl = reference.run(config, workload, shapes, 3000000017, precision="fp8")
    ok, checks = check.judge(check.readings(ctl, ref), workload["limits"])
    assert not ok, checks


def test_fp8_rounding_keeps_three_mantissa_bits():
    x = jax.numpy.asarray([1.0, 1.0625, 1.125, 448.0, -3.0])
    y = reference.common.fp8_round(x)
    assert float(y[1]) in (1.0, 1.125)          # 1 + 2^-4 is not an e4m3 value
    assert float(y[2]) == pytest.approx(1.125)
    assert float(y[3]) == pytest.approx(448.0)
