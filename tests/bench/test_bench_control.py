"""The control, the reference put in the program's place in float8, fails
the comparison at smoke size on the CPU (`bench/control.py` reads it at the
cells' own size on the chip)."""
import pytest

from bench_small import small_cell


@pytest.mark.parametrize("config", ["qwen3-0.6b", "mamba2-2.7b-d8"])
def test_the_control_fails(config):
    """The reference in the program's place, in float8, against the
    reference in float32 (`bench/control.py`), on three seeds."""
    from bench import control
    cell = small_cell(config)
    limits = cell.config["limits"]
    for seed in (1, 2, 3):
        gaps = control.readings(cell.config, cell.ref, seed)["control"]
        assert any(gaps[k] > limits[k] for k in limits), gaps
