"""A run of each cell at smoke size on the CPU, with the chip check
skipped, comes out correct against the limits of that size."""
import pytest

from bench_small import judged


@pytest.mark.parametrize("config,traffic", [
    ("qwen3-0.6b", "steady"), ("qwen3-0.6b", "failover"),
    ("qwen3-0.6b", "nockpt"), ("mamba2-2.7b-d8", "steady")])
def test_a_sound_run_is_correct(config, traffic):
    ok, numbers = judged(config, traffic)
    assert ok, numbers
