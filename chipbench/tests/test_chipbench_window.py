"""The window arithmetic of the end-to-end metrics."""
import pytest

from chipbench import run


@pytest.mark.parametrize("calls,per_call,window,rate", [
    (7, 24, 10.5, 16.0),          # seven gt-drl days ending at 10.5 s
    (11, 1536, 10.6, 1593.96),    # eleven 64-point sweeps
    (1, 384, 12.0, 32.0),         # one call longer than the window
])
def test_rate_is_over_the_whole_window(calls, per_call, window, rate):
    assert run.fleet_rate(calls, per_call, window) == pytest.approx(rate,
                                                                    rel=1e-4)
