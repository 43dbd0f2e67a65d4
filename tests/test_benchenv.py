"""The device-time measurement helpers (benches/benchenv.py) and the HBM
peak table they judge against (benchmark/harness/peaks.py, the only one
the repo keeps)."""

from types import SimpleNamespace

import pytest

from benches import benchenv
from benches.benchenv import UnknownDeviceKind, resolve_roofline


def _dev(kind):
    return SimpleNamespace(device_kind=kind, platform="tpu")


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_resolve_roofline_knows_the_v5e(kind):
    assert resolve_roofline(_dev(kind)) == (819.0, kind)


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite", ""])
def test_resolve_roofline_unknown_kind_raises(kind):
    """A device that is not in the peak table is an error, never the
    v5e figure by default."""
    with pytest.raises(UnknownDeviceKind):
        resolve_roofline(_dev(kind))


def test_validated_chain_slope_refuses_an_unknown_device():
    with pytest.raises(UnknownDeviceKind):
        benchenv.validated_chain_slope(lambda k: 1.0 + k, 1e9,
                                       _dev("cpu"))


def test_chain_slope_recovers_the_per_iteration_time():
    """timed(k) = fixed overhead + k * sweep: the Theil-Sen slope is
    the sweep, whatever the overhead."""
    r = benchenv.chain_slope_gbps(lambda k: 0.070 + k * 0.002, 1e9,
                                  reps=1)
    assert r["per_iter_s"] == pytest.approx(0.002)
    assert r["gbps_median"] == pytest.approx(500.0)
    assert r["slope_pairs"] == 6 and r["slope_pairs_nonpositive"] == 0


def test_chain_slope_refuses_a_flat_chain():
    """No dependence on k means the sweeps were elided or the clock is
    noise: that is no measurement."""
    with pytest.raises(RuntimeError, match="non-positive"):
        benchenv.chain_slope_gbps(lambda k: 0.070, 1e9, reps=1)


def test_validated_chain_slope_marks_above_roofline_invalid():
    """1 GB per 0.5 ms = 2000 GB/s on an 819 GB/s part: recorded as
    invalid, not as a number."""
    r = benchenv.validated_chain_slope(lambda k: k * 0.0005, 1e9,
                                       _dev("TPU v5 lite"), reps=1)
    assert r["invalid"] is True and r["roofline_gbps_assumed"] == 819.0
    ok = benchenv.validated_chain_slope(lambda k: k * 0.002, 1e9,
                                        _dev("TPU v5 lite"), reps=1)
    assert "invalid" not in ok
    assert ok["roofline_frac"] == pytest.approx(500.0 / 819.0)
