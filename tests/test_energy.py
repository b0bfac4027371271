"""Calibrated energy model vs every measured number in the paper."""

import pytest

from hypothesis import given, settings, strategies as st

from repro.core import energy as E
from repro.core.power import PowerDomain, PowerManager, PowerState


def approx(value, target, tol=0.025):
    assert abs(value - target) / target < tol, (value, target)


# -- §IV-C silicon envelope --------------------------------------------------

def test_sleep_32khz_270uw():
    approx(E.power_sleep_32khz(), 270.0, 0.02)


def test_max_corner_48mw():
    approx(E.power_max_470mhz_1v2() / 1000, 48.0, 0.02)


def test_processing_ladder():
    approx(E.power_processing(False) / 1000, 8.17, 0.02)   # all on
    approx(E.power_processing(True) / 1000, 7.68, 0.02)    # -6 %
    saving = 1 - E.power_processing(True) / E.power_processing(False)
    assert 0.05 < saving < 0.07


def test_acquisition_ladder():
    approx(E.power_acquisition(0), 384.0, 0.02)
    approx(E.power_acquisition(1), 310.0, 0.02)
    approx(E.power_acquisition(2), 286.0, 0.02)
    s1 = 1 - E.power_acquisition(1) / E.power_acquisition(0)
    assert 0.17 < s1 < 0.21  # paper: 19 %
    s2 = 1 - E.power_acquisition(2) / E.power_acquisition(1)
    assert 0.06 < s2 < 0.10  # paper: 8 %


def test_cgra_cnn_4mw():
    approx(E.power_cgra_cnn() / 1000, 4.01, 0.02)


# -- §IV-D DVFS ---------------------------------------------------------------

def test_dvfs_ratios():
    power, perf, en = E.dvfs_ratios()
    approx(power, 5.9, 0.02)
    approx(perf, 2.8, 0.02)
    approx(en, 2.1, 0.03)


# -- Fig. 6 CGRA benefit -------------------------------------------------------

def test_cgra_benefit_4_9x():
    approx(E.cgra_benefit(), 4.9, 0.02)


# -- §VI peripheral trim -------------------------------------------------------

def test_gp_peripheral_trim():
    assert abs(E.gp_trim_saving(E.HEARTBEAT) - 0.27) < 0.015
    assert abs(E.gp_trim_saving(E.SEIZURE) - 0.03) < 0.015


# -- Fig. 5 orderings ----------------------------------------------------------

def test_fig5_heartbeat_ordering():
    m = E.mcu_models()
    tot = {k: sum(v.app_energy_mj(E.HEARTBEAT)) for k, v in m.items()}
    assert tot["apollo3_blue"] < tot["heepocrates"] < tot["gap9"]


def test_fig5_seizure_ordering():
    m = E.mcu_models()
    tot = {k: sum(v.app_energy_mj(E.SEIZURE)) for k, v in m.items()}
    assert tot["gap9"] < tot["heepocrates"] < tot["apollo3_blue"]
    # processing-phase ordering (paper §VI text)
    proc = {k: v.app_energy_mj(E.SEIZURE)[1] for k, v in m.items()}
    assert proc["gap9"] < proc["heepocrates"] < proc["apollo3_blue"]


def test_always_on_leakage_split_35_65():
    pm = E.build_heepocrates_pm()
    ess = pm.domains["ao_essential"].leak_uw
    gp = pm.domains["ao_gp_periph"].leak_uw
    total = ess + gp
    approx(ess / total, 0.35, 0.02)
    approx(gp / total, 0.65, 0.02)


def test_retention_saves_42_5_percent():
    d = PowerDomain("bank", leak_uw=10.0, retainable=True)
    on = d.power_uw(PowerState.CLOCK_GATED, 0, 0)
    ret = d.power_uw(PowerState.RETENTION, 0, 0)
    approx(1 - ret / on, 0.425, 0.01)


# -- power-manager semantics (property) ----------------------------------------

@settings(max_examples=100, deadline=None)
@given(leak=st.floats(0.1, 100), idle=st.floats(0, 10), act=st.floats(0, 100),
       duty=st.floats(0, 1), freq=st.floats(0.01, 500))
def test_power_state_monotonicity(leak, idle, act, duty, freq):
    act = max(act, idle)  # active switching >= idle clock tree
    d = PowerDomain("x", leak_uw=leak, idle_dyn_uw_mhz=idle,
                    active_dyn_uw_mhz=act, retainable=True)
    p_off = d.power_uw(PowerState.OFF, duty, freq)
    p_ret = d.power_uw(PowerState.RETENTION, duty, freq)
    p_cg = d.power_uw(PowerState.CLOCK_GATED, duty, freq)
    p_on = d.power_uw(PowerState.ON, duty, freq)
    assert p_off <= p_ret <= p_cg <= p_on + 1e-9


def test_power_manager_rejects_invalid_retention():
    pm = PowerManager([PowerDomain("cpu", leak_uw=1.0)])
    with pytest.raises(ValueError):
        pm.set_state("cpu", PowerState.RETENTION)


def test_xaif_power_port_attach():
    pm = E.build_heepocrates_pm()
    before = pm.leakage_uw()
    pm.add_domain(PowerDomain("my_accel", leak_uw=7.0))
    assert pm.leakage_uw() == pytest.approx(before + 7.0)
    pm.set_state("my_accel", PowerState.OFF)
    assert pm.leakage_uw() == pytest.approx(before)
