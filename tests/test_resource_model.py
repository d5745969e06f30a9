"""Resource / frequency / GEMV-engine model consistency with the paper."""
import pytest

from repro.core.gemv_engine import GemvEngineConfig, gemv_latency_s, table_vii
from repro.core.mac import MacConfig
from repro.core import resource_model as RM


def test_table_v_consistent_with_table_iii():
    """Table V per-op xtramac x 4 lanes == Table III config II instance."""
    per_op = RM.TABLE_V["xtramac"]["bf16"]
    inst = RM.TABLE_III["II:int8xint8+int32|bf16"]
    assert per_op.lut * 4 == pytest.approx(inst.lut, rel=1e-6)
    assert per_op.dsp * 4 == pytest.approx(inst.dsp, rel=1e-6)


def test_paper_mean_reductions():
    """Average LUT/FF/DSP reductions across Table IV match Section V-E1."""
    red = {"lut": [], "ff": [], "dsp": []}
    for (a, bcp), (vendor, ours) in RM.TABLE_IV.items():
        red["lut"].append(1 - ours.lut / vendor.lut)
        red["ff"].append(1 - ours.ff / vendor.ff)
        red["dsp"].append(1 - ours.dsp / vendor.dsp)
    for k, vals in red.items():
        mean = sum(vals) / len(vals)
        assert mean == pytest.approx(RM.PAPER_MEAN_REDUCTION[k], abs=0.01), (k, mean)


def test_compute_density_range():
    """Comp.Den. between 1.4x and 2.0x for every Table IV combo (abstract)."""
    for (a, bcp) in RM.TABLE_IV:
        d = RM.compute_density(a, bcp)
        for k, v in d.items():
            assert 1.35 <= v <= 2.05, ((a, bcp), k, v)


def test_fmax_model():
    assert RM.fmax_mhz(1) == 483.0
    assert RM.fmax_mhz(4) == 462.0
    for n in range(1, 5):
        assert RM.fmax_mhz(n) > RM.FMAX_FLOOR_MHZ
    assert RM.system_fmax_mhz(512) == 300.0
    assert 250.0 <= RM.system_fmax_mhz(1920) <= 270.0


def test_parametric_model_calibration():
    """Eq.7/8-based model reproduces the Table III instances it was fit on.

    Calibration is non-negative least squares (physical resource counts;
    plain lstsq with 4 rows x 6 features is underdetermined and produced
    negative/non-monotone coefficients), which trades fit for validity —
    hence the looser R^2 bound."""
    assert RM.CALIBRATION_R2 > 0.5
    cases = {
        "I:int4xbf16+bf16": [MacConfig.make("int4", "bf16", "bf16", "bf16"),
                             MacConfig.make("bf16", "bf16", "bf16", "bf16")],
        "III:fp8xfp8+bf16|bf16": [MacConfig.make("fp8_e4m3", "fp8_e4m3", "bf16", "bf16"),
                                  MacConfig.make("bf16", "bf16", "bf16", "bf16")],
    }
    for key, cfgs in cases.items():
        est = RM.estimate_instance(cfgs)
        meas = RM.TABLE_III[key]
        assert est.lut == pytest.approx(meas.lut, rel=0.25), key


def test_gemv_engine_dimensions():
    """Section VI-C: 512/(4x2)=64 MACs/channel; 30 channels -> 1920 units."""
    cfg = GemvEngineConfig()
    assert cfg.n_mac_per_channel == 64
    assert cfg.n_instances == 1920
    assert 250e6 <= cfg.freq_hz <= 300e6


def test_table_vii_reproduction():
    """Model-predicted GEMV latency lands on the paper's measured Table VII."""
    rows = table_vii()
    for shape, row in rows.items():
        # model within 5% of the paper's measured FPGA latency
        assert row["model_vs_paper"] == pytest.approx(1.0, abs=0.05), (shape, row)
        assert row["bound"] == "memory"  # paper: bandwidth-bound at scale
        assert row["speedup"] == pytest.approx(1.2, abs=0.1)
        assert row["energy_eff"] == pytest.approx(1.9, abs=0.15)


def test_gemv_compute_bound_at_large_batch():
    """Large m flips the kernel into the compute-bound regime (Fig. 14)."""
    cfg = GemvEngineConfig()
    r1 = gemv_latency_s(cfg, 1, 4096, 4096)
    r64 = gemv_latency_s(cfg, 64, 4096, 4096)
    assert r1["bound"] == "memory" and r64["bound"] == "compute"


def test_decode_latency_gemv_engine_pricing_is_datatype_adaptive():
    """Routing the channel-streaming GEMV engine into ``decode_latency``
    makes the compute phase per-datatype: a 4-bit scheme runs its
    projections on 4x the MAC lanes of bf16 from the same channels, and
    the memory phase is derated by the engine's measured HBM utilization
    — the pricing ``serve/slo.py`` plans with."""
    from repro.configs import get_config
    from repro.perfmodel import decode_latency, gemv_engine_for

    int4 = gemv_engine_for("awq_int4")
    bf16 = gemv_engine_for("bf16")
    assert int4.n_mac_per_channel == 4 * bf16.n_mac_per_channel

    cfg = get_config("granite-8b", smoke=True)
    kw = dict(batch=8, context=512, design="xtramac")
    flat = decode_latency(cfg, "awq_int4", **kw)
    priced = decode_latency(cfg, "awq_int4", engine_model=int4, **kw)
    # engine pricing: quant units are the engine's lane count, and the
    # memory phase pays the 74% effective-bandwidth derate
    assert priced["units_quant"] == int4.macs_per_cycle
    assert priced["units_quant"] != flat["units_quant"]
    assert priced["t_mem_s"] > flat["t_mem_s"]
    # same engine, wider weights -> fewer lanes -> slower compute phase
    w8 = decode_latency(cfg, "w8a8", engine_model=gemv_engine_for("w8a8"),
                        **kw)
    assert w8["t_compute_s"] > priced["t_compute_s"]
