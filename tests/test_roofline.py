"""Roofline machinery: HLO collective parsing, extrapolation, model FLOPs."""
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import roofline as rl
from repro.models.config import SHAPES

HLO_SAMPLE = """
HloModule jit_step
  %x.1 = bf16[256,1024]{1,0} all-reduce(bf16[256,1024]{1,0} %p0), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
  %y.2 = f32[64,128]{1,0} all-gather(f32[64,32]{1,0} %p1), replica_groups=[2,4]<=[8], dimensions={1}
  %z.3 = (f32[16,16]{1,0}, f32[16,16]{1,0}) reduce-scatter(f32[64,16]{1,0} %a, f32[64,16]{1,0} %b), replica_groups={{0,1,2,3}}
  %w.4 = f32[8,8]{1,0} collective-permute(f32[8,8]{1,0} %c), source_target_pairs={{0,1},{1,0}}
  %v.5 = bf16[32]{0} all-to-all(bf16[32]{0} %d), replica_groups=[1,8]<=[8]
"""


def test_parse_collectives_counts_and_bytes():
    stats = rl.parse_collectives(HLO_SAMPLE)
    assert stats.counts == {"all-reduce": 1, "all-gather": 1, "reduce-scatter": 1,
                            "collective-permute": 1, "all-to-all": 1}
    assert stats.result_bytes["all-reduce"] == 256 * 1024 * 2
    assert stats.result_bytes["all-gather"] == 64 * 128 * 4
    assert stats.result_bytes["reduce-scatter"] == 2 * 16 * 16 * 4
    # ring model: all-reduce over groups of 4 → 2·(3/4)·bytes
    np.testing.assert_allclose(stats.wire_bytes["all-reduce"],
                               2 * 0.75 * 256 * 1024 * 2)
    # all-gather group size from iota form [2,4]<=[8] → 4
    np.testing.assert_allclose(stats.wire_bytes["all-gather"],
                               0.75 * 64 * 128 * 4)


def test_extrapolation_linear():
    assert rl.extrapolate(10.0, 14.0, 5) == 10.0 + 4 * 4.0
    assert rl.extrapolate(10.0, 9.0, 5) == 10.0  # negative delta clamps


def test_roofline_terms_dominance():
    t = rl.roofline_terms(197e12, 819e9 * 2, 50e9 * 0.5)
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(2.0)
    assert t["collective_s"] == pytest.approx(0.5)
    assert t["dominant"] == "memory"


def test_model_flops_train_scales_6nd():
    cfg = get_config("tinyllama-1.1b")
    shape = SHAPES["train_4k"]
    mf = rl.model_flops(cfg, shape)
    nd6 = 6.0 * cfg.param_count() * shape.global_batch * shape.seq_len
    assert mf > nd6  # includes attention
    assert mf < nd6 * 1.5


def test_model_flops_moe_uses_active_params():
    cfg = get_config("qwen3-moe-235b-a22b")
    shape = SHAPES["train_4k"]
    mf = rl.model_flops(cfg, shape)
    active = 6.0 * cfg.active_param_count() * shape.global_batch * shape.seq_len
    total = 6.0 * cfg.param_count() * shape.global_batch * shape.seq_len
    assert mf < total * 0.25      # far below dense-equivalent
    assert mf > active * 0.9


def test_model_flops_decode_is_tiny_vs_train():
    cfg = get_config("mistral-nemo-12b")
    assert (rl.model_flops(cfg, SHAPES["decode_32k"])
            < rl.model_flops(cfg, SHAPES["train_4k"]) / 1000)


def test_window_caps_attention_span():
    cfg = get_config("jamba-1.5-large-398b")
    mf_500k = rl.model_flops(cfg, SHAPES["long_500k"])
    # one token, window 32k on 9 attention layers: far below a dense-attn arch
    assert mf_500k < 2.5 * cfg.active_param_count()


class _Dev:
    def __init__(self, platform, device_kind):
        self.platform = platform
        self.device_kind = device_kind


def test_chip_peaks_keyed_by_device_kind():
    v5e = rl.chip_peaks(_Dev("tpu", "TPU v5 lite"))
    assert v5e["flops"] == 197e12 and v5e["hbm_bw"] == 819e9
    # off the TPU (CPU, the Pallas interpreter) tiles rank on the named row
    assert rl.chip_peaks(_Dev("cpu", "cpu")) is rl.PEAKS[rl.REFERENCE_KIND]
    with pytest.raises(ValueError, match="no roofline peaks"):
        rl.chip_peaks(_Dev("tpu", "TPU v99"))
