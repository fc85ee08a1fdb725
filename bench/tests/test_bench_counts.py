"""The work counts against hand-worked counts at the configurations'
own shapes, and the peak table."""
from pathlib import Path

import pytest

from harness import common

CONFIGS = Path(common.BENCH) / "configs"


def _load(name):
    conf = common.load_json(CONFIGS / f"{name}.json")
    work = common.load_module(CONFIGS / conf["work"], f"work_{name[:7]}")
    return conf, work


def test_granite_prefill_counts_routed_work_of_real_tokens():
    conf, work = _load("granite-moe-1b-a400m")
    w = work.prefill(conf, 512)
    # per layer, 512 tokens: q,k,v,o 2*512*1024*(2*1024 + 2*512)
    # = 3,221,225,472; causal attention 4*16*64*(512*513/2) = 537,919,488;
    # router 2*512*1024*32 = 33,554,432; routed experts
    # 512*8 * 3 * 2*1024*512 = 12,884,901,888.  Sum 16,677,601,280 x 24
    # layers + the head on the last position 2*1024*49155 = 100,669,440.
    assert w["flops"] == 400_363_100_160
    assert w["flash_attention"]["flops"] == 24 * 537_919_488
    # q and o 512*1024 each, k and v 512*512 each, bf16
    assert w["flash_attention"]["bytes"] == 24 * 2 * 512 * 3072
    assert w["moe_expert_ffn"]["flops"] == 24 * 12_884_901_888


def test_granite_decode_counts_each_lanes_context():
    conf, work = _load("granite-moe-1b-a400m")
    w = work.decode(conf, [100, 200, 300])
    # per layer, 3 lanes: projections 2*3*1024*3072 = 18,874,368;
    # attention 4*1024*(100+200+300) = 2,457,600; router 196,608;
    # experts 3*8*6*1024*512 = 75,497,472 -> 97,026,048 x 24, plus the
    # head 3*2*1024*49155 = 302,008,320
    assert w["flops"] == 2_630_633_472
    k = w["moe_expert_ffn"]
    assert k["flops"] == 24 * 75_497_472
    # 32*(1 - 0.75**3) = 18.5 experts reached of 3*1024*512*2 bytes each,
    # plus 3*8 rows of 1024 in and out
    assert k["bytes"] == pytest.approx(24 * (18.5 * 3_145_728 + 98_304))


def test_peaks_table_has_v5e_and_refuses_an_unknown_kind():
    v5e = common.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        common.peaks_for("TPU v4")


def test_a_run_without_a_tpu_exits_without_a_result():
    with pytest.raises(SystemExit, match="needs a TPU"):
        common.require_chips(1)
