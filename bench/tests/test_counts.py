"""Operations and bytes counted from shapes, against figures worked by
hand from the published widths."""

from bench import harness
from bench.flops import dlrm_forward_flops, lookup_bytes


def test_rm2_forward_flops():
    cfg = harness.load_json("configs", "dlrm-rm2-robe.json")
    # bottom 13·512 + 512·256 + 256·64 = 154,112 MACs; the triangle of 27
    # features, 351 pairs · 64 = 22,464; top 415·512 + 512·512 + 512·256 +
    # 256·1 = 605,952
    assert dlrm_forward_flops(cfg) == 2 * (154_112 + 22_464 + 605_952)
    assert dlrm_forward_flops(cfg) == 1_565_056


def test_criteo_tb_forward_flops():
    cfg = harness.load_json("configs", "dlrm-criteo-tb-robe.json")
    # bottom 13·512 + 512·256 + 256·128 = 170,496; triangle 351 · 128 =
    # 44,928; top 479·1024 + 1024·1024 + 1024·512 + 512·256 + 256·1 =
    # 2,194,688
    assert dlrm_forward_flops(cfg) == 2 * (170_496 + 44_928 + 2_194_688)
    assert 3 * dlrm_forward_flops(cfg) == 14_460_672


def test_lookup_bytes_bulk_batch():
    cfg = harness.load_json("configs", "dlrm-rm2-robe.json")
    b = 262_144
    rows = b * 26
    assert lookup_bytes(cfg, b) == rows * 64 * 4 * 2 + rows * 4
    # 3.5 GB: 4.3 ms at the v5e's 819 GB/s
    assert abs(lookup_bytes(cfg, b) / 819e9 - 4.29e-3) < 0.01e-3


def test_robe_sizes_are_the_papers_compression():
    for name, d in (("dlrm-rm2-robe", 64), ("dlrm-criteo-tb-robe", 128)):
        cfg = harness.load_json("configs", name + ".json")
        assert cfg["embed_dim"] == d
        assert cfg["robe_size"] == sum(cfg["vocab_sizes"]) * d // 1000
