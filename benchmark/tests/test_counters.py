"""Operations and bytes from shapes, against values worked out by hand."""
import json
import os

from benchmark import harness as H

CONFIGS = os.path.join(H.CODE, "configs")


def cfg(name):
    return json.load(open(os.path.join(CONFIGS, name + ".json")))


def test_resnet_bottleneck_by_hand():
    fam = H.load_module("families", "resnet")
    # stage1_unit1 on a 56x56 map: 1x1 64->64, 3x3 64->64, 1x1 64->256,
    # and the 1x1 64->256 projection shortcut
    hw = 56 * 56
    by_hand = hw * (64 * 64 + 64 * 64 * 9 + 64 * 256 + 64 * 256)
    assert by_hand == 231_211_008
    got = (fam.conv_macs(64, 64, 1, 56) + fam.conv_macs(64, 64, 3, 56)
           + fam.conv_macs(64, 256, 1, 56) + fam.conv_macs(64, 256, 1, 56))
    assert got == by_hand
    total, stem = fam.forward_macs(cfg("resnet50"))
    assert stem == 3 * 64 * 49 * 112 * 112 == 118_013_952
    # the usual count for ResNet-50 (stride on the 3x3): 4.09 GMAC
    assert total == 4_089_184_256
    flops = fam.train_flops_per_step(cfg("resnet50"), {"batch": 256})
    assert flops == 2.0 * (3 * total - stem) * 256


def test_opt_layer_by_hand():
    fam = H.load_module("families", "opt")
    c = cfg("opt-1.3b")
    # qkv 3 x 2048^2, proj 2048^2, two 2048 x 8192 matrices
    assert fam.layer_macs_per_token(c) == 4 * 2048 ** 2 + 2 * 2048 * 8192 \
        == 50_331_648
    # one query over 100 keys: QK^T and PV, 2 x 100 x 2048
    assert fam.attention_macs(c, 1, 100) == 409_600
    # weights a decode step reads, bf16: 24 layers + head (+ vectors)
    per_layer = 50_331_648 + 9 * 2048 + 8192
    want = 2 * (24 * per_layer + 2048 * 50272 + 50272 + 2 * 2048)
    assert fam.decode_weight_bytes(c) == want
    assert 2.6e9 < want < 2.7e9
    # K and V of one cache row over 24 layers, bf16: 192 KiB
    assert fam.decode_cache_bytes_per_row(c) == 2 * 2 * 24 * 2048 \
        == 192 * 1024
    t = {"batch": 4, "seq_len": 2048}
    c4 = cfg("opt-1.3b-train")
    n = c4["num_hidden_layers"]
    per_row = 2048 * (n * 50_331_648 + 2048 * 50272) \
        + n * 2 * 2048 * (2049 / 2.0) * 2048
    assert fam.train_flops_per_step(c4, t) == 2.0 * 3.0 * 4 * per_row
    flops, nbytes = fam.flash_train_cost(c4, t)
    assert flops == n * 7.0 * 2.0 * 4 * 2048 * 2049 / 2.0 * 2048
    assert nbytes == n * 12.0 * 4 * 2048 * 2048 * 2


def test_generator_offers_every_seed_the_same_work():
    from benchmark import generate as G
    t = json.load(open(os.path.join(H.CODE, "traffic", "serve-chat.json")))
    a = G.requests(t, 50272, 1, 20.0)
    b = G.requests(t, 50272, 3_000_000_007, 20.0)
    assert len(a) == len(b) == round(t["rate_per_s"] * 20.0)
    assert sorted(len(p) for _, p, _ in a) == sorted(len(p) for _, p, _ in b)
    assert sorted(n for _, _, n in a) == sorted(n for _, _, n in b)
    # the file fixes the order: every seed offers one sample path
    assert [len(p) for _, p, _ in a] == [len(p) for _, p, _ in b]
    assert any((x[1][:64] != y[1][:64]).any() for x, y in zip(a, b))
    assert all(0 < d < 20.0 for d, _, _ in a)
    assert all(t["prompt"]["min"] <= len(p) <= t["prompt"]["max"]
               for _, p, _ in a)
    same = G.requests(t, 50272, 1, 20.0)
    assert all((x[1] == y[1]).all() and x[0] == y[0] and x[2] == y[2]
               for x, y in zip(a, same))
