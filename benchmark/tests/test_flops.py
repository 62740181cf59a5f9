import pytest

from harness import device, flops, manifest

RESNET = manifest.load_json(manifest.BENCH_DIR + "/configs/resnet50.json")
OPT = manifest.load_json(manifest.BENCH_DIR + "/configs/opt-1.3b.json")


def test_first_bottleneck_by_hand():
    # 56x56 maps, 64 -> (64, 64, 256), with a projection shortcut
    convs = {n: conv for n, *conv in flops.resnet_convs(RESNET)}
    hand = {"s0b0.conv1": 2 * 56 * 56 * 64 * 64,            # 25,690,112
            "s0b0.conv2": 2 * 56 * 56 * 9 * 64 * 64,        # 231,211,008
            "s0b0.conv3": 2 * 56 * 56 * 64 * 256,           # 102,760,448
            "s0b0.down": 2 * 56 * 56 * 64 * 256}
    assert sum(hand.values()) == 462_422_016
    for name, want in hand.items():
        _hi, ho, k, cin, cout, _s = convs[name]
        assert flops.conv_flops(ho, ho, k, k, cin, cout) == want


def test_resnet50_is_four_gigamacs():
    assert len(flops.resnet_convs(RESNET)) == 53
    fwd = flops.resnet_forward_flops(RESNET)
    assert fwd == pytest.approx(2 * 4.09e9, rel=0.02)
    assert flops.resnet_train_flops_per_step(RESNET, 256) == 3 * 256 * fwd


def test_one_opt_layer_by_hand():
    # q, k, v, o: 4 x 2048^2; feed-forward: 2 x 2048 x 8192
    assert flops.lm_layer_matmul_params(OPT) == 16_777_216 + 33_554_432
    one = dict(OPT, num_hidden_layers=1)
    tokens, seq = 2048, 2048
    dense = 2 * tokens * 50_331_648
    attn = 2 * 2 * tokens * seq * 2048 // 2                 # causal
    head = 2 * tokens * 2048 * 50_273
    assert flops.lm_forward_flops(one, tokens, seq) == dense + attn + head


def test_opt_weights_are_2p6_gb():
    assert flops.lm_weight_bytes(OPT, 2) == 2 * 1_311_365_120
    # a decode step with 1000 live positions reads 2 x 24 x 2048 x 4 B each
    assert flops.decode_step_bytes(OPT, 1000, 2, 4) \
        == 2 * 1_311_365_120 + 2 * 24 * 2048 * 1000 * 4


def test_conv_bytes_by_hand():
    tiny = {"image_size": 32, "layers": [1, 0, 0, 0], "num_classes": 10}
    convs = flops.resnet_convs(tiny)
    assert [c[0] for c in convs] == ["stem", "s0b0.conv1", "s0b0.conv2",
                                     "s0b0.conv3", "s0b0.down"]
    per_image = 0
    for _n, hi, ho, _k, cin, cout, _s in convs:
        x, y = hi * hi * cin, ho * ho * cout
        per_image += 3 * (x + y)
    assert flops.resnet_conv_bn_bytes_per_step(tiny, 2, 2) == per_image * 4


def test_peaks_are_keyed_by_device_kind():
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks("TPU v9")
