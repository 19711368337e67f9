"""The work arithmetic against hand counts of one layer of each kind."""
import json

import pytest

from harness import spec, work


def config(name):
    return json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())


def layer(cfg, name):
    return next(lay for lay in work.layers(config(cfg)) if lay.name == name)


# (config, layer, MACs per image, least bytes of one image at m=2)
HAND = [
    # 224x224x3 -> 3x3 stride 2 SAME -> 112x112x32: 112*112*32*27 MACs;
    # f32 in 224*224*3*4 + out 112*112*32*4, 2 bits x 864 weights,
    # 2 x 32 f32 alphas, 32 f32 biases
    ("mobilenet_b2", "stem", 112 * 112 * 32 * 27,
     224 * 224 * 3 * 4 + 112 * 112 * 32 * 4 + 216 + 256 + 128),
    # depth-wise 112x112x64 -> 3x3 stride 2 SAME -> 56x56x64
    ("mobilenet_b2", "dw1", 56 * 56 * 64 * 9,
     112 * 112 * 64 * 4 + 56 * 56 * 64 * 4 + 144 + 512 + 256),
    # head after the global average pool: 1024 -> 1000
    ("mobilenet_b2", "head", 1024 * 1000,
     1024 * 4 + 1000 * 4 + 256000 + 8000 + 4000),
    # CNN-A conv2: 21x21x5 -> 4x4 VALID -> 18x18x150, max-pool 6 -> 3x3x150
    ("cnn_a", "conv2", 18 * 18 * 150 * 80,
     21 * 21 * 5 * 4 + 3 * 3 * 150 * 4 + 2 * 12000 // 8 + 1200 + 600),
]


@pytest.mark.parametrize("cfg,name,macs,nbytes", HAND,
                         ids=[h[1] for h in HAND])
def test_layer_counts(cfg, name, macs, nbytes):
    lay = layer(cfg, name)
    assert lay.macs == macs
    assert lay.least_bytes(1, 2) == nbytes


def test_least_bytes_scale_with_batch_and_levels():
    lay = layer("mobilenet_b2", "head")
    act = (1024 + 1000) * 4
    assert lay.least_bytes(64, 2) - lay.least_bytes(1, 2) == 63 * act
    assert lay.least_bytes(1, 2) - lay.least_bytes(1, 1) == \
        1024 * 1000 // 8 + 1000 * 4


def test_whole_networks():
    # BinArray section V-A1 quotes 569 M MACs for CNN-B2 and about 9 M for
    # CNN-A, whose exact count from its layers is 5.83 M
    assert work.macs_per_image(config("mobilenet_b2")) == 568_740_352
    assert work.macs_per_image(config("cnn_a")) == (
        42 * 42 * 5 * 147 + 18 * 18 * 150 * 80 + 1350 * 340 + 340 * 490
        + 490 * 43)


def test_least_time_names_its_bound():
    peak = {"flops": 197e12, "bytes_per_s": 819e9}
    t, bound = layer("mobilenet_b2", "pw12").least_s(64, 2, peak)
    assert bound == "compute"
    assert t == pytest.approx(2 * 7 * 7 * 1024 * 1024 * 64 / 197e12)
    t, bound = layer("mobilenet_b2", "dw0").least_s(64, 2, peak)
    assert bound == "memory"
    assert t == pytest.approx(
        layer("mobilenet_b2", "dw0").least_bytes(64, 2) / 819e9)
