"""The work arithmetic against hand counts of one layer of each kind, and
the walk of a residual layer graph."""
import json

import pytest

from harness import model, spec, work

DATA = spec.BENCH / "tests" / "data"


def config(name):
    """A configuration the benchmark runs, from ``bench/configs``."""
    return json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())


def fixture(name):
    """A configuration of the tests alone, from ``bench/tests/data``."""
    return json.loads((DATA / f"{name}.json").read_text())


def layer(cfg, name):
    return next(lay for lay in work.layers(cfg) if lay.name == name)


B2, CNNA, R50 = config("mobilenet_b2"), config("cnn_a"), \
    fixture("resnet50_v15")


# (config, layer, MACs per image, least bytes of one image at m=2)
HAND = [
    # 224x224x3 -> 3x3 stride 2 SAME -> 112x112x32: 112*112*32*27 MACs;
    # f32 in 224*224*3*4 + out 112*112*32*4, 2 bits x 864 weights,
    # 2 x 32 f32 alphas, 32 f32 biases
    (B2, "stem", 112 * 112 * 32 * 27,
     224 * 224 * 3 * 4 + 112 * 112 * 32 * 4 + 216 + 256 + 128),
    # depth-wise 112x112x64 -> 3x3 stride 2 SAME -> 56x56x64
    (B2, "dw1", 56 * 56 * 64 * 9,
     112 * 112 * 64 * 4 + 56 * 56 * 64 * 4 + 144 + 512 + 256),
    # head after the global average pool: 1024 -> 1000
    (B2, "head", 1024 * 1000,
     1024 * 4 + 1000 * 4 + 256000 + 8000 + 4000),
    # CNN-A conv2: 21x21x5 -> 4x4 VALID -> 18x18x150, max-pool 6 -> 3x3x150
    (CNNA, "conv2", 18 * 18 * 150 * 80,
     21 * 21 * 5 * 4 + 3 * 3 * 150 * 4 + 2 * 12000 // 8 + 1200 + 600),
    # ResNet-50 stem: 224x224x3 -> 7x7 stride 2 padded 3 -> 112x112x64,
    # max-pool 3 at stride 2 padded 1 -> 56x56x64
    (R50, "conv1", 112 * 112 * 64 * 147,
     224 * 224 * 3 * 4 + 56 * 56 * 64 * 4 + 2 * 9408 // 8 + 512 + 256),
    # the first projection reads the pooled stem: 56x56x64 -> 1x1 ->
    # 56x56x256
    (R50, "layer1.0.downsample", 56 * 56 * 256 * 64,
     56 * 56 * 64 * 4 + 56 * 56 * 256 * 4 + 2 * 16384 // 8 + 2048 + 1024),
    # stride 2 on the 3x3 conv, padded 1: 56x56x128 -> 28x28x128
    (R50, "layer2.0.conv2", 28 * 28 * 128 * 1152,
     56 * 56 * 128 * 4 + 28 * 28 * 128 * 4 + 2 * 147456 // 8 + 1024 + 512),
    # the last 1x1 of a block adds its skip operand, one more f32 read:
    # 28x28x128 -> 28x28x512, + 28x28x512 from layer2.0.downsample
    (R50, "layer2.0.conv3", 28 * 28 * 512 * 128,
     28 * 28 * 128 * 4 + 2 * 28 * 28 * 512 * 4 + 2 * 65536 // 8 + 4096
     + 2048),
]


@pytest.mark.parametrize("cfg,name,macs,nbytes", HAND,
                         ids=[f"{h[0]['name']}.{h[1]}" for h in HAND])
def test_layer_counts(cfg, name, macs, nbytes):
    lay = layer(cfg, name)
    assert lay.macs == macs
    assert lay.least_bytes(1, 2) == nbytes


def test_least_bytes_scale_with_batch_and_levels():
    lay = layer(B2, "head")
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
    t, bound = layer(B2, "pw12").least_s(64, 2, peak)
    assert bound == "compute"
    assert t == pytest.approx(2 * 7 * 7 * 1024 * 1024 * 64 / 197e12)
    t, bound = layer(B2, "dw0").least_s(64, 2, peak)
    assert bound == "memory"
    assert t == pytest.approx(
        layer(B2, "dw0").least_bytes(64, 2) / 819e9)


def test_resnet50_counts():
    """torchvision's resnet50 (v1.5): 4.09 G MACs per image, and its
    25,557,032 parameters are the 25,502,912 conv and dense weights, the
    batch norms' scale and shift on each conv's 26,560 output channels, and
    the dense layer's 1,000 biases."""
    cfg = R50
    lays = work.layers(cfg)
    assert len(lays) == 54
    assert work.macs_per_image(cfg) == 4_089_184_256
    n_weights = sum(lay.n_weights for lay in lays)
    assert n_weights == 25_502_912
    bn = sum(lay.out_shape[-1] for lay in lays if lay.kind == "conv")
    assert bn == 26_560
    assert n_weights + 2 * bn + lays[-1].n_bias == 25_557_032
    assert lays[-2].name == "layer4.2.conv3"
    assert lays[-2].out_shape == (7, 7, 2048)
    assert lays[-1].in_shape == (2048,) and lays[-1].out_shape == (1000,)
    # 16 residual adds, one per bottleneck
    assert sum(lay.n_add > 0 for lay in lays) == 16


def test_resnet50_weight_shapes():
    cfg = R50
    shapes = model.weight_shapes(cfg)
    assert list(shapes) == [s["name"] for s in cfg["layers"]]
    assert shapes["conv1"] == ((7, 7, 3, 64), 64)
    assert shapes["layer1.0.conv1"] == ((1, 1, 64, 64), 64)
    assert shapes["layer2.0.downsample"] == ((1, 1, 256, 512), 512)
    assert shapes["layer2.0.conv1"] == ((1, 1, 256, 128), 128)
    assert shapes["layer3.5.conv2"] == ((3, 3, 256, 256), 256)
    assert shapes["layer4.2.conv3"] == ((1, 1, 512, 2048), 2048)
    assert shapes["fc"] == ((2048, 1000), 1000)


@pytest.mark.parametrize("load,name", [(config, "cnn_a"),
                                       (config, "mobilenet_b2"),
                                       (fixture, "tiny_mobilenet")],
                         ids=["cnn_a", "mobilenet_b2", "tiny_mobilenet"])
def test_chain_configurations_count_what_they_did(load, name):
    """A configuration with none of the graph's keys is counted exactly as
    the chain walk counted it (``data/chain_layers.json``)."""
    want = json.loads((DATA / "chain_layers.json").read_text())["layers"][name]
    lays = work.layers(load(name))
    got = [[lay.name, lay.kind, list(lay.in_shape), list(lay.out_shape),
            lay.macs, lay.n_weights, lay.n_alpha, lay.n_bias,
            lay.least_bytes(64, 2), lay.least_bytes(1, 1)]
           for lay in lays]
    assert got == want
    assert all(lay.n_add == 0 for lay in lays)


def _edit(layer, **keys):
    cfg = fixture("tiny_resnet")
    lay = next(s for s in cfg["layers"] if s["name"] == layer)
    lay.update(keys)
    return cfg


BAD = [
    # src names a layer that does not exist, or one that comes later
    (_edit("layer1.1.conv1", src="nowhere"), "src 'nowhere'"),
    (_edit("layer1.1.conv1", src="layer2.0.conv3"), "src 'layer2.0.conv3'"),
    # add names an unknown layer, a later one, or one of another shape
    (_edit("layer1.1.conv3", add="nowhere"), "add 'nowhere'"),
    (_edit("layer1.1.conv3", add="fc"), "add 'fc'"),
    (_edit("layer1.1.conv3", add="layer1.1.conv2"), "has shape"),
    (_edit("layer2.0.conv3", add="layer1.1.conv3"), "has shape"),
    # a pool that does not overlap has to divide the conv's 8x8 output
    (_edit("layer1.1.conv2", pool=3), "not divisible by pool 3"),
    # a name given twice, or the input's
    (_edit("layer1.1.conv2", name="layer1.1.conv1"), "is taken"),
    (_edit("conv1", name="input"), "is taken"),
]


@pytest.mark.parametrize("cfg,message", BAD, ids=[b[1] for b in BAD])
def test_bad_graph_is_refused(cfg, message):
    with pytest.raises(ValueError, match=message):
        work.layers(cfg)


def test_src_input_and_explicit_pads():
    """``src: "input"`` reads the image; explicit pads differ from SAME
    where the odd element would go high."""
    lay = work.layers(_edit("fc", src="input"))[-1]
    assert lay.in_shape == (3,)
    assert work.window_out(56, 56, (3, 3), 2, [[1, 1], [1, 1]]) == (28, 28)
    assert work.window_out(56, 56, (3, 3), 2, "SAME") == (28, 28)
    assert work.window_out(224, 224, (7, 7), 2, [[3, 3], [3, 3]]) == \
        (112, 112)
    assert work.window_out(112, 112, (3, 3), 2, [[1, 1], [1, 1]]) == (56, 56)
    assert work.same_pads(224, 7, 2) == (2, 3)
