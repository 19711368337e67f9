"""The fused conv's row path (kernels/binary_conv.py, "Row path"): a
lane-dense row GEMM against banded alpha-folded weights for stride-1 convs
over lane-sparse inputs.

It must match the HBM-materialized im2col oracle (kernels/ref.py, flat
packing) at every level count, be bit-exact across batch tiles, and be
taken by exactly the layers the shape rule names: CNN-A ``conv1`` and no
layer of MobileNet-B1 or -B2.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import deploy
from repro.analysis import verify_program
from repro.configs.mobilenet_v1 import CNN_B1, CNN_B2
from repro.core import binconv
from repro.core.binlinear import QuantConfig
from repro.kernels import binary_conv as bck
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.binary_matmul import unpack_signs

jax.config.update("jax_platform_name", "cpu")

# name -> (kh, kw, C, D, H, W, pool, B, padding)
ROW_CASES = {
    "cnn_a_conv1": (7, 7, 3, 5, 48, 48, 2, 2, "VALID"),
    "pool1_same_ragged": (3, 3, 3, 12, 10, 10, 1, 3, "SAME"),
    "pool3_c2_ragged": (3, 3, 2, 7, 11, 11, 3, 5, "VALID"),
}


def _case(name, M=2):
    kh, kw, C, D, H, W, pool, B, padding = ROW_CASES[name]
    kx, kw_key, kb = jax.random.split(
        jax.random.PRNGKey(sum(ROW_CASES[name][:-1])), 3)
    w = jax.random.normal(kw_key, (kh, kw, C, D), jnp.float32) * 0.2
    b = jax.random.normal(kb, (D,), jnp.float32)
    p = binconv.binarize_conv_params(
        {"w": w, "b": b}, QuantConfig(mode="binary", M=M, K_iters=6))
    x = jax.random.normal(kx, (B, H, W, C), jnp.float32)
    return p, x


def _padded_hw(name):
    kh, kw, _, _, H, W, _, _, padding = ROW_CASES[name]
    if padding == "SAME":
        return (H + sum(binconv.same_pads(H, kh, 1)),
                W + sum(binconv.same_pads(W, kw, 1)))
    return H, W


class TestRowPathKernel:
    @pytest.mark.parametrize("m_active", [1, 2])
    @pytest.mark.parametrize("case", sorted(ROW_CASES))
    def test_matches_conv_oracle(self, case, m_active):
        kh, kw, C, D, _, _, pool, B, padding = ROW_CASES[case]
        Hp, Wp = _padded_hw(case)
        assert bck.pick_conv_path(Hp, Wp, C, D, kh, kw, pool=pool,
                                  m=2) == "row"
        p, x = _case(case)
        got = kops.binary_conv2d(
            x, p["B_tap_packed"], p["alpha"], p["b"], kh=kh, kw=kw,
            padding=padding, pool=pool, m_active=m_active, nb=2,
            interpret=True)
        want = kref.fused_binary_conv_relu_pool_ref(
            x, p["B_packed"], p["alpha"], kh=kh, kw=kw, padding=padding,
            pool=pool, m_active=m_active, bias=p["b"])
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("case", sorted(ROW_CASES))
    def test_bit_exact_across_batch_tiles(self, case):
        kh, kw, C, D, _, _, pool, B, padding = ROW_CASES[case]
        p, x = _case(case)
        x = jnp.pad(x, ((0, 0),) + tuple(
            binconv.same_pads(n, k, 1) if padding == "SAME" else (0, 0)
            for n, k in ((x.shape[1], kh), (x.shape[2], kw))) + ((0, 0),))
        gs = kh * kw * C // p["alpha"].shape[1]

        def run(nb):
            return np.asarray(bck.binary_conv2d_pallas(
                x, p["B_tap_packed"], p["alpha"], p["b"], kh=kh, kw=kw,
                pool=pool, group_size=gs, nb=nb, path="row",
                interpret=True))

        per_image = run(1)
        for nb in (2, B, None):
            np.testing.assert_array_equal(per_image, run(nb))

    def test_band_holds_the_alpha_folded_taps(self):
        """Band row (l, i, w·C + c), lane col(v, d) is alpha·B at tap
        (i, w − v) inside the window and exactly 0 outside it."""
        kh, kw, C, D, pool, Wp = 3, 3, 2, 7, 3, 11
        p, _ = _case("pool3_c2_ragged")
        band = np.asarray(bck.row_band(
            p["B_tap_packed"], p["alpha"], kh=kh, kw=kw, C=C, Wp=Wp,
            pool=pool, group_size=kh * kw * C))
        signs = np.asarray(unpack_signs(p["B_tap_packed"]))[:, :, :C]
        alpha = np.asarray(p["alpha"])[:, 0, :]          # [M, D], G == 1
        V = Wp - kw + 1
        vo = V // pool
        for l in range(2):
            for i in range(kh):
                for wc in range(Wp):
                    for v in range(V):
                        q = (v % pool) * vo + v // pool
                        j = wc - v
                        for c in range(C):
                            row = band[(l * kh + i) * Wp * C + wc * C + c,
                                       q * D:(q + 1) * D]
                            want = (signs[l, i * kw + j, c] * alpha[l]
                                    if 0 <= j < kw else np.zeros(D))
                            np.testing.assert_array_equal(row, want)


class TestRowPathPick:
    def test_shape_rule(self):
        # CNN-A conv1: stride 1, 3 channels, the band fits -> row
        assert bck.pick_conv_path(48, 48, 3, 5, 7, 7, pool=2, m=2) == "row"
        # CNN-A conv2: 5 channels but a 9.5 MB band, double-buffered
        assert bck.row_vmem_bytes(21, 21, 5, 150, 4, 4, pool=6,
                                  m=2) > bck.DEFAULT_VMEM_BUDGET
        assert bck.pick_conv_path(21, 21, 5, 150, 4, 4, pool=6,
                                  m=2) == "tile"
        # MobileNet stem: stride 2; point-wise: lane-dense inputs
        assert bck.pick_conv_path(225, 225, 3, 32, 3, 3, stride=2,
                                  m=2) == "tile"
        assert bck.pick_conv_path(10, 10, 8, 16, 3, 3, m=2) == "tile"
        # a pool that does not divide the conv output stays on the tile
        # path, whose wrapper refuses it
        assert bck.pick_conv_path(10, 10, 3, 16, 3, 3, pool=3,
                                  m=2) == "tile"

    def test_batch_tile_fills_mxu_rows_within_budget(self):
        nb = bck.pick_row_nb(256, 48, 48, 3, 5, 7, 7, pool=2, m=2)
        assert 1 < nb <= bck.ROW_PATH_MAX_NB
        assert bck.row_vmem_bytes(48, 48, 3, 5, 7, 7, pool=2, m=2,
                                  nb=nb) <= bck.DEFAULT_VMEM_BUDGET
        assert (bck.batch_padded_rows(256, nb, 42)
                < bck.batch_padded_rows(256, 1, 42))
        assert bck.batch_row_utilization(256, nb, 42) >= 0.95


def _abstract(arch, shape, **kw):
    qc = QuantConfig(mode="binary", M=2, K_iters=1)
    return deploy.abstract_program(arch, qc, shape, **kw)


class TestCompiledPrograms:
    @pytest.mark.parametrize("arch,shape,kw,row_layers", [
        ("cnn_a", (8, 48, 48, 3), {}, ["conv1"]),
        ("mobilenet", (8, CNN_B1["resolution"], CNN_B1["resolution"], 3),
         {"width_mult": CNN_B1["width_mult"]}, []),
        ("mobilenet", (8, CNN_B2["resolution"], CNN_B2["resolution"], 3),
         {"width_mult": CNN_B2["width_mult"]}, []),
    ])
    def test_row_path_engages_on_the_shape_rule_only(self, arch, shape, kw,
                                                     row_layers):
        prog = _abstract(arch, shape, **kw)
        convs = [i for i in prog.instrs if i.kind == "conv"]
        assert [i.name for i in convs if i.plan.path == "row"] == row_layers
        assert all(i.plan.path == "tile" for i in convs
                   if i.name not in row_layers)
        assert prog.conv_paths() == {"row": len(row_layers),
                                     "tile": len(convs) - len(row_layers)}

    def test_cnn_a_row_plan_and_stats(self):
        prog = _abstract("cnn_a", (8, 48, 48, 3))
        conv1 = prog.instrs[0]
        nb = bck.pick_row_nb(8, 48, 48, 3, 5, 7, 7, pool=2, m=2)
        assert conv1.plan == dataclasses.replace(
            conv1.plan, nb=nb, bu=21, bd=None, path="row")
        assert conv1.stats.vmem_bytes == bck.row_vmem_bytes(
            48, 48, 3, 5, 7, 7, pool=2, m=2, nb=nb)
        assert conv1.stats.hbm_fused_bytes == bck.row_hbm_bytes(
            48, 48, 3, 5, 7, 7, pool=2, m=2, nb=nb)
        row = prog.layer_stats()[0]
        assert row["path"] == "row" and row["plan"]["path"] == "row"

    def test_cnn_a_program_verifies_without_errors(self):
        prog = _abstract("cnn_a", (256, 48, 48, 3))
        assert prog.instrs[0].plan.path == "row"
        findings = verify_program(prog)
        assert not [f for f in findings if f.severity == "ERROR"], \
            [str(f) for f in findings]
        assert not [f for f in findings if f.index == 0
                    and f.rule == "plan-noncanonical"]

    def test_verify_flags_stale_paths(self):
        prog = _abstract("cnn_a", (8, 48, 48, 3))

        def tamper(idx, **plan):
            instrs = list(prog.instrs)
            instrs[idx] = dataclasses.replace(
                instrs[idx],
                plan=dataclasses.replace(instrs[idx].plan, **plan))
            return verify_program(dataclasses.replace(prog,
                                                      instrs=tuple(instrs)))

        rules = {(f.rule, f.severity) for f in tamper(0, path=None)
                 if f.index == 0}
        assert ("plan-missing", "ERROR") in rules
        # conv2 on the row path: not the pick, and its band blows VMEM
        rules = {(f.rule, f.severity) for f in tamper(1, path="row")
                 if f.index == 1}
        assert ("plan-noncanonical", "WARN") in rules
        assert ("vmem-budget", "ERROR") in rules
        # conv1 asked for row tiles it never runs
        rules = {(f.rule, f.severity) for f in tamper(0, bu=7)
                 if f.index == 0}
        assert rules == {("plan-noncanonical", "WARN")}
