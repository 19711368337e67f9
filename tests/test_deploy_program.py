"""Compile-once deployment API (repro.deploy): BinArrayProgram tests.

The claims under test (ISSUE 5 acceptance bar):
  * ``compile`` + ``execute`` of packed CNN-A and MobileNet are *bit-exact*
    against the legacy per-call ``QuantConfig.fuse_conv`` forwards;
  * ``pick_tile``/packing run only at compile time — the plan-pick counter
    proves zero scheduling decisions inside the jitted execute trace (and
    that the legacy path does keep re-picking per trace);
  * per-layer ``m_active`` schedules (§IV-D generalized): a schedule equals
    the per-layer reference composition, a global int equals the old
    ``QuantConfig(m_active=k)`` path, entries clamp to each layer's M;
  * programs round-trip through checkpoint/manager.py bit-exact, with an
    abstract (eval_shape) program as the restore target;
  * ``layer_stats()`` is a faithful static description (shape chaining,
    exact MAC accounting vs models/cnn.cnn_a_macs).

MobileNet-B2 proper (224², width 1.0) runs in the slow tier; the fast tier
covers the same code paths at reduced width/resolution.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import deploy
from repro.checkpoint.manager import CheckpointManager
from repro.core import binconv
from repro.core import binlinear as bl
from repro.core.binlinear import QuantConfig
from repro.kernels import binary_conv as bck
from repro.models import cnn

jax.config.update("jax_platform_name", "cpu")

QC = QuantConfig(mode="binary", M=2, K_iters=4, interpret=True)
FUSED = QC.replace(fuse_conv=True, use_pallas=True)


@pytest.fixture(scope="module")
def cnn_a():
    params = cnn.init_cnn_a(jax.random.PRNGKey(0))
    bp = cnn.binarize_cnn_a(params, QC)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 48, 48, 3), jnp.float32)
    prog = deploy.compile(bp, "cnn_a", QC, (3, 48, 48, 3))
    return bp, x, prog


@pytest.fixture(scope="module")
def mobilenet_small():
    params = cnn.init_mobilenet(jax.random.PRNGKey(2), width_mult=0.25,
                                n_classes=10)
    qc = QC.replace(K_iters=2)
    bp = cnn.binarize_mobilenet(params, qc)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 32, 3), jnp.float32)
    prog = deploy.compile(bp, "mobilenet", qc, (2, 32, 32, 3))
    return bp, x, prog


class TestCompileExecuteBitExact:
    def test_cnn_a_matches_legacy_fused_forward(self, cnn_a):
        bp, x, prog = cnn_a
        want = cnn.cnn_a_forward(bp, x, FUSED)
        got = deploy.execute(prog, x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_mobilenet_matches_legacy_fused_forward(self, mobilenet_small):
        bp, x, prog = mobilenet_small
        want = cnn.mobilenet_forward(bp, x,
                                     FUSED.replace(K_iters=2))
        got = deploy.execute(prog, x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_compile_from_fp_tree_equals_compile_from_packed(self, cnn_a):
        """compile() binarizes fp trees with the same offline packing the
        binarize_* helpers use -> identical programs, identical logits."""
        bp, x, prog = cnn_a
        params = cnn.init_cnn_a(jax.random.PRNGKey(0))
        prog_fp = deploy.compile(params, "cnn_a", QC, (3, 48, 48, 3))
        for a, b in zip(jax.tree_util.tree_leaves(prog),
                        jax.tree_util.tree_leaves(prog_fp)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            np.asarray(deploy.execute(prog_fp, x)),
            np.asarray(deploy.execute(prog, x)))

    def test_compile_upgrades_legacy_flat_trees_silently(self):
        """Conv params carrying only B_packed compile fine (ensure_tap_packed
        runs at compile time) and never hit the deprecated per-call repack."""
        params = cnn.init_cnn_a(jax.random.PRNGKey(4))
        bp = cnn.binarize_cnn_a(params, QC)
        legacy = {name: {k: v for k, v in layer.items()
                         if k != "B_tap_packed"}
                  for name, layer in bp.items()}
        x = jax.random.normal(jax.random.PRNGKey(5), (2, 48, 48, 3),
                              jnp.float32)
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            prog = deploy.compile(legacy, "cnn_a", QC, (2, 48, 48, 3))
            got = deploy.execute(prog, x)
        for i in prog.instrs:
            if i.kind == "conv":
                assert i.B_tap_packed is not None
        want = cnn.cnn_a_forward(bp, x, FUSED)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_other_batch_sizes_stay_correct(self, cnn_a):
        """Plans are optimized for the compiled batch but valid for any:
        the kernels' tiling bit-exactness covers the clamped plans."""
        bp, _, prog = cnn_a  # compiled for B=3
        x = jax.random.normal(jax.random.PRNGKey(6), (5, 48, 48, 3),
                              jnp.float32)
        want = cnn.cnn_a_forward(bp, x, FUSED)
        got = deploy.execute(prog, x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestMActiveSchedules:
    def test_global_int_matches_quantconfig_path(self, cnn_a):
        bp, x, prog = cnn_a
        for k in (1, 2):
            want = cnn.cnn_a_forward(bp, x, FUSED.replace(m_active=k))
            got = deploy.execute(prog, x, m_active=k)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_schedule_matches_per_layer_composition(self, cnn_a):
        """[M, M-1, ...]-style schedule == composing the legacy per-layer
        calls with each layer's own m_active, bit-exact."""
        bp, x, prog = cnn_a
        sched = (2, 1, 2, 1, 1)
        got = deploy.execute(prog, x, m_active=sched)
        y = binconv.conv2d_relu_pool(bp["conv1"], x, pool=2,
                                     quant=FUSED.replace(m_active=sched[0]))
        y = binconv.conv2d_relu_pool(bp["conv2"], y, pool=6,
                                     quant=FUSED.replace(m_active=sched[1]))
        y = y.reshape(y.shape[0], -1)
        y = jax.nn.relu(bl.apply_linear(
            bp["fc1"], y, FUSED.replace(m_active=sched[2])))
        y = jax.nn.relu(bl.apply_linear(
            bp["fc2"], y, FUSED.replace(m_active=sched[3])))
        want = bl.apply_linear(bp["fc3"], y, FUSED.replace(m_active=sched[4]))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_schedule_clamps_to_packed_levels(self, cnn_a):
        bp, x, prog = cnn_a
        full = deploy.execute(prog, x)
        over = deploy.execute(prog, x, m_active=[7] * len(prog))
        np.testing.assert_array_equal(np.asarray(full), np.asarray(over))
        assert prog.resolve_schedule(7) == tuple(i.M for i in prog.instrs)

    def test_schedule_validation(self, cnn_a):
        _, _, prog = cnn_a
        with pytest.raises(ValueError, match="entries"):
            prog.resolve_schedule([2, 2])
        with pytest.raises(ValueError, match=">= 1"):
            prog.resolve_schedule([0] * len(prog))
        with pytest.raises(ValueError, match=">= 1"):
            prog.resolve_schedule(0)

    def test_fewer_levels_change_logits(self, cnn_a):
        _, x, prog = cnn_a
        full = deploy.execute(prog, x)
        m1 = deploy.execute(prog, x, m_active=1)
        assert not np.allclose(np.asarray(full), np.asarray(m1))


class TestZeroPlanPicksInTrace:
    def test_execute_trace_runs_zero_plan_picks(self, cnn_a):
        """The acceptance counter: tracing execute() performs no pick_tile /
        pick_bu / pick_matmul_plan calls — plans are frozen in the program —
        while tracing the legacy per-call forward re-picks every time."""
        bp, x, prog = cnn_a
        jax.clear_caches()
        bck.reset_plan_pick_count()
        jax.make_jaxpr(
            lambda p, x: deploy.execute(p, x, m_active=2))(prog, x)
        assert bck.plan_pick_count() == 0
        jax.make_jaxpr(
            lambda x: cnn.cnn_a_forward(bp, x, FUSED))(x)
        assert bck.plan_pick_count() > 0

    def test_compile_is_where_the_picks_happen(self):
        params = cnn.init_cnn_a(jax.random.PRNGKey(7))
        bp = cnn.binarize_cnn_a(params, QC)
        bck.reset_plan_pick_count()
        deploy.compile(bp, "cnn_a", QC, (2, 48, 48, 3))
        assert bck.plan_pick_count() > 0


class TestProgramStructure:
    def test_layer_stats_chain_and_macs(self, cnn_a):
        _, _, prog = cnn_a
        stats = prog.layer_stats()
        assert [s["name"] for s in stats] == ["conv1", "conv2", "fc1", "fc2",
                                              "fc3"]
        # shapes chain: each layer's input is the previous output (modulo
        # the declared pre-op)
        assert stats[0]["out_shape"] == [3, 21, 21, 5]
        assert stats[1]["out_shape"] == [3, 3, 3, 150]
        assert stats[2]["in_shape"] == [3, 1350]          # flatten pre-op
        assert stats[-1]["out_shape"] == [3, 43]
        # MAC accounting is exact vs the hand-derived count
        assert sum(s["macs"] for s in stats) == cnn.cnn_a_macs()

    def test_plans_respect_vmem_budget_default(self, mobilenet_small):
        _, _, prog = mobilenet_small
        for s in prog.layer_stats():
            if s["kind"] in ("conv", "dwconv"):
                assert s["vmem_bytes"] <= bck.DEFAULT_VMEM_BUDGET, s

    def test_quant_overrides_freeze_into_plan(self):
        params = cnn.init_cnn_a(jax.random.PRNGKey(8))
        qc = QC.replace(conv_batch_tile=2, conv_vmem_budget=2 * 2**20)
        prog = deploy.compile(params, "cnn_a", qc, (4, 48, 48, 3))
        assert prog.instrs[1].plan.nb == 2  # conv2: forced batch tile
        x = jax.random.normal(jax.random.PRNGKey(9), (4, 48, 48, 3),
                              jnp.float32)
        base = deploy.compile(params, "cnn_a", QC, (4, 48, 48, 3))
        np.testing.assert_array_equal(
            np.asarray(deploy.execute(prog, x)),
            np.asarray(deploy.execute(base, x)))  # tiling never changes math

    def test_abstract_program_matches_concrete_structure(self, cnn_a):
        _, _, prog = cnn_a
        ab = deploy.abstract_program("cnn_a", QC, (3, 48, 48, 3))
        # eval_shape cannot execute the golden probe, so abstract programs
        # carry golden=None (load_program re-attaches the record from the
        # checkpoint manifest); structure matches modulo that field
        assert ab.golden is None and prog.golden is not None
        assert (jax.tree_util.tree_structure(ab)
                == jax.tree_util.tree_structure(
                    dataclasses.replace(prog, golden=None)))
        assert ab.layer_stats() == prog.layer_stats()
        for got, want in zip(jax.tree_util.tree_leaves(ab),
                             jax.tree_util.tree_leaves(prog)):
            assert got.shape == want.shape and got.dtype == want.dtype

    def test_program_is_jit_transparent(self, cnn_a):
        """The program pytree crosses jit boundaries: plans ride in the
        treedef, weights are leaves."""
        _, x, prog = cnn_a
        leaves, treedef = jax.tree_util.tree_flatten(prog)
        rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
        np.testing.assert_array_equal(
            np.asarray(deploy.execute(rebuilt, x)),
            np.asarray(deploy.execute(prog, x)))


class TestCheckpointRoundTrip:
    def test_program_roundtrip_bit_exact(self, mobilenet_small, tmp_path):
        """save_program -> load_program (abstract target) is bit-exact, both
        in the packed buffers and in the executed logits."""
        _, x, prog = mobilenet_small
        mgr = CheckpointManager(str(tmp_path))
        deploy.save_program(mgr, 0, prog)
        like = deploy.abstract_program(
            "mobilenet", QC.replace(K_iters=2), (2, 32, 32, 3),
            width_mult=0.25, n_classes=10)
        back = deploy.load_program(mgr, 0, like)
        for a, b in zip(jax.tree_util.tree_leaves(prog),
                        jax.tree_util.tree_leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            np.asarray(deploy.execute(back, x)),
            np.asarray(deploy.execute(prog, x)))

    def test_roundtrip_preserves_plans_and_stats(self, cnn_a, tmp_path):
        _, _, prog = cnn_a
        mgr = CheckpointManager(str(tmp_path))
        deploy.save_program(mgr, 3, prog, extra={"note": "cnn-a"})
        back = deploy.load_program(
            mgr, 3, deploy.abstract_program("cnn_a", QC, (3, 48, 48, 3)))
        assert back.layer_stats() == prog.layer_stats()
        assert [i.plan for i in back.instrs] == [i.plan for i in prog.instrs]


@pytest.mark.slow
class TestMobileNetB2:
    """The real CNN-B2 (width 1.0, 224²) through compile/execute — nightly
    tier (interpret-mode kernels at 224² are minutes-scale on CPU)."""

    def test_b2_compile_execute_matches_legacy_and_roundtrips(self, tmp_path):
        params = cnn.init_mobilenet(jax.random.PRNGKey(0), width_mult=1.0,
                                    n_classes=1000)
        qc = QuantConfig(mode="binary", M=2, K_iters=1, interpret=True)
        bp = cnn.binarize_mobilenet(params, qc)
        # golden=False: each golden rung is another minutes-scale 224²
        # interpret execute, and this test never self-tests
        prog = deploy.compile(bp, "mobilenet", qc, (1, 224, 224, 3),
                              golden=False)
        # the early maps must be row-tiled (VMEM) and the 7² back half
        # batch-planned — the compile decisions the paper's §IV-E predicts
        stats = {s["name"]: s for s in prog.layer_stats()}
        assert stats["pw0"]["plan"]["bu"] < stats["pw0"]["out_shape"][1]
        assert stats["pw11"]["plan"]["bu"] == 7
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 224, 224, 3),
                              jnp.float32)
        want = cnn.mobilenet_forward(
            bp, x, qc.replace(fuse_conv=True, use_pallas=True))
        got = deploy.execute(prog, x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # serialization round-trip of the full B2 program
        mgr = CheckpointManager(str(tmp_path))
        deploy.save_program(mgr, 0, prog)
        back = deploy.load_program(
            mgr, 0, deploy.abstract_program("mobilenet", qc,
                                            (1, 224, 224, 3)))
        for a, b in zip(jax.tree_util.tree_leaves(prog),
                        jax.tree_util.tree_leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# A compiled forward cut down to one op of each ownership rule of
# executor.op_owners; the fused computation's body is not a device op.
HLO = """HloModule jit__execute_jit

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %negate.1 = f32[4]{0} negate(%param_0), metadata={op_name="jit(_execute_jit)/binarray.i01.fc/neg"}
}

ENTRY %main.9 (w.1: u8[4], x.1: f32[4]) -> f32[4] {
  %w.1 = u8[4]{0} parameter(0), metadata={op_name="program.instrs[1].B_packed"}
  %x.1 = f32[4]{0} parameter(1), metadata={op_name="x"}
  %constant.1 = f32[] constant(0), metadata={op_name="jit(_execute_jit)/binarray.i00.c0/pad"}
  %constant.2 = pred[4]{0} constant({1, 0, 1, 0})
  %copy.1 = f32[4]{0} copy(%x.1), metadata={op_name="x"}
  %pad.3 = f32[4]{0} pad(%copy.1, %constant.1), padding=0_0, metadata={op_name="jit(_execute_jit)/binarray.i00.c0/jit(binary_conv2d_pallas)/pad"}
  %binary_conv2d_pallas.2 = f32[4]{0} custom-call(%pad.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(_execute_jit)/binarray.i00.c0/jit(binary_conv2d_pallas)/binary_conv2d_pallas"}
  %copy-start = (u8[4]{0}, u8[4]{0}, u32[]) copy-start(%w.1)
  %copy-done = u8[4]{0} copy-done(%copy-start)
  %copy.2 = u8[4]{0} copy(%copy-done), metadata={op_name="program.instrs[1].B_packed"}
  %copy-start.1 = (pred[4]{0}, pred[4]{0}, u32[]) copy-start(%constant.2)
  %copy-done.1 = pred[4]{0} copy-done(%copy-start.1)
  %bitcast.1 = f32[4]{0} bitcast(%binary_conv2d_pallas.2)
  %tuple.1 = (f32[4]{0}, u8[4]{0}) tuple(%bitcast.1, %copy.2)
  ROOT %fusion = f32[4]{0} fusion(%bitcast.1, %copy.2, %copy-done.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(_execute_jit)/binarray.i01.fc/neg"}
}
"""


class TestOpOwners:
    def test_ownership_rules_on_compiled_text(self):
        from repro.deploy.executor import _hlo_owners

        assert _hlo_owners(HLO, ["c0", "fc"]) == {
            "copy.1": "input",                        # (c) op_name "x"
            "pad.3": "i00.c0",                        # (a) its scope
            "binary_conv2d_pallas.2": "i00.c0",
            "copy-start": "i01.fc",                   # (d) its operand's
            "copy-done": "i01.fc",
            "copy.2": "i01.fc",                       # (b) the weights'
            "copy-start.1": "unattributed",           # (e)
            "copy-done.1": "unattributed",
            "fusion": "i01.fc",
        }

    def test_each_scope_maps_to_its_instruction(self):
        """On the CPU, in interpret mode: every instruction of a small
        program owns ops of the compiled forward under its own index."""
        from repro.deploy.executor import op_owners
        from repro.testing.scenarios import tiny_cnn_program

        prog = tiny_cnn_program(batch=2)
        x = jax.ShapeDtypeStruct(prog.input_shape, jnp.float32)
        owners = op_owners(prog, x)
        want = {f"i{i:02d}.{instr.name}"
                for i, instr in enumerate(prog.instrs)}
        assert want <= set(owners.values()) <= want | {"input",
                                                        "unattributed"}
