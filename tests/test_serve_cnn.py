"""SLO-governed CNN serving: ladder, admission control, SLO feedback.

The degradation-ladder contract (ISSUE 8): every rung is strictly cheaper
than the one above it, every rung's outputs are bit-exact against
``deploy.execute`` at the same schedule on the same padded batch, lower
level counts run measurably faster, and the controller degrades under
latency pressure and recovers to full-M when it clears — while admission
sheds explicitly (named reasons, counted) instead of queueing unboundedly.

Everything here is deterministic: the service runs on a
``testing.faults.ManualClock`` and latency pressure is synthesized by
advancing that clock from inside a stub ``execute_fn`` — no wall-clock
sleeps, no flaky thresholds (the one real-time check, conv kernel latency
vs level count, compares medians of repeated jitted calls).
"""
import time

import jax
import numpy as np
import pytest

from repro import deploy
from repro.serve_cnn import (STAGES, CNNService, SLOConfig,
                             default_ladder, schedule_cost)
from repro.testing.faults import ManualClock
from repro.testing.scenarios import tiny_cnn_program

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def program():
    return tiny_cnn_program(batch=4)


def _images(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((8, 8, 3), dtype=np.float32)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# the §IV-D degradation ladder
# ---------------------------------------------------------------------------

class TestLadder:
    def test_strictly_decreasing_cost_full_m_first(self, program):
        ladder = default_ladder(program)
        assert ladder[0] == program.resolve_schedule(None)
        costs = [schedule_cost(program, s) for s in ladder]
        assert all(a > b for a, b in zip(costs, costs[1:])), costs
        assert len(ladder) >= 2     # M=2 program must have a reduced rung

    def test_single_level_program_gets_one_rung(self):
        prog = tiny_cnn_program(batch=2, m=1)
        assert default_ladder(prog) == (prog.resolve_schedule(None),)

    def test_every_rung_bit_exact_vs_execute(self, program):
        """A request served at rung k returns exactly what deploy.execute
        produces at that rung's schedule on the same padded batch — the
        ladder changes cost, never numerics."""
        for rung, sched in enumerate(default_ladder(program)):
            svc = CNNService(program, initial_rung=rung, batch_size=4)
            reqs = [svc.submit(im) for im in _images(3, seed=rung)]
            done = svc.drain()
            assert [r.status for r in done] == ["done"] * 3
            ref = np.asarray(deploy.execute(
                program, svc.last_batch, sched))
            for r in done:
                assert r.m_schedule == sched and r.rung == rung
                assert np.array_equal(r.logits, ref[r.batch_index]), rung
            assert reqs[0] is done[0]

    def test_lower_m_active_lower_latency(self, program):
        """§IV-D's point: fewer levels, fewer MXU passes, faster batch.
        Median of repeated steady-state jitted calls, full-M vs the bottom
        rung (every layer at 1 of 2 levels — half the matmul work)."""
        ladder = default_ladder(program)
        x = np.stack(_images(4))

        def median_t(sched, n=7):
            deploy.execute(program, x, sched).block_until_ready()  # compile
            ts = []
            for _ in range(n):
                t0 = time.perf_counter()
                deploy.execute(program, x, sched).block_until_ready()
                ts.append(time.perf_counter() - t0)
            return sorted(ts)[n // 2]

        t_full, t_low = median_t(ladder[0]), median_t(ladder[-1])
        # direction only, with headroom for CPU-interpret noise: the cost
        # model says 2x — flag only a real inversion
        assert t_low < t_full * 1.25, (t_low, t_full)


# ---------------------------------------------------------------------------
# admission control: explicit sheds, bounded queue
# ---------------------------------------------------------------------------

class TestAdmission:
    def test_rejects_wrong_image_shape(self, program):
        svc = CNNService(program)
        with pytest.raises(ValueError, match=r"\(9, 8, 3\).*\(8, 8, 3\)"):
            svc.submit(np.zeros((9, 8, 3), np.float32))

    def test_expired_deadline_shed_at_admit(self, program):
        clock = ManualClock(100.0)
        svc = CNNService(program, clock=clock)
        r = svc.submit(_images(1)[0], deadline_s=99.0)
        assert r.status == "shed" and r.shed_reason == "deadline_expired"
        assert svc.stats["shed"]["deadline_expired"] == 1
        assert svc.stats["shed_count"] == 1
        assert not svc.queue

    def test_deadline_expiring_in_queue_shed_at_dispatch(self, program):
        clock = ManualClock()
        svc = CNNService(program, clock=clock, batch_size=2)
        ok = svc.submit(_images(1)[0])
        tight = svc.submit(_images(1)[0], deadline_s=clock() + 0.5)
        clock.advance(1.0)          # deadline passes while queued
        finished = svc.step()
        assert tight in finished
        assert tight.status == "shed"
        assert tight.shed_reason == "deadline_expired"
        assert ok.status == "done"  # the live request still served

    def test_queue_full_backpressure(self, program):
        svc = CNNService(program, max_queue=3)
        results = [svc.submit(im) for im in _images(5)]
        assert [r.status for r in results[:3]] == ["queued"] * 3
        assert all(r.status == "shed" and r.shed_reason == "queue_full"
                   for r in results[3:])
        assert svc.stats["shed"]["queue_full"] == 2
        svc.drain()
        assert svc.stats["completed"] == 3

    def test_drain_raises_instead_of_spinning(self, program):
        svc = CNNService(program, batch_size=1, max_queue=8)
        for im in _images(3):
            svc.submit(im)
        with pytest.raises(RuntimeError, match="failed to drain"):
            svc.drain(max_steps=1)


# ---------------------------------------------------------------------------
# SLO feedback: degrade under pressure, recover when it clears
# ---------------------------------------------------------------------------

def _pressured_service(program, slow_s, *, target_ms=10.0, clock=None):
    """Service whose executor advances the shared virtual clock by
    ``slow_s[i]`` on call i — deterministic latency pressure."""
    clock = clock or ManualClock()
    calls = [0]

    def execute_fn(prog, x, sched, *, interpret=None):
        dt = slow_s[min(calls[0], len(slow_s) - 1)]
        calls[0] += 1
        clock.advance(dt)
        return deploy.execute(prog, x, sched, interpret=interpret)

    svc = CNNService(
        program,
        slo=SLOConfig(target_ms=target_ms, window=16, min_samples=4,
                      recover_at=0.5, recover_after=2),
        batch_size=4, clock=clock, sleep=clock.sleep,
        execute_fn=execute_fn)
    return svc, clock


class TestSLOController:
    def test_degrades_under_pressure_then_recovers(self, program):
        ladder = default_ladder(program)
        # 6 slow batches (5x target), then fast forever
        svc, clock = _pressured_service(program, [0.05] * 6 + [0.0])
        rungs = []
        for i in range(16):
            for im in _images(4, seed=i):
                svc.submit(im)
            svc.step()
            rungs.append(svc.controller.rung)
        assert max(rungs) > 0, rungs                      # degraded
        assert rungs[-1] == 0, rungs                      # fully recovered
        hist = svc.stats["rung_hist"]
        assert set(hist) == set(range(len(ladder))), hist  # walked the ladder
        # degraded batches still served (degrade-before-shed)
        assert svc.stats["completed"] == svc.stats["admitted"]

    def test_static_service_never_moves(self, program):
        svc, _ = _pressured_service(program, [0.05], target_ms=None)
        for i in range(6):
            for im in _images(4, seed=i):
                svc.submit(im)
            svc.step()
        assert svc.stats["rung_hist"] == {0: 6}
        assert not svc.controller.shedding

    def test_shedding_is_backpressure_not_outage(self, program):
        """Past the last rung the service sheds load that would *queue*,
        but keeps serving a batch's worth — otherwise no latency samples
        ever arrive and shedding latches forever (the stuck-queue bug this
        tier exists to prevent)."""
        svc, clock = _pressured_service(program, [0.05] * 10 + [0.0])
        shed_seen = recovered = False
        for i in range(40):
            for im in _images(8, seed=i):  # 2x service rate: overload
                svc.submit(im)
            svc.step()
            shed_seen = shed_seen or svc.controller.shedding
            if (shed_seen and not svc.controller.shedding
                    and svc.controller.rung == 0):
                recovered = True
                break
        assert shed_seen
        assert recovered                           # shedding never latched
        assert svc.stats["shed"]["slo_shed"] > 0
        assert svc.stats["completed"] > 0          # kept serving throughout
        svc.drain()
        assert not svc.queue

    def test_rung_change_clears_the_window(self, program):
        """Decisions at a new rung must be based on latencies measured at
        that rung — stale pre-degradation samples would cascade the
        controller straight to shed."""
        svc, clock = _pressured_service(program, [0.05] + [0.0])
        for i in range(2):
            for im in _images(4, seed=i):
                svc.submit(im)
            svc.step()
        assert svc.controller.rung == 1            # one decision, one rung
        # only the post-change step's 4 samples remain
        assert len(svc.controller._window) == 4


# ---------------------------------------------------------------------------
# where a step's time goes: stage seconds, queue waits, batch fill, step ids
# ---------------------------------------------------------------------------

class TestStageAccounting:
    def test_stage_seconds_sum_to_the_steps(self, program, monkeypatch):
        """Each stage's seconds on the service clock land in its own key;
        the watchdog's self-test and a retry's backoff included."""
        from repro.deploy import selftest

        clock = ManualClock()
        calls = [0]

        def execute_fn(prog, x, sched, *, interpret=None):
            calls[0] += 1
            clock.advance(0.01)
            if calls[0] == 1:
                raise RuntimeError("transient")
            return deploy.execute(prog, x, sched, interpret=interpret)

        def slow_self_test(prog, rungs=None):
            clock.advance(0.2)
            return len(rungs)

        monkeypatch.setattr(selftest, "self_test", slow_self_test)
        svc = CNNService(program, batch_size=4, clock=clock,
                         sleep=clock.sleep, backoff_s=0.5,
                         execute_fn=execute_fn, selftest_every=1)
        for im in _images(6):
            svc.submit(im)
        t0 = clock()
        done = svc.drain()
        assert [r.status for r in done] == ["done"] * 6
        st = svc.stats["stage_s"]
        assert tuple(st) == STAGES
        assert st["watchdog"] == pytest.approx(2 * 0.2)    # two steps
        # three dispatches (one failed) and one backoff
        assert st["execute"] == pytest.approx(3 * 0.01 + 0.5)
        assert st["assemble"] == st["screen"] == st["fetch"] == 0.0
        assert st["respond"] == 0.0
        assert sum(st.values()) == pytest.approx(clock() - t0)

    def test_queue_wait_is_dispatch_minus_submit(self, program):
        clock = ManualClock(10.0)
        svc = CNNService(program, batch_size=2, clock=clock)
        a = svc.submit(_images(1)[0])
        clock.advance(0.25)
        b = svc.submit(_images(1)[0])
        clock.advance(0.5)
        svc.step()
        assert a.dispatch_t == b.dispatch_t == 10.75
        for r in (a, b):
            assert r.queue_wait_s == r.dispatch_t - r.submit_t
        assert a.queue_wait_s == pytest.approx(0.75)
        assert b.queue_wait_s == pytest.approx(0.5)

    def test_batch_fill_on_a_partial_batch(self, program):
        svc = CNNService(program, batch_size=4, clock=ManualClock())
        for im in _images(3):
            svc.submit(im)
        svc.step()
        st = svc.stats
        assert (st["batch_slots"], st["images_batched"]) == (4, 3)
        for im in _images(4):
            svc.submit(im)
        svc.step()
        st = svc.stats
        assert st["images_batched"] / st["batch_slots"] == 7 / 8

    def test_step_ids_link_requests_to_steps(self, program):
        clock = ManualClock()
        svc = CNNService(program, batch_size=2, clock=clock)
        assert svc.step() == []                  # an empty step is a step
        reqs = [svc.submit(im) for im in _images(5)]
        late = svc.submit(_images(1)[0], deadline_s=clock() + 0.5)
        clock.advance(1.0)
        svc.drain()
        assert [r.step for r in reqs] == [1, 1, 2, 2, 3]
        assert late.status == "shed" and late.step is None

    def test_step_and_stage_spans_in_a_profiler_trace(self, program,
                                                      tmp_path):
        """Under ``jax.profiler`` each step is one ``binarray.serve.step``
        span with its step id, rung and fill, holding one span per stage."""
        import glob

        svc = CNNService(program, batch_size=4, selftest_every=1)
        for im in _images(6):
            svc.submit(im)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        svc.drain()
        jax.profiler.stop_trace()
        path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
        events = [e for plane in
                  jax.profiler.ProfileData.from_file(path).planes
                  for line in plane.lines for e in line.events
                  if e.name.startswith("binarray.serve.")]
        steps = [e for e in events if e.name == "binarray.serve.step"]
        assert [dict(e.stats) for e in steps] == [
            {"step": 0, "rung": 0, "filled": 4},
            {"step": 1, "rung": 0, "filled": 2}]
        for s in steps:
            inside = [e.name.rsplit(".", 1)[1] for e in events
                      if e is not s and s.start_ns <= e.start_ns
                      and e.start_ns + e.duration_ns
                      <= s.start_ns + s.duration_ns]
            assert inside == list(STAGES)


# ---------------------------------------------------------------------------
# LM server: the same admission contract (satellite)
# ---------------------------------------------------------------------------

class TestLMServerDeadline:
    @pytest.fixture(scope="class")
    def server(self):
        from repro.configs import base as cb
        from repro.launch.serve import Server
        from repro.models import api

        cfg = cb.reduced(cb.get_config("gemma_2b")).replace(dtype="float32")
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        return Server(cfg, params, max_batch=2, max_len=32)

    def test_expired_deadline_rejected_and_counted(self, server):
        from repro.launch.serve import Request

        req = Request(prompt=np.array([3, 7], np.int32), max_new_tokens=1,
                      deadline_s=time.monotonic() - 1.0)
        before = server.stats["shed_count"]
        assert server.admit(req) is False
        assert server.stats["shed_count"] == before + 1
        assert all(s is None for s in server.slots)  # no slot consumed

    def test_live_deadline_admitted(self, server):
        from repro.launch.serve import Request

        req = Request(prompt=np.array([3, 7], np.int32), max_new_tokens=1,
                      deadline_s=time.monotonic() + 60.0)
        assert server.admit(req) is True
        server.run_until_done()
        assert server.stats["shed_count"] == 1      # unchanged by success
