"""Multi-process autotune: process 0 tunes and the other processes adopt
the tuned fusion-threshold/cycle-time (the reference coordinator's
parameter broadcast, parameter_manager.cc:66-81). Under rank-0
negotiation the values ride every CycleResponse; in the non-negotiated
fallback they sync via the count-scheduled allgather
(_sync_tuned_params, HOROVOD_AUTOTUNE_SYNC_COLLECTIVES) the TestSyncUnit
cases exercise."""

import numpy as np

from horovod_tpu.run.launch import run

_ENV = {
    "JAX_PLATFORMS": "cpu",
    "HOROVOD_AUTOTUNE": "1",
    "HOROVOD_AUTOTUNE_SYNC_COLLECTIVES": "4",
}


class TestAutotuneSync:
    def test_processes_adopt_identical_tuned_params(self):
        def fn():
            import numpy as np
            import horovod_tpu as hvd
            # one suggestion per flush cycle, so tuning definitely moves
            # the knobs within a short run
            from horovod_tpu.utils import autotune as at
            at.CYCLES_PER_SAMPLE = 1
            at.SAMPLES_PER_STEP = 1
            hvd.init()
            for i in range(9):
                hvd.allreduce(np.ones((4,), np.float32), name=f"t{i}",
                              average=False)
            from horovod_tpu.common import state
            cfg = state.global_state().config
            out = (int(cfg.fusion_threshold),
                   round(float(cfg.cycle_time_ms), 3))
            hvd.shutdown()
            return out

        results = run(fn, num_proc=2, env=_ENV)
        # every process adopted tuned (non-default) values: rank 0 tunes,
        # the others mirror the knobs off the coordinator's responses.
        # Exact equality across processes is not asserted — a worker's
        # mirror is as fresh as its last applied response, and rank 0 may
        # have staged a newer suggestion since (the reference has the
        # same propagation lag between coordinator tune steps and worker
        # parameter updates, parameter_manager.cc:66-81).
        default = (64 * 1024 * 1024, 5.0)
        for res in results:
            assert res != default, results

    def test_results_stay_correct_while_tuning(self):
        def fn():
            import numpy as np
            import horovod_tpu as hvd
            from horovod_tpu.utils import autotune as at
            at.CYCLES_PER_SAMPLE = 1
            at.SAMPLES_PER_STEP = 1
            hvd.init()
            vals = []
            for i in range(10):
                out = hvd.allreduce(np.full((3,), float(i), np.float32),
                                    average=False, name=f"v{i}")
                vals.append(float(np.asarray(out)[0]))
            hvd.shutdown()
            return vals

        results = run(fn, num_proc=2, env=_ENV)
        want = [2.0 * i for i in range(10)]
        assert results[0] == want and results[1] == want, results


class TestSyncUnit:
    def test_sync_applies_row0(self, hvd):
        import horovod_tpu
        coord = horovod_tpu.common.state.global_state().coordinator
        coord._proposed_params = (123456.0, 7.5)
        coord._sync_tuned_params()
        cfg = horovod_tpu.common.state.global_state().config
        assert cfg.fusion_threshold == 123456
        assert cfg.cycle_time_ms == 7.5
        assert coord._proposed_params is None

    def test_sync_roundtrips_large_threshold(self, hvd):
        # thresholds >= 2 GiB must survive the int32 wire format exactly
        import horovod_tpu
        coord = horovod_tpu.common.state.global_state().coordinator
        coord._proposed_params = (float(3 * 1024 ** 3 + 12345), 2.0)
        coord._sync_tuned_params()
        cfg = horovod_tpu.common.state.global_state().config
        assert cfg.fusion_threshold == 3 * 1024 ** 3 + 12345

    def test_sync_clears_pending_adoption(self, hvd):
        import horovod_tpu
        coord = horovod_tpu.common.state.global_state().coordinator
        coord._proposed_params = (1024.0, 3.0)
        coord._autotune_pending_adoption = True
        coord._sync_tuned_params()
        assert coord._autotune_pending_adoption is False

    def test_sync_marks_adoption_flush(self, hvd):
        # the adoption flush must be excluded from autotune scoring
        import horovod_tpu
        coord = horovod_tpu.common.state.global_state().coordinator
        coord._adopted_this_flush = False
        coord._proposed_params = (2048.0, 4.0)
        coord._sync_tuned_params()
        assert coord._adopted_this_flush is True

    def test_sync_without_proposal_keeps_current(self, hvd):
        import horovod_tpu
        coord = horovod_tpu.common.state.global_state().coordinator
        cfg = horovod_tpu.common.state.global_state().config
        before = (cfg.fusion_threshold, cfg.cycle_time_ms)
        coord._sync_tuned_params()
        assert (cfg.fusion_threshold, cfg.cycle_time_ms) == before


class TestPassiveScoring:
    """Round-4 passive scorer: a cycle is scored as its batch bytes over
    the wall time to the NEXT flush — timestamps the loop already has
    (the reference ParameterManager's approach, operations.cc:1553-1555,
    no extra synchronization). Scoring must not force device syncs, and
    idle gaps between flushes must not be scored."""

    def _attach(self, seed=3):
        import horovod_tpu
        from horovod_tpu.utils import autotune as at

        state = horovod_tpu.common.state.global_state()
        coord, cfg = state.coordinator, state.config
        saved = (coord.autotuner, coord._autotune_defer,
                 coord._at_prev_flush, coord._autotune_pending_adoption)
        tuner = at.Autotuner(cfg, seed=seed)
        coord.autotuner = tuner
        coord._autotune_defer = False
        coord._at_prev_flush = None
        coord._autotune_pending_adoption = False
        calls = []
        orig = tuner.record_cycle
        tuner.record_cycle = lambda b, d: (calls.append((b, d)),
                                           orig(b, d))[1]

        def restore():
            (coord.autotuner, coord._autotune_defer,
             coord._at_prev_flush,
             coord._autotune_pending_adoption) = saved
        return coord, tuner, calls, restore

    def _burst(self, coord, hvd, tag, i):
        import numpy as np
        with coord.hold_cycle():
            h = hvd.allreduce_async(np.ones((2, 8), np.float32),
                                    average=False, name=f"{tag}.{i}")
        coord.flush()
        hvd.synchronize(h)

    def test_scores_previous_cycle_over_inter_flush_window(self, hvd):
        coord, tuner, calls, restore = self._attach()
        try:
            self._burst(coord, hvd, "pas", 0)   # seeds the window
            self._burst(coord, hvd, "pas", 1)   # scores burst 0
            assert len(calls) == 1
            nbytes, dur = calls[0]
            assert nbytes == 2 * 8 * 4
            assert 0 < dur < 1.0
        finally:
            restore()

    def test_scoring_never_blocks_on_device(self, hvd):
        import jax
        coord, tuner, calls, restore = self._attach()
        blocked = []
        orig = jax.block_until_ready
        jax.block_until_ready = lambda x: (blocked.append(1), orig(x))[1]
        try:
            self._burst(coord, hvd, "nosync", 0)
            self._burst(coord, hvd, "nosync", 1)
            assert len(calls) == 1
            assert not blocked, \
                "passive scoring must not force a device sync"
        finally:
            jax.block_until_ready = orig
            restore()

    def test_idle_gap_is_not_scored(self, hvd):
        import time
        coord, tuner, calls, restore = self._attach()
        try:
            self._burst(coord, hvd, "idle", 0)
            time.sleep(1.05)                    # > idle cap (1s default)
            self._burst(coord, hvd, "idle", 1)  # gap: skipped
            assert calls == []
            self._burst(coord, hvd, "idle", 2)  # quick: scored
            assert len(calls) == 1 and calls[0][1] < 1.0
        finally:
            restore()

    def test_window_resets_when_knobs_move(self, hvd):
        from horovod_tpu.utils import autotune as at
        saved = (at.CYCLES_PER_SAMPLE, at.SAMPLES_PER_STEP)
        at.CYCLES_PER_SAMPLE = 1
        at.SAMPLES_PER_STEP = 1
        coord, tuner, calls, restore = self._attach()
        try:
            self._burst(coord, hvd, "move", 0)
            self._burst(coord, hvd, "move", 1)  # scores + moves knobs
            assert len(calls) == 1
            # knob change restarts the window: the next flush seeds, the
            # one after scores — an interval straddling old/new knobs is
            # never attributed to either
            assert coord._at_prev_flush is None
            self._burst(coord, hvd, "move", 2)
            assert coord._at_prev_flush is not None
        finally:
            restore()
            (at.CYCLES_PER_SAMPLE, at.SAMPLES_PER_STEP) = saved


class TestFreeze:
    def test_freeze_adopts_best_and_stops_scoring(self, hvd):
        """Autotuner.freeze: the reference ParameterManager's converged
        state (tune, then run at the best values with scoring off,
        parameter_manager.cc:155-210). After freeze, record_cycle is a
        no-op and the knobs hold the best scored point."""
        from horovod_tpu.common.config import HorovodConfig
        from horovod_tpu.utils import autotune as at

        cfg = HorovodConfig.from_env()
        tuner = at.Autotuner(cfg, seed=1)
        # score two points directly through the engine, then freeze
        tuner._engine.record(1 << 20, 5.0, 10.0)
        tuner._engine.record(8 << 20, 7.0, 50.0)
        best = tuner.freeze()
        assert best is not None
        assert (tuner.threshold, tuner.cycle_time_ms) == (best[0], best[1])
        assert best[2] == 50.0 and tuner.threshold == 8 << 20
        # scoring is off: many cycles never advance the knobs
        for _ in range(200):
            assert tuner.record_cycle(1 << 20, 0.001) is False
        assert tuner.threshold == 8 << 20

    def test_freeze_clamps_boundary_cycle(self, hvd):
        """A best point parked at the top of CYCLE_BOUNDS_MS is a
        flat-score artifact of passive scoring (r5 adopted 99.22 ms this
        way), not a tuned value: freeze keeps the threshold but falls
        back to the pre-tune default cycle and says so."""
        from horovod_tpu.common.config import HorovodConfig
        from horovod_tpu.utils import autotune as at

        cfg = HorovodConfig.from_env()
        default_cycle = float(cfg.cycle_time_ms)
        tuner = at.Autotuner(cfg, seed=4)
        tuner._engine.record(8 << 20, 99.22, 50.0)  # the r5 adoption
        best = tuner.freeze()
        assert best is not None and best[1] == 99.22
        assert tuner.threshold == 8 << 20          # threshold kept
        assert tuner.cycle_time_ms == default_cycle
        assert tuner.cycle_boundary_clamped is True

        # interior points are untouched (and the flag stays down)
        tuner2 = at.Autotuner(cfg, seed=5)
        upper = (at.CYCLE_BOUNDS_MS[1]
                 - 2 * at.CYCLE_BOUNDARY_FRAC
                 * (at.CYCLE_BOUNDS_MS[1] - at.CYCLE_BOUNDS_MS[0]))
        tuner2._engine.record(4 << 20, upper, 50.0)
        tuner2.freeze()
        assert tuner2.cycle_time_ms == upper
        assert tuner2.cycle_boundary_clamped is False

    def test_coordinator_freeze_applies_config(self, hvd):
        import horovod_tpu
        from horovod_tpu.utils import autotune as at

        state = horovod_tpu.common.state.global_state()
        coord = state.coordinator
        cfg = state.config
        saved = (cfg.fusion_threshold, cfg.cycle_time_ms,
                 coord.autotuner, coord._autotune_defer)
        try:
            coord.autotuner = at.Autotuner(cfg, seed=2)
            coord._autotune_defer = False
            coord.autotuner._engine.record(4 << 20, 9.0, 42.0)
            best = coord.freeze_autotune()
            assert best is not None
            assert cfg.fusion_threshold == 4 << 20
            assert cfg.cycle_time_ms == 9.0
        finally:
            (cfg.fusion_threshold, cfg.cycle_time_ms,
             coord.autotuner, coord._autotune_defer) = saved
