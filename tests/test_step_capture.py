"""Whole-step static capture with buffer donation (ISSUE 11).

The acceptance surface for ``core/step_capture.py`` /
``paddle.jit.capture_step``:

* **one executable per signature** — N supervised steps compile exactly
  one XLA program (``jit.compiles_total == 1``) and a warm captured step
  performs exactly ONE dispatch (zero eager op dispatches leak around the
  program call); shape changes and ``set_flags`` writes re-trace instead
  of serving a stale executable;
* **parity** — the captured trajectory tracks the eager tier at ulp
  scale on both optimizer legs (fp32 Adam and int8 block-quantized
  moments). NOT bitwise, by measurement and by construction: XLA
  contracts ``a*x + b*y`` to FMA inside the fused whole-step kernel,
  which per-op eager dispatch cannot express (micro-repro:
  ``jit(lambda: b1*m + (1-b1)*g)`` differs from the op-by-op value by
  1 ulp, with ``--xla_allow_excess_precision=false`` making no
  difference). The forward alone IS bitwise — pinned on step 1;
* **bitwise within the captured tier** — identical captured runs are
  bit-identical, kill-at-step resume under the PR 10 supervisor running
  the captured path continues bit-identically, and donation never leaves
  state readable-after-donate (save → restore → continue);
* **NaN gate** — a non-finite loss withholds the folded update
  in-program: parameters, moments, step count bitwise untouched;
* **clean bypasses** — seams (live trace, dispatch.* fault injection,
  ``off``) run the eager tier with identical semantics, counted by
  reason; per-step host writes into carried state (``scheduler.step()``
  inside the captured update) raise typed, never serve stale constants.
"""

import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import step_capture as sc
from paddle_tpu.core.tensor import Parameter
from paddle_tpu.resilience import faults
from paddle_tpu.resilience.trainer import TrainingSupervisor


@pytest.fixture(autouse=True)
def _capture_on(monkeypatch):
    """The suite default is PADDLE_TPU_STEP_CAPTURE=off (conftest); this
    module is the captured tier's own suite."""
    monkeypatch.setenv("PADDLE_TPU_STEP_CAPTURE", "auto")
    sc.stats_clear()
    yield
    sc.stats_clear()


def build_run(seed=7, *, q8=False, n=32, batch_size=8, shuffle=True):
    """One complete training setup, as a fresh process would construct it
    (the test_train_chaos pattern: param names must be deterministic per
    construction order)."""
    Parameter._param_counter = 0
    paddle.seed(seed)
    net = paddle.nn.Linear(8, 4)
    kw = dict(moment_dtype="int8") if q8 else {}
    opt = paddle.optimizer.Adam(learning_rate=0.05,
                                parameters=net.parameters(), **kw)
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(n, 8)).astype(np.float32)
    ys = rng.normal(size=(n, 4)).astype(np.float32)
    ds = paddle.io.TensorDataset([paddle.to_tensor(xs), paddle.to_tensor(ys)])
    loader = paddle.io.DataLoader(ds, batch_size=batch_size, shuffle=shuffle)
    loss_fn = paddle.nn.MSELoss()

    def step_fn(batch):
        x, y = batch
        loss = loss_fn(net(x), y)
        loss.backward()
        return loss

    def update_fn():
        opt.step()
        opt.clear_grad()

    def clear_fn():
        opt.clear_grad()

    from types import SimpleNamespace
    return SimpleNamespace(net=net, opt=opt, loader=loader, loss=loss_fn,
                           step=step_fn, update=update_fn, clear=clear_fn)


def run_supervised(r, tmpdir=None, *, epochs=2, save_every=2, **knobs):
    sup = TrainingSupervisor(r.net, r.opt, r.loader,
                             ckpt_dir=str(tmpdir) if tmpdir else None,
                             save_every=save_every, **knobs)
    return sup.run(r.step, r.loader, epochs=epochs, update_fn=r.update,
                   clear_fn=r.clear)


def eager_losses(*, q8=False, steps=12, monkeypatch=None):
    """The eager-tier trajectory of the same run (capture off)."""
    os.environ["PADDLE_TPU_STEP_CAPTURE"] = "off"
    try:
        r = build_run(q8=q8, shuffle=False)
        out = []
        for _ in range(3):
            for batch in r.loader:
                loss = r.step(batch)
                r.update()
                out.append(float(np.asarray(loss._data)))
                if len(out) >= steps:
                    return out
        return out
    finally:
        os.environ["PADDLE_TPU_STEP_CAPTURE"] = "auto"


def captured_losses(*, q8=False, steps=12):
    r = build_run(q8=q8, shuffle=False)
    cap = sc.capture_step(r.step, update_fn=r.update, clear_fn=r.clear)
    out = []
    for _ in range(3):
        for batch in r.loader:
            loss = cap(batch)
            out.append(float(np.asarray(loss._data)))
            if len(out) >= steps:
                return out
    return out


# ---------------------------------------------------------------------------
# one executable per signature, one dispatch per step
# ---------------------------------------------------------------------------

class TestOneProgramPerSignature:
    def test_supervised_run_compiles_exactly_one_program(self, metrics):
        r = build_run()
        run_supervised(r, None, epochs=2, save_every=0)
        snap = metrics.snapshot()
        # 2 epochs x 4 batches = 8 steps: ONE compiled program, 7 hits
        assert snap.get("jit.compiles_total", 0) == 1
        assert snap.get("train.capture_retraces_total", 0) == 1
        assert snap.get("train.capture_hits_total", 0) == 7
        assert "train.capture_bypasses_total" not in snap
        assert snap.get("train.capture_donated_bytes", 0) > 0

    def test_warm_captured_step_is_one_dispatch(self, metrics):
        r = build_run(shuffle=False)
        cap = sc.capture_step(r.step, update_fn=r.update)
        batch = next(iter(r.loader))
        cap(batch)          # trace + compile
        before = metrics.snapshot().get("dispatch.ops_total", 0)
        cap(batch)          # warm: the single program call, zero eager ops
        after = metrics.snapshot().get("dispatch.ops_total", 0)
        assert after - before == 0
        assert cap.stats == {"hits": 1, "retraces": 1, "bypasses": {}}

    def test_shape_change_retraces_never_stale(self):
        r = build_run(shuffle=False)
        cap = sc.capture_step(r.step, update_fn=r.update)
        it = iter(r.loader)
        b = next(it)
        cap(b)
        small = [b[0][:3], b[1][:3]]      # new leading dim: new signature
        cap(small)
        assert cap.stats["retraces"] == 2

    def test_flags_epoch_retraces(self):
        r = build_run(shuffle=False)
        cap = sc.capture_step(r.step, update_fn=r.update)
        batch = next(iter(r.loader))
        cap(batch)
        cap(batch)
        assert cap.stats["retraces"] == 1
        # any runtime set_flags bumps the epoch: compiled steps bake flag
        # reads at trace time, so the old program must never be served
        paddle.set_flags({"FLAGS_log_level": 0})
        cap(batch)
        assert cap.stats["retraces"] == 2

    def test_closure_scalar_mutation_retraces(self):
        # the PR 2 structural signature keys on closure CONTENT: a python
        # scalar the step math bakes in must retire the program when it
        # changes, not serve the stale constant
        Parameter._param_counter = 0
        paddle.seed(0)
        net = paddle.nn.Linear(4, 2)
        # lr=0: params never move, so the two calls differ ONLY through
        # the mutated closure scalar
        opt = paddle.optimizer.SGD(learning_rate=0.0,
                                   parameters=net.parameters())
        scale = [1.0]

        def step(x):
            loss = (net(x) * scale[0]).sum()
            loss.backward()
            return loss

        cap = sc.capture_step(step, update_fn=lambda: (opt.step(),
                                                       opt.clear_grad()))
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        l1 = float(np.asarray(cap(x)._data))
        scale[0] = -1.0
        l2 = float(np.asarray(cap(x)._data))
        assert cap.stats["retraces"] == 2
        assert l2 == -l1


# ---------------------------------------------------------------------------
# parity: captured vs eager (both optimizer legs)
# ---------------------------------------------------------------------------

class TestParityEagerVsCaptured:
    @pytest.mark.parametrize("q8", [False, True], ids=["adam_fp32",
                                                       "adam_int8"])
    def test_trajectory_tracks_eager_at_ulp_scale(self, q8):
        ref = eager_losses(q8=q8)
        got = captured_losses(q8=q8)
        # captured vs eager is a CROSS-tier comparison, so the pin is
        # ulp-scale closeness, never bitwise (within a tier it is bitwise
        # — the kill/resume tests). Step 1's loss is the pre-update
        # forward over identical params: the whole-program forward and the
        # per-op forward differ by FMA contraction only (seen on jaxlib
        # 0.9.0's CPU codegen: 1.4 fp32 ulp), so 4 ulp. Over the
        # trajectory the optimizer update's a*x+b*y chains fuse too and
        # the differences compound: rtol 1e-5.
        np.testing.assert_allclose(got[0], ref[0],
                                   rtol=4 * np.finfo(np.float32).eps)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)

    def test_scheduler_stepped_outside_rides_carried_state(self):
        # the LR VALUE is carried state (opt_lr): a host-side
        # scheduler.step() between captured calls takes effect on the
        # next call with NO retrace — the "LR step" half of the tentpole
        def run(captured):
            os.environ["PADDLE_TPU_STEP_CAPTURE"] = \
                "auto" if captured else "off"
            try:
                Parameter._param_counter = 0
                paddle.seed(5)
                net = paddle.nn.Linear(8, 4)
                sched = paddle.optimizer.lr.StepDecay(0.05, step_size=2,
                                                      gamma=0.1)
                opt = paddle.optimizer.Adam(learning_rate=sched,
                                            parameters=net.parameters())
                loss_fn = paddle.nn.MSELoss()
                rng = np.random.default_rng(5)
                x = paddle.to_tensor(rng.normal(size=(8, 8))
                                     .astype(np.float32))
                y = paddle.to_tensor(rng.normal(size=(8, 4))
                                     .astype(np.float32))

                def step():
                    loss = loss_fn(net(x), y)
                    loss.backward()
                    return loss

                def update():
                    opt.step()
                    opt.clear_grad()

                cap = sc.capture_step(step, update_fn=update) \
                    if captured else None
                out = []
                for _ in range(6):
                    loss = cap() if captured else (step(), update())[0]
                    out.append(float(np.asarray(loss._data)))
                    sched.step()       # host-side, between steps: legal
                if captured:
                    assert cap.stats["retraces"] == 1
                    assert cap.stats["hits"] == 5
                return out
            finally:
                os.environ["PADDLE_TPU_STEP_CAPTURE"] = "auto"

        np.testing.assert_allclose(run(True), run(False), rtol=1e-5,
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# bitwise within the captured tier: determinism, kill-resume, donation
# ---------------------------------------------------------------------------

class TestCapturedTierBitwise:
    @pytest.mark.parametrize("q8", [False, True], ids=["adam_fp32",
                                                       "adam_int8"])
    def test_identical_captured_runs_are_bitwise(self, q8):
        assert captured_losses(q8=q8) == captured_losses(q8=q8)

    def test_kill_at_step_resume_bitwise_on_captured_path(self, tmp_path):
        # the PR 10 acceptance proof, re-run over the captured step: a
        # KillPoint at step 6 escapes, a FRESH supervisor (fresh model,
        # fresh trace, fresh executable) resumes from the last verified
        # TrainState and the trajectory is bitwise identical
        r = build_run()
        ref = run_supervised(r, tmp_path / "ref", save_every=1).losses
        assert len(ref) == 8

        r2 = build_run()
        ck = tmp_path / "ck"
        sched = faults.FaultSchedule().kill("train.step", on=(6,))
        with faults.installed(sched):
            with pytest.raises(faults.KillPoint):
                run_supervised(r2, ck, save_every=1)
        assert sched.trace == [("train.step", 6, "kill")]

        r3 = build_run()
        sup = TrainingSupervisor(r3.net, r3.opt, r3.loader, ckpt_dir=str(ck),
                                 save_every=1)
        rep = sup.run(r3.step, r3.loader, epochs=2, update_fn=r3.update,
                      clear_fn=r3.clear, resume=True)
        assert rep.resumed_from == str(ck / "step-5")
        assert rep.losses == ref[5:]       # bitwise, not allclose

    def test_no_use_after_donate_across_save_restore(self, tmp_path):
        # donation rebinds every state tensor to a live output buffer per
        # call; a verified save mid-run, a restore, and the continuation
        # must all read live arrays and stay bitwise on the captured tier
        r = build_run(shuffle=False)
        cap = sc.capture_step(r.step, update_fn=r.update, clear_fn=r.clear)
        sup = TrainingSupervisor(r.net, r.opt, r.loader,
                                 ckpt_dir=str(tmp_path), save_every=2)
        rep = sup.run(cap, r.loader, epochs=2)
        assert rep.steps == 8
        ref = rep.losses

        r2 = build_run(shuffle=False)
        cap2 = sc.capture_step(r2.step, update_fn=r2.update,
                               clear_fn=r2.clear)
        sup2 = TrainingSupervisor(r2.net, r2.opt, r2.loader,
                                  ckpt_dir=str(tmp_path / "ck2"),
                                  save_every=2)
        rep2 = sup2.run(cap2, r2.loader, epochs=1)
        # every state tensor must be a live, readable array (a donated
        # input left bound anywhere would raise "Array has been deleted")
        for p in r2.net.parameters():
            np.asarray(p._data)
        np.asarray(r2.opt._step_t._data)
        # fresh process: restore the mid-run checkpoint and continue over
        # a FRESH captured program — bitwise continuation
        r3 = build_run(shuffle=False)
        cap3 = sc.capture_step(r3.step, update_fn=r3.update,
                               clear_fn=r3.clear)
        sup3 = TrainingSupervisor(r3.net, r3.opt, r3.loader,
                                  ckpt_dir=str(tmp_path), save_every=2)
        rep3 = sup3.run(cap3, r3.loader, epochs=2, resume=True)
        assert rep3.resumed_from == str(tmp_path / "step-8")
        assert rep2.losses == ref[:4]
        for p in r3.net.parameters():
            np.asarray(p._data)

    def test_restore_preserves_uncommitted_placement(self, tmp_path):
        # regression (found by the zero-sharding suite): the checkpoint
        # loader used to restore single-host state as COMMITTED arrays
        # (orbax reads under an explicit sharding); the next captured jit
        # then committed its ENTIRE state carry — including unrelated
        # live models' tensors — to that one device, which broke any
        # later mesh-committed program sharing the registry. Restore into
        # an uncommitted destination must stay uncommitted.
        r = build_run(shuffle=False)
        cap = sc.capture_step(r.step, update_fn=r.update, clear_fn=r.clear)
        sup = TrainingSupervisor(r.net, r.opt, r.loader,
                                 ckpt_dir=str(tmp_path), save_every=2)
        sup.run(cap, r.loader, epochs=1)
        r2 = build_run(shuffle=False)
        cap2 = sc.capture_step(r2.step, update_fn=r2.update,
                               clear_fn=r2.clear)
        sup2 = TrainingSupervisor(r2.net, r2.opt, r2.loader,
                                  ckpt_dir=str(tmp_path), save_every=2)
        sup2.run(cap2, r2.loader, epochs=2, resume=True)
        from paddle_tpu.core.random import default_generator
        from paddle_tpu.core.tensor import _state_registry
        assert not getattr(default_generator._key._data, "_committed", False)
        for p in r2.net.parameters():
            assert not getattr(p._data, "_committed", False), p.name
        # nothing in the whole live registry got silently pinned either
        assert not any(getattr(t._data, "_committed", False)
                       for _, t in _state_registry.alive_items())


# ---------------------------------------------------------------------------
# NaN gate
# ---------------------------------------------------------------------------

class TestNaNGate:
    def test_nonfinite_loss_withholds_update_in_program(self):
        r = build_run(shuffle=False)
        cap = sc.capture_step(r.step, update_fn=r.update, clear_fn=r.clear,
                              nan_gate=True)
        batch = next(iter(r.loader))
        cap(batch)
        before = {p.name: np.asarray(p._data).copy()
                  for p in r.net.parameters()}
        m_before = np.asarray(
            r.opt._accumulators["moment1"][
                id(r.net.parameters()[0])]._data).copy()
        step_before = int(np.asarray(r.opt._step_t._data))
        bad = [paddle.to_tensor(np.full((8, 8), np.inf, np.float32)),
               batch[1]]
        loss = cap(bad)
        assert not np.isfinite(float(np.asarray(loss._data)))
        # the withheld update leaves params, moments AND the step count
        # bitwise untouched — the eager skip path's exact contract
        for p in r.net.parameters():
            assert np.array_equal(before[p.name], np.asarray(p._data))
        assert np.array_equal(
            m_before, np.asarray(r.opt._accumulators["moment1"][
                id(r.net.parameters()[0])]._data))
        assert int(np.asarray(r.opt._step_t._data)) == step_before
        # and a following healthy batch trains normally
        good = cap(batch)
        assert np.isfinite(float(np.asarray(good._data)))
        assert not np.array_equal(before["param_0"],
                                  np.asarray(r.net.parameters()[0]._data))

    def test_vector_loss_gates_on_first_element_like_the_supervisor(self):
        # regression (review finding): the gate must read the SAME value
        # the supervisor's _loss_value / the eager bypass reads — the
        # FIRST element — not all(isfinite(vector)). A vector loss of
        # [finite, nan] applies the update on BOTH tiers.
        def run(captured):
            os.environ["PADDLE_TPU_STEP_CAPTURE"] = \
                "auto" if captured else "off"
            try:
                Parameter._param_counter = 0
                paddle.seed(9)
                net = paddle.nn.Linear(4, 2)
                opt = paddle.optimizer.SGD(learning_rate=0.1,
                                           parameters=net.parameters())
                mask = paddle.to_tensor(np.array([1.0, np.nan], np.float32))

                def step(x):
                    per_out = net(x).sum(axis=0)      # shape (2,)
                    loss = per_out + (mask - mask)    # [v, nan] vector
                    per_out.sum().backward()
                    return loss

                cap = sc.capture_step(
                    step, update_fn=lambda: (opt.step(), opt.clear_grad()),
                    clear_fn=lambda: opt.clear_grad(), nan_gate=True)
                x = paddle.to_tensor(np.ones((3, 4), np.float32))
                w0 = np.asarray(net.parameters()[0]._data).copy()
                cap(x)
                return not np.array_equal(
                    w0, np.asarray(net.parameters()[0]._data))
            finally:
                os.environ["PADDLE_TPU_STEP_CAPTURE"] = "auto"

        assert run(True) is True      # captured tier applied the update
        assert run(False) is True     # and so did the eager bypass

    def test_supervisor_counts_skip_over_captured_path(self):
        r = build_run(shuffle=False)
        # poison one batch: the supervisor must count the skip while the
        # in-program gate withholds the update
        xs = np.asarray([np.asarray(b[0]._data) for b in r.loader])
        cap = sc.capture_step(r.step, update_fn=r.update, clear_fn=r.clear,
                              nan_gate=True)
        sup = TrainingSupervisor(r.net, r.opt, r.loader, max_skipped=3)
        poisoned = [([paddle.to_tensor(np.full((8, 8), np.nan, np.float32)),
                      paddle.to_tensor(np.zeros((8, 4), np.float32))]
                     if i == 2 else
                     [paddle.to_tensor(xs[i]),
                      paddle.to_tensor(np.zeros((8, 4), np.float32))])
                    for i in range(4)]
        rep = sup.run(cap, poisoned, epochs=1)
        assert rep.skipped_batches == 1
        assert rep.steps == 3


# ---------------------------------------------------------------------------
# bypasses and guards
# ---------------------------------------------------------------------------

class TestBypassesAndGuards:
    def test_mode_off_is_the_eager_tier(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_STEP_CAPTURE", "off")
        ref = eager_losses(steps=4)
        # same construction THROUGH the capture wrapper with mode off:
        # bitwise identical to plain eager (it IS plain eager)
        os.environ["PADDLE_TPU_STEP_CAPTURE"] = "off"
        r = build_run(shuffle=False)
        cap = sc.capture_step(r.step, update_fn=r.update)
        got = []
        for batch in r.loader:
            got.append(float(np.asarray(cap(batch)._data)))
        assert got == ref
        assert cap.stats["hits"] == 0
        assert cap.stats["bypasses"] == {"off": 4}

    def test_dispatch_fault_injection_bypasses(self):
        # scripted per-op faults must keep firing per op: a compiled
        # program would run the dispatch seams only at trace time
        r = build_run(shuffle=False)
        cap = sc.capture_step(r.step, update_fn=r.update)
        batch = next(iter(r.loader))
        sched = faults.FaultSchedule().error("dispatch.lower", on=(10 ** 9,))
        with faults.installed(sched):
            cap(batch)
        assert cap.stats["bypasses"] == {"fault_injection": 1}
        cap(batch)     # schedule gone: captures
        assert cap.stats["retraces"] == 1

    def test_live_trace_seam_bypasses(self):
        from paddle_tpu.core.tracing import (TraceState, pop_trace_state,
                                             push_trace_state)
        r = build_run(shuffle=False)
        cap = sc.capture_step(r.step, update_fn=r.update)
        batch = next(iter(r.loader))
        ts = TraceState()
        push_trace_state(ts)
        try:
            cap(batch)
        finally:
            pop_trace_state()
            ts.restore()
        assert cap.stats["bypasses"] == {"capture_seam": 1}

    def test_untraceable_step_memoizes_eager(self):
        Parameter._param_counter = 0
        paddle.seed(0)
        net = paddle.nn.Linear(4, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())

        def step(x):
            loss = net(x).sum()
            loss.backward()
            float(loss)        # host read mid-step: cannot trace
            return loss

        cap = sc.capture_step(step, update_fn=lambda: (opt.step(),
                                                       opt.clear_grad()))
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        with pytest.warns(UserWarning, match="cannot be captured"):
            cap(x)
        cap(x)
        assert cap.stats["bypasses"] == {"untraceable": 2}
        assert cap.stats["retraces"] == 0
        for p in net.parameters():     # both eager steps applied
            np.asarray(p._data)

    def test_scheduler_step_inside_captured_update_raises_typed(self):
        Parameter._param_counter = 0
        paddle.seed(0)
        net = paddle.nn.Linear(4, 2)
        sched = paddle.optimizer.lr.StepDecay(0.05, step_size=1, gamma=0.5)
        opt = paddle.optimizer.Adam(learning_rate=sched,
                                    parameters=net.parameters())

        def step(x):
            loss = net(x).sum()
            loss.backward()
            return loss

        def update():
            opt.step()
            opt.clear_grad()
            sched.step()       # per-step host write into carried state

        cap = sc.capture_step(step, update_fn=update)
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        with pytest.raises(sc.HostStateWriteError, match="scheduler.step"):
            cap(x)

    def test_supervisor_rejects_double_update(self):
        r = build_run()
        cap = sc.capture_step(r.step, update_fn=r.update)
        sup = TrainingSupervisor(r.net, r.opt, r.loader)
        with pytest.raises(ValueError, match="already folds"):
            sup.run(cap, r.loader, epochs=1, update_fn=r.update)

    def test_steps_per_epoch_mode_never_wraps(self):
        # a step that sources its own batches would consume one during a
        # speculative trace; data=None stays on the caller's tier
        r = build_run(shuffle=False)
        batches = list(r.loader)
        it = iter(batches * 3)
        before = sc.capture_info()

        def step(_):
            loss = r.step(next(it))
            return loss

        sup = TrainingSupervisor(r.net, r.opt, None)
        rep = sup.run(step, None, epochs=1, steps_per_epoch=4,
                      update_fn=r.update, clear_fn=r.clear)
        after = sc.capture_info()
        assert rep.steps == 4
        assert after["hits"] == before["hits"]
        assert after["retraces"] == before["retraces"]


# ---------------------------------------------------------------------------
# hapi routing
# ---------------------------------------------------------------------------

class TestHapiRouting:
    def _data(self, seed=1, n=32):
        rng = np.random.default_rng(seed)
        xs = rng.normal(size=(n, 8)).astype(np.float32)
        ys = rng.normal(size=(n, 4)).astype(np.float32)
        return paddle.io.TensorDataset([paddle.to_tensor(xs),
                                        paddle.to_tensor(ys)])

    def _model(self):
        Parameter._param_counter = 0
        paddle.seed(3)
        net = paddle.nn.Linear(8, 4)
        m = paddle.Model(net)
        m.prepare(optimizer=paddle.optimizer.Adam(
            learning_rate=0.05, parameters=net.parameters()),
            loss=paddle.nn.MSELoss())
        return m

    def test_plain_fit_equals_supervised_fit_bitwise(self, tmp_path):
        # both fit paths ride the captured step: the whole-step program is
        # identical, so the trajectories are bitwise equal
        ds = self._data()
        h1 = self._model().fit(ds, batch_size=8, epochs=2, verbose=0)
        h2 = self._model().fit(
            ds, batch_size=8, epochs=2, verbose=0,
            fault_tolerance={"ckpt_dir": str(tmp_path), "save_every": 2})
        assert h1["loss"] == h2["loss"]
        assert sc.capture_info()["hits"] > 0

    def test_plain_fit_tracks_eager_fit(self, monkeypatch):
        ds = self._data()
        cap_hist = self._model().fit(ds, batch_size=8, epochs=2, verbose=0)
        monkeypatch.setenv("PADDLE_TPU_STEP_CAPTURE", "off")
        eager_hist = self._model().fit(ds, batch_size=8, epochs=2, verbose=0)
        np.testing.assert_allclose(cap_hist["loss"], eager_hist["loss"],
                                   rtol=1e-5, atol=1e-6)

    def test_plain_fit_with_metrics_captures(self):
        # metrics update on the program's CONCRETE outputs after each
        # call — the plain path keeps capture even with metrics on
        ds = self._data()
        m = self._model()
        m._metrics = [paddle.metric.Accuracy()]
        before = sc.capture_info()["hits"]
        m.fit(ds, batch_size=8, epochs=1, verbose=0)
        assert sc.capture_info()["hits"] > before

    def test_supervised_fit_with_metrics_stays_eager(self, tmp_path):
        # the supervised split step feeds metrics from inside train_batch;
        # that path needs eager outputs, so capture stays off for it
        ds = self._data()
        m = self._model()
        m._metrics = [paddle.metric.Accuracy()]
        before = sc.capture_info()
        m.fit(ds, batch_size=8, epochs=1, verbose=0,
              fault_tolerance={"ckpt_dir": str(tmp_path)})
        after = sc.capture_info()
        assert after["hits"] == before["hits"]
        assert after["retraces"] == before["retraces"]
