"""Training under fire (ISSUE 10): the fault-tolerant training supervisor.

The acceptance surface for ``resilience.trainer``:

* **kill-at-step proof** — a seeded ``KillPoint`` at ``train.step`` call N
  escapes the supervisor (simulated process death), and a FRESH supervisor
  (fresh model/optimizer/loader, same construction order) with
  ``resume=True`` restores the last verified ``TrainState`` and produces a
  loss trajectory bitwise identical to an uninterrupted run — RNG,
  optimizer step/moments, LR-schedule position, and dataloader cursor all
  resume exactly;
* **watchdog trip** and **NaN escalation** each have a deterministic
  regression test (restore-last-good keeps the trajectory bitwise);
* **seeded FaultSchedule sweep** over the ``train.*`` sites x >= 3 seeds
  with the invariants: every run terminates typed, same seed => same
  retry/restart trace AND same losses, and any run that completes decodes
  the exact fault-free trajectory (pre-step faults never corrupt a step);
* the DataLoader resume-mid-epoch parity and the verified ModelCheckpoint
  fallback chain (PR 10 satellites) are pinned here too.

"Fresh process" is simulated by resetting ``Parameter._param_counter``
before each rebuild: optimizer state keys derive from auto-generated
param names, which are deterministic per construction order in a real
restart but drift when several models are built in one test process.
"""

import math
import os
from types import SimpleNamespace

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Parameter
from paddle_tpu.resilience import faults, reset_policies
from paddle_tpu.resilience.trainer import (FaultTolerance, NonFiniteLossError,
                                           TrainAborted, TrainState,
                                           TrainingSupervisor)


@pytest.fixture(autouse=True)
def _fast_retry_policies(monkeypatch):
    """Millisecond backoff for the train.* policies: the retry SCHEDULE is
    under test, not the wall clock."""
    for site in ("STEP", "DATA", "SAVE"):
        monkeypatch.setenv(f"PADDLE_TPU_RETRY_TRAIN_{site}_BASE_DELAY",
                           "0.001")
        monkeypatch.setenv(f"PADDLE_TPU_RETRY_TRAIN_{site}_MAX_DELAY",
                           "0.002")
    reset_policies()
    yield
    reset_policies()


def build_run(seed=7, *, lr_sched=False, n=32, batch_size=8):
    """One complete training setup, as a fresh process would construct it."""
    Parameter._param_counter = 0   # fresh-process simulation (see module doc)
    paddle.seed(seed)
    net = paddle.nn.Linear(8, 4)
    lr = (paddle.optimizer.lr.StepDecay(0.05, step_size=3, gamma=0.5)
          if lr_sched else 0.05)
    opt = paddle.optimizer.Adam(learning_rate=lr,
                                parameters=net.parameters())
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(n, 8)).astype(np.float32)
    ys = rng.normal(size=(n, 4)).astype(np.float32)
    ds = paddle.io.TensorDataset([paddle.to_tensor(xs), paddle.to_tensor(ys)])
    loader = paddle.io.DataLoader(ds, batch_size=batch_size, shuffle=True)
    loss_fn = paddle.nn.MSELoss()

    def step_fn(batch):
        x, y = batch
        loss = loss_fn(net(x), y)
        loss.backward()
        return loss

    def update_fn():
        opt.step()
        opt.clear_grad()
        if lr_sched:
            opt._learning_rate.step()

    def clear_fn():
        opt.clear_grad()

    return SimpleNamespace(net=net, opt=opt, loader=loader, step=step_fn,
                           update=update_fn, clear=clear_fn)


def run_supervised(r, tmpdir, *, epochs=2, save_every=2, **knobs):
    sup = TrainingSupervisor(r.net, r.opt, r.loader,
                             ckpt_dir=str(tmpdir) if tmpdir else None,
                             save_every=save_every, **knobs)
    return sup.run(r.step, r.loader, epochs=epochs, update_fn=r.update,
                   clear_fn=r.clear)


def reference_losses(tmp_path, **build_kw):
    r = build_run(**build_kw)
    return run_supervised(r, tmp_path / "ref").losses


# ---------------------------------------------------------------------------
# the acceptance proof: kill-at-step, restart, bitwise-identical trajectory
# ---------------------------------------------------------------------------

class TestKillAtStepBitIdentical:
    def test_kill_resume_trajectory_bitwise(self, tmp_path):
        ref = reference_losses(tmp_path, lr_sched=True)
        assert len(ref) == 8       # 2 epochs x 4 batches

        r = build_run(lr_sched=True)
        ck = tmp_path / "ck"
        sched = faults.FaultSchedule().kill("train.step", on=(6,))
        with faults.installed(sched):
            with pytest.raises(faults.KillPoint):
                run_supervised(r, ck, save_every=1)
        assert sched.trace == [("train.step", 6, "kill")]

        # "process restart": rebuild everything in construction order and
        # resume from the last verified TrainState (step 5, mid-epoch 2)
        r2 = build_run(lr_sched=True)
        sup = TrainingSupervisor(r2.net, r2.opt, r2.loader,
                                 ckpt_dir=str(ck), save_every=1)
        rep = sup.run(r2.step, r2.loader, epochs=2, update_fn=r2.update,
                      clear_fn=r2.clear, resume=True)
        assert rep.resumed_from == str(ck / "step-5")
        assert rep.steps == 3
        # the pinned claim: bitwise equality, not allclose
        assert rep.losses == ref[5:]

    def test_kill_mid_commit_resumes_from_previous_good(self, tmp_path):
        ref = reference_losses(tmp_path)
        r = build_run()
        ck = tmp_path / "ck"
        # the 3rd TrainState save dies INSIDE the writer's commit window:
        # pointer rotation never happens, last-good stays step-2
        sched = faults.FaultSchedule().kill("checkpoint.commit", on=(3,))
        with faults.installed(sched):
            with pytest.raises(faults.KillPoint):
                run_supervised(r, ck, save_every=1)
        r2 = build_run()
        sup = TrainingSupervisor(r2.net, r2.opt, r2.loader, ckpt_dir=str(ck),
                                 save_every=1)
        rep = sup.run(r2.step, r2.loader, epochs=2, update_fn=r2.update,
                      clear_fn=r2.clear, resume=True)
        assert rep.resumed_from == str(ck / "step-2")
        assert rep.losses == ref[2:]


# ---------------------------------------------------------------------------
# in-process recovery: retry, restore-last-good, watchdog, NaN
# ---------------------------------------------------------------------------

class TestInProcessRecovery:
    def test_transient_fault_is_retried_trajectory_unchanged(self, tmp_path):
        ref = reference_losses(tmp_path)
        r = build_run()
        sched = faults.FaultSchedule().error("train.step", on=(2,))
        with faults.installed(sched):
            rep = run_supervised(r, tmp_path / "ck")
        assert rep.retries == 1 and rep.restarts == 0
        assert rep.losses == ref

    def test_retry_budget_exhausted_restores_last_good(self, tmp_path):
        ref = reference_losses(tmp_path)
        r = build_run()
        # attempt 3 of step 3 plus its two retries: the train.step policy
        # budget (3 attempts) is spent, the supervisor rolls back to the
        # step-2 checkpoint and re-runs the batch
        sched = faults.FaultSchedule().error("train.step", on=(3, 4, 5))
        with faults.installed(sched):
            rep = run_supervised(r, tmp_path / "ck")
        assert rep.retries == 2 and rep.restarts == 1
        assert rep.losses == ref

    def test_data_fault_retry_and_restore(self, tmp_path):
        ref = reference_losses(tmp_path)
        r = build_run()
        sched = faults.FaultSchedule().error("train.data", on=(3, 4, 5))
        with faults.installed(sched):
            rep = run_supervised(r, tmp_path / "ck")
        assert rep.restarts == 1
        assert rep.losses == ref

    def test_real_iterator_fault_restores_instead_of_truncating(self,
                                                                tmp_path):
        # review regression: an exception raised by the loader ITSELF (not
        # a pre-next() injected fault) closes the generator; retrying
        # next() on it would read StopIteration as a silent epoch end.
        # The supervisor must restore-last-good and replay the full epoch.
        class FlakyDataset(paddle.io.Dataset):
            def __init__(self, xs, ys):
                self.xs, self.ys = xs, ys
                self.fail_once = True

            def __getitem__(self, i):
                if i == 20 and self.fail_once:
                    self.fail_once = False
                    raise IOError("transient storage fault")
                return self.xs[i], self.ys[i]

            def __len__(self):
                return len(self.xs)

        rng = np.random.default_rng(7)
        xs = rng.normal(size=(32, 8)).astype(np.float32)
        ys = rng.normal(size=(32, 4)).astype(np.float32)
        flaky = paddle.io.DataLoader(FlakyDataset(xs, ys), batch_size=8)
        # the reference for THIS data (unshuffled, clean pass)
        r_ref = build_run()
        clean = paddle.io.DataLoader(
            paddle.io.TensorDataset(
                [paddle.to_tensor(xs), paddle.to_tensor(ys)]), batch_size=8)
        sup = TrainingSupervisor(r_ref.net, r_ref.opt, clean,
                                 ckpt_dir=str(tmp_path / "ref2"),
                                 save_every=2)
        want = sup.run(r_ref.step, clean, epochs=2, update_fn=r_ref.update,
                       clear_fn=r_ref.clear)
        r = build_run()
        sup = TrainingSupervisor(r.net, r.opt, flaky,
                                 ckpt_dir=str(tmp_path / "ck"), save_every=2)
        rep = sup.run(r.step, flaky, epochs=2, update_fn=r.update,
                      clear_fn=r.clear)
        assert rep.restarts == 1
        assert rep.steps == 8, "epoch was truncated"   # 2 epochs x 4 batches
        assert rep.losses == want.losses

    def test_restart_budget_exhausted_aborts_typed(self, tmp_path):
        r = build_run()
        sched = faults.FaultSchedule().error("train.step",
                                             on=tuple(range(3, 40)))
        with faults.installed(sched):
            with pytest.raises(TrainAborted) as ei:
                run_supervised(r, tmp_path / "ck", max_restarts=1)
        assert isinstance(ei.value.__cause__, faults.FaultInjected)

    def test_unrecoverable_without_checkpoint_aborts_typed(self, tmp_path):
        r = build_run()
        sched = faults.FaultSchedule().error("train.step", on=(1, 2, 3))
        with faults.installed(sched):
            with pytest.raises(TrainAborted):
                run_supervised(r, None)   # no ckpt_dir: nothing to roll to

    def test_watchdog_trip_restores_bitwise(self, tmp_path):
        ref = reference_losses(tmp_path)
        r = build_run()
        # a delay fault INSIDE the armed window simulates a hung device
        # step; the step returns past budget, its outputs are distrusted,
        # the run restores step-2 and re-runs — deterministically, because
        # the delay is scripted on one call index
        sched = faults.FaultSchedule().delay("train.step", on=(3,),
                                             seconds=0.5)
        with faults.installed(sched):
            rep = run_supervised(r, tmp_path / "ck", watchdog_s=0.12)
        assert rep.restarts == 1
        assert rep.losses == ref

    def test_nan_skip_withholds_update_and_counts(self, tmp_path):
        r = build_run()
        calls = [0]
        real_step = r.step

        def step(batch):
            calls[0] += 1
            if calls[0] == 2:
                return paddle.to_tensor(np.float32(np.nan))
            return real_step(batch)

        w_probe = []

        def update():
            w_probe.append(np.asarray(r.net.weight._data).copy())
            r.update()

        sup = TrainingSupervisor(r.net, r.opt, r.loader, max_skipped=3)
        rep = sup.run(step, r.loader, epochs=1, update_fn=update,
                      clear_fn=r.clear)
        # 4 batches, one skipped: 3 applied steps, the NaN batch's update
        # never ran (update_fn not called for it)
        assert rep.steps == 3 and rep.skipped_batches == 1
        assert len(w_probe) == 3
        assert all(math.isfinite(l) for l in rep.losses)

    def test_nan_escalation_rolls_back_then_recovers(self, tmp_path):
        ref = reference_losses(tmp_path)
        r = build_run()
        calls = [0]
        real_step = r.step

        def step(batch):
            calls[0] += 1
            if calls[0] in (4, 5, 6):     # 3 consecutive non-finite losses
                return paddle.to_tensor(np.float32(np.inf))
            return real_step(batch)

        sup = TrainingSupervisor(r.net, r.opt, r.loader,
                                 ckpt_dir=str(tmp_path / "ck"), save_every=2,
                                 max_skipped=3)
        rep = sup.run(step, r.loader, epochs=2, update_fn=r.update,
                      clear_fn=r.clear)
        assert rep.restarts == 1 and rep.skipped_batches == 3
        assert rep.losses == ref

    def test_nan_policy_raise_is_immediate_and_typed(self):
        r = build_run()

        def step(batch):
            return paddle.to_tensor(np.float32(np.nan))

        sup = TrainingSupervisor(r.net, r.opt, r.loader, nan_policy="raise")
        with pytest.raises(NonFiniteLossError):
            sup.run(step, r.loader, epochs=1, update_fn=r.update,
                    clear_fn=r.clear)


# ---------------------------------------------------------------------------
# seeded chaos sweep over the train.* sites
# ---------------------------------------------------------------------------

def _chaos_schedule(seed):
    sched = faults.FaultSchedule(seed)
    sched.error("train.step", prob=0.12)
    sched.error("train.data", prob=0.08)
    sched.error("train.save", prob=0.10)
    return sched


def _chaos_run(seed, tmp_path, tag):
    r = build_run(seed=3)
    sched = _chaos_schedule(seed)
    outcome = {"trace": None}
    with faults.installed(sched):
        try:
            rep = run_supervised(r, tmp_path / f"ck-{tag}", save_every=1,
                                 max_restarts=4)
            outcome.update(kind="completed", losses=rep.losses,
                           retries=rep.retries, restarts=rep.restarts)
        except TrainAborted as e:
            outcome.update(kind="aborted",
                           cause=type(e.__cause__).__name__)
        except faults.FaultInjected:
            # a save that failed past its retry budget surfaces raw — the
            # operator must know checkpoints stopped flowing
            outcome.update(kind="save_failed")
    outcome["trace"] = list(sched.trace)
    return outcome


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_chaos_sweep_terminates_typed_and_deterministic(seed, tmp_path):
    ref = reference_losses(tmp_path, seed=3)
    first = _chaos_run(seed, tmp_path, f"{seed}a")
    again = _chaos_run(seed, tmp_path, f"{seed}b")
    # same seed => same injected-fault trace AND same terminal state
    assert first["trace"] == again["trace"]
    assert first["kind"] == again["kind"]
    if first["kind"] == "completed":
        assert first["losses"] == again["losses"]
        assert (first["retries"], first["restarts"]) == \
            (again["retries"], again["restarts"])
        # pre-step faults may delay/retry/roll back but can NEVER corrupt
        # a step: a completed chaos run decodes the exact clean trajectory
        assert first["losses"] == ref


@pytest.mark.parametrize("seed", [0, 2])
def test_chaos_sweep_trace_invariants(seed, tmp_path, tracing):
    """ISSUE 12: the same seeded sweep with tracing on — every span
    balanced through retries/restores/aborts, the retry/restore events
    ride the step spans, and every abort path leaves a parseable flight
    dump whose tail names a train.* fault site."""
    import json

    outcome = _chaos_run(seed, tmp_path, f"{seed}t")
    evs = tracing.events()
    assert tracing.span_problems(evs) == []
    names = {e["name"] for e in evs}
    assert {"train.run", "train.step", "train.fwd_bwd"} <= names
    if any(site == "train.step" and kind == "error"
           for site, _, kind in outcome["trace"]):
        assert "train.retry" in names or "train.restore" in names
    if outcome["kind"] == "aborted":
        dump = os.path.join(
            str(tmp_path), f"flight-{os.getpid()}-train_aborted.json")
        assert os.path.exists(dump)
        doc = json.load(open(dump))
        sites = [e["attrs"].get("site") for e in doc["events"]
                 if e["name"] == "fault"]
        assert sites and sites[-1].startswith("train.")
    # the chrome export of the whole chaos run still loads
    json.dumps(tracing.export_chrome())


def test_kill_at_step_leaves_parseable_dump_with_fault_site(tmp_path,
                                                            tracing):
    """ISSUE 12 acceptance: a killed run's flight dump tail matches the
    injected fault site (here the kill itself at train.step)."""
    import json

    r = build_run()
    sched = faults.FaultSchedule().kill("train.step", on=(3,))
    with faults.installed(sched):
        with pytest.raises(faults.KillPoint):
            run_supervised(r, tmp_path / "ck", save_every=1)
    dump = os.path.join(
        str(tmp_path), f"flight-{os.getpid()}-supervisor_exit.json")
    assert os.path.exists(dump)
    doc = json.load(open(dump))
    assert doc["info"]["error"] == "KillPoint"
    fault_evs = [e for e in doc["events"] if e["name"] == "fault"]
    assert fault_evs and fault_evs[-1]["attrs"]["site"] == "train.step"
    assert fault_evs[-1]["attrs"]["injected"] == "kill"
    # spans unwound (balanced) even through the BaseException kill
    assert tracing.span_problems() == []


# ---------------------------------------------------------------------------
# TrainState: verified persistence + pointer-chain fallback
# ---------------------------------------------------------------------------

class TestTrainState:
    def test_restore_latest_falls_back_past_corrupt_manifest(self, tmp_path):
        r = build_run()
        run_supervised(r, tmp_path / "ck", save_every=1, epochs=1)
        ck = tmp_path / "ck"
        assert (ck / "latest").read_text().strip() == "step-4"
        # interrupt the newest save after the fact: no committed manifest
        os.remove(ck / "step-4" / "manifest.json")
        r2 = build_run()
        st = TrainState(r2.net, r2.opt, r2.loader)
        path, py = st.restore_latest(str(ck))
        assert path == str(ck / "step-3") and py["step"] == 3

    def test_restore_latest_none_when_nothing_committed(self, tmp_path):
        r = build_run()
        st = TrainState(r.net, r.opt, r.loader)
        assert st.restore_latest(str(tmp_path / "empty")) is None

    def test_wrong_tree_is_user_error_not_fallback(self, tmp_path):
        r = build_run()
        run_supervised(r, tmp_path / "ck", save_every=1, epochs=1)
        Parameter._param_counter = 0
        paddle.seed(0)
        other = paddle.nn.Linear(3, 2)     # wrong shapes for this ckpt
        st = TrainState(other, None, None)
        with pytest.raises((KeyError, ValueError)):
            st.restore_latest(str(tmp_path / "ck"))

    def test_metrics_visible(self, tmp_path, metrics):
        r = build_run()
        sched = faults.FaultSchedule().error("train.step", on=(2,))
        with faults.installed(sched):
            run_supervised(r, tmp_path / "ck", epochs=1)
        snap = metrics.snapshot()
        assert snap["train.steps_total"] == 4
        assert snap["train.retries_total"]["site=train.step"] == 1
        assert snap["train.saves_total"] == 2
        assert snap["train.step_seconds"]["count"] >= 4
        text = metrics.prometheus_text()
        assert "train_steps_total" in text


# ---------------------------------------------------------------------------
# satellites: DataLoader resume parity, watchdog extraction, ModelCheckpoint
# ---------------------------------------------------------------------------

class TestDataLoaderResume:
    def _loader(self, n=24, bs=4):
        xs = np.arange(n * 2, dtype=np.float32).reshape(n, 2)
        ds = paddle.io.TensorDataset([paddle.to_tensor(xs)])
        return paddle.io.DataLoader(ds, batch_size=bs, shuffle=True)

    def test_resume_mid_epoch_matches_uninterrupted(self):
        paddle.seed(11)
        loader = self._loader()
        ref = [np.asarray(b[0]._data).copy() for b in loader]

        paddle.seed(11)
        loader2 = self._loader()
        it = iter(loader2)
        got = [np.asarray(next(it)[0]._data).copy() for _ in range(2)]
        state = loader2.state_dict()
        assert state["in_epoch"] and state["batch"] == 2
        it = None  # abandon the interrupted iteration

        # "restart": fresh loader + the saved cursor; the global RNG at
        # this point is arbitrary — resume must not depend on it
        paddle.seed(999)
        loader3 = self._loader()
        loader3.load_state_dict(state)
        rng_before = np.asarray(
            paddle.get_rng_state()[0]._data).copy()
        rest = [np.asarray(b[0]._data).copy() for b in loader3]
        # rng-neutral: replaying the epoch's shuffle draw left the live
        # generator untouched
        np.testing.assert_array_equal(
            np.asarray(paddle.get_rng_state()[0]._data), rng_before)
        full = got + rest
        assert len(full) == len(ref)
        for a, b in zip(full, ref):
            np.testing.assert_array_equal(a, b)

    def test_state_roundtrip_between_epochs(self):
        paddle.seed(5)
        loader = self._loader()
        list(loader)
        st = loader.state_dict()
        assert st["epochs_completed"] == 1 and not st["in_epoch"]
        assert st["batch"] == 0
        loader.load_state_dict(st)
        assert len(list(loader)) == len(loader)

    def test_version_gate(self):
        loader = self._loader()
        with pytest.raises(ValueError):
            loader.load_state_dict({"version": 99})
        with pytest.raises(ValueError):
            loader.load_state_dict({"batch": 1})


def test_watchdog_backcompat_reexport():
    from paddle_tpu import serving
    from paddle_tpu.resilience import watchdog as rwd
    assert serving.StepWatchdog is rwd.StepWatchdog     # still exported
    assert serving.WatchdogTimeout is rwd.WatchdogTimeout


def test_watchdog_train_metric_name(metrics):
    import time
    from paddle_tpu.resilience.watchdog import StepWatchdog
    wd = StepWatchdog(0.1, metric="train.watchdog_trips_total",
                      label="train")
    gen = wd.arm()
    time.sleep(0.15)              # past budget, inside 2x (no zombie)
    verdict = wd.disarm(gen)
    wd.stop()
    assert verdict == "hung"
    snap = metrics.snapshot()
    assert snap["train.watchdog_trips_total"]["kind=hung"] == 1


class TestSupervisedFit:
    def _model(self, n=32):
        Parameter._param_counter = 0
        paddle.seed(4)
        net = paddle.nn.Linear(8, 4)
        model = paddle.Model(net)
        model.prepare(
            optimizer=paddle.optimizer.SGD(learning_rate=0.05,
                                           parameters=net.parameters()),
            loss=paddle.nn.MSELoss())
        rng = np.random.default_rng(1)
        xs = rng.normal(size=(n, 8)).astype(np.float32)
        ys = rng.normal(size=(n, 4)).astype(np.float32)
        ds = paddle.io.TensorDataset(
            [paddle.to_tensor(xs), paddle.to_tensor(ys)])
        return model, ds

    def test_supervised_fit_matches_plain_fit(self, tmp_path):
        model, ds = self._model()
        events = []

        class Rec(paddle.hapi.callbacks.Callback):
            def on_train_batch_end(self, step, logs=None):
                events.append(logs["loss"])

        plain = model.fit(ds, batch_size=8, epochs=2, verbose=0,
                          callbacks=[Rec()])
        plain_steps = list(events)

        model2, ds2 = self._model()
        events.clear()
        hist = model2.fit(
            ds2, batch_size=8, epochs=2, verbose=0, callbacks=[Rec()],
            fault_tolerance={"ckpt_dir": str(tmp_path / "ck"),
                             "save_every": 2})
        assert events == plain_steps          # bitwise, via the callback
        assert hist["supervisor"].steps == 8
        assert hist["loss"] == plain["loss"]

    def test_epoch_end_hooks_not_duplicated_by_rollback(self, tmp_path):
        # review regression: a restore that rolls back ACROSS an epoch
        # boundary replays that epoch's end; history/eval/EarlyStopping
        # bookkeeping must record each epoch exactly once
        model, ds = self._model()
        clean = model.fit(ds, batch_size=8, epochs=2, verbose=0)["loss"]

        model2, ds2 = self._model()
        # 4 batches/epoch, saves at steps 3 and 6; fault at global step 5
        # (epoch 1) exhausts the retry budget and restores to step-3
        # (mid-epoch 0) — epoch 0 then completes a second time
        sched = faults.FaultSchedule().error("train.step", on=(5, 6, 7))
        with faults.installed(sched):
            hist = model2.fit(
                ds2, batch_size=8, epochs=2, verbose=0, eval_data=ds2,
                fault_tolerance={"ckpt_dir": str(tmp_path / "ck"),
                                 "save_every": 3})
        assert hist["supervisor"].restarts == 1
        assert len(hist["loss"]) == 2
        assert len(hist["eval_loss"]) == 2
        assert hist["loss"] == clean

    def test_multiplicative_decay_state_roundtrip(self):
        # review regression: the _bound_opts exclusion must not drop
        # MultiplicativeDecay._cur (the accumulated product IS the
        # schedule position)
        sched = paddle.optimizer.lr.MultiplicativeDecay(
            0.1, lambda e: 0.5)
        for _ in range(3):
            sched.step()
        state = sched.state_dict()
        assert "_cur" in state and "_bound_opts" not in state
        fresh = paddle.optimizer.lr.MultiplicativeDecay(0.1, lambda e: 0.5)
        fresh.set_state_dict(state)
        sched.step()
        fresh.step()
        assert fresh.last_lr == sched.last_lr

    def test_supervised_fit_recovers_from_injected_fault(self, tmp_path):
        model, ds = self._model()
        clean = model.fit(ds, batch_size=8, epochs=2, verbose=0)["loss"]

        model2, ds2 = self._model()
        sched = faults.FaultSchedule().error("train.step", on=(3, 4, 5))
        with faults.installed(sched):
            hist = model2.fit(
                ds2, batch_size=8, epochs=2, verbose=0,
                fault_tolerance={"ckpt_dir": str(tmp_path / "ck"),
                                 "save_every": 1})
        assert hist["supervisor"].restarts == 1
        assert hist["loss"] == clean
