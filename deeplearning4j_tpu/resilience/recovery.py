"""Auto-recovering training: rollback-to-verified-checkpoint on NaN/inf.

The stack already *detects* failures — the checkify NaN guard raises, the
host can see a non-finite loss — but detection kills the run. This module
closes the loop (SURVEY §5.3/§5.4: the reference's production value was
surviving exactly this): :class:`FaultTolerantTrainer` wraps a built
``Trainer`` and drives the same compiled step, but

- checkpoints on a step cadence with the *verified* writer
  (``serde.checkpoint``: per-array SHA-256 manifest, atomic replace),
  including an anchor checkpoint before the first step so a rollback
  target always exists;
- after every step, host-checks the loss for NaN/inf (and catches the
  checkify guard's raise when ``check_nan`` is on);
- on failure, restores the **latest verified** checkpoint — walking the
  rotation index past corrupt/truncated/missing entries, quarantining the
  bad ones — and resumes from the rolled-back step, with
  :class:`RecoveryPolicy` bounding total rollbacks;
- optionally cuts the effective learning rate on each rollback (update
  scaling: exact for every updater, applied by re-jitting the step), and
  skips a batch that keeps producing NaN (poison data, not a transient);
- wraps the data iterator with ``retrying()`` for transient IO errors.

Donation-correct: the compiled step donates the input TrainState, so a
failed step cannot be retried in place — the donated buffers are gone.
Rollback therefore always goes through the host-side checkpoint, which is
also why the anchor save at step 0 is unconditional. The per-step host
read of the scalar loss costs one tiny D2H sync; ``check_every`` amortizes
it when steps are short.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import Any, Dict, List, Optional, Set, Tuple

import jax

from deeplearning4j_tpu.observability.trace import IterationLegs, annotate


class NonFiniteLossError(RuntimeError):
    """Raised host-side when a step's loss is NaN/inf (the recovery
    trigger when the compiled checkify guard is off)."""

    def __init__(self, msg: str, step: Optional[int] = None):
        super().__init__(msg)
        self.step = step


def _obs():
    """Shared-registry resilience bundle, or None when instrumentation is
    off — recovery events (rollbacks, skips, LR cuts) are rare, so the
    lazy lookup per event is free next to the checkpoint IO around it."""
    from deeplearning4j_tpu.observability import metrics as _obsm

    return _obsm.get_resilience_metrics() if _obsm.enabled() else None


def _flight(kind: str, **data):
    """Recovery events into the black-box ring: a crash report's timeline
    must show the rollbacks/skips that preceded it."""
    from deeplearning4j_tpu.observability.flightrecorder import record_event

    record_event(kind, **data)


def _train_obs():
    """The same training bundle Trainer.fit feeds — FaultTolerantTrainer
    drives the compiled step from its own loop, so it reports step/sample
    counts itself or a recovering run would vanish from the scrape."""
    from deeplearning4j_tpu.observability import metrics as _obsm

    return _obsm.get_training_metrics() if _obsm.enabled() else None


def _nan_exception_types():
    """Exception classes that mean 'this step produced non-finite values':
    our host check, numpy's FP errors, and the checkify guard's raise."""
    from jax.experimental import checkify

    return (NonFiniteLossError, FloatingPointError,
            checkify.JaxRuntimeError)


@dataclasses.dataclass
class RecoveryPolicy:
    """Knobs for :class:`FaultTolerantTrainer` (all host-side).

    ``max_rollbacks``: total rollbacks allowed per ``fit`` before the
    failure propagates (a run that cannot make progress must eventually
    surface, not loop forever). ``checkpoint_every``: steps between
    rolling verified saves (the rollback granularity). ``lr_cut``: each
    rollback multiplies the effective LR by this (1.0 = off; applied as an
    update scale, re-jitting the step — a compile per rollback, not per
    step). ``skip_poison_after``: a batch whose step has failed this many
    times is skipped on replay (0 = never skip; transients never hit this
    because the retry usually succeeds). ``data_retries``: transient-IO
    retry budget for the iterator wrapper (0 = don't wrap).

    Poison-batch attribution assumes ``check_every == 1``: with a larger
    cadence the NaN is detected up to ``check_every - 1`` steps after the
    batch that caused it, so ``skip_poison_after`` may skip the detection
    batch rather than the poison one (rollback and ``lr_cut`` still
    work — only the skip targets the wrong batch). Keep ``check_every=1``
    when relying on poison skipping.
    """

    max_rollbacks: int = 3
    checkpoint_every: int = 25
    checkpoint_every_epoch: bool = True
    keep_last: int = 3
    lr_cut: float = 1.0
    skip_poison_after: int = 2
    data_retries: int = 5
    data_base_delay: float = 0.05
    data_max_delay: float = 2.0
    check_every: int = 1


class FaultTolerantTrainer:
    """Wrap a ``Trainer`` with checkpointed auto-recovery.

    Usage::

        trainer = Trainer(model)
        ft = FaultTolerantTrainer(trainer, "ckpts", model=model)
        ts = ft.fit(trainer.init_state(), data, epochs=3)

    ``fit`` resumes from the latest *verified* checkpoint in ``directory``
    if one exists (same relaunch story as ``PreemptionCheckpointer``, but
    integrity-checked), so a crashed/preempted/NaN-killed run continues
    with ``ft.fit(...)`` unchanged. ``recoveries`` records every rollback
    and skipped batch for post-mortems.

    Standard backprop only — TBPTT's window-carry state is not
    checkpointed at window granularity, so rolling back inside a batch
    would silently zero carries.
    """

    def __init__(self, trainer, directory: str | Path, *,
                 policy: Optional[RecoveryPolicy] = None, model=None):
        if getattr(trainer.net, "backprop_type", "standard") == "tbptt":
            raise ValueError(
                "FaultTolerantTrainer supports backprop_type='standard' "
                "only (TBPTT carries are not checkpointed per window)")
        self.trainer = trainer
        self.directory = Path(directory)
        self.policy = policy or RecoveryPolicy()
        self.model = model
        self.recoveries: List[dict] = []
        self._lr_scale = 1.0
        self._step_fn = trainer.train_step
        if not 0.0 < self.policy.lr_cut <= 1.0:
            raise ValueError(
                f"lr_cut must be in (0, 1], got {self.policy.lr_cut}")
        # the unwrapped updater, captured now: _install_lr_scale always
        # wraps THIS, so repeated fits (or a second wrapper on the same
        # trainer) never stack scalings
        self._orig_upd = trainer._upd_update

    def _install_lr_scale(self):
        """Wrap the updater so update vectors are scaled by ``_lr_scale``
        (scaling the *updates* is an exact LR cut for any updater, unlike
        scaling gradients under Adam). The scale is read at trace time:
        each cut re-jits the step (see ``_rollback``). Installed only for
        the duration of ``fit`` — a shared Trainer must not keep tracing
        through a stale scale after this wrapper's run ended."""
        orig_upd = self._orig_upd

        def scaled_update(grads, opt_state, params, step):
            updates, new_opt = orig_upd(grads, opt_state, params, step)
            s = self._lr_scale
            if s != 1.0:
                updates = jax.tree_util.tree_map(lambda u: u * s, updates)
            return updates, new_opt

        self.trainer._upd_update = scaled_update

    # -- checkpoint plumbing -------------------------------------------------

    def _save(self, ts, *, epoch: int, batch_in_epoch: int, tag: str):
        from deeplearning4j_tpu.serde.checkpoint import save_checkpoint

        # Never checkpoint a poisoned state: NaN/inf params hash cleanly
        # (integrity digests are content-blind), so a saved one would
        # verify forever and become an inescapable rollback target. This
        # window exists whenever detection lags the damage (check_every>1,
        # or a loss that goes non-finite a few steps after the params do).
        # The check reduces on device — one scalar D2H, not a second full
        # host copy of a state save_checkpoint is about to snapshot anyway.
        import jax.numpy as jnp

        ok = True
        for leaf in jax.tree_util.tree_leaves(ts.params):
            arr = jnp.asarray(leaf)
            if jnp.issubdtype(arr.dtype, jnp.floating):
                ok = jnp.logical_and(ok, jnp.isfinite(arr).all())
        if not bool(jax.device_get(ok)):
            step = int(jax.device_get(ts.step))
            self.recoveries.append({
                "kind": "skip_checkpoint",
                "step": step,
                "reason": "non-finite params"})
            rm = _obs()
            if rm is not None:
                rm.checkpoint_skips_total.inc()
            _flight("resilience.checkpoint_skip", step=step,
                    reason="non-finite params")
            return
        save_checkpoint(
            self.directory, ts, model=self.model, tag=tag,
            keep_last=self.policy.keep_last,
            extra_meta={"epoch": epoch, "batch_in_epoch": batch_in_epoch})

    def _latest_verified(self) -> Optional[str]:
        from deeplearning4j_tpu.serde.checkpoint import (
            latest_verified_checkpoint,
        )

        return latest_verified_checkpoint(self.directory)

    def resume(self, ts) -> Any:
        """Restore the latest verified checkpoint into ``ts`` (template);
        returns ``ts`` unchanged when none exists."""
        restored, _ = self._resume(ts)
        return restored

    def _resume(self, ts) -> Tuple[Any, Tuple[int, int]]:
        from deeplearning4j_tpu.serde.checkpoint import restore_checkpoint

        d = self._latest_verified()
        if d is None:
            return ts, (0, 0)
        meta = json.loads((Path(d) / "meta.json").read_text())
        return (restore_checkpoint(d, ts),
                (int(meta.get("epoch", 0)), int(meta.get("batch_in_epoch", 0))))

    def _rollback(self, template, err) -> Tuple[Any, Tuple[int, int]]:
        from deeplearning4j_tpu.serde.checkpoint import restore_checkpoint

        d = self._latest_verified()
        if d is None:
            raise RuntimeError(
                "no verified checkpoint to roll back to "
                f"(directory={self.directory})") from err
        meta = json.loads((Path(d) / "meta.json").read_text())
        ts = restore_checkpoint(d, template)
        self.recoveries.append({
            "kind": "rollback", "checkpoint": d,
            "to_step": int(meta.get("step", 0)), "cause": repr(err)})
        rm = _obs()
        if rm is not None:
            rm.rollbacks_total.inc()
        _flight("resilience.rollback", checkpoint=str(d),
                to_step=int(meta.get("step", 0)), cause=repr(err)[:200])
        return ts, (int(meta.get("epoch", 0)),
                    int(meta.get("batch_in_epoch", 0)))

    # -- fit -----------------------------------------------------------------

    def fit(self, ts, data, *, epochs: int = 1, listeners: Optional[List] = None,
            steps_per_epoch: Optional[int] = None, resume: bool = True):
        from deeplearning4j_tpu.data.dataset import as_batch_dict
        from deeplearning4j_tpu.resilience.cluster import touch_heartbeat
        from deeplearning4j_tpu.resilience.faults import get_fault_injector
        from deeplearning4j_tpu.resilience.retry import (
            RetryingIterator,
            retrying,
        )

        tr = self.trainer
        pol = self.policy
        listeners = listeners or []
        inj = get_fault_injector()
        nan_types = _nan_exception_types()
        self._lr_scale = 1.0          # cuts do not carry across fits
        self._step_fn = tr.train_step
        if pol.lr_cut != 1.0:
            self._install_lr_scale()

        start_epoch, skip_batches = 0, 0
        if resume:
            ts, (start_epoch, skip_batches) = self._resume(ts)
        if pol.data_retries and not isinstance(data, RetryingIterator):
            data = retrying(data, max_retries=pol.data_retries,
                            base_delay=pol.data_base_delay,
                            max_delay=pol.data_max_delay, seed=0)
        # outermost wrap (prefetch over the retrying reader) so retried
        # reads are what the background thread overlaps; no-op unless
        # DL4J_TPU_AUTO_PREFETCH=1 (both wrappers pass set_epoch through)
        from deeplearning4j_tpu.data.iterators import maybe_auto_prefetch

        data = maybe_auto_prefetch(data)
        host_step = int(jax.device_get(ts.step))
        # Anchor: a rollback target must exist before the first step can
        # fail (the donated input state is unrecoverable host-side).
        if self._latest_verified() is None:
            self._save(ts, epoch=start_epoch, batch_in_epoch=skip_batches,
                       tag="init")

        rollbacks = 0
        fail_counts: Dict[Tuple[int, int], int] = {}
        skip_set: Set[Tuple[int, int]] = set()
        stop = False
        tm = _train_obs()
        if tm is not None:
            from deeplearning4j_tpu.train.trainer import _StepTelemetry

            tele = _StepTelemetry(tr, tm)
        # the same legs as Trainer.fit, through the same helper: spans on
        # the profiler's clock and, with the metrics on, the timing
        # histograms and the host timeline's rows. The read is timed so the
        # starvation detector sees FT runs too; a skipped batch and a step
        # rolled back leave no row and no reading
        legs = IterationLegs(tm, annotate=annotate)
        for lst in listeners:
            lst.on_fit_start(tr, ts)
        # incident pipeline: arm the "train" device-capture hook for the
        # life of this fit, exactly like Trainer.fit (the per-step
        # note below is a no-op global check when nothing is pending)
        from deeplearning4j_tpu.observability.incidents import (
            enter_training,
            exit_training,
            note_train_step,
        )

        enter_training()
        try:
            epoch = start_epoch
            while epoch < epochs and not stop:
                if hasattr(data, "set_epoch"):
                    # pin the shuffle permutation to the logical epoch:
                    # a relaunched process (fresh iterator at epoch 0) or
                    # a rollback replay fast-forwards skip_batches of the
                    # SAME order the checkpoint position was recorded
                    # against, not a different permutation's prefix
                    data.set_epoch(epoch)
                for lst in listeners:
                    lst.on_epoch_start(epoch)
                restart_epoch = False
                b = 0
                it = iter(data)
                while True:
                    with legs.step(host_step + 1):
                        try:
                            with legs.read:
                                batch = next(it)
                        except StopIteration:
                            break
                        if b < skip_batches:
                            b += 1
                            continue
                        if (epoch, b) in skip_set:
                            self.recoveries.append(
                                {"kind": "skip_batch", "epoch": epoch,
                                 "batch": b})
                            rm = _obs()
                            if rm is not None:
                                rm.skipped_batches_total.inc()
                            _flight("resilience.skip_batch", epoch=epoch,
                                    batch=b)
                            b += 1
                            continue
                        batch = as_batch_dict(batch)
                        if inj.enabled:
                            # "train.worker_kill": die here (SIGKILL under
                            # !kill) so supervisor relaunch/resume paths are
                            # chaos-testable at an exact step
                            inj.maybe_fail("train.worker_kill")
                            batch = inj.maybe_poison_batch(batch)
                        if tr._batch_sharding is not None:
                            with legs.put:
                                batch = jax.device_put(
                                    batch, tr._batch_sharding)
                        new_ts = None
                        try:
                            # the leg holds the loss check, as step_seconds
                            # always has here
                            with legs.dispatch:
                                new_ts, metrics = self._step_fn(ts, batch)
                                if pol.check_every and \
                                        (host_step + 1) % pol.check_every == 0:
                                    loss = float(jax.device_get(
                                        metrics["total_loss"]))
                                    if not math.isfinite(loss):
                                        raise NonFiniteLossError(
                                            f"non-finite loss {loss} at step "
                                            f"{host_step + 1}",
                                            step=host_step + 1)
                        except nan_types as e:
                            rollbacks += 1
                            key = (epoch, b)
                            fail_counts[key] = fail_counts.get(key, 0) + 1
                            if rollbacks > pol.max_rollbacks:
                                raise
                            if pol.skip_poison_after and \
                                    fail_counts[key] >= pol.skip_poison_after:
                                skip_set.add(key)
                            template = new_ts if new_ts is not None else ts
                            ts, (r_epoch, r_skip) = self._rollback(template, e)
                            host_step = int(jax.device_get(ts.step))
                            if pol.lr_cut != 1.0:
                                self._lr_scale *= pol.lr_cut
                                # fresh jit wrapper → fresh trace → the new
                                # scale constant is baked into the executable
                                self._step_fn = tr._jit_with_nan_guard(
                                    tr._raw_step, tr._jit_kwargs)
                                self.recoveries.append(
                                    {"kind": "lr_cut",
                                     "scale": self._lr_scale})
                                rm = _obs()
                                if rm is not None:
                                    rm.lr_cuts_total.inc()
                                _flight("resilience.lr_cut",
                                        scale=self._lr_scale)
                            epoch = r_epoch
                            skip_batches = r_skip
                            restart_epoch = True
                            break
                        ts = new_ts
                        host_step += 1
                        note_train_step()  # armed incident capture boundary
                        touch_heartbeat()  # supervisor hang-detector beacon
                        if tm is not None:
                            tm.steps_total.inc()
                            feats = jax.tree_util.tree_leaves(
                                batch["features"])
                            tm.samples_total.inc(feats[0].shape[0])
                            tele.on_step(ts, batch, legs.read_s, legs.step_s,
                                         host_step)
                        b += 1
                        if pol.checkpoint_every and \
                                host_step % pol.checkpoint_every == 0:
                            self._save(ts, epoch=epoch, batch_in_epoch=b,
                                       tag="auto")
                        with legs.listeners:
                            for lst in listeners:
                                if lst.on_iteration(epoch, host_step, ts,
                                                    metrics):
                                    stop = True
                        if (steps_per_epoch is not None
                                and b >= steps_per_epoch):
                            break
                        if stop:
                            break
                if restart_epoch:
                    if hasattr(data, "reset"):
                        data.reset()
                    continue  # same (or rolled-back) epoch, fast-forwarding
                skip_batches = 0
                for lst in listeners:
                    if lst.on_epoch_end(epoch, ts):
                        stop = True
                if hasattr(data, "reset"):
                    data.reset()
                if tm is not None:
                    tm.epochs_total.inc()
                epoch += 1
                if pol.checkpoint_every_epoch and epoch < epochs:
                    # position = start of the next epoch: a rollback in
                    # epoch e+1 never replays epoch e's batches
                    self._save(ts, epoch=epoch, batch_in_epoch=0,
                               tag=f"epoch{epoch - 1}")
        finally:
            legs.close()
            exit_training()
            tr._upd_update = self._orig_upd
            for lst in listeners:
                lst.on_fit_end(tr, ts)
        return ts
