"""Training-health sentinel: in-band anomaly detection + automatic recovery.

Multi-week runs die in ways the reference fork only handles reactively —
loss blow-ups, stuck loss scales, corrupted batches, hung hosts. This
module closes the loop:

- **Device-side probe** (`probe_update`): a handful of scalar ops fused
  into the existing jitted train step. It reuses the global grad norm and
  overflow flag the step already computes (`engine._apply_update`) and
  flags non-finite loss/grads plus EMA z-score spikes in loss and
  grad-norm. Debiased EMA mean/variance are carried in `HealthState`
  (part of `EngineState`), so detection costs no extra passes over the
  gradient tree and no host round-trips beyond the one scalar flags read.
- **In-jit quarantine**: with policy `skip_batch` or higher, a flagged
  step's optimizer update is skipped branchlessly (the same select
  machinery as the fp16 overflow skip) — a NaN gradient can never reach
  the master weights, even in bf16 runs with no loss-scale machinery.
- **Host-side escalation** (`TrainingHealthSentinel.after_step`):
  `warn` -> `skip_batch` (quarantine + dataloader provenance epoch/offset)
  -> `rollback` (restore the last committed checkpoint via the
  `AsyncCheckpointManager`, keep the dataloader past the bad window)
  -> `abort` (raise `TrainingHealthError`) after K consecutive anomalies.
- **Hang watchdog** (`HangWatchdog`): a per-step wall-clock deadline armed
  around every `train_batch`; on expiry it dumps all-thread stacks and
  triggers the existing preemption-style emergency save.

Everything is driven by the validated ``"training_health"`` JSON block
(`runtime/config.py`); the subsystem is entirely absent from the compiled
program when disabled. `runtime/fault_injection.py` drives every path
deterministically for tests (no benchmark cell runs the sentinel).
"""

import threading
import time
import traceback
import weakref
from typing import Any, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from ..utils.logging import log_dist, logger

# anomaly bitmask (HealthState.flags)
ANOM_NONFINITE_LOSS = 1
ANOM_NONFINITE_GRAD = 2
ANOM_LOSS_SPIKE = 4
ANOM_GRAD_SPIKE = 8

FLAG_NAMES = {
    ANOM_NONFINITE_LOSS: "nonfinite_loss",
    ANOM_NONFINITE_GRAD: "nonfinite_grad",
    ANOM_LOSS_SPIKE: "loss_spike",
    ANOM_GRAD_SPIKE: "grad_norm_spike",
}

# escalation ladder; the configured `policy` is the HIGHEST rung allowed
POLICIES = ("warn", "skip_batch", "rollback", "abort")


class TrainingHealthError(RuntimeError):
    """Raised when the sentinel escalates to `abort` (or a rollback is
    requested but impossible): the run is sick beyond automatic repair."""


class HealthState(NamedTuple):
    """Device-resident probe state, carried through the jitted step.

    EMAs are stored un-debiased (`ema / (1 - beta^count)` is the mean);
    `count` only advances on healthy steps so anomalies never poison the
    statistics they are measured against."""
    loss_ema: jnp.ndarray      # f32: EMA of loss
    loss_sq_ema: jnp.ndarray   # f32: EMA of loss^2
    gnorm_ema: jnp.ndarray     # f32: EMA of grad norm
    gnorm_sq_ema: jnp.ndarray  # f32: EMA of grad norm^2
    count: jnp.ndarray         # i32: healthy samples incorporated
    flags: jnp.ndarray         # i32: bitmask for the LAST step
    anomalies: jnp.ndarray     # i32: cumulative anomalous steps
    quarantined: jnp.ndarray   # i32: cumulative in-jit skipped updates


class ProbeConfig(NamedTuple):
    """Static (trace-time) probe knobs from the training_health block."""
    loss_zscore: float
    grad_norm_zscore: float
    ema_beta: float
    warmup_steps: int
    quarantine: bool    # policy >= skip_batch: hard anomalies skip in-jit


def init_health_state():
    # distinct arrays per field: the engine DONATES its state pytree to
    # the jitted step, and a buffer appearing twice in a donated tree is
    # an XLA error ("attempt to donate the same buffer twice")
    def z32():
        return jnp.array(0.0, jnp.float32)

    def i32():
        return jnp.array(0, jnp.int32)

    return HealthState(loss_ema=z32(), loss_sq_ema=z32(), gnorm_ema=z32(),
                       gnorm_sq_ema=z32(), count=i32(), flags=i32(),
                       anomalies=i32(), quarantined=i32())


def _zscore(value, ema, sq_ema, count, beta):
    """Debiased EMA z-score; robust to the flat-metric case (var -> 0).

    The sd gets a floor of 2% of the mean: right after warmup the EMA
    variance is built from few samples and can be arbitrarily small, so
    a raw z-score flags ordinary jitter (measured: two near-equal losses
    put a 10% wiggle at z ~ 7.7). With the floor, a z of 6 requires a
    deviation of at least ~12% of the running mean — noise never clears
    it, while real blow-ups (orders of magnitude) always do."""
    n = jnp.maximum(count, 1).astype(jnp.float32)
    corr = 1.0 - jnp.power(jnp.float32(beta), n)
    mean = ema / corr
    var = jnp.maximum(sq_ema / corr - mean * mean, 0.0)
    sd = jnp.sqrt(var) + 0.02 * jnp.abs(mean) + 1e-12
    return (value - mean) / sd


def probe_update(health, loss, grad_norm, bad_grad, cfg):
    """One probe step: (new HealthState, hard-anomaly bool).

    Pure jnp scalar math — traced inside the jitted train step on the
    standard path, or run eagerly by the sentinel for host-optimizer
    tiers. `loss` may be None (update-only paths).

    `bad_grad` is the CALLER's non-finite-gradient verdict (may be a
    static Python False). The caller owns it because the right condition
    is precision-dependent: for bf16/fp32 runs it is `~isfinite(norm)`
    (no other machinery catches a NaN there), while for fp16 loss-scaled
    runs an overflow is a ROUTINE, self-correcting event during the
    scale search — it only becomes an anomaly once the scaler is pinned
    at its floor (see `grad_anomaly_in_jit`). Treating every overflow as
    an anomaly would escalate a healthy run to rollback/abort during the
    first dozen startup steps.
    """
    gn = jnp.asarray(grad_norm, jnp.float32)
    gn_finite = jnp.isfinite(gn)
    flags = jnp.where(jnp.asarray(bad_grad, jnp.bool_),
                      ANOM_NONFINITE_GRAD, 0).astype(jnp.int32)

    warm = health.count >= cfg.warmup_steps
    if cfg.grad_norm_zscore > 0:
        gz = _zscore(gn, health.gnorm_ema, health.gnorm_sq_ema,
                     health.count, cfg.ema_beta)
        g_spike = jnp.logical_and(jnp.logical_and(warm, gn_finite),
                                  gz > cfg.grad_norm_zscore)
        flags = flags | jnp.where(g_spike, ANOM_GRAD_SPIKE, 0)

    if loss is not None:
        ls = jnp.asarray(loss, jnp.float32)
        l_finite = jnp.isfinite(ls)
        flags = flags | jnp.where(l_finite, 0, ANOM_NONFINITE_LOSS)
        if cfg.loss_zscore > 0:
            lz = _zscore(ls, health.loss_ema, health.loss_sq_ema,
                         health.count, cfg.ema_beta)
            l_spike = jnp.logical_and(jnp.logical_and(warm, l_finite),
                                      lz > cfg.loss_zscore)
            flags = flags | jnp.where(l_spike, ANOM_LOSS_SPIKE, 0)

    anomalous = flags != 0
    hard = jnp.logical_and(anomalous, cfg.quarantine)

    beta = jnp.float32(cfg.ema_beta)

    def ema(prev, value):
        # frozen on anomalous steps: a spike must not drag the baseline
        # toward itself (the next spike would then look normal)
        value = jnp.where(jnp.isfinite(value), value, prev)
        return jnp.where(anomalous, prev, beta * prev + (1 - beta) * value)

    new = HealthState(
        loss_ema=(ema(health.loss_ema, jnp.asarray(loss, jnp.float32))
                  if loss is not None else health.loss_ema),
        loss_sq_ema=(ema(health.loss_sq_ema,
                         jnp.square(jnp.asarray(loss, jnp.float32)))
                     if loss is not None else health.loss_sq_ema),
        gnorm_ema=ema(health.gnorm_ema, gn),
        gnorm_sq_ema=ema(health.gnorm_sq_ema, jnp.square(gn)),
        count=health.count + jnp.where(anomalous, 0, 1).astype(jnp.int32),
        flags=flags,
        anomalies=health.anomalies +
        jnp.where(anomalous, 1, 0).astype(jnp.int32),
        quarantined=health.quarantined +
        jnp.where(hard, 1, 0).astype(jnp.int32))
    return new, hard


def grad_anomaly_in_jit(engine, scale_state, grad_norm, overflow):
    """The `bad_grad` input for `probe_update` on the jitted path.

    - loss-scaled (fp16): overflow steps are the dynamic scaler's normal
      startup search and already skip their update; they count as an
      anomaly only once the scale is pinned at `min_loss_scale` (no room
      left to self-correct — the run is genuinely sick). The non-finite
      norm on such steps is the overflow itself, so the norm check is
      NOT applied separately.
    - unscaled (bf16/fp32): `overflow` is statically False and nothing
      else catches a NaN — a non-finite global norm IS the anomaly.
    """
    if engine._config.loss_scaling_enabled:
        if not engine.dynamic_loss_scale():
            # static scale: nothing self-corrects — overflow IS sickness
            return jnp.asarray(overflow, jnp.bool_)
        args = engine._config.dynamic_loss_scale_args or {}
        min_scale = float(args.get("min_loss_scale", 1))
        at_floor = scale_state.cur_scale <= min_scale
        return jnp.logical_and(jnp.asarray(overflow, jnp.bool_), at_floor)
    return jnp.logical_not(jnp.isfinite(
        jnp.asarray(grad_norm, jnp.float32)))


def decode_flags(flags):
    """Human-readable anomaly names for a flags bitmask."""
    return [name for bit, name in FLAG_NAMES.items() if flags & bit]


def dump_all_stacks():
    """Format every thread's current Python stack (watchdog expiry)."""
    import sys
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for ident, frame in sys._current_frames().items():
        out.append(f"--- thread {names.get(ident, '?')} ({ident}) ---")
        out.extend(line.rstrip()
                   for line in traceback.format_stack(frame))
    return "\n".join(out)


class HangWatchdog:
    """Per-step wall-clock deadline on a daemon thread.

    `arm()` at step entry, `feed()` after the step's host work completes.
    On expiry the callback fires ONCE per armed window (a genuinely hung
    step must not spam a dump per poll tick). The thread holds only a
    weakref to its owner so discarded engines stay collectible; it exits
    when the owner does.

    Two owners share it: the training sentinel (`TrainingHealthSentinel`,
    `training_health.hang_timeout_seconds`) and the serving engine
    (`InferenceEngine._on_serving_hang`, `inference.hang_timeout_s` —
    expiry there requests a drain-style emergency flush instead of an
    emergency checkpoint). Both skip arming while the step's program is
    still compiling: an XLA compile is not a hang."""

    def __init__(self, timeout_s, owner, on_expire_name):
        self.timeout_s = float(timeout_s)
        self._lock = threading.Lock()
        self._deadline = None
        self._fired = False
        self._stop = threading.Event()
        owner_ref = weakref.ref(owner)
        poll = max(min(self.timeout_s / 4.0, 1.0), 0.02)

        def loop():
            while not self._stop.wait(poll):
                owner = owner_ref()
                if owner is None:
                    return
                with self._lock:
                    expired = (self._deadline is not None
                               and not self._fired
                               and time.monotonic() > self._deadline)
                    if expired:
                        # one-shot per armed step: consume the deadline
                        # so the (slow) callback can't race a re-check —
                        # only the next arm() re-enables expiry
                        self._fired = True
                        self._deadline = None
                if expired:
                    try:
                        getattr(owner, on_expire_name)()
                    except Exception as e:  # pragma: no cover
                        logger.error(f"hang watchdog callback failed: {e}")
                del owner

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="ds-hang-watchdog")
        self._thread.start()

    def arm(self):
        with self._lock:
            self._deadline = time.monotonic() + self.timeout_s
            self._fired = False

    def feed(self):
        with self._lock:
            self._deadline = None
            self._fired = False

    def stop(self):
        self._stop.set()


class TrainingHealthSentinel:
    """Host-side policy engine over the device probe's verdicts.

    Owned by the engine (constructed from the "training_health" config
    block); holds only a weakref back so the engine stays collectible."""

    def __init__(self, engine, policy="warn", loss_zscore=6.0,
                 grad_norm_zscore=6.0, ema_beta=0.98, warmup_steps=20,
                 rollback_after=2, abort_after=5, max_rollbacks=2,
                 hang_timeout_seconds=0.0, max_quarantine_records=64):
        self.policy = policy
        self.policy_rank = POLICIES.index(policy)
        self.rollback_after = int(rollback_after)
        self.abort_after = int(abort_after)
        self.max_rollbacks = int(max_rollbacks)
        self.max_quarantine_records = int(max_quarantine_records)
        self._engine_ref = weakref.ref(engine)

        # Host-optimizer tiers (ZeRO-Offload / param streaming) apply the
        # update on the host — no jitted update to fuse the probe into.
        # The sentinel then probes eagerly from the (already host-side)
        # step metrics; quarantine degrades to the tiers' own non-finite
        # skip, while rollback/abort still fully work.
        self.device_probe = not (getattr(engine, "host_offload", False)
                                 or getattr(engine, "param_offload", False))
        self.probe_config = ProbeConfig(
            loss_zscore=float(loss_zscore),
            grad_norm_zscore=float(grad_norm_zscore),
            ema_beta=float(ema_beta),
            warmup_steps=int(warmup_steps),
            quarantine=(self.policy_rank >= POLICIES.index("skip_batch")
                        and self.device_probe))
        self._host_health = None if self.device_probe else \
            init_health_state()

        # host-side mirrors / telemetry
        self.anomalies = 0
        self.quarantined = 0
        self.consecutive = 0
        self.rollbacks = 0
        self.quarantined_windows = []   # provenance records
        self.last_flags = 0
        self.watchdog_fires = 0
        self.last_stack_dump = None
        self._warned = 0

        self.watchdog = None
        if hang_timeout_seconds and hang_timeout_seconds > 0:
            self.watchdog = HangWatchdog(hang_timeout_seconds, self,
                                         "_on_hang")

    # ------------------------------------------------------------------
    # watchdog plumbing (called by the engine around every step)
    # ------------------------------------------------------------------

    def watchdog_arm(self):
        if self.watchdog is not None:
            self.watchdog.arm()

    def watchdog_feed(self):
        if self.watchdog is not None:
            self.watchdog.feed()

    def _on_hang(self):
        """Runs on the watchdog thread: the armed step blew its deadline."""
        self.watchdog_fires += 1
        dump = dump_all_stacks()
        self.last_stack_dump = dump
        logger.error(
            f"hang watchdog: step exceeded the "
            f"{self.watchdog.timeout_s:.1f}s deadline; all-thread stack "
            f"dump follows\n{dump}")
        engine = self._engine_ref()
        if engine is None:
            return
        # local hang vs peer failure: a step wedged inside a collective
        # because a PEER died looks identical from this host's stacks —
        # the heartbeat monitor (elasticity/heartbeat.py) disambiguates.
        # Stale peers -> name them (the supervisor/operator should look
        # THERE); all peers healthy -> this really is a local hang.
        peer_monitor = getattr(engine, "peer_monitor", None)
        if peer_monitor is not None:
            stale = [name for name, st in
                     peer_monitor.peer_status().items()
                     if st["status"] != "ok"]
            # cite the fleet skew probe's quantitative per-host verdict
            # when one exists (runtime/fleet.py note_skew): the
            # LOCAL-vs-peer call is then backed by measured ms/step
            skew_fn = getattr(peer_monitor, "skew_context", None)
            cites = []
            if skew_fn is not None:
                cites = [c for c in (skew_fn(n) for n in sorted(stale))
                         if c]
            if stale:
                logger.error(
                    f"hang watchdog: peer(s) {sorted(stale)} have stale "
                    f"heartbeats — this step is most likely blocked on a "
                    f"DEAD/SLOW PEER inside a collective, not hung "
                    f"locally (peer-failure escalation will fire at "
                    f"fail_after_s)"
                    + (f" [fleet skew probe: {'; '.join(cites)}]"
                       if cites else ""))
            else:
                logger.error(
                    "hang watchdog: all peer heartbeats are fresh — "
                    "treating this as a LOCAL hang")
        # memory snapshot now (host-side reads are thread-safe); a trace
        # is armed for the next step in case the hang clears
        self._telemetry_anomaly(engine, "watchdog_hang")
        manager = getattr(engine, "checkpoint_manager", None)
        if manager is not None and manager.save_on_preemption and \
                manager.save_dir:
            # preemption-style: flag only; the emergency save runs on the
            # main thread at the next step boundary (if the hang clears)
            manager.preemption_requested = True
            logger.error("hang watchdog: requested a preemption-style "
                         "emergency checkpoint at the next step boundary")

    # ------------------------------------------------------------------
    # per-step verdict + escalation
    # ------------------------------------------------------------------

    def after_step(self, engine, metrics, overflow):
        """Read the probe's verdict for the step that just ran and apply
        the escalation policy. Returns one of "ok", "warned",
        "quarantined", "rollback"; raises TrainingHealthError on abort."""
        if self.device_probe:
            health = engine.state.health
            if health is None:
                return "ok"
            flags = int(np.asarray(health.flags))
        else:
            # host-optimizer tiers detect non-finite grads on the host
            # regardless of precision; the same scale-search exemption
            # as grad_anomaly_in_jit applies (a dynamic scaler with room
            # to halve owns overflow recovery)
            bad_grad = bool(overflow)
            if bad_grad and engine.dynamic_loss_scale():
                args = engine._config.dynamic_loss_scale_args or {}
                bad_grad = float(engine.state.scale.cur_scale) <= \
                    float(args.get("min_loss_scale", 1))
            self._host_health, _ = probe_update(
                self._host_health, metrics.loss, metrics.grad_norm,
                bad_grad, self.probe_config)
            flags = int(np.asarray(self._host_health.flags))

        self.last_flags = flags
        if flags == 0:
            self.consecutive = 0
            return "ok"

        self.anomalies += 1
        self.consecutive += 1
        record = self._provenance(engine, flags)
        quarantined = self.probe_config.quarantine
        if quarantined:
            self.quarantined += 1
            self.quarantined_windows.append(record)
            del self.quarantined_windows[:-self.max_quarantine_records]
        self._warn(record, quarantined)
        self._record_monitor(engine)
        self._telemetry_anomaly(engine, "+".join(record["kinds"]))

        if self.policy_rank >= POLICIES.index("rollback") and \
                self.consecutive >= self.rollback_after and \
                self._can_rollback(engine):
            if self.rollbacks >= self.max_rollbacks:
                raise TrainingHealthError(
                    f"training health: {self.consecutive} consecutive "
                    f"anomalous steps and the rollback budget "
                    f"({self.max_rollbacks}) is exhausted; aborting. "
                    f"Last anomaly: {record}")
            self._do_rollback(engine, record)
            return "rollback"
        if self.policy_rank >= POLICIES.index("abort") and \
                self.consecutive >= self.abort_after:
            raise TrainingHealthError(
                f"training health: {self.consecutive} consecutive "
                f"anomalous steps (abort_after={self.abort_after}); "
                f"aborting. Last anomaly: {record}")
        return "quarantined" if quarantined else "warned"

    def after_window(self, engine):
        """`train_steps` windows advance many steps in one jitted call;
        per-step escalation is impossible, but the in-jit quarantine
        still protected the weights. Sync the host mirrors and warn."""
        if not self.device_probe or engine.state.health is None:
            return
        health = engine.state.health
        anomalies = int(np.asarray(health.anomalies))
        quarantined = int(np.asarray(health.quarantined))
        if anomalies > self.anomalies:
            logger.warning(
                f"training health: {anomalies - self.anomalies} anomalous "
                f"step(s) inside the fused train_steps window "
                f"({quarantined - self.quarantined} quarantined in-jit); "
                "per-step escalation needs the train_batch loop")
            self._record_monitor(engine)
        self.anomalies = anomalies
        self.quarantined = quarantined

    # ------------------------------------------------------------------
    # actions
    # ------------------------------------------------------------------

    def _provenance(self, engine, flags):
        """Where in the data stream the anomaly happened (PR 3's
        dataloader state_dict provenance: epoch + batch offset)."""
        record = {"step": int(engine.global_steps),
                  "flags": flags,
                  "kinds": decode_flags(flags)}
        loader = getattr(engine, "training_dataloader", None)
        if loader is not None and hasattr(loader, "position"):
            record.update(loader.position())
        return record

    def _warn(self, record, quarantined):
        self._warned += 1
        # rate-limited: first 5, then every 50th — a pathological run
        # must not drown the log in per-step anomaly lines
        if self._warned <= 5 or self._warned % 50 == 0:
            action = "update quarantined" if quarantined else \
                "detection only (policy=warn)"
            log_dist(f"TRAINING HEALTH: anomalous step "
                     f"{record['kinds']} at {record} — {action}; "
                     f"{self.consecutive} consecutive", ranks=[0])

    def _telemetry_anomaly(self, engine, kind):
        """Hand the anomaly to the telemetry layer (runtime/telemetry):
        with `capture_on_anomaly` it snapshots device memory now and
        arms a profiler trace over the next step(s) — once per
        consecutive-anomaly episode."""
        telemetry = getattr(engine, "telemetry", None)
        if telemetry is not None:
            telemetry.on_anomaly(engine, kind)

    def _record_monitor(self, engine):
        monitor = getattr(engine, "monitor", None)
        if monitor is not None and hasattr(monitor, "record_health"):
            monitor.record_health(engine.global_samples, {
                "anomalies": self.anomalies,
                "quarantined": self.quarantined,
                "rollbacks": self.rollbacks,
                "consecutive": self.consecutive,
                "watchdog_fires": self.watchdog_fires,
            })

    def _can_rollback(self, engine):
        manager = getattr(engine, "checkpoint_manager", None)
        return manager is not None and manager.save_dir is not None

    def _do_rollback(self, engine, record):
        """Restore the last committed checkpoint; keep the dataloader at
        its CURRENT position (already past the bad window) instead of
        rewinding it with the checkpoint — replaying the quarantined
        batch would re-trigger the same anomaly on real data corruption."""
        manager = engine.checkpoint_manager
        from .telemetry import NULL_TELEMETRY
        telemetry = getattr(engine, "telemetry", NULL_TELEMETRY)
        with telemetry.span("rollback_restore"):
            manager.wait()   # newest commit must be durable before load
            path, _ = engine.load_checkpoint(manager.save_dir,
                                             load_dataloader_states=False)
        if path is None:
            raise TrainingHealthError(
                f"training health: rollback requested after {record} but "
                f"no committed checkpoint exists under "
                f"{manager.save_dir}")
        self.rollbacks += 1
        self.consecutive = 0
        log_dist(f"TRAINING HEALTH: rolled back to {path} after "
                 f"anomaly {record}; dataloader continues past the "
                 f"quarantined window (rollback {self.rollbacks}/"
                 f"{self.max_rollbacks})", ranks=[0])
        self._record_monitor(engine)
