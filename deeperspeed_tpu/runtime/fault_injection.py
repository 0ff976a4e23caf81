"""Deterministic fault injection for the training-health sentinel.

The recovery machinery in `runtime/sentinel.py` is only trustworthy if
every path can be driven on demand: this harness injects NaN gradients,
loss spikes, and stalled steps at chosen steps so tests exercise
detect -> quarantine -> rollback -> abort and the hang watchdog end to
end.

Gating (zero overhead when off):

- config: ``{"training_health": {"fault_injection": {"faults": [...]}}}``
- env:    ``DS_FAULT_INJECT='{"faults": [...]}'`` (JSON, same schema)

When neither is present the engine holds no injector and compiles the
exact same program as before — no extra arguments, no extra ops. When
active, the train step compiles ONE extra variant taking a tiny
``(mode, factor)`` scalar pair; the per-step plan is pure host
bookkeeping.

Fault schema (all faults validated at parse time)::

    {"kind": "nan_grads" | "loss_spike" | "stall"
             | "peer_death" | "slow_peer" | "barrier_timeout"
             | "dcn_delay" | "slice_kill"
             | "prefill_error" | "decode_error" | "decode_stall"
             | "page_pool_pressure",
     "step": N,          # 0-based optimizer-step serial in this process
     "times": 1,         # fires on steps [step, step+times)
     "factor": 1e3,      # loss_spike: loss multiplier;
                         # page_pool_pressure: fraction of the FREE
                         # page pool seized for the step (0 < f <= 1,
                         # default 0.9)
     "seconds": 1.0,     # stall/decode_stall: sleep length;
                         # slow_peer: heartbeat gap;
                         # dcn_delay: injected latency PER EXPOSED
                         # cross-slice crossing (the engine multiplies
                         # by the schedule-aware crossing count —
                         # parallel.schedule.dcn_exposed_crossings)
     "peer": "sim0",     # peer_death/slow_peer: simulated peer name
     "slice": "slice1"}  # slice_kill: multislice slice name to kill

``step`` counts train_batch invocations in THIS process (a monotonic
serial, never rewound by rollback) — so a replayed window after a
rollback does not re-trigger a one-shot fault, which is exactly the
"transient corruption" scenario the recovery tests need. For the
serving engine the serial counts `InferenceEngine.step()` calls.

The elastic kinds are HOST faults (no device-step variant): the engine
pops them via `take_host_faults()` right after `plan_next_step()`.
``peer_death`` / ``slow_peer`` act on a SIMULATED peer registered with
the peer-health monitor (`elasticity/heartbeat.py`) — on one host they
reproduce exactly what a dead/wedged remote host looks like to the
observer; ``barrier_timeout`` arms `utils.distributed.barrier` to raise
a typed `BarrierTimeoutError` on its next rendezvous (e.g. the next
checkpoint commit), driving the fail-fast-and-hand-off path.

The MULTISLICE kinds (docs/multislice.md; require the ``multislice``
config block) make the two-slice regime drivable single-host:
``dcn_delay`` injects cross-slice wire latency host-side and
SCHEDULE-AWARE — ``seconds`` is charged once per EXPOSED DCN crossing
of the step (overlapped wire exposes only fill/drain crossings, the
classic wire every micro-batch hop), folded into the same host sleep
the ``stall`` kind uses; ``slice_kill`` stops the heartbeats of every
simulated peer of the named slice (`PeerHealthMonitor.kill_slice`),
driving slice-granular escalation -> `SliceLostError` -> re-partition.

The SERVING kinds are host faults too, consumed by `InferenceEngine`
(the training engine ignores them): ``prefill_error`` /
``decode_error`` raise an `InjectedServingFault` in place of the
compiled prefill/decode call — driving the quarantine → retry → poison
path; ``decode_stall`` sleeps inside the decode phase (drives the
serving hang watchdog); ``page_pool_pressure`` seizes a fraction of
the free page pool for the step (drives eviction under memory
pressure and the admission controller's shedding signal). Together
they make every shed/quarantine/retry/watchdog path single-host
testable (`docs/inference.md`, the ``chaos`` test marker).
"""

import json
import os

import jax.numpy as jnp

from .config_utils import DeepSpeedConfigError

SERVING_FAULT_KINDS = ("prefill_error", "decode_error", "decode_stall",
                       "page_pool_pressure")
MULTISLICE_FAULT_KINDS = ("dcn_delay", "slice_kill")
FAULT_KINDS = ("nan_grads", "loss_spike", "stall",
               "peer_death", "slow_peer", "barrier_timeout") + \
    MULTISLICE_FAULT_KINDS + SERVING_FAULT_KINDS
HOST_FAULT_KINDS = ("peer_death", "slow_peer", "barrier_timeout") + \
    MULTISLICE_FAULT_KINDS + SERVING_FAULT_KINDS
DEFAULT_SIM_PEER = "sim_peer_0"
PAGE_POOL_PRESSURE_DEFAULT_FRACTION = 0.9


class InjectedServingFault(RuntimeError):
    """The exception `prefill_error`/`decode_error` faults raise in
    place of the compiled serving call — a stand-in for a real
    transient step failure (XLA runtime error, device OOM burst), typed
    so tests can tell injected failures from genuine bugs."""

# device-side injection modes (the (mode, factor) scalar pair)
MODE_NONE = 0
MODE_NAN_GRADS = 1
MODE_LOSS_SPIKE = 2

ENV_VAR = "DS_FAULT_INJECT"


def validate_fault_spec(spec, where="training_health.fault_injection"):
    """Validate an injection spec dict -> normalized list of fault dicts.
    Raises DeepSpeedConfigError on any malformed entry (parse-time
    strictness: a typo'd fault plan must fail at startup, not silently
    never fire)."""
    if not isinstance(spec, dict):
        raise DeepSpeedConfigError(
            f"{where} must be an object with a 'faults' list, got "
            f"{type(spec).__name__}")
    unknown = sorted(set(spec) - {"faults"})
    if unknown:
        raise DeepSpeedConfigError(
            f"Unknown {where} key(s) {unknown}; valid keys: ['faults']")
    faults = spec.get("faults", [])
    if not isinstance(faults, (list, tuple)):
        raise DeepSpeedConfigError(
            f"{where}.faults must be a list, got "
            f"{type(faults).__name__}")
    known = {"kind", "step", "times", "factor", "seconds", "peer",
             "slice"}
    out = []
    for i, fault in enumerate(faults):
        if not isinstance(fault, dict):
            raise DeepSpeedConfigError(
                f"{where}.faults[{i}] must be an object, got "
                f"{type(fault).__name__}")
        unknown = sorted(set(fault) - known)
        if unknown:
            raise DeepSpeedConfigError(
                f"Unknown {where}.faults[{i}] key(s) {unknown}; valid "
                f"keys: {sorted(known)}")
        kind = fault.get("kind")
        if kind not in FAULT_KINDS:
            raise DeepSpeedConfigError(
                f"{where}.faults[{i}].kind must be one of "
                f"{list(FAULT_KINDS)}, got {kind!r}")
        step = fault.get("step")
        if not isinstance(step, int) or isinstance(step, bool) or step < 0:
            raise DeepSpeedConfigError(
                f"{where}.faults[{i}].step must be an int >= 0, got "
                f"{step!r}")
        times = fault.get("times", 1)
        if not isinstance(times, int) or isinstance(times, bool) \
                or times < 1:
            raise DeepSpeedConfigError(
                f"{where}.faults[{i}].times must be an int >= 1, got "
                f"{times!r}")
        factor = fault.get("factor",
                           PAGE_POOL_PRESSURE_DEFAULT_FRACTION
                           if kind == "page_pool_pressure" else 1e3)
        seconds = fault.get("seconds", 1.0)
        for key, value in (("factor", factor), ("seconds", seconds)):
            if not isinstance(value, (int, float)) or \
                    isinstance(value, bool) or value <= 0:
                raise DeepSpeedConfigError(
                    f"{where}.faults[{i}].{key} must be a number > 0, "
                    f"got {value!r}")
        if kind == "page_pool_pressure" and factor > 1:
            raise DeepSpeedConfigError(
                f"{where}.faults[{i}].factor is the fraction of the "
                f"free page pool to seize for a page_pool_pressure "
                f"fault — must be in (0, 1], got {factor!r}")
        peer = fault.get("peer", DEFAULT_SIM_PEER)
        if not isinstance(peer, str) or not peer:
            raise DeepSpeedConfigError(
                f"{where}.faults[{i}].peer must be a non-empty string, "
                f"got {peer!r}")
        if "peer" in fault and kind not in ("peer_death", "slow_peer"):
            raise DeepSpeedConfigError(
                f"{where}.faults[{i}].peer only applies to "
                f"peer_death/slow_peer faults, not {kind!r}")
        slice_name = fault.get("slice")
        if kind == "slice_kill":
            if not isinstance(slice_name, str) or not slice_name:
                raise DeepSpeedConfigError(
                    f"{where}.faults[{i}].slice is required for a "
                    f"slice_kill fault (the multislice slice name to "
                    f"kill), got {slice_name!r}")
        elif "slice" in fault:
            raise DeepSpeedConfigError(
                f"{where}.faults[{i}].slice only applies to slice_kill "
                f"faults, not {kind!r}")
        out.append({"kind": kind, "step": step, "times": times,
                    "factor": float(factor), "seconds": float(seconds),
                    "peer": peer, "slice": slice_name,
                    "remaining": times})
    return out


class FaultInjector:
    """Per-process deterministic fault plan.

    `plan_next_step()` is called exactly once per optimizer-step attempt
    and returns ``(mode, factor, stall_seconds)`` for that step; `mode`
    and `factor` ride into the jitted step as scalars (see
    `apply_fault`), `stall_seconds` is slept on the host."""

    def __init__(self, faults):
        self.faults = faults
        self.serial = 0       # monotonic step-attempt counter
        self.fired = []       # (serial, kind) audit trail
        self._pending_host = []   # host faults fired by the last plan

    @classmethod
    def from_config_env(cls, config_spec=None, env=None):
        """Build from the config block and/or the DS_FAULT_INJECT env var
        (faults from both are merged); None when neither is present."""
        env = os.environ if env is None else env
        faults = []
        if config_spec:
            faults += validate_fault_spec(config_spec)
        raw = env.get(ENV_VAR)
        if raw:
            try:
                spec = json.loads(raw)
            except json.JSONDecodeError as e:
                raise DeepSpeedConfigError(
                    f"{ENV_VAR} is not valid JSON: {e}") from e
            faults += validate_fault_spec(spec, where=ENV_VAR)
        if not faults:
            return None
        return cls(faults)

    @property
    def has_device_faults(self):
        return any(f["kind"] in ("nan_grads", "loss_spike")
                   for f in self.faults)

    @property
    def has_serving_faults(self):
        return any(f["kind"] in SERVING_FAULT_KINDS for f in self.faults)

    @property
    def has_multislice_faults(self):
        return any(f["kind"] in MULTISLICE_FAULT_KINDS
                   for f in self.faults)

    @property
    def simulated_peers(self):
        """Names of simulated peers the fault plan will act on — the
        engine registers these with the peer-health monitor up front so
        they heartbeat healthily until their fault fires."""
        return sorted({f["peer"] for f in self.faults
                       if f["kind"] in ("peer_death", "slow_peer")})

    def plan_next_step(self):
        serial = self.serial
        self.serial += 1
        mode, factor, stall = MODE_NONE, 1.0, 0.0
        for fault in self.faults:
            if fault["remaining"] <= 0:
                continue
            if not (fault["step"] <= serial
                    < fault["step"] + fault["times"]):
                continue
            fault["remaining"] -= 1
            self.fired.append((serial, fault["kind"]))
            if fault["kind"] == "nan_grads":
                mode = MODE_NAN_GRADS
            elif fault["kind"] == "loss_spike":
                mode = MODE_LOSS_SPIKE
                factor = fault["factor"]
            elif fault["kind"] == "stall":
                stall = max(stall, fault["seconds"])
            elif fault["kind"] in HOST_FAULT_KINDS:
                self._pending_host.append(dict(fault))
        return mode, factor, stall

    def take_host_faults(self):
        """Host-side faults fired by the most recent `plan_next_step`
        (peer_death / slow_peer / barrier_timeout); the engine applies
        them before dispatching the step. Drains the queue."""
        out, self._pending_host = self._pending_host, []
        return out


def apply_fault(loss, grads, fault):
    """In-jit injection: corrupt the accumulated grads / the step loss
    according to the ``(mode, factor)`` scalar pair. A `mode` of 0 is the
    identity (the `where`s select the clean values)."""
    import jax

    mode, factor = fault
    is_nan = mode == MODE_NAN_GRADS
    grads = jax.tree_util.tree_map(
        lambda g: jnp.where(is_nan, jnp.full(g.shape, jnp.nan, g.dtype), g),
        grads)
    loss = jnp.where(mode == MODE_LOSS_SPIKE,
                     loss * jnp.asarray(factor, loss.dtype), loss)
    return loss, grads
