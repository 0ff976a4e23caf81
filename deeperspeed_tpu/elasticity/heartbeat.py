"""Peer-health detection over coordination-service heartbeats.

PR 4's hang watchdog answers "is THIS process stuck?"; on a multi-host
pod the dominant failure mode is the opposite one — a PEER host dying
or being preempted mid-run, which previously surfaced only as a
DEADLINE_EXCEEDED out of `utils.distributed.barrier()` (or, worse, an
unbounded hang inside a device collective) with no way to tell WHICH
host vanished. This module closes that gap:

- every process publishes a monotonic heartbeat (serial + training
  step) to a shared key-value store — the same `jax.distributed`
  coordination client the barrier helper already uses, so no extra
  service is deployed;
- a daemon thread consumes every peer's stream and tracks staleness by
  LOCAL observation time (when did *I* last see this peer's serial
  advance) — no cross-host clock comparison;
- staleness escalates per peer: ``ok`` → ``slow`` (past ``warn_after_s``;
  logged once, telemetry scalar) → ``dead`` (past ``fail_after_s``).
  A dead peer sets a flag the engine reads at the next step boundary
  (the preemption-handler pattern: detection on the thread, action on
  the main thread) — emergency checkpoint, then a typed
  `PeerFailureError` whose exit code the supervisor recognizes as
  restartable.

The transport is pluggable: `CoordinationTransport` (multi-host,
coordination-service KV) and `InMemoryTransport` (single-process). The
fault-injection harness (`runtime/fault_injection.py` ``peer_death`` /
``slow_peer``) drives SIMULATED peers through the in-memory transport,
so the full detect → checkpoint → exit → supervised-restart loop is
testable on one host.
"""

import json
import threading
import time
import weakref

from ..utils.logging import logger
from .config import PeerFailureError

PEER_OK = "ok"
PEER_SLOW = "slow"
PEER_DEAD = "dead"

# synthetic "peer" name under which continuous heartbeat-TRANSPORT
# failure is reported: the coordination service lives on process 0, so
# an unreachable store is itself a (very likely) peer failure
COORDINATOR = "<coordination-service>"

_KV_PREFIX = "ds_elastic/hb"

# checkpointing's commit-barrier failure path asks the live monitor (if
# any) which peers look stale — "record which peer was absent"
_active_monitor_ref = None


def active_monitor():
    """The most recently started PeerHealthMonitor, or None."""
    ref = _active_monitor_ref
    return ref() if ref is not None else None


def suspect_peers():
    """Names of peers the active monitor considers slow/dead (empty
    when no monitor runs) — used to annotate barrier timeouts."""
    monitor = active_monitor()
    if monitor is None:
        return []
    return [name for name, st in monitor.peer_status().items()
            if st["status"] != PEER_OK]


class InMemoryTransport:
    """Process-local heartbeat store: the single-host stand-in (and the
    seam the fault injector's simulated peers publish through)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._beats = {}

    def publish(self, peer, payload):
        with self._lock:
            self._beats[str(peer)] = dict(payload)

    def read_all(self):
        with self._lock:
            return {k: dict(v) for k, v in self._beats.items()}

    def discard(self, peer):
        """Drop a key (best-effort; absent is fine). The KV-page
        handoff channel retires consumed offer/ack slots through this
        so a long-lived serving split cannot grow the store without
        bound."""
        with self._lock:
            self._beats.pop(str(peer), None)


class CoordinationTransport:
    """Heartbeats over the jax.distributed coordination-service KV store
    (the same client `utils.distributed.barrier` uses): one key per
    peer, overwritten on every beat."""

    def __init__(self, client, prefix=_KV_PREFIX):
        self._client = client
        self._prefix = prefix

    def publish(self, peer, payload):
        self._client.key_value_set(f"{self._prefix}/{peer}",
                                   json.dumps(payload),
                                   allow_overwrite=True)

    def read_all(self):
        try:
            entries = self._client.key_value_dir_get(self._prefix)
        except Exception:  # pragma: no cover - no beats published yet
            return {}
        beats = {}
        for key, value in entries:
            try:
                payload = json.loads(value)
            except (TypeError, ValueError):  # pragma: no cover
                continue
            peer = key[len(self._prefix):].strip("/").split("/")[0]
            beats[peer] = payload
        return beats

    def discard(self, peer):
        """Best-effort delete of one key (absent is fine) — the handoff
        channel's slot retirement."""
        try:
            self._client.key_value_delete(f"{self._prefix}/{peer}")
        except Exception:        # already gone / service hiccup
            pass


class _SimulatedPeer:
    """A fake peer the monitor itself keeps alive each poll — the
    single-host handle `peer_death`/`slow_peer` faults act on."""

    def __init__(self, name):
        self.name = name
        self.alive = True
        self.delay_s = 0.0         # publish at most once per delay_s
        self.serial = 0
        self._last_pub = None


class PeerHealthMonitor:
    """Publish-and-observe heartbeat loop with per-peer staleness
    escalation. Thread-hosted in production (`start()`); every decision
    lives in `poll_once(now)` so tests drive it with a fake clock."""

    def __init__(self, self_name, peers=(), interval_s=5.0,
                 warn_after_s=15.0, fail_after_s=60.0, transport=None,
                 clock=time.monotonic, step_fn=None):
        self.self_name = str(self_name)
        self.interval_s = float(interval_s)
        self.warn_after_s = float(warn_after_s)
        self.fail_after_s = float(fail_after_s)
        self.transport = transport if transport is not None \
            else InMemoryTransport()
        self._clock = clock
        # step_fn feeds the published payload (weakly bound by the
        # engine: lambda over a weakref) — peers' dashboards can see how
        # far each host got, and the supervisor's steps-lost accounting
        # reads it from the progress the payload mirrors
        self._step_fn = step_fn or (lambda: -1)

        self._lock = threading.Lock()
        self._serial = 0
        self._last_publish = None
        # name -> {"serial", "step", "seen": local time the serial last
        # advanced, "status"}
        self._peers = {str(p): None for p in peers if str(p) !=
                       self.self_name}
        self._simulated = {}
        self.failed = {}             # name -> staleness at death
        self.warned = set()
        # peer name -> slice name (docs/multislice.md): when set, the
        # SLICE becomes the unit of staleness escalation — one dead
        # host breaks its slice's ICI mesh, so the whole slice is lost
        self._slice_map = {}
        # quantitative per-host step skew from the fleet probe
        # (runtime/fleet.py note_skew): whole-dict swaps, read lock-free
        # from the poll thread so escalation logs can cite it
        self._skew_behind_ms = {}
        self._skew_steps = {}
        self.transport_errors = 0
        self._transport_fail_since = None
        self._first_poll = None      # first-beat grace starts here
        self._stop = threading.Event()
        self._thread = None

    # -- lifecycle ---------------------------------------------------------

    def start(self):
        global _active_monitor_ref
        if self._thread is not None:
            return self
        _active_monitor_ref = weakref.ref(self)
        self_ref = weakref.ref(self)

        def loop():
            while True:
                monitor = self_ref()
                if monitor is None:
                    return
                stop, poll = monitor._stop, monitor._poll_period()
                monitor.poll_once()
                del monitor          # don't pin across the wait
                if stop.wait(poll):
                    return

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="ds-peer-health")
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None

    def _poll_period(self):
        # observe a few times per publish interval so a peer crossing
        # warn/fail thresholds is noticed promptly
        return max(min(self.interval_s / 2.0, 1.0), 0.05)

    # -- fleet skew probe (runtime/fleet.py) -------------------------------

    def note_skew(self, behind_ms_by_peer, behind_steps_by_peer):
        """Record the fleet probe's quantitative verdict: per-host EMA
        of step-time lateness behind the fleet median (ms) and the
        consecutive steps each host has spent past the slow threshold.
        Whole-dict swaps (atomic under the GIL) — the poll thread reads
        without taking the monitor lock, so `skew_context` is safe from
        inside `_observe`."""
        self._skew_behind_ms = {str(k): float(v)
                                for k, v in behind_ms_by_peer.items()}
        self._skew_steps = {str(k): int(v)
                            for k, v in behind_steps_by_peer.items()}

    def skew_context(self, name):
        """Human-readable skew citation for one peer ("host 3 is
        180ms/step behind the median for 50 consecutive steps"), or
        None when the probe has nothing quantitative on it — the slow
        escalation and the hang watchdog's LOCAL-vs-peer verdict cite
        this instead of a staleness guess."""
        name = str(name)
        behind = self._skew_behind_ms.get(name)
        if behind is None or behind <= 0:
            return None
        steps = self._skew_steps.get(name, 0)
        return (f"host {name} is {behind:.0f}ms/step behind the median "
                f"for {steps} consecutive steps")

    # -- fault-injection hooks (single-host simulated peers) ---------------

    def ensure_simulated_peer(self, name):
        name = str(name)
        with self._lock:
            if name not in self._simulated:
                self._simulated[name] = _SimulatedPeer(name)
                self._peers.setdefault(name, None)
        return name

    def inject_peer_death(self, name):
        """The simulated peer stops heartbeating — indistinguishable,
        to the observer, from the host dying."""
        sim = self._simulated.get(str(name))
        if sim is None:
            raise KeyError(f"no simulated peer {name!r} registered")
        sim.alive = False
        logger.warning(f"fault injection: simulated peer {name} died "
                       f"(heartbeats stop)")

    def inject_slow_peer(self, name, delay_s):
        """The simulated peer heartbeats at most once per `delay_s` —
        a wedged-but-alive host (straggler / thrashing)."""
        sim = self._simulated.get(str(name))
        if sim is None:
            raise KeyError(f"no simulated peer {name!r} registered")
        sim.delay_s = float(delay_s)
        logger.warning(f"fault injection: simulated peer {name} slowed "
                       f"to one heartbeat per {delay_s:.1f}s")

    def simulated_delays(self):
        """{name: delay_s} of the LIVE simulated peers — the fleet skew
        probe's single-host gather reads a `slow_peer` fault's delay as
        that host's per-step arrival lateness."""
        with self._lock:
            return {name: sim.delay_s
                    for name, sim in self._simulated.items() if sim.alive}

    # -- slice granularity (docs/multislice.md) ----------------------------

    def set_slice_map(self, peer_to_slice):
        """Promote escalation to SLICE granularity: map each heartbeat
        peer to its slice. Unmapped peers (and the COORDINATOR
        pseudo-peer) keep host-granular semantics — their loss is never
        a slice loss."""
        self._slice_map = {str(p): str(s)
                           for p, s in dict(peer_to_slice).items()}

    def slice_of(self, name):
        return self._slice_map.get(str(name))

    def peers_in_slice(self, slice_name):
        return sorted(p for p, s in self._slice_map.items()
                      if s == str(slice_name))

    @property
    def failed_slices(self):
        """Slice names with >= 1 dead member. A single dead host is a
        hole in its slice's ICI mesh: the slice's collectives cannot
        complete, so the slice — not the host — is the failure unit."""
        return sorted({self._slice_map[p] for p in self.failed
                       if p in self._slice_map})

    def slice_status(self, now=None):
        """{slice: {"status", "peers", "dead"}} — "ok" only when every
        member is ok; any dead member makes the slice "dead"."""
        per_peer = self.peer_status(now)
        out = {}
        for peer, sname in self._slice_map.items():
            ent = out.setdefault(sname, {"status": "ok", "peers": [],
                                         "dead": []})
            ent["peers"].append(peer)
            status = (per_peer.get(peer) or {}).get("status", "unknown")
            if peer in self.failed or status == "dead":
                ent["status"] = "dead"
                ent["dead"].append(peer)
            elif status == "slow" and ent["status"] == "ok":
                ent["status"] = "slow"
        for ent in out.values():
            ent["peers"].sort()
            ent["dead"].sort()
        return out

    def kill_slice(self, slice_name):
        """Fault-injection hook: stop the heartbeats of every SIMULATED
        member of `slice_name` (the `slice_kill` fault kind). Raises if
        the slice has no simulated members — a silently inert kill
        would pass the chaos drill without testing anything."""
        members = self.peers_in_slice(slice_name)
        sims = [p for p in members if p in self._simulated]
        if not sims:
            raise KeyError(
                f"slice {slice_name!r} has no simulated peers "
                f"registered (members: {members})")
        for p in sims:
            self.inject_peer_death(p)
        logger.warning(f"fault injection: slice {slice_name} killed "
                       f"({len(sims)} simulated peer(s))")

    # -- the observable core ----------------------------------------------

    def poll_once(self, now=None):
        """One publish-and-observe turn. Returns the current
        {peer: status-dict} view.

        Transport errors (the coordination service going unreachable —
        most likely because the host backing it died) must not kill the
        monitor thread and silently disable detection: they are caught,
        counted, and after ``fail_after_s`` of CONTINUOUS failure the
        coordination service itself is declared a dead peer (the
        escalation path then runs exactly as for any other peer)."""
        now = self._clock() if now is None else now
        if self._first_poll is None:
            self._first_poll = now
        try:
            self._publish_self(now)
            self._publish_simulated(now)
            self._observe(now)
        except Exception as e:
            self._note_transport_error(now, e)
        else:
            self._transport_fail_since = None
        return self.peer_status()

    def _note_transport_error(self, now, exc):
        self.transport_errors += 1
        if self._transport_fail_since is None:
            self._transport_fail_since = now
            logger.warning(
                f"peer health: heartbeat transport error "
                f"({type(exc).__name__}: {exc}) — the coordination "
                f"service may be unreachable; escalating to peer "
                f"failure after {self.fail_after_s:.1f}s of continuous "
                f"failure")
            return
        outage = now - self._transport_fail_since
        if outage > self.fail_after_s and COORDINATOR not in self.failed:
            self.failed[COORDINATOR] = outage
            logger.error(
                f"peer health: heartbeat transport unreachable for "
                f"{outage:.1f}s (> fail_after_s={self.fail_after_s:.1f})"
                f" — declaring the coordination service (process 0) "
                f"DEAD")

    def _publish_self(self, now):
        if self._last_publish is not None and \
                now - self._last_publish < self.interval_s:
            return
        self._last_publish = now
        self._serial += 1
        try:
            step = int(self._step_fn())
        except Exception:   # engine mid-teardown: keep heartbeating
            step = -1
        self.transport.publish(self.self_name,
                               {"serial": self._serial, "step": step})

    def _publish_simulated(self, now):
        with self._lock:
            sims = list(self._simulated.values())
        for sim in sims:
            if not sim.alive:
                continue
            period = max(self.interval_s, sim.delay_s)
            if sim._last_pub is not None and \
                    now - sim._last_pub < period:
                continue
            sim._last_pub = now
            sim.serial += 1
            self.transport.publish(sim.name,
                                   {"serial": sim.serial, "step": -1})

    def _observe(self, now):
        beats = self.transport.read_all()
        with self._lock:
            # adopt peers discovered from the store (a regrown topology
            # may add ranks the constructor never listed)
            for name in beats:
                if name != self.self_name:
                    self._peers.setdefault(name, None)
            for name in list(self._peers):
                beat = beats.get(name)
                state = self._peers[name]
                if beat is None and state is None:
                    # peer has NEVER published. The grace is BOUNDED by
                    # the same thresholds, measured from the monitor's
                    # first poll: a host dead at bring-up must escalate
                    # like any other (unbounded grace would leave it
                    # permanently 'ok' and misdiagnose the resulting
                    # collective hang as local).
                    silent = now - self._first_poll
                    if silent > self.fail_after_s:
                        self._peers[name] = {
                            "serial": -1, "step": -1,
                            "seen": self._first_poll,
                            "status": PEER_DEAD}
                        self.failed[name] = silent
                        logger.error(
                            f"peer health: peer {name} NEVER published "
                            f"a heartbeat in {silent:.1f}s (> "
                            f"fail_after_s={self.fail_after_s:.1f}) — "
                            f"declaring it DEAD (died during bring-up?)")
                    elif silent > self.warn_after_s and \
                            name not in self.warned:
                        self.warned.add(name)
                        logger.warning(
                            f"peer health: peer {name} has not "
                            f"published its first heartbeat after "
                            f"{silent:.1f}s — slow bring-up or dead; "
                            f"escalating at {self.fail_after_s:.1f}s")
                    continue
                if state is None or (beat is not None and
                                     beat["serial"] > state["serial"]):
                    if state is not None and \
                            state["status"] == PEER_DEAD:
                        # dead is STICKY: by the time a declared-dead
                        # peer heartbeats again the collective world is
                        # already torn — the escalation (restart) must
                        # proceed, not be raced away by a revival
                        continue
                    if state is not None and \
                            state["status"] == PEER_SLOW:
                        logger.info(
                            f"peer health: peer {name} recovered after "
                            f"{now - state['seen']:.1f}s of silence")
                    self._peers[name] = {
                        "serial": beat["serial"],
                        "step": beat.get("step", -1),
                        "seen": now, "status": PEER_OK}
                    continue
                staleness = now - state["seen"]
                if staleness > self.fail_after_s:
                    if state["status"] != PEER_DEAD:
                        state["status"] = PEER_DEAD
                        self.failed[name] = staleness
                        logger.error(
                            f"peer health: peer {name} heartbeat stale "
                            f"for {staleness:.1f}s (> fail_after_s="
                            f"{self.fail_after_s:.1f}) — declaring it "
                            f"DEAD; last seen at step {state['step']}")
                elif staleness > self.warn_after_s:
                    if state["status"] == PEER_OK:
                        state["status"] = PEER_SLOW
                        self.warned.add(name)
                        # cite the fleet probe's quantitative skew when
                        # available: "slow" backed by measured ms/step,
                        # not just a staleness guess
                        skew = self.skew_context(name)
                        logger.warning(
                            f"peer health: peer {name} heartbeat stale "
                            f"for {staleness:.1f}s (> warn_after_s="
                            f"{self.warn_after_s:.1f}) — slow or "
                            f"wedged; escalating to dead at "
                            f"{self.fail_after_s:.1f}s"
                            + (f" [fleet skew probe: {skew}]" if skew
                               else ""))

    # -- views -------------------------------------------------------------

    def peer_status(self, now=None):
        now = self._clock() if now is None else now
        with self._lock:
            out = {}
            for name, state in self._peers.items():
                if state is None:
                    out[name] = {"status": PEER_OK, "staleness_s": 0.0,
                                 "step": -1}
                else:
                    out[name] = {"status": state["status"],
                                 "staleness_s": now - state["seen"],
                                 "step": state["step"]}
            return out

    def max_staleness(self, now=None):
        """Worst peer staleness in seconds (0.0 with no peers) — the
        per-step `Train/Elastic/heartbeat_staleness_s` scalar."""
        status = self.peer_status(now)
        return max((s["staleness_s"] for s in status.values()),
                   default=0.0)

    @property
    def has_failure(self):
        return bool(self.failed)

    def raise_if_failed(self):
        """Main-thread escalation point (engine step boundary): a dead
        peer becomes a typed PeerFailureError for the supervisor."""
        if not self.failed:
            return
        peers = sorted(self.failed)
        staleness = max(self.failed.values())
        raise PeerFailureError(
            f"peer(s) {peers} declared dead (heartbeat stale "
            f"{staleness:.1f}s > fail_after_s={self.fail_after_s:.1f}); "
            f"exiting for a supervised restart",
            peers=peers, staleness_s=staleness)


def build_peer_monitor(params, step_fn=None):
    """Construct the monitor from a validated heartbeat params dict
    (`elasticity.config.parse_heartbeat_block`): coordination-service
    transport when a multi-host client exists, in-memory otherwise."""
    import jax

    from ..utils.distributed import _distributed_client
    from ..utils.kv_retry import wrap_kv_transport
    transport = None
    peers = ()
    if jax.process_count() > 1:
        client = _distributed_client()
        if client is not None:
            # shared retry policy (utils/kv_retry.py): transient KV
            # blips are retried with capped backoff × jitter;
            # PERSISTENT failure still raises into poll_once — the
            # monitor's continuous-outage escalation (declare the
            # coordination service dead after fail_after_s) depends on
            # seeing it, so heartbeats never degrade-to-local
            transport = wrap_kv_transport(
                CoordinationTransport(client), degrade_to_local=False,
                name="peer-health heartbeat")
            peers = [str(i) for i in range(jax.process_count())]
        else:  # pragma: no cover - private-API drift
            logger.warning(
                "elasticity.heartbeat: no coordination client available; "
                "peer heartbeats degrade to process-local (peer failures "
                "will only surface as barrier timeouts)")
    return PeerHealthMonitor(
        self_name=str(jax.process_index()), peers=peers,
        interval_s=params["interval_s"],
        warn_after_s=params["warn_after_s"],
        fail_after_s=params["fail_after_s"],
        transport=transport, step_fn=step_fn)
