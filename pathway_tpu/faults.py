"""Fault-injection registry — the chaos harness behind Round-13.

Failure handling that is only exercised by real failures is failure
handling that does not work.  This module generalizes the Round-12
ad-hoc ``PW_FABRIC_SEND_DELAY_MS``/``PW_FABRIC_DELAY_PID`` env hooks
into a small registry of *fault points*: named places in the send path,
the data-plane walk, the decode engine and the persistence journal call
:func:`fire` with a point name, and a matching installed fault triggers
an action there — programmatically (tests call :func:`install`) or from
the environment (``PW_FAULT`` specs, so the CLI-spawned multi-process
tests can arm a fault inside a child they never touch directly).

Fault points wired in this round (call sites in parentheses):

========================  =====================================================
``fabric.send.data``      one logical data-lane frame about to be written
                          (parallel/comm.py ``_PeerSender``); actions:
                          ``delay``/``drop``/``close``/``kill``
``fabric.send.ctl``       one ctl-lane frame (marks/ctl/eot/heartbeats); same
                          actions
``fabric.mark``           a counted mark is about to be posted at an exchange
                          point (parallel/cluster.py ``_run_time``); ``kill``
                          here is the canonical "die mid-exchange"
``engine.dispatch.chain`` the Nth chained decode dispatch
                          (kvcache/engine.py); ``raise`` models a failing
                          device program
``engine.dispatch.step``  / ``engine.dispatch.mixed``: the other
                          dispatch kinds, same semantics
``engine.dispatch.verify``  the Round-18 speculative verify dispatch,
                          same semantics as the other dispatch kinds
``engine.draft``          the speculative draft phase, BEFORE proposals
                          are computed (kvcache/engine.py ``_spec_round``);
                          ``drop`` suppresses drafting for the round (the
                          engine falls through to the chain/step paths)
``engine.sync``           inside the (watchdog-bounded) device->host sync;
                          ``hang`` models a wedged device program
``persistence.append``    a journal record is about to be written; ``kill``
                          here is "die mid-ingest", ``raise`` a failing
                          backend
``persistence.commit``    the journal record landed; ``kill`` here is "die
                          post-commit" (the exactly-once squash-check's
                          hardest case: the row is journaled but its effects
                          never flushed)
========================  =====================================================

Spec syntax (``PW_FAULT``, ``;``-separated)::

    point:action[:nth[:arg[:pid]]]

- ``nth``: 1-based hit count at which the fault fires (``0`` = every hit;
  default 1).
- ``arg``: milliseconds for ``delay``/``hang``; ignored otherwise.
- ``pid``: only fire in the worker with this ``PATHWAY_PROCESS_ID``.

e.g. ``PW_FAULT="fabric.send.data:drop:3:0:1"`` drops pid 1's 3rd
outgoing data frame; ``PW_FAULT="persistence.commit:kill:2"`` kills the
process right after its 2nd journal append.

``PW_FAULT_STAMP_DIR``: when set, each spec writes a stamp file there the
first time it fires and never fires again while the stamp exists — the
supervisor restart loop re-runs the same program with the same env, and
a kill that re-fired on every incarnation would restart forever.  The
stamp doubles as the test's proof that the fault actually fired.

Every firing lands as a ``fault.injected`` event in the flight recorder,
so an injected fault is visible (and attributable) in the same Perfetto
dump that shows its blast radius.
"""

from __future__ import annotations

import logging
import os
import sys
import threading
import time as _time

logger = logging.getLogger(__name__)

_ACTIONS = ("delay", "drop", "close", "kill", "raise", "hang")

#: exit code used by the ``kill`` action — distinct from the rescale
#: codes (10/12) and from a clean abort, so supervisors and tests can
#: tell an injected death from everything else
KILL_EXIT_CODE = 137


class InjectedFault(RuntimeError):
    """Raised at a fault point armed with the ``raise`` action."""


class FaultSpec:
    __slots__ = ("point", "action", "nth", "arg_ms", "pid", "hits", "fired")

    def __init__(self, point: str, action: str, nth: int = 1,
                 arg_ms: float = 0.0, pid: int | None = None):
        if action not in _ACTIONS:
            raise ValueError(
                f"unknown fault action {action!r}; expected one of {_ACTIONS}"
            )
        self.point = point
        self.action = action
        self.nth = int(nth)
        self.arg_ms = float(arg_ms)
        self.pid = pid
        self.hits = 0
        self.fired = False

    def __repr__(self) -> str:  # pragma: no cover - diagnostics
        return (f"FaultSpec({self.point}:{self.action}:{self.nth}"
                f":{self.arg_ms}:{self.pid})")

    def _stamp_path(self) -> str | None:
        d = os.environ.get("PW_FAULT_STAMP_DIR")
        if not d:
            return None
        # the FULL spec identity: two specs differing only in pid (or
        # arg) must not share a stamp, or only the first to fire would
        # ever fire across incarnations
        pid = "any" if self.pid is None else self.pid
        safe = (f"{self.point}_{self.action}_{self.nth}"
                f"_{self.arg_ms:g}_{pid}").replace("/", "_")
        return os.path.join(d, f"{safe}.fired")

    def should_fire(self) -> bool:
        """(caller holds the registry lock)  Count this hit; decide."""
        self.hits += 1
        if self.nth == 0:
            pass  # every hit
        elif self.hits != self.nth:
            return False
        stamp = self._stamp_path()
        if stamp is not None:
            if os.path.exists(stamp):
                return False  # already fired in a previous incarnation
            try:
                os.makedirs(os.path.dirname(stamp), exist_ok=True)
                with open(stamp, "w") as f:
                    f.write(f"pid={os.getpid()} ts={_time.time():.3f}\n")
            except OSError:
                pass  # stamping is best-effort; the fault still fires
        self.fired = True
        return True


def parse_spec(text: str) -> FaultSpec:
    parts = text.strip().split(":")
    if len(parts) < 2:
        raise ValueError(
            f"bad PW_FAULT spec {text!r}: want point:action[:nth[:arg[:pid]]]"
        )
    point, action = parts[0], parts[1]
    nth = int(parts[2]) if len(parts) > 2 and parts[2] != "" else 1
    arg = float(parts[3]) if len(parts) > 3 and parts[3] != "" else 0.0
    pid = int(parts[4]) if len(parts) > 4 and parts[4] != "" else None
    return FaultSpec(point, action, nth, arg, pid)


class FaultRegistry:
    def __init__(self):
        self._lock = threading.Lock()
        self._specs: list[FaultSpec] = []
        self._env_loaded = False

    # -- management --------------------------------------------------------
    def install(self, point: str, action: str, *, nth: int = 1,
                arg_ms: float = 0.0, pid: int | None = None) -> FaultSpec:
        spec = FaultSpec(point, action, nth, arg_ms, pid)
        with self._lock:
            self._load_env_locked()
            self._specs.append(spec)
        return spec

    def clear(self) -> None:
        """Drop every spec AND forget the env (tests; a later fire()
        re-reads ``PW_FAULT`` so env-armed child processes still work)."""
        with self._lock:
            self._specs = []
            self._env_loaded = False

    def specs(self) -> list[FaultSpec]:
        with self._lock:
            self._load_env_locked()
            return list(self._specs)

    def _load_env_locked(self) -> None:
        if self._env_loaded:
            return
        self._env_loaded = True
        raw = os.environ.get("PW_FAULT", "")
        for part in raw.split(";"):
            part = part.strip()
            if not part:
                continue
            try:
                self._specs.append(parse_spec(part))
            except ValueError as exc:
                # a chaos knob must never take the subject down with a
                # typo — log loudly and run fault-free instead
                logger.error("ignoring bad PW_FAULT spec %r: %s", part, exc)

    # -- the fault point ---------------------------------------------------
    def fire(self, point: str, **ctx) -> str | None:
        """Advance counters for ``point``; trigger a matching fault.

        Inline actions (handled here): ``delay``/``hang`` sleep,
        ``kill`` terminates the process (``os._exit``, exit code
        :data:`KILL_EXIT_CODE` — deliberately not an exception so the
        death is as abrupt as a real SIGKILL), ``raise`` raises
        :class:`InjectedFault`.  Caller-interpreted actions are returned
        as a string: ``"drop"`` (skip the frame) and ``"close"`` (sever
        the connection).  Returns None when nothing fired."""
        my_pid = None
        triggered: list[FaultSpec] = []
        with self._lock:
            self._load_env_locked()
            if not self._specs:
                return None
            # EVERY matching spec's counter advances on every hit — an
            # every-hit spec firing first must not starve a later spec's
            # nth count (two armed faults = two faults that fire)
            for spec in self._specs:
                if spec.point != point:
                    continue
                if spec.pid is not None:
                    if my_pid is None:
                        my_pid = int(
                            os.environ.get("PATHWAY_PROCESS_ID", "0") or 0
                        )
                    if spec.pid != my_pid:
                        continue
                if spec.should_fire():
                    triggered.append(spec)
        if not triggered:
            return None
        from . import obs

        for spec in triggered:
            obs.event(
                "fault.injected", point=point, action=spec.action,
                nth=spec.nth, **{k: str(v) for k, v in ctx.items()},
            )
            logger.warning("fault injected: %s -> %s (hit %d)%s", point,
                           spec.action, spec.hits,
                           f" ctx={ctx}" if ctx else "")
        result: str | None = None  # caller-interpreted ("drop"/"close")
        inline: str | None = None  # informational (delay/hang happened)
        for spec in triggered:
            if spec.action in ("delay", "hang"):
                _time.sleep(max(spec.arg_ms, 0.0) / 1000.0)
                inline = inline or spec.action
                continue
            if spec.action == "kill":
                print(
                    f"[pathway-tpu] fault.injected kill at {point} "
                    f"(hit {spec.hits})", file=sys.stderr, flush=True,
                )
                # dying processes leave evidence: flush the flight
                # recorder like a real crash handler would (best-effort)
                try:
                    obs.recorder().dump_on_failure(
                        "fault_kill", InjectedFault(point)
                    )
                except Exception:  # noqa: BLE001 - dying anyway
                    pass
                os._exit(KILL_EXIT_CODE)
            if spec.action == "raise":
                raise InjectedFault(
                    f"injected fault at {point} (hit {spec.hits})"
                )
            # caller-interpreted: "drop" | "close" — first one wins
            result = result or spec.action
        return result or inline


_REGISTRY = FaultRegistry()

install = _REGISTRY.install
clear = _REGISTRY.clear
specs = _REGISTRY.specs


def fire(point: str, **ctx) -> str | None:
    return _REGISTRY.fire(point, **ctx)


def active() -> bool:
    """Cheap guard for hot paths: any specs installed/armed?"""
    return bool(_REGISTRY.specs())
