"""The campaign control plane: many campaigns, one shared fleet.

``ControlPlane`` is the long-lived daemon behind ``python -m
repro.control serve``. It owns:

* a durable :class:`~repro.control.state.StateStore` of campaign records
  (crash-safe; ``recover()`` re-stages interrupted campaigns on boot);
* a scheduler tick that apportions the shared fleet's slots across
  schedulable campaigns by weighted fair share with priority preemption
  (:mod:`repro.control.scheduler`);
* one runner thread per running campaign, each hosting a full
  :class:`~repro.core.app.ColmenaApp` built from the submitted spec with
  its managed pool sizes overridden to the current grant — pause is
  ``app.pause()`` (checkpoint + release every slot), resume is a fresh
  app with ``resume=True`` (checkpoint + journal replay).

The HTTP API lives in :mod:`repro.control.api`; this module is fully
usable in-process (the tests drive it directly).
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time
import tomllib
from typing import Any, Dict, List, Optional

from repro.observe import EventLog

from . import scheduler as fair
from .state import (
    DONE,
    FAILED,
    PAUSED,
    RUNNING,
    STAGED,
    SUBMITTED,
    CampaignRecord,
    StateStore,
)

logger = logging.getLogger("repro.control.plane")


def _load_toml_text(text: str) -> Dict[str, Any]:
    return tomllib.loads(text)


class _Runner:
    """Hosts one running campaign's ColmenaApp on its own thread."""

    def __init__(self, plane: "ControlPlane", rec: CampaignRecord, grant: Dict[str, int]) -> None:
        self.plane = plane
        self.cid = rec.id
        self.grant = dict(grant)
        self.app: Optional[Any] = None
        self.pause_evt = threading.Event()
        self.pause_reason = "preempted"
        self.done_evt = threading.Event()
        self.outcome: Optional[str] = None
        self.error: Optional[str] = None
        self.thread = threading.Thread(
            target=self._run, daemon=True, name=f"campaign-{self.cid}"
        )

    def start(self) -> "_Runner":
        self.thread.start()
        return self

    def request_pause(self, reason: str) -> None:
        self.pause_reason = reason
        self.pause_evt.set()

    def apply_grant(self, grant: Dict[str, int]) -> None:
        """Live-resize the app's managed pools to a new grant."""
        app = self.app
        if app is None:
            return
        for pool, target in grant.items():
            live = app.pools.get(pool)
            if live is None or live.n_workers == target:
                continue
            old, new = live.resize(target)
            if new != old and app.event_log is not None:
                app.event_log.pool_resize(pool, old, new, reason="fair-share")
        self.grant = dict(grant)

    def _run(self) -> None:
        try:
            app = self.plane._build_app(self.cid, self.grant)
            self.app = app
            app.start()
            while True:
                if self.pause_evt.is_set():
                    app.pause()
                    self.outcome = "paused"
                    break
                if app.wait(timeout=0.2):
                    exc = app.thinker_exception
                    if exc is not None:
                        self.outcome, self.error = "failed", f"{type(exc).__name__}: {exc}"
                    else:
                        self.outcome = "done"
                    app.stop()
                    break
        except Exception as exc:  # noqa: BLE001 - a runner crash is a campaign failure
            logger.exception("campaign %s runner crashed", self.cid)
            self.outcome, self.error = "failed", f"{type(exc).__name__}: {exc}"
        finally:
            self.done_evt.set()
            self.plane._on_runner_exit(self)


class ControlPlane:
    """Persistent multi-campaign scheduler over one shared fleet."""

    def __init__(
        self,
        root: str,
        fleet: Dict[str, int],
        tick_s: float = 0.5,
        event_log: Optional[EventLog] = None,
    ) -> None:
        if not fleet:
            raise ValueError("the control plane needs a non-empty fleet ({pool: slots})")
        self.root = root
        self.fleet = {str(k): int(v) for k, v in fleet.items()}
        self.tick_s = max(0.1, tick_s)
        os.makedirs(root, exist_ok=True)
        self.store = StateStore(root)
        self.accounting = fair.FleetAccounting(os.path.join(root, "fleet_accounting.json"))
        self.event_log = event_log or EventLog(
            jsonl_path=os.path.join(root, "plane-events.jsonl")
        )
        self._runners: Dict[str, _Runner] = {}
        self._lock = threading.RLock()
        self._stop = threading.Event()
        self._tick_thread: Optional[threading.Thread] = None
        self._last_tick: Optional[float] = None
        self.started_at = time.time()

    # -------------------------------------------------------------- lifecycle
    def start(self) -> "ControlPlane":
        restaged = self.store.recover()
        for rec in restaged:
            self.event_log.campaign_state(rec.name, STAGED, id=rec.id, reason="crash-recovery")
        if restaged:
            logger.info("recovered %d interrupted campaign(s)", len(restaged))
        self._tick_thread = threading.Thread(
            target=self._tick_loop, daemon=True, name="control-plane-tick"
        )
        self._tick_thread.start()
        return self

    def stop(self, pause_running: bool = True) -> None:
        self._stop.set()
        if self._tick_thread is not None:
            self._tick_thread.join(timeout=10)
        runners = list(self._runners.values())
        if pause_running:
            for r in runners:
                r.request_pause("daemon stop")
        for r in runners:
            r.done_evt.wait(timeout=15)

    # ----------------------------------------------------------------- submit
    def submit(self, spec_text: str, name: Optional[str] = None) -> CampaignRecord:
        """Validate and durably admit one campaign TOML; returns its record
        already ``staged`` (the next tick schedules it)."""
        from repro.core.specfile import spec_from_dict

        try:
            d = _load_toml_text(spec_text)
        except Exception as exc:  # noqa: BLE001 - surface as a 400, not a 500
            raise ValueError(f"invalid campaign spec: {exc}") from exc
        d.pop("smoke", None)
        # The daemon owns durable state placement; a submitted spec may
        # omit [campaign] (or its state_dir) entirely.
        camp = dict(d.get("campaign", {}))
        camp.setdefault("state_dir", "state")  # placeholder; overridden per-campaign
        d["campaign"] = camp
        try:
            spec = spec_from_dict(d)  # fail fast: bad specs never enter the store
        except Exception as exc:  # noqa: BLE001 - surface as a 400, not a 500
            raise ValueError(f"invalid campaign spec: {exc}") from exc
        if not spec.server.in_process:
            raise ValueError(
                "control-plane campaigns run in_process servers; remote sites "
                "are reached through the queue control channel instead"
            )
        ctl = spec.control
        demand: Dict[str, int] = {}
        # Demand counts only pools the submission itself declares (or
        # routes tasks to) — AppSpec normalization adds a "default" pool
        # that an all-custom-pool campaign never touches.
        declared = set(d.get("pools", {})) or set(spec.pools or {})
        for pname, ps in (spec.pools or {}).items():
            if pname in self.fleet and pname in declared:
                demand[pname] = ps.size
        for td in spec.tasks:
            pool = getattr(td, "pool", "default")
            if pool in self.fleet:
                demand.setdefault(pool, 1)
        if ctl is not None and ctl.demand is not None:
            demand = {p: min(v, ctl.demand) for p, v in demand.items()}
        if not demand:
            raise ValueError(
                f"campaign demands no fleet pool (fleet: {sorted(self.fleet)})"
            )
        with self._lock:
            rec = self.store.create(
                name or (spec.campaign.name if spec.campaign else "campaign"),
                spec_text,
                weight=ctl.weight if ctl else 1.0,
                priority=ctl.priority if ctl else 0,
                min_slots=ctl.min_slots if ctl else 1,
                demand=demand,
            )
            self.event_log.campaign_state(rec.name, SUBMITTED, id=rec.id)
            self._transition(rec.id, STAGED, reason="admitted")
        logger.info("campaign %s (%s) submitted: demand=%s", rec.id, rec.name, demand)
        return rec

    # ------------------------------------------------------------ pause/resume
    def pause(self, cid: str, wait_s: float = 15.0) -> CampaignRecord:
        """Operator pause: checkpoint + release slots; stays paused across
        daemon restarts until resumed."""
        with self._lock:
            rec = self.store.get(cid)
            self.store.set_paused_by_user(cid, True)
            if rec.state in (SUBMITTED, STAGED):
                return self._transition(cid, PAUSED, reason="user")
            if rec.state != RUNNING:
                return rec
            runner = self._runners.get(cid)
        if runner is not None:
            runner.request_pause("user")
            runner.done_evt.wait(timeout=wait_s)
        return self.store.get(cid)

    def resume(self, cid: str) -> CampaignRecord:
        with self._lock:
            rec = self.store.get(cid)
            if rec.state != PAUSED:
                return rec
            self.store.set_paused_by_user(cid, False)
            return self._transition(cid, STAGED, reason="user resume")

    # ------------------------------------------------------------------ status
    def status(self) -> Dict[str, Any]:
        with self._lock:
            records = self.store.list()
            grants = fair.compute_grants(records, self.fleet, self._schedulable_states())
            return {
                "uptime_s": round(time.time() - self.started_at, 3),
                "fleet": dict(self.fleet),
                "campaigns": [
                    {**r.to_dict(), "grant": grants.get(r.id, {})} for r in records
                ],
                "accounting": self.accounting.report(),
            }

    # ------------------------------------------------------------------- tick
    @staticmethod
    def _schedulable_states() -> List[str]:
        return [STAGED, RUNNING, PAUSED]

    def _tick_loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.tick()
            except Exception:  # noqa: BLE001 - one bad tick must not kill the daemon
                logger.exception("control-plane tick failed")
            self._stop.wait(self.tick_s)

    def tick(self) -> None:
        now = time.monotonic()
        with self._lock:
            dt = 0.0 if self._last_tick is None else now - self._last_tick
            self._last_tick = now
            records = self.store.list()
            # Auto-paused campaigns stay in the grant computation: they
            # re-stage the moment contention eases enough to meet their
            # floor (deterministic apportionment -> no flapping).
            schedulable = [
                r for r in records
                if r.state in (STAGED, RUNNING)
                or (r.state == PAUSED and not r.paused_by_user)
            ]
            grants = fair.compute_grants(schedulable, self.fleet, self._schedulable_states())
            for rec in records:
                grant = grants.get(rec.id, {})
                if rec.state == RUNNING:
                    runner = self._runners.get(rec.id)
                    if runner is None or runner.done_evt.is_set():
                        continue  # exit path owns the transition
                    if not fair.meets_floor(rec, grant):
                        runner.request_pause("preempted")
                    elif grant != runner.grant:
                        runner.apply_grant(grant)
                        self.event_log.gauge(
                            "campaign_slots", fair.total_slots(grant), campaign=rec.id
                        )
                elif rec.state == PAUSED and not rec.paused_by_user:
                    if fair.meets_floor(rec, grant):
                        self._transition(rec.id, STAGED, reason="capacity freed")
                        rec = self.store.get(rec.id)
                if rec.state == STAGED and fair.meets_floor(rec, grant):
                    self._launch(rec, grant)
            self.accounting.observe(schedulable, grants, self.fleet, dt)

    def _launch(self, rec: CampaignRecord, grant: Dict[str, int]) -> None:
        self._transition(rec.id, RUNNING, reason=f"granted {grant}")
        self.event_log.gauge("campaign_slots", fair.total_slots(grant), campaign=rec.id)
        self._runners[rec.id] = _Runner(self, rec, grant).start()

    def _on_runner_exit(self, runner: _Runner) -> None:
        with self._lock:
            self._runners.pop(runner.cid, None)
            try:
                rec = self.store.get(runner.cid)
            except KeyError:
                return
            if rec.state != RUNNING:
                return
            if runner.outcome == "done":
                self._transition(runner.cid, DONE, reason="completed")
            elif runner.outcome == "paused":
                self._transition(runner.cid, PAUSED, reason=runner.pause_reason)
                self.event_log.gauge("campaign_slots", 0, campaign=runner.cid)
            else:
                self._transition(
                    runner.cid, FAILED, reason="runner exit", error=runner.error
                )

    def _transition(self, cid: str, state: str, *, reason: str = "", error: Optional[str] = None) -> CampaignRecord:
        rec = self.store.transition(cid, state, reason=reason, error=error)
        self.event_log.campaign_state(rec.name, state, id=cid, reason=reason)
        return rec

    # ------------------------------------------------------------- app build
    def _build_app(self, cid: str, grant: Dict[str, int]) -> Any:
        from repro.core.app import CampaignSpec, ColmenaApp
        from repro.core.executors import PoolSpec
        from repro.core.specfile import spec_from_dict

        rec = self.store.get(cid)
        with open(self.store.spec_path(cid)) as f:
            d = _load_toml_text(f.read())
        d.pop("smoke", None)
        camp = dict(d.get("campaign", {}))
        camp.setdefault("state_dir", "state")  # placeholder; replaced below
        d["campaign"] = camp
        spec = spec_from_dict(d)
        # Durable state lives with the record; resume always on — a first
        # run simply finds no checkpoint.
        spec.campaign = CampaignSpec(
            state_dir=self.store.state_dir(cid),
            checkpoint_interval_s=(
                spec.campaign.checkpoint_interval_s if spec.campaign else 2.0
            ),
            name=rec.name,
            resume=True,
        )
        # Managed pools run at their granted size, elastic within the
        # fleet's band so later ticks can live-resize without a restart.
        for pool, slots in grant.items():
            base = spec.pools.get(pool) or PoolSpec(pool, max(1, slots))
            spec.pools[pool] = dataclasses.replace(
                base,
                size=max(1, slots),
                min_size=0,
                max_size=max(self.fleet.get(pool, slots), slots, base.size),
            )
        return ColmenaApp(spec)


__all__ = ["ControlPlane"]
