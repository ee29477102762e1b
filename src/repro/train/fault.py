"""Fault tolerance: step monitoring, straggler detection, restart policy.

On a real multi-pod deployment each host runs this monitor next to the
training loop; here the same logic is exercised single-process (tests
simulate failures by killing/restarting the loop).

* StepMonitor  — EMA of step wall-time; flags stragglers (step > k x EMA)
  and writes a heartbeat file other hosts / the launcher can watch, with
  the process's count of compiled executables (a step that recompiled
  raises it).
* RestartPolicy — decides recovery actions: resume from the latest
  checkpoint (deterministic data stream makes the replay exact), and
  supports *elastic* restarts onto a smaller/larger mesh via
  checkpoint.restore(shardings=new_mesh).
"""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import time

from repro import obs


@dataclasses.dataclass
class StepMonitor:
    heartbeat_path: str | None = None
    straggler_factor: float = 3.0
    ema_decay: float = 0.9
    ema: float | None = None
    last_t: float | None = None
    stragglers: int = 0
    steps: int = 0
    # self-tuning stamp: the launch loop updates these after every
    # accepted controller decision, so the heartbeat carries WHICH
    # compression plan the run is currently executing (other hosts and
    # the elastic restart path compare it against a policy artifact's
    # recorded hash before trusting the artifact)
    tune_plan_hash: str | None = None
    tune_decision_step: int | None = None

    def begin(self):
        self.last_t = time.monotonic()

    def end(self, step: int) -> dict:
        now = time.monotonic()
        dt = now - (self.last_t or now)
        self.steps += 1
        is_straggler = False
        if self.ema is not None and dt > self.straggler_factor * self.ema:
            self.stragglers += 1
            is_straggler = True
        self.ema = dt if self.ema is None else \
            self.ema_decay * self.ema + (1 - self.ema_decay) * dt
        if self.heartbeat_path:
            hb = {"step": step, "t": time.time(), "dt": dt,
                  "ema": self.ema, "straggler": is_straggler,
                  "compiles": obs.compile_count()}
            if self.tune_plan_hash is not None:
                hb["tune_plan_hash"] = self.tune_plan_hash
                hb["tune_decision_step"] = self.tune_decision_step
            p = pathlib.Path(self.heartbeat_path)
            tmp = p.with_suffix(".tmp")
            tmp.write_text(json.dumps(hb))
            os.replace(tmp, p)
        return {"dt": dt, "ema": self.ema, "straggler": is_straggler}


def heartbeat_stale(path, timeout_s: float) -> bool:
    """Launcher-side liveness check: no heartbeat for timeout -> dead host."""
    p = pathlib.Path(path)
    if not p.exists():
        return True
    try:
        hb = json.loads(p.read_text())
    except (ValueError, OSError):
        return True
    return (time.time() - hb["t"]) > timeout_s


def tune_restart_warnings(artifact: dict, mesh_info,
                          heartbeat_path=None) -> list:
    """Loud pre-flight for resuming with a tuned-policy artifact.

    Returns human-readable warning lines (empty = clean).  Two checks:
    the artifact's recorded topology against the live mesh (an elastic
    restart onto a different dp/node split invalidates the byte
    arithmetic the rules were derived from), and — when the dead run's
    heartbeat survives — the artifact's ``plan_hash`` against the plan
    hash the run was actually executing, which catches replaying a stale
    artifact from an earlier decision round."""
    from repro.tune import policy_artifact
    warnings = []
    for diff in policy_artifact.topology_mismatch(artifact, mesh_info):
        warnings.append(f"tune_policy topology mismatch — {diff}")
    if heartbeat_path:
        p = pathlib.Path(heartbeat_path)
        if p.exists():
            try:
                hb = json.loads(p.read_text())
            except (ValueError, OSError):
                hb = {}
            run_hash = hb.get("tune_plan_hash")
            art_hash = artifact.get("plan_hash")
            if run_hash and art_hash and run_hash != art_hash:
                warnings.append(
                    f"tune_policy plan_hash {art_hash} != last heartbeat "
                    f"plan {run_hash} (decision step "
                    f"{hb.get('tune_decision_step')}) — the artifact is "
                    "stale relative to the run it came from")
    return warnings


@dataclasses.dataclass
class RestartPolicy:
    ckpt_dir: str
    max_restarts: int = 10
    restarts: int = 0

    def should_restart(self) -> bool:
        return self.restarts < self.max_restarts

    def on_failure(self) -> int | None:
        """Returns the step to resume from (None = cold start)."""
        from repro.train import checkpoint
        self.restarts += 1
        return checkpoint.latest_step(self.ckpt_dir)
