"""Population controller: N trials on a pool of preemptible slots.

The control loop composes the single-run guarantees from PRs 2 and 5 into a
fleet. Each trial incarnation is a supervised ``sheeprl.py`` subprocess whose
own ``PreemptionGuard`` turns SIGTERM into checkpoint-and-exit-0; the
controller classifies every exit (completed / preempted / diverged / failed)
and feeds the scheduler. Divergence verdicts come from tailing the trial's
``health/events.jsonl`` — the trial's HealthSentinel is the fitness oracle,
the controller never inspects losses itself.

Exit classification uses three signals, in precedence order:

1. the controller's own *kill intent* (it sent the SIGTERM — for an injected
   preemption drill, a divergence kill, or an exploit kill);
2. the preemption **flag file** (``SHEEPRL_PREEMPTION_FLAG_FILE``) the child's
   guard touches when a REAL signal lands — distinguishing "exited 0 because
   preempted" from "exited 0 because finished", which are byte-identical at
   the returncode level;
3. the returncode.

The controller is itself preemptible: it runs under
``PreemptionGuard(forward_to_children=True)``, so SIGTERM fans out to every
trial, everyone checkpoints, the journal records the fleet as
preempted-and-requeued, and a restart with the same ``--state-dir`` resumes
with no duplicated or lost trials (reconciliation kills/requeues any trial the
journal thought was running).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

from sheeprl_tpu.core import failpoints
from sheeprl_tpu.core.health import DIVERGENCE_EVENT_KINDS, EVENTS_FILENAME, read_events
from sheeprl_tpu.core.resilience import FLAG_FILE_ENV_VAR, READY_FILE_ENV_VAR, PreemptionGuard
from sheeprl_tpu.orchestrate import resolve
from sheeprl_tpu.orchestrate import trial as T
from sheeprl_tpu.orchestrate.journal import Journal
from sheeprl_tpu.orchestrate.lineage import LineageLog
from sheeprl_tpu.orchestrate.resow import certified_fitness, perturb, select_parent
from sheeprl_tpu.orchestrate.scheduler import SlotScheduler
from sheeprl_tpu.orchestrate.trial import Trial, TrialSpec
from sheeprl_tpu.telemetry import trace
from sheeprl_tpu.utils.checkpoint import ckpt_sort_key

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Env var overriding the trainee entry point (default: <repo>/sheeprl.py). The
# orchestrate unit tests point this at a stub trainee so the full
# spawn/preempt/diverge/resow loop runs in milliseconds without importing jax.
ENTRY_ENV_VAR = "SHEEPRL_TPU_ORCH_ENTRY"

READY_FILENAME = ".guard_ready"
FLAG_FILENAME = ".preempt_flag"


def _entry_point() -> str:
    return os.environ.get(ENTRY_ENV_VAR) or os.path.join(REPO_ROOT, "sheeprl.py")


def _newest_ckpt(root: str) -> Optional[str]:
    """Newest ``*.ckpt`` under ``root``, certified or not — preemption resume
    prefers the trial's very last save (often the guard's emergency checkpoint,
    uncertified by design: the sentinel only certifies healthy saves)."""
    best, best_key = None, None
    for base, _, files in os.walk(root):
        for name in files:
            if not name.endswith(".ckpt"):
                continue
            cand = os.path.join(base, name)
            key = ckpt_sort_key(cand)
            if best_key is None or key > best_key:
                best, best_key = cand, key
    return best


def _pid_alive(pid: Optional[int]) -> bool:
    if not pid:
        return False
    try:
        os.kill(int(pid), 0)
    except (ProcessLookupError, PermissionError, OSError):
        return False
    return True


class PopulationController:
    def __init__(
        self,
        specs: List[TrialSpec],
        state_dir: str,
        cfg: Any = None,
        inject_preempt: int = 0,
        inject_spacing_s: float = 2.0,
    ):
        self.cfg = resolve(cfg)
        self.state_dir = os.path.abspath(state_dir)
        os.makedirs(self.state_dir, exist_ok=True)
        self.journal = Journal(os.path.join(self.state_dir, "journal.json"))
        self.lineage = LineageLog(os.path.join(self.state_dir, "lineage.jsonl"))
        self.scheduler = SlotScheduler(
            slots=self.cfg.slots,
            max_preemptions=self.cfg.trial.max_preemptions,
            max_failures=self.cfg.trial.max_failures,
            backoff_base_s=self.cfg.trial.requeue_backoff_base_s,
            backoff_max_s=self.cfg.trial.requeue_backoff_max_s,
        )
        # The journal is the source of truth across controller incarnations:
        # specs only seed it the FIRST time this state_dir is used. A restart
        # with a different spec list does not add/drop trials silently.
        self.trials = self.journal.load_trials()
        if not self.trials:
            self.trials = [Trial(s) for s in specs]
        self.counters: Dict[str, Any] = (self.journal.load() or {}).get("counters") or {}
        self.counters.setdefault("spawn_seq", 0)
        self.counters.setdefault("preempt_recoveries", [])
        self.counters.setdefault("resow_walls", [])
        self.counters.setdefault("injections", 0)
        self.counters.setdefault("controller_incarnations", 0)
        self.counters["controller_incarnations"] += 1

        self._procs: Dict[str, subprocess.Popen] = {}
        self._logs: Dict[str, Any] = {}  # open log file handles
        self._run_names: Dict[str, str] = {}  # current incarnation's run_name
        self._intents: Dict[str, str] = {}  # key -> why WE killed it
        self._event_offsets: Dict[str, int] = {}  # events-file path -> byte offset
        self._preempted_at: Dict[str, float] = {}
        self._diverged_at: Dict[str, float] = {}
        self._resow_deadline: Dict[str, float] = {}
        self._inject_remaining = int(inject_preempt)
        self._inject_spacing_s = float(inject_spacing_s)
        self._injected: Dict[str, int] = {}
        self._last_inject = 0.0
        self._last_exploit = 0.0
        self.guard: Optional[PreemptionGuard] = None

    # -- paths ----------------------------------------------------------------- #

    def trial_dir(self, key: str) -> str:
        return os.path.join(self.state_dir, "trials", key)

    def _ready_file(self, key: str) -> str:
        return os.path.join(self.trial_dir(key), READY_FILENAME)

    def _flag_file(self, key: str) -> str:
        return os.path.join(self.trial_dir(key), FLAG_FILENAME)

    def _trial(self, key: str) -> Trial:
        return next(t for t in self.trials if t.key == key)

    def _save(self) -> None:
        # Drill site: journal durability — a kill here must leave either the
        # old or the new journal under the final name (Journal writes via
        # tmp+rename), never a torn file.
        failpoints.failpoint("orchestrate.journal", path=self.journal.path)
        self.journal.save(self.trials, self.counters)

    def _log(self, msg: str) -> None:
        print(f"[orchestrate] {msg}", flush=True)

    # -- spawning --------------------------------------------------------------- #

    def _spawn(self, trial: Trial, now: float) -> None:
        # Drill site: `orchestrate.spawn:kill:9:hit=N` dies between the journal
        # state change and the Popen — the restart-reconciliation path must
        # requeue the trial the journal thought was starting.
        failpoints.failpoint("orchestrate.spawn", key=trial.key)
        seq = self.counters["spawn_seq"]
        self.counters["spawn_seq"] = seq + 1
        run_name = f"inc{seq:04d}_{trial.key}"
        tdir = self.trial_dir(trial.key)
        os.makedirs(tdir, exist_ok=True)
        for path in (self._ready_file(trial.key), self._flag_file(trial.key)):
            try:
                os.remove(path)
            except OSError:
                pass

        overrides = list(trial.spec.overrides)
        if trial.generation == 0 and trial.spec.chaos_overrides:
            # transient environmental faults belong to generation 0 only: a
            # resown generation is rescheduled weather-free (and the ChaosEnv
            # step counter restarting at 0 in a new process would otherwise
            # re-fire the fault window every generation)
            overrides += trial.spec.chaos_overrides
        overrides += [f"{k}={v}" for k, v in trial.hyperparams.items()]
        overrides.append(f"run_name={run_name}")
        if trial.resume_ckpt:
            overrides.append(f"checkpoint.resume_from={trial.resume_ckpt}")
            # the sidecar merge takes the OLD config wholesale; these dotted
            # keys keep the NEW invocation's values — the perturbed
            # hyperparameters, and the wrapper stack composed from THIS
            # generation's overrides (a resow from a chaos-gen-0 peer must not
            # inherit the peer's fault injection)
            preserve = sorted(set(list(trial.hyperparams) + ["env.wrapper"]))
            overrides.append("checkpoint.resume_preserve=[" + ",".join(preserve) + "]")

        kind = {T.PENDING: "seed", T.RESUMED: "resume", T.RESOWN: "resow"}.get(trial.state, "seed")
        log_path = os.path.join(tdir, f"{run_name}.log")
        log_f = open(log_path, "ab")
        # JAX_PLATFORMS passes through as the launcher's own environment has it
        # (unset stays unset): trainees land on the backend the operator chose,
        # never on a CPU nobody asked for
        env = dict(
            os.environ,
            **{
                READY_FILE_ENV_VAR: self._ready_file(trial.key),
                FLAG_FILE_ENV_VAR: self._flag_file(trial.key),
            },
        )
        proc = subprocess.Popen(
            [sys.executable, _entry_point()] + overrides,
            cwd=tdir,
            env=env,
            stdout=log_f,
            stderr=subprocess.STDOUT,
        )
        self._procs[trial.key] = proc
        self._logs[trial.key] = log_f
        self._run_names[trial.key] = run_name
        trial.pid = proc.pid
        if self.guard is not None:
            self.guard.register_child(proc.pid)

        if trial.state == T.RESUMED and trial.key in self._preempted_at:
            self.counters["preempt_recoveries"].append(
                {"trial": trial.key, "latency_s": round(now - self._preempted_at.pop(trial.key), 3)}
            )
        if trial.state == T.RESOWN and trial.key in self._diverged_at:
            self.counters["resow_walls"].append(
                {"trial": trial.key, "wall_s": round(now - self._diverged_at.pop(trial.key), 3)}
            )
        trial.to(T.RUNNING, pid=proc.pid, run_name=run_name, kind=kind)
        trace.instant(
            "orchestrate/spawn", trial=trial.key, gen=trial.generation, kind=kind, pid=proc.pid
        )
        self.lineage.record(
            kind,
            trial.key,
            trial.generation,
            parent=trial.parent if kind == "resow" else None,
            ckpt=trial.resume_ckpt,
            hyperparams=trial.hyperparams,
            run_name=run_name,
        )
        self._log(
            f"spawn {trial.key} gen={trial.generation} kind={kind} pid={proc.pid} "
            f"resume={'yes' if trial.resume_ckpt else 'no'}"
        )
        self._save()

    # -- exit classification ----------------------------------------------------- #

    def _reap(self, key: str) -> None:
        proc = self._procs.pop(key, None)
        if proc is not None and self.guard is not None:
            self.guard.unregister_child(proc.pid)
        log_f = self._logs.pop(key, None)
        if log_f is not None:
            try:
                log_f.close()
            except OSError:
                pass
        self._run_names.pop(key, None)
        self._trial(key).pid = None

    def _classify_exit(self, trial: Trial, rc: int, now: float) -> None:
        key = trial.key
        intent = self._intents.pop(key, None)
        flagged = os.path.exists(self._flag_file(key))
        self._reap(key)
        if intent in ("diverged", "exploit"):
            trial.to(T.DIVERGED, rc=rc, cause=intent)
            self._diverged_at.setdefault(key, now)
            self._log(f"exit {key}: diverged (cause={intent}, rc={rc})")
            self._try_resow(trial, now)
        elif intent == "preempt" or flagged:
            trial.to(T.PREEMPTED, rc=rc, injected=intent == "preempt")
            self._preempted_at[key] = now
            ckpt = _newest_ckpt(self.trial_dir(key))
            state = self.scheduler.requeue_preempted(trial, ckpt, now)
            self._log(f"exit {key}: preempted (rc={rc}) -> {state}")
        elif rc == 0:
            trial.to(T.COMPLETED, rc=0)
            self._log(f"exit {key}: completed")
        else:
            trial.resume_ckpt = _newest_ckpt(self.trial_dir(key))
            state = self.scheduler.requeue_failed(trial, f"rc={rc}", now)
            self._log(f"exit {key}: failed (rc={rc}) -> {state}")
        trace.instant("orchestrate/exit", trial=key, rc=rc, state=str(trial.state))
        self._save()

    def _poll_exits(self, now: float) -> None:
        for key, proc in list(self._procs.items()):
            rc = proc.poll()
            if rc is None:
                continue
            self._classify_exit(self._trial(key), rc, now)

    # -- divergence watch --------------------------------------------------------- #

    def _events_files(self, key: str) -> List[str]:
        """The CURRENT incarnation's health event files only. Earlier
        incarnations' files stay on disk; re-reading them after a controller
        restart must not re-condemn a healthy resown generation."""
        run_name = self._run_names.get(key)
        if not run_name:
            return []
        found = []
        for base, _, files in os.walk(self.trial_dir(key)):
            if EVENTS_FILENAME in files and run_name in base:
                found.append(os.path.join(base, EVENTS_FILENAME))
        return sorted(found)

    def _watch_health(self, now: float) -> None:
        for trial in self.trials:
            if trial.state != T.RUNNING or trial.key in self._intents:
                continue
            for path in self._events_files(trial.key):
                events, offset = read_events(path, self._event_offsets.get(path, 0))
                self._event_offsets[path] = offset
                verdict = next(
                    (
                        e
                        for e in events
                        if e.get("event") in DIVERGENCE_EVENT_KINDS
                        and "divergence" in str(e.get("reason", ""))
                    ),
                    None,
                )
                if verdict is None:
                    continue
                self._intents[trial.key] = "diverged"
                self._diverged_at[trial.key] = now
                self._log(
                    f"divergence verdict for {trial.key} at step {verdict.get('step')}: "
                    f"{verdict.get('reason')} -> SIGTERM"
                )
                self._signal(trial.key, signal.SIGTERM)
                break

    def _signal(self, key: str, signum: int) -> None:
        proc = self._procs.get(key)
        if proc is not None and proc.poll() is None:
            try:
                proc.send_signal(signum)
            except (ProcessLookupError, OSError):
                pass

    # -- exploit/explore ----------------------------------------------------------- #

    def _try_resow(self, trial: Trial, now: float) -> None:
        rcfg = self.cfg.resow
        if not rcfg.enabled or trial.resows >= int(rcfg.max_per_trial):
            trial.to(T.FAILED, reason=f"resow budget exhausted ({trial.resows}/{rcfg.max_per_trial})")
            self._resow_deadline.pop(trial.key, None)
            self._log(f"{trial.key}: resow budget exhausted -> failed")
            return
        exclude = [trial.key] + [t.key for t in self.trials if t.state == T.DIVERGED]
        dirs = {t.key: self.trial_dir(t.key) for t in self.trials if t.key != trial.key}
        parent = select_parent(dirs, exclude=exclude)
        if parent is not None:
            pkey, ckpt, step = parent
            trial.resows += 1
            trial.generation += 1
            trial.parent = pkey
            trial.hyperparams = perturb(
                trial.hyperparams, list(rcfg.perturb.keys or []), list(rcfg.perturb.factors or [])
            )
            trial.resume_ckpt = ckpt
            trial.next_eligible = now
            trial.to(T.RESOWN, parent=pkey, ckpt=ckpt, parent_step=step)
            self._resow_deadline.pop(trial.key, None)
            self._log(
                f"resow {trial.key} gen={trial.generation} from {pkey}'s certified step-{step} "
                f"checkpoint, hyperparams={trial.hyperparams}"
            )
            return
        deadline = self._resow_deadline.setdefault(trial.key, now + float(rcfg.parent_wait_s))
        if now < deadline:
            return  # stay DIVERGED; retried every poll until a peer certifies
        # no peer certified anything within the window: from-scratch requeue,
        # counted against the failure budget (matches configs/orchestrate)
        self._resow_deadline.pop(trial.key, None)
        trial.failures += 1
        if trial.failures > self.scheduler.max_failures:
            trial.to(T.FAILED, reason="no resow parent and failure budget exhausted")
            self._log(f"{trial.key}: no resow parent, budget exhausted -> failed")
            return
        trial.generation += 1
        trial.parent = None
        trial.resume_ckpt = None
        trial.next_eligible = now
        trial.to(T.RESOWN, parent=None, ckpt=None, fallback="scratch")
        self._log(f"{trial.key}: no certified peer within parent_wait_s, resowing from scratch")

    def _retry_diverged(self, now: float) -> None:
        for trial in self.trials:
            if trial.state == T.DIVERGED:
                self._try_resow(trial, now)
                self._save()

    def _maybe_exploit(self, now: float) -> None:
        ecfg = self.cfg.exploit
        interval = float(ecfg.interval_s)
        if interval <= 0 or now - self._last_exploit < interval:
            return
        self._last_exploit = now
        fits: Dict[str, int] = {}
        for t in self.trials:
            if t.terminal:
                continue
            fit = certified_fitness(self.trial_dir(t.key))
            if fit is not None:
                fits[t.key] = fit[1]
        if len(fits) < int(ecfg.min_peers):
            return
        from sheeprl_tpu.orchestrate.resow import bottom_quantile

        leader = max(fits.values())
        for key in bottom_quantile(fits, float(ecfg.quantile)):
            t = self._trial(key)
            if t.state != T.RUNNING or key in self._intents:
                continue
            if leader - fits[key] <= int(ecfg.min_lead):
                continue
            self._intents[key] = "exploit"
            self._log(f"exploit: {key} (step {fits[key]}) trails leader (step {leader}) -> SIGTERM")
            self._signal(key, signal.SIGTERM)
            break  # at most one exploit kill per tick keeps the fleet stable

    # -- chaos injection (drill knob) ----------------------------------------------- #

    def _maybe_inject(self, now: float) -> None:
        if self._inject_remaining <= 0:
            return
        if failpoints.has("orchestrate.inject"):
            # Deterministic drill clock: `orchestrate.inject:fire::every=N`
            # injects on every Nth eligible controller tick, independent of
            # wall-clock spacing (which races trial startup on loaded hosts).
            if failpoints.failpoint("orchestrate.inject", remaining=self._inject_remaining) is not True:
                return
        elif now - self._last_inject < self._inject_spacing_s:
            return
        candidates = [
            t
            for t in self.trials
            if t.state == T.RUNNING
            and t.key not in self._intents
            and os.path.exists(self._ready_file(t.key))  # guard armed: SIGTERM is survivable
            and _newest_ckpt(self.trial_dir(t.key))  # something to resume from
        ]
        if not candidates:
            return
        candidates.sort(key=lambda t: (self._injected.get(t.key, 0), t.key))
        victim = candidates[0]
        self._intents[victim.key] = "preempt"
        self._injected[victim.key] = self._injected.get(victim.key, 0) + 1
        self._inject_remaining -= 1
        self._last_inject = now
        self.counters["injections"] += 1
        self._log(f"injecting preemption into {victim.key} (pid {victim.pid})")
        self._signal(victim.key, signal.SIGTERM)

    # -- restart reconciliation ------------------------------------------------------ #

    def _reconcile(self, now: float) -> None:
        """Journal says RUNNING but this controller incarnation owns no such
        process: the previous controller died. A still-alive orphan is
        preempted (SIGTERM -> its guard checkpoints); either way the trial
        requeues from its newest checkpoint. Completion cannot be inferred
        without a returncode, and resuming an already-finished run is benign
        (total_steps reached -> immediate clean exit)."""
        for trial in self.trials:
            if trial.state != T.RUNNING or trial.key in self._procs:
                continue
            if _pid_alive(trial.pid):
                self._log(f"reconcile: orphan pid {trial.pid} of {trial.key} alive -> SIGTERM")
                try:
                    os.kill(int(trial.pid), signal.SIGTERM)
                except OSError:
                    pass
                deadline = time.time() + 30.0
                while _pid_alive(trial.pid) and time.time() < deadline:
                    time.sleep(0.2)
                if _pid_alive(trial.pid):
                    try:
                        os.kill(int(trial.pid), signal.SIGKILL)
                    except OSError:
                        pass
            trial.pid = None
            trial.to(T.PREEMPTED, reason="controller restart")
            self._preempted_at[trial.key] = now
            ckpt = _newest_ckpt(self.trial_dir(trial.key))
            self.scheduler.requeue_preempted(trial, ckpt, now)
            self._log(f"reconcile: {trial.key} requeued (resume={'yes' if ckpt else 'no'})")
        for trial in self.trials:
            if trial.state == T.DIVERGED:
                self._diverged_at.setdefault(trial.key, now)
        self._save()

    # -- shutdown ------------------------------------------------------------------- #

    def _drain(self, status: str, already_signalled: bool) -> str:
        """Forward SIGTERM (if the guard has not already), wait out the
        children's emergency checkpoints, classify every exit, journal."""
        if not already_signalled:
            for key in list(self._procs):
                self._signal(key, signal.SIGTERM)
        deadline = time.time() + float(self.cfg.shutdown.drain_timeout_s)
        while self._procs and time.time() < deadline:
            self._poll_exits(time.time())
            time.sleep(0.1)
        for key, proc in list(self._procs.items()):
            self._log(f"drain: {key} did not exit in time, killing")
            try:
                proc.kill()
                proc.wait(timeout=10)
            except Exception:
                pass
            trial = self._trial(key)
            self._reap(key)
            trial.to(T.PREEMPTED, reason="drain timeout kill")
            self.scheduler.requeue_preempted(trial, _newest_ckpt(self.trial_dir(key)), time.time())
        self._save()
        self._log(f"controller exiting: {status}")
        return status

    # -- main loop -------------------------------------------------------------------- #

    def run(self, max_runtime_s: Optional[float] = None) -> str:
        start = time.time()
        with PreemptionGuard(enabled=True, forward_to_children=True) as guard:
            self.guard = guard
            self._reconcile(time.time())
            while True:
                now = time.time()
                if guard.should_stop:
                    self._log(f"controller received {guard.describe()}; draining fleet")
                    # the guard already forwarded the signal to every child
                    return self._drain("preempted", already_signalled=True)
                if max_runtime_s is not None and now - start > max_runtime_s:
                    return self._drain("timeout", already_signalled=False)
                self._poll_exits(now)
                self._watch_health(now)
                self._retry_diverged(now)
                self._maybe_exploit(now)
                self._maybe_inject(now)
                for trial in self.scheduler.next_to_run(self.trials, now):
                    self._spawn(trial, now)
                if all(t.terminal for t in self.trials):
                    self._save()
                    self._log("all trials terminal")
                    return "done"
                time.sleep(float(self.cfg.poll_interval_s))

    def summary(self, status: str) -> Dict[str, Any]:
        return {
            "status": status,
            "trials": {t.key: {"state": t.state, "generation": t.generation} for t in self.trials},
            "counters": {
                k: v
                for k, v in self.counters.items()
                if k in ("spawn_seq", "preempt_recoveries", "resow_walls", "injections", "controller_incarnations")
            },
        }


def load_spec(path: str) -> Tuple[List[TrialSpec], Any]:
    """Population spec JSON: ``{"orchestrate": {...policy...}, "trials": [...]}``.
    Returns the trial specs and the raw dict (``resolve`` reads the group)."""
    with open(path) as f:
        spec = json.load(f)
    specs = [TrialSpec.from_dict(d) for d in spec.get("trials", [])]
    if not specs:
        raise SystemExit(f"population spec {path} declares no trials")
    return specs, spec


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--spec", required=True, help="population spec JSON")
    parser.add_argument("--state-dir", required=True, help="journal/lineage/trial-dir root")
    parser.add_argument(
        "--inject-preempt",
        type=int,
        default=0,
        help="drill knob: SIGTERM this many armed running trials, spaced out",
    )
    parser.add_argument("--inject-spacing-s", type=float, default=2.0)
    parser.add_argument("--max-runtime-s", type=float, default=None)
    cli = parser.parse_args(argv)
    # the fused backend dispatches BEFORE load_spec: a fused-population spec
    # hosts the whole fleet in one trainee and declares no per-trial specs
    # (load_spec treats an empty trial list as a config error)
    with open(cli.spec) as f:
        raw_spec = json.load(f)
    if str(resolve(raw_spec).population.backend).lower() == "fused":
        from sheeprl_tpu.orchestrate.fused import FusedPopulationController

        fused = FusedPopulationController(cli.spec, cli.state_dir, cfg=raw_spec)
        status = fused.run(max_runtime_s=cli.max_runtime_s)
        print("ORCHESTRATE_RESULT " + json.dumps(fused.summary(status)), flush=True)
        return 0 if status in ("done", "preempted") else 3
    specs, spec = load_spec(cli.spec)
    controller = PopulationController(
        specs,
        cli.state_dir,
        cfg=spec,
        inject_preempt=cli.inject_preempt,
        inject_spacing_s=cli.inject_spacing_s,
    )
    status = controller.run(max_runtime_s=cli.max_runtime_s)
    print("ORCHESTRATE_RESULT " + json.dumps(controller.summary(status)), flush=True)
    return 0 if status in ("done", "preempted") else 3


if __name__ == "__main__":
    raise SystemExit(main())
