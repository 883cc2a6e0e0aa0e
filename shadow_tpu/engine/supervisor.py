"""Auto-resume supervision: ``shadow_tpu run --until-complete``.

The durability tentpole's third piece (docs/durability.md): a long
run must survive being killed — OOM, preemption, a node reboot — and
finish as if nothing happened. The supervisor runs the simulation in
a CHILD process (the same CLI, minus the supervisor flags), watches
its exit, and on a crash re-execs it with ``--resume latest`` so it
restores the newest valid snapshot of the crash-safe checkpoint store
(engine.checkpoint). Capped retries with exponential backoff bound a
crash loop; every attempt leaves a crash-cause record in
``<checkpoint base>.supervisor.jsonl`` and — when the obs layer is
installed (PR 1) — a ``supervisor.attempt`` span plus
``supervisor.*`` metrics.

The interrupted≡uninterrupted contract this enables is PROVEN by the
flight recorder: a SIGKILLed-and-resumed run's digest chain is
byte-identical to an uninterrupted same-seed run's
(tests/test_until_complete.py, tools/divergence.py exit 0).
"""

from __future__ import annotations

import signal
import subprocess
import sys
import time

# exit code of a cooperative preemption (SIGTERM under --checkpoint:
# the run saved a snapshot at a chunk boundary and stopped — see
# engine.sim.Preempted). EX_TEMPFAIL: "try again later" — a resume
# completes the run; supervisors treat it as resumable, never as a
# crash that counts toward giving up / quarantine.
EXIT_PREEMPTED = 75

# flags the supervisor consumes; never forwarded to the child
_SUPERVISOR_FLAGS = {"--until-complete"}
_SUPERVISOR_OPTS = {"--max-retries", "--retry-backoff"}


def backoff_delay(base_s: float, failures: int,
                  cap_s: float = 60.0) -> float:
    """Exponential backoff: delay before retry number `failures`
    (1-based count of crashes so far), doubling from `base_s` to a
    cap. The one backoff rule both the single-run supervisor and the
    fleet scheduler (shadow_tpu.fleet) apply."""
    return min(float(base_s) * (2 ** max(int(failures) - 1, 0)),
               float(cap_s))


class CrashLog:
    """Append-only crash-cause journal (``<base>.supervisor.jsonl`` /
    the fleet's per-run ``crash.jsonl``): one JSON line per attempt,
    appended atomically and fsync'd so a kill mid-append can tear at
    most the line in flight — which read() skips (the obs.ledger
    torn-line contract). The fleet's quarantine decision and the
    post-mortem both read this file, so it must survive exactly the
    crashes it documents."""

    def __init__(self, path: str, log=None):
        self.path = path
        self._log = log or (lambda msg: sys.stderr.write(
            f"shadow_tpu: crash log: {msg}\n"))

    def append(self, rec: dict):
        from ..obs.ledger import jsonl_append
        try:
            jsonl_append(self.path, rec, fsync=True, sort_keys=True)
        except OSError as e:
            self._log(f"cannot write {self.path}: {e}")

    def read(self) -> list:
        from ..obs.ledger import jsonl_read
        return jsonl_read(self.path, label="crash log")


def strip_supervisor_args(argv: list) -> list:
    """The child's CLI: the original argv minus supervisor-only
    flags (handles both ``--opt v`` and ``--opt=v`` spellings)."""
    out = []
    skip = False
    for a in argv:
        if skip:
            skip = False
            continue
        if a in _SUPERVISOR_FLAGS:
            continue
        if a in _SUPERVISOR_OPTS:
            skip = True
            continue
        if any(a.startswith(opt + "=") for opt in _SUPERVISOR_OPTS):
            continue
        out.append(a)
    return out


def _strip_resume(argv: list) -> list:
    """Drop any user ``--resume X`` before injecting ``--resume
    latest`` on a retry (the user's explicit snapshot applies to the
    FIRST attempt only; retries must pick up the newest state)."""
    out = []
    skip = False
    for a in argv:
        if skip:
            skip = False
            continue
        if a == "--resume":
            skip = True
            continue
        if a.startswith("--resume="):
            continue
        out.append(a)
    return out


def classify_exit(status: int) -> str:
    """Child exit status -> human crash cause."""
    if status == 0:
        return "completed"
    if status < 0:
        try:
            name = signal.Signals(-status).name
        except ValueError:
            name = f"signal {-status}"
        return f"killed by {name}"
    return f"exited status={status}"


class Supervisor:
    """Run one CLI invocation to completion across crashes."""

    def __init__(self, child_argv: list, checkpoint: str,
                 max_retries: int = 5, backoff_s: float = 1.0,
                 backoff_cap_s: float = 60.0, python: str = None,
                 log=None, max_preemptions: int = 100):
        self.child_argv = list(child_argv)
        # engine.checkpoint.base_of, inlined: importing the checkpoint
        # module would pull jax into the (deliberately light)
        # supervisor parent
        self.checkpoint_base = (checkpoint[:-4]
                                if checkpoint.endswith(".npz")
                                else checkpoint)
        self.max_retries = int(max_retries)
        self.backoff_s = float(backoff_s)
        self.backoff_cap_s = float(backoff_cap_s)
        # preemptions (exit 75) are resumable and never count as
        # crashes, but an environment that SIGTERMs every attempt
        # must not loop us forever — the same livelock bound the
        # fleet scheduler applies (max_spont_preempts), sized for a
        # long spot-instance run here
        self.max_preemptions = int(max_preemptions)
        self.python = python or sys.executable
        self.log = log or (lambda msg: sys.stderr.write(
            f"shadow_tpu: supervisor: {msg}\n"))
        self.attempts = []          # attempt records (also JSONL'd)
        self.crash_log = CrashLog(self.log_path(), log=self.log)

    def log_path(self) -> str:
        return self.checkpoint_base + ".supervisor.jsonl"

    def read_log(self) -> list:
        """All attempt records of this store's crash-cause journal,
        torn-line tolerant (a kill mid-append never breaks the next
        supervisor's — or the fleet's — read of it)."""
        return self.crash_log.read()

    def _record(self, rec: dict):
        self.attempts.append(rec)
        self.crash_log.append(rec)

    def _child_argv(self, attempt: int) -> list:
        if attempt == 1:
            return [self.python, "-m", "shadow_tpu"] + self.child_argv
        # retries resume from the newest valid snapshot; the CLI's
        # ``--resume latest`` starts fresh (with a warning) when the
        # crash predated the first checkpoint
        return ([self.python, "-m", "shadow_tpu"]
                + _strip_resume(self.child_argv)
                + ["--resume", "latest"])

    def run(self) -> int:
        from ..obs import metrics as MT
        from ..obs import trace as TR
        attempt = 0
        crashes = 0
        preemptions = 0
        while True:
            attempt += 1
            argv = self._child_argv(attempt)
            resumed = attempt > 1
            t0 = time.perf_counter()  # simlint: ok DET101 -- attempt wall for the crash journal, not sim time
            if TR.ENABLED:
                _s_tok = TR.TRACER.begin("supervisor.attempt",
                                         attempt=attempt,
                                         resumed=resumed)
            try:
                rc = subprocess.call(argv)
            except KeyboardInterrupt:
                # the operator killed US: do not respawn under them
                raise
            wall = time.perf_counter() - t0  # simlint: ok DET101 -- attempt wall for the crash journal, not sim time
            cause = classify_exit(rc)
            if TR.ENABLED:
                TR.TRACER.end(_s_tok, exit_status=rc, cause=cause)
            if MT.ENABLED:
                reg = MT.REGISTRY
                reg.counter("supervisor.attempts").inc()
                reg.gauge("supervisor.last_exit_status").set(rc)
                if rc != 0 and rc != EXIT_PREEMPTED:
                    # preemptions are resumable, not crashes — a
                    # dashboard alerting on crashes must not fire on
                    # healthy spot-instance churn
                    reg.counter("supervisor.crashes").inc()
                if resumed:
                    reg.counter("supervisor.resumes").inc()
            self._record({
                "attempt": attempt, "exit_status": rc, "cause": cause,
                "wall_s": round(wall, 3), "resumed": resumed,
                "argv": argv[1:],      # drop the interpreter path
                "time": time.strftime("%Y-%m-%dT%H:%M:%S"),
            })
            if rc == 0:
                if attempt > 1:
                    self.log(f"run completed on attempt {attempt}")
                return 0
            self.log(f"attempt {attempt} {cause}")
            if rc == 2:
                # argparse usage errors are deterministic — the same
                # argv fails identically every time, so retrying only
                # reproduces one message max_retries times over
                self.log("usage error is not a crash; not retrying")
                if MT.ENABLED:
                    MT.REGISTRY.counter("supervisor.gave_up").inc()
                return rc
            if rc == EXIT_PREEMPTED:
                # a cooperative preemption (SIGTERM → snapshot at the
                # boundary, engine.sim.Preempted) is not a crash: it
                # never counts toward the retry cap — but a child
                # preempted on EVERY attempt is a livelock, so it has
                # its own generous bound
                preemptions += 1
                if MT.ENABLED:
                    MT.REGISTRY.counter("supervisor.preemptions").inc()
                if preemptions > self.max_preemptions:
                    self.log(
                        f"preempted {preemptions} times without "
                        "completing — something SIGTERMs every "
                        "attempt; giving up (state is resumable)")
                    if MT.ENABLED:
                        MT.REGISTRY.counter("supervisor.gave_up").inc()
                    return rc
                self.log("child was preempted (saved a snapshot); "
                         "resuming from 'latest'")
                time.sleep(self.backoff_s)
                continue
            crashes += 1
            if crashes > self.max_retries:
                self.log(
                    f"giving up after {crashes} crashes "
                    f"({self.max_retries} retries); last cause: "
                    f"{cause}")
                if MT.ENABLED:
                    MT.REGISTRY.counter("supervisor.gave_up").inc()
                return rc if rc > 0 else 70    # EX_SOFTWARE for signals
            delay = backoff_delay(self.backoff_s, crashes,
                                  self.backoff_cap_s)
            self.log(f"restarting from 'latest' in {delay:.1f}s "
                     f"(retry {crashes}/{self.max_retries})")
            if MT.ENABLED:
                MT.REGISTRY.gauge("supervisor.backoff_s").set(delay)
            time.sleep(delay)
