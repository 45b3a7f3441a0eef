"""Runtime lock-order validator (utils/lockdep.py, ISSUE 10).

Violation-provoking scenarios run in SUBPROCESSES with OGT_LOCKDEP=1:
arming is an import-time decision (that is what makes the unarmed path
a zero-cost class alias), and a deliberately created cycle must never
poison the parent session's zero-violations gate (conftest
`_lockdep_session_gate`).
"""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, armed: bool = True, extra_env: dict | None = None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("OGT_LOCKDEP", None)
    env.pop("OGT_LOCKDEP_HOLD_MS", None)
    if armed:
        env["OGT_LOCKDEP"] = "1"
    env.update(extra_env or {})
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=120)
    return proc


PREAMBLE = """
import threading, time
from opengemini_tpu.utils import lockdep
assert lockdep.enabled()
"""


def test_cycle_detected_with_both_stacks():
    """A->B in one thread, B->A in another: one 'possible circular
    locking dependency' report carrying BOTH acquisition stack pairs
    (the function names of both threads appear in the report), and
    check() raises."""
    proc = _run(PREAMBLE + """
A = lockdep.name_class(lockdep.RLock(), "lock.A")
B = lockdep.name_class(lockdep.RLock(), "lock.B")

def forward_order():
    with A:
        with B:
            pass

def inverted_order():
    with B:
        with A:
            pass

for fn in (forward_order, inverted_order):
    t = threading.Thread(target=fn); t.start(); t.join()

v = lockdep.violations()
assert len(v) == 1, v
rep = v[0]
assert "possible circular locking dependency" in rep
assert "lock.A" in rep and "lock.B" in rep
# both stack pairs: the edge that closed the cycle AND the previously
# witnessed inverse chain
assert "inverted_order" in rep and "forward_order" in rep
try:
    lockdep.check()
except lockdep.LockdepError as e:
    assert "circular" in str(e)
    print("CHECK-RAISED")
""")
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "CHECK-RAISED" in proc.stdout


def test_rlock_reentrancy_and_same_class_nesting_not_flagged():
    """Reentrant re-acquire of one RLock and nesting two INSTANCES of
    one class (two shards' locks) are not order facts — no findings."""
    proc = _run(PREAMBLE + """
def make():  # one construction site = one lock class
    return lockdep.RLock()

R = make()
with R:
    with R:  # reentrant
        pass

x, y = make(), make()
with x:
    with y:  # same-class instance nesting (engine iterating shards)
        pass
assert lockdep.violations() == [], lockdep.violations()
lockdep.check()
print("CLEAN")
""")
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "CLEAN" in proc.stdout


def test_condition_wait_releases_and_reacquires_tracking():
    """Condition.wait routes through _release_save/_acquire_restore:
    while waiting the lock leaves the thread's held set (and the
    reacquire re-enters it), so waiting under a Condition can never
    fabricate hold-time or blocking findings."""
    proc = _run(PREAMBLE + """
L = lockdep.name_class(lockdep.RLock(), "cond.lock")
C = lockdep.Condition(L)
seen = {}

def waiter():
    with C:
        with C:  # reentrant hold released IN FULL by wait
            seen["pre"] = lockdep.held_classes()
            C.wait(timeout=5)
            seen["post"] = lockdep.held_classes()
    seen["after"] = lockdep.held_classes()

t = threading.Thread(target=waiter); t.start()
time.sleep(0.3)
with C:
    C.notify_all()
t.join()
assert seen["pre"] == ["cond.lock"], seen
assert seen["post"] == ["cond.lock"], seen
assert seen["after"] == [], seen
assert lockdep.violations() == [], lockdep.violations()
print("COND-OK")
""")
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "COND-OK" in proc.stdout


def test_blocking_under_hot_lock_and_allow_blocking_scope():
    """fsync/sleep under a hot class is a violation; the SAME call
    inside lockdep.allow_blocking() is an audited exception, and the
    annotation is scoped — it stops applying once the block exits."""
    proc = _run(PREAMBLE + """
import os as _os
H = lockdep.mark_hot(lockdep.Lock(), "test.hot")

with H:
    with lockdep.allow_blocking("audited"):
        time.sleep(0.001)     # annotated: no finding
        fd = _os.open(_os.devnull, _os.O_WRONLY)
        try:
            _os.fsync(fd)     # annotated: no finding
        except OSError:
            pass
        finally:
            _os.close(fd)
assert lockdep.violations() == [], lockdep.violations()

with H:
    time.sleep(0.001)         # NOT annotated: flagged
v = lockdep.violations()
assert len(v) == 1 and "time.sleep" in v[0] and "test.hot" in v[0], v

with H:
    pass  # cold path after the scope: no new findings
assert len(lockdep.violations()) == 1
print("SCOPE-OK")
""")
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "SCOPE-OK" in proc.stdout


def test_hold_budget_is_advisory():
    """OGT_LOCKDEP_HOLD_MS records over-budget holds into
    hold_reports() — visible, but never a check() failure (wall-clock
    holds are noisy on a GIL-starved CI box)."""
    proc = _run(PREAMBLE + """
L = lockdep.name_class(lockdep.Lock(), "held.long")
with L:
    with lockdep.allow_blocking("test sleep"):
        time.sleep(0.05)
reps = lockdep.hold_reports()
assert len(reps) == 1 and "held.long" in reps[0], reps
lockdep.check()  # advisory: does not raise
print("HOLD-OK")
""", extra_env={"OGT_LOCKDEP_HOLD_MS": "10"})
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "HOLD-OK" in proc.stdout


def test_unarmed_is_a_class_alias_not_a_shim():
    """OGT_LOCKDEP unset: the exported names ARE the threading classes
    (identity, the strongest form of bit-identical) — zero
    per-acquisition work by construction, not by measurement."""
    proc = _run("""
import threading
from opengemini_tpu.utils import lockdep
assert not lockdep.enabled()
assert lockdep.Lock is threading.Lock
assert lockdep.RLock is threading.RLock
assert lockdep.Condition is threading.Condition
# the rest of the API is inert
assert lockdep.violations() == [] and lockdep.hold_reports() == []
assert lockdep.check() is None
assert lockdep.held_classes() == []
lk = lockdep.mark_hot(lockdep.Lock(), "x")
assert type(lk) is type(threading.Lock())
with lockdep.allow_blocking("noop"):
    pass
assert lockdep.stats_snapshot() == {}
print("ALIAS-OK")
""", armed=False)
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "ALIAS-OK" in proc.stdout


_WORKLOAD = """
import hashlib, json, os, tempfile
from opengemini_tpu.query.executor import Executor
from opengemini_tpu.storage.engine import Engine
from opengemini_tpu.utils import lockdep

assert lockdep.enabled() == (os.environ.get("OGT_LOCKDEP") == "1")
NS = 1_000_000_000
BASE = 1_700_000_000
with tempfile.TemporaryDirectory() as d:
    eng = Engine(d, sync_wal=False)
    eng.create_database("db")
    eng.write_lines("db", "\\n".join(
        f"cpu,host=h{s} v={50 + (s + p) % 50} {(BASE + p) * NS}"
        for p in range(300) for s in range(6)))
    eng.flush_all()
    out = Executor(eng).execute(
        "SELECT mean(v), max(v), count(v) FROM cpu "
        f"WHERE time >= {BASE * NS} AND time < {(BASE + 300) * NS} "
        "GROUP BY time(1m), host", db="db", now_ns=(BASE + 300) * NS)
    lockdep.check()  # armed: ingest, flush and query were violation-free
    eng.close()
print("DIGEST " + hashlib.sha256(
    json.dumps(out, sort_keys=True).encode()).hexdigest())
"""


def test_armed_run_answers_as_unarmed():
    """Arming only observes: the same ingest + flush + GROUP BY time()
    in an armed and an unarmed process gives the same result digest,
    and the armed run witnesses no violation."""
    digests = []
    for armed in (False, True):
        proc = _run(_WORKLOAD, armed=armed)
        assert proc.returncode == 0, proc.stderr + proc.stdout
        digests.append([ln for ln in proc.stdout.splitlines()
                        if ln.startswith("DIGEST ")][-1])
    assert digests[0] == digests[1]


def test_synthetic_inverted_flush_lock_order_is_caught():
    """The acceptance scenario: the REAL shard records
    _flush_lock -> _lock during a flush; a synthetic inverted
    acquisition (_lock then _flush_lock — the PR 3 compact/flush
    deadlock shape) is reported with both stacks, naming both
    classes."""
    proc = _run("""
import threading
from opengemini_tpu.record import FieldType
from opengemini_tpu.storage.shard import Shard
from opengemini_tpu.utils import lockdep
import tempfile

NS = 1_000_000_000
BASE = 1_700_000_000 * NS
with tempfile.TemporaryDirectory() as d:
    sh = Shard(d + "/s", BASE - NS, BASE + 1000 * NS)
    sh.write_points_structured(
        [("m", (("host", "a"),), BASE + i * NS,
          {"v": (FieldType.FLOAT, float(i))}) for i in range(8)])
    sh.flush()  # legit order: _flush_lock -> _lock
    assert lockdep.violations() == [], lockdep.violations()

    def inverted():
        with sh._lock:
            with sh._flush_lock:
                pass
    t = threading.Thread(target=inverted); t.start(); t.join()
    v = lockdep.violations()
    assert len(v) == 1, v
    rep = v[0]
    assert "possible circular locking dependency" in rep
    assert "shard._lock" in rep and "shard._flush_lock" in rep
    assert "inverted" in rep       # the closing edge's stack
    assert "flush" in rep          # the witnessed chain's stack
    sh.close()
    print("INVERTED-CAUGHT")
""")
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "INVERTED-CAUGHT" in proc.stdout


def test_armed_stats_section_rides_debug_vars():
    """Armed processes export a `lockdep` stats section (the cluster
    torture harness asserts violations == 0 over live /debug/vars)."""
    proc = _run(PREAMBLE + """
from opengemini_tpu.utils import stats
snap = stats.GLOBAL.snapshot()
assert "lockdep" in snap, sorted(snap)
sect = snap["lockdep"]
assert sect["violations"] == 0
assert set(sect) >= {"violations", "edges", "classes", "hold_reports"}
print("STATS-OK")
""")
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "STATS-OK" in proc.stdout
