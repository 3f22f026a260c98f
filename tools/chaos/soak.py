"""Crash-soak harness (DESIGN.md §12): kill a serving worker under load,
recover, and prove the recovered state bit-exact.

One *life* = spawn a worker subprocess that observes deterministic
seeded batches through a durable :class:`ShardedEngine` (WAL every batch,
async snapshot cadence), then kill it — either an external SIGKILL mid
load, or a self-SIGKILL armed *inside* a persistence failpoint via
``MCQ_FAILPOINTS`` (``site=kill@nth:K``), so deaths land mid-append,
mid-fsync, mid-snapshot-write and mid-manifest-commit, not just between
steps.  After each death the harness:

  1. recovers in a fresh checker process (``restore()`` = newest complete
     snapshot + WAL replay), timing it — the recovery-time series is the
     B9 benchmark.  The parent never imports JAX, so on an accelerator
     each worker and checker in turn gets the device;
  2. rebuilds an *oracle* engine with no persistence at all by replaying
     every durable WAL record from an empty chain through the same
     ``observe()`` pipeline;
  3. asserts every array leaf of the recovered published snapshot equals
     the oracle's bit-for-bit, and that the recovered WAL position equals
     the last durable record.

Because a batch is WAL-appended strictly before it is applied (I3) and
the apply pipeline is replay-deterministic (I7/A12), snapshot+tail-replay
and full-replay-from-empty must converge to the identical state whatever
instant the process died at.  Any divergence — a torn record applied, a
record applied twice across a snapshot boundary, a half-published epoch
restored — fails the soak.

  PYTHONPATH=src python -m tools.chaos.soak --kills 20 \
      --junit chaos.xml --out benchmarks/BENCH_faults.json

Rows land in ``BENCH_faults.json`` (schema-checked by
``benchmarks/run.py --validate``); ``--junit`` writes one testcase per
kill for CI.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
from typing import List, Optional
from xml.sax.saxutils import escape

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(_HERE))

#: kill schedule, cycled per life: None = external SIGKILL at a jittered
#: step; otherwise the failpoint armed (via MCQ_FAILPOINTS) to SIGKILL the
#: worker from *inside* the persistence edge at a jittered hit count
KILL_MODES = (
    None,
    "wal.append.write",
    None,
    "wal.append.fsync",
    None,
    "snapshot.arrays_write",
    "wal.append.write",
    "snapshot.manifest_commit",
)

#: hard cap on steps per life — an armed failpoint the worker never
#: reaches (e.g. snapshot cadence not yet due) falls back to an external
#: kill instead of hanging the soak
MAX_STEPS_PER_LIFE = 40


def batch_for(seed: int, step: int, rows: int, batch: int):
    """The deterministic load stream: batch ``step`` is a pure function of
    (seed, step), so worker lives and the oracle generate identical data
    without sharing anything but the WAL."""
    rng = np.random.default_rng([seed, step])
    src = rng.integers(0, rows, batch).astype(np.int32)
    dst = rng.integers(0, rows, batch).astype(np.int32)
    return src, dst


# ---------------------------------------------------------------------------
# engine plumbing (imported lazily: --help must not pay jax init)
# ---------------------------------------------------------------------------


def _build_engine(workdir: Optional[str], rows: int, *,
                  snapshot_every: int = 0):
    from repro.core import mcprioq as mc
    from repro.core import sharded as sh
    from repro.serve.engine import ShardedEngine, ShardedServeConfig

    scfg = sh.ShardedConfig(base=mc.MCConfig(num_rows=rows, capacity=16,
                                             sort_passes=1),
                            num_shards=1, bucket_factor=2.0)
    cfg = ShardedServeConfig(
        sharded=scfg,
        snapshot_dir=os.path.join(workdir, "snap") if workdir else None,
        wal_dir=os.path.join(workdir, "wal") if workdir else None,
        wal_fsync="always",
        snapshot_every=snapshot_every,
        decay_threshold=1 << 30,   # no decay: lives stay comparable
    )
    return ShardedEngine(cfg)


def worker_main(args) -> None:
    """The killable serving loop: restore (or lay down the step-0 base
    snapshot), then observe deterministic batches forever, one WAL record
    per step, printing ``STEP <seq>`` after each durable+applied batch."""
    eng = _build_engine(args.dir, args.rows,
                        snapshot_every=args.snapshot_every)
    try:
        info = eng.restore()
        print(f"RESTORED step={info['step']} replayed={info['replayed']}",
              flush=True)
    except FileNotFoundError:
        eng.checkpoint()   # step-0 base: recovery always has a snapshot
    start = eng.wal.next_seq
    print(f"READY {start}", flush=True)
    step = start
    while True:
        src, dst = batch_for(args.seed, step, args.rows, args.batch)
        eng.observe(src, dst)
        print(f"STEP {step}", flush=True)
        step += 1
        if args.sleep:
            time.sleep(args.sleep)


# ---------------------------------------------------------------------------
# the soak loop (parent)
# ---------------------------------------------------------------------------


def _child_env() -> dict:
    """A child's environment: the repo importable, nothing armed."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO, "src"), REPO, env.get("PYTHONPATH", "")])
    for key in ("MCQ_METRICS", "MCQ_METRICS_INCIDENT_DIR", "MCQ_FAILPOINTS"):
        env.pop(key, None)
    return env


def _spawn_worker(workdir: str, rows: int, batch: int, seed: int,
                  snapshot_every: int, kill_site: Optional[str],
                  kill_hit: int, telemetry: bool = False,
                  poison: bool = False) -> subprocess.Popen:
    env = _child_env()
    if telemetry:
        # arm the obs gate in the worker (spans, histograms, incidents) and
        # point the flight recorder's incident dumps at the workdir
        env["MCQ_METRICS"] = "1"
        env["MCQ_METRICS_INCIDENT_DIR"] = os.path.join(workdir, "incidents")
    if kill_site is not None:
        # a poison life raises ENOSPC (persistent) instead of SIGKILLing:
        # the write path poisons, dumps a flight-recorder incident, and the
        # worker dies on the escalation — a diagnosable death, not a silent
        # one, exercising the incident pipeline under real load
        action = "raise:28" if poison else "kill"
        env["MCQ_FAILPOINTS"] = f"{kill_site}={action}@nth:{kill_hit}"
    return subprocess.Popen(
        [sys.executable, "-m", "tools.chaos.soak", "--worker",
         "--dir", workdir, "--rows", str(rows), "--batch", str(batch),
         "--seed", str(seed), "--snapshot-every", str(snapshot_every)],
        cwd=REPO, env=env, stdout=subprocess.PIPE, text=True)


def _run_life(proc: subprocess.Popen, kill_after_steps: int) -> dict:
    """Read worker progress until the kill moment (or the armed failpoint
    fires); returns what the parent observed about the life."""
    steps_seen = 0
    armed_death = False
    deadline_steps = kill_after_steps
    for line in proc.stdout:
        if line.startswith("STEP "):
            steps_seen += 1
            if steps_seen >= deadline_steps:
                break
    else:
        armed_death = True   # stdout closed: the failpoint killed it
    if not armed_death:
        proc.send_signal(signal.SIGKILL)
    proc.wait()
    proc.stdout.close()
    return {"steps_seen": steps_seen, "armed_death": armed_death,
            "exit": proc.returncode}


def _verify_recovery(workdir: str, rows: int, batch: int, seed: int):
    """Recover, rebuild the oracle from the full deterministic history,
    and compare bit-exactly.

    The WAL alone is not the full history — committed snapshots GC the
    segments they cover — so the oracle replays ``batch_for(seed, 0..L)``
    from an empty chain through the same ``observe()`` pipeline, where
    ``L`` (the last durable step) is established independently of the
    recovered engine: the newest complete snapshot's ``wal_seq`` plus the
    WAL tail.  Each surviving WAL record is also checked against the
    deterministic stream, so a torn record that replay failed to reject
    is caught directly.

    Returns (recovery_seconds, last_seq, replayed, mismatches).
    """
    import jax
    from repro.persist import snapshot as snapshot_io
    from repro.persist.wal import WriteAheadLog

    t0 = time.perf_counter()
    eng = _build_engine(workdir, rows)
    info = eng.restore()
    recovery_s = time.perf_counter() - t0

    mismatches: List[str] = []
    snap_dir = os.path.join(workdir, "snap")
    step = snapshot_io.latest_complete_step(snap_dir)
    last = snapshot_io.load_meta(snap_dir, step)["wal_seq"] if step is not None else -1
    for seq, s, d, w in WriteAheadLog(os.path.join(workdir, "wal")).replay():
        last = max(last, seq)
        es, ed = batch_for(seed, seq, rows, batch)
        if not (np.array_equal(s, es) and np.array_equal(d, ed)
                and np.all(np.asarray(w) == 1)):
            mismatches.append(f"durable record {seq} does not match the "
                              f"deterministic stream (torn record "
                              f"survived replay)")
    if eng._seq != last:
        mismatches.append(
            f"wal position: recovered seq {eng._seq} != last durable "
            f"step {last}")

    oracle = _build_engine(None, rows)
    for i in range(last + 1):
        oracle.observe(*batch_for(seed, i, rows, batch))
    durable = last + 1   # number of durable steps
    snap_r, snap_o = eng.store.acquire(), oracle.store.acquire()
    try:
        leaves_r = jax.tree_util.tree_leaves(snap_r.state)
        leaves_o = jax.tree_util.tree_leaves(snap_o.state)
        for i, (lr, lo) in enumerate(zip(leaves_r, leaves_o)):
            if not np.array_equal(np.asarray(lr), np.asarray(lo)):
                mismatches.append(f"state leaf {i} diverged from the "
                                  f"WAL-replay oracle")
    finally:
        eng.store.release(snap_r)
        oracle.store.release(snap_o)

    # probe reads must agree too (the user-visible surface of the state)
    probe = np.arange(min(rows, 64), dtype=np.int32)
    for name, (a, b) in {
        "query": (eng.query(probe), oracle.query(probe)),
        "topn": (eng.topn(8), oracle.topn(8)),
    }.items():
        for xa, xb in zip(a, b):
            if not np.array_equal(np.asarray(xa), np.asarray(xb)):
                mismatches.append(f"{name} answers diverged")
                break
    eng.close()
    oracle.close()
    return recovery_s, durable, info["replayed"], mismatches


def _verify_in_child(workdir: str, rows: int, batch: int, seed: int):
    """:func:`_verify_recovery` in a fresh process, so the soak parent
    never imports JAX: a parent holding the accelerator would leave every
    later worker without one.  Same return value."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.chaos.soak", "--verify",
         "--dir", workdir, "--rows", str(rows), "--batch", str(batch),
         "--seed", str(seed)],
        cwd=REPO, env=_child_env(), capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"recovery check exited {proc.returncode}: "
                           f"{proc.stderr[-2000:]}")
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    return (out["recovery_s"], out["durable"], out["replayed"],
            out["mismatches"])


def _check_incidents(directory: str):
    """Every incident dump a poisoned worker left behind must parse and
    carry the flight-recorder payload (spans + metric deltas); returns
    ``(ok, message, count)``."""
    files = sorted(f for f in (os.listdir(directory)
                               if os.path.isdir(directory) else [])
                   if f.endswith(".json"))
    if not files:
        return False, "poison lives ran but no incident dump landed", 0
    bad = []
    for name in files:
        try:
            with open(os.path.join(directory, name)) as f:
                doc = json.load(f)
            if doc.get("schema") != "mcq-incident-v1":
                bad.append(f"{name}: wrong schema")
            elif not doc.get("spans"):
                bad.append(f"{name}: no spans")
            elif "deltas" not in doc or "reason" not in doc:
                bad.append(f"{name}: missing deltas/reason")
        except (OSError, json.JSONDecodeError) as e:
            bad.append(f"{name}: unparseable ({e})")
    if bad:
        return False, "; ".join(bad), len(files)
    return True, (f"{len(files)} incident dump(s), all parseable with "
                  f"spans + deltas"), len(files)


def run_soak(kills: int, *, rows: int = 256, batch: int = 128, seed: int = 0,
             snapshot_every: int = 5, min_steps: int = 3,
             max_steps: int = 12, workdir: Optional[str] = None,
             telemetry: bool = False) -> dict:
    """Run the kill/recover/verify loop; returns BENCH-shaped rows plus an
    ok flag (every life recovered bit-exactly).  ``telemetry=True`` arms
    the obs gate in every worker and turns ``wal.append.write`` lives into
    poison-raise lives, so the soak also proves a killed-under-load run
    leaves a parseable flight-recorder incident dump behind."""
    owns_dir = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="mcq-chaos-")
    rng = np.random.default_rng(seed)
    rows_out, all_ok = [], True
    recoveries = []
    n_poison = 0
    try:
        for k in range(kills):
            site = KILL_MODES[k % len(KILL_MODES)]
            kill_hit = int(rng.integers(1, 8))
            kill_after = int(rng.integers(min_steps, max_steps + 1))
            if site is not None:
                kill_after = MAX_STEPS_PER_LIFE   # fallback external kill
            poison = telemetry and site == "wal.append.write"
            n_poison += int(poison)
            proc = _spawn_worker(workdir, rows, batch, seed,
                                 snapshot_every, site, kill_hit,
                                 telemetry=telemetry, poison=poison)
            life = _run_life(proc, kill_after)
            t_rec, durable, replayed, bad = _verify_in_child(
                workdir, rows, batch, seed)
            ok = not bad
            all_ok &= ok
            recoveries.append(t_rec)
            mode = site or "sigkill"
            rows_out.append({
                "name": f"B9_crash_soak[kill={k};mode={mode}]",
                "us_per_call": round(t_rec * 1e6, 1),
                "derived": (f"recovered {durable} records "
                            f"(replayed {replayed}) "
                            f"{'bit-exact' if ok else 'DIVERGED: ' + '; '.join(bad)}"),
                "kill_mode": mode, "steps": durable,
                "replayed": replayed, "bitexact": ok,
            })
            print(f"kill {k}: mode={mode} durable={durable} "
                  f"replayed={replayed} recovery={t_rec * 1e3:.0f} ms "
                  f"{'ok' if ok else 'DIVERGED'}", flush=True)
            if not ok:
                break   # state is wrong: every later life would be too
        if telemetry and n_poison:
            inc_ok, inc_msg, n_inc = _check_incidents(
                os.path.join(workdir, "incidents"))
            all_ok &= inc_ok
            rows_out.append({
                "name": "B9_telemetry_incidents",
                "us_per_call": 0.0,
                "derived": inc_msg,
                "incidents": n_inc, "parseable": inc_ok,
            })
            print(f"incidents: {inc_msg}", flush=True)
        if recoveries:
            rows_out.append({
                "name": "B9_recovery_summary",
                "us_per_call": round(float(np.mean(recoveries)) * 1e6, 1),
                "derived": (f"{len(recoveries)} kills, max recovery "
                            f"{max(recoveries) * 1e3:.0f} ms, "
                            f"all bit-exact={all_ok}"),
                "kills": len(recoveries),
                "mean_recovery_us": round(float(np.mean(recoveries)) * 1e6, 1),
                "max_recovery_us": round(float(np.max(recoveries)) * 1e6, 1),
                "bitexact": all_ok,
            })
    finally:
        if owns_dir:
            shutil.rmtree(workdir, ignore_errors=True)
    return {"rows": rows_out, "ok": all_ok}


def write_junit(result: dict, path: str) -> None:
    cases = []
    for row in result["rows"]:
        body = ""
        if not row.get("bitexact", True):
            body = (f'<failure message="divergence">'
                    f'{escape(row["derived"])}</failure>')
        cases.append(f'<testcase classname="chaos" '
                     f'name="{escape(row["name"])}" '
                     f'time="{row["us_per_call"] / 1e6:.3f}">{body}'
                     f"</testcase>")
    fails = sum(1 for c in cases if "<failure" in c)
    xml = ('<?xml version="1.0" encoding="utf-8"?>\n'
           f'<testsuite name="chaos-soak" tests="{len(cases)}" '
           f'failures="{fails}">' + "".join(cases) + "</testsuite>\n")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        f.write(xml)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.chaos.soak", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--kills", type=int, default=20)
    ap.add_argument("--rows", type=int, default=256)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--snapshot-every", type=int, default=5)
    ap.add_argument("--sleep", type=float, default=0.0,
                    help="worker inter-step sleep (worker mode)")
    ap.add_argument("--dir", default=None,
                    help="persist under this directory instead of a "
                         "temp dir (worker mode: required)")
    ap.add_argument("--out", default=os.path.join(REPO, "benchmarks",
                                                  "BENCH_faults.json"),
                    help="BENCH JSON path ('' to skip writing)")
    ap.add_argument("--junit", default=None, metavar="FILE")
    ap.add_argument("--telemetry", action="store_true",
                    help="arm the obs gate in every worker and verify "
                         "poisoned lives leave parseable flight-recorder "
                         "incident dumps (DESIGN.md §13)")
    ap.add_argument("--worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--verify", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.worker or args.verify:
        if not args.dir:
            ap.error("--worker/--verify require --dir")
        if args.worker:
            worker_main(args)
        else:
            t_rec, durable, replayed, bad = _verify_recovery(
                args.dir, args.rows, args.batch, args.seed)
            print(json.dumps({"recovery_s": t_rec, "durable": durable,
                              "replayed": replayed, "mismatches": bad}))
        return 0

    result = run_soak(args.kills, rows=args.rows, batch=args.batch,
                      seed=args.seed, snapshot_every=args.snapshot_every,
                      workdir=args.dir, telemetry=args.telemetry)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"bench": "faults", "rows": result["rows"]}, f,
                      indent=1)
        print(f"wrote {args.out}")
    if args.junit:
        write_junit(result, args.junit)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
