"""The measured window: one ingester thread and a pool of reader threads
drive ``ShardedEngine.observe``, ``.query`` and ``.topn``.

* A closed-loop ingester sends full batches back to back until the window's
  length has passed; the last batch it started counts whole.
* An open-loop ingester takes every event due by now, up to one batch,
  pads the batch to its full size with ``src = -1`` (so no new shape ever
  compiles), and drains the events due in the window after it closes.
* Readers share one schedule: each takes the next read call, waits for its
  due time and issues it; latency runs from the due time, so a stall counts
  against every call it delays.

Every read records the epoch it saw (through a per-thread hook on the
store's ``acquire``) and the last epoch acknowledged to the ingester
before it was issued.
"""

from __future__ import annotations

import contextlib
import gc
import threading
import time
import traceback

import numpy as np

from compare import Read
from reference import route_drops


class EpochProbe:
    """Records, per thread, the version of the last snapshot acquired."""

    def __init__(self, store):
        self.local = threading.local()
        acquire = store.acquire

        def probed():
            snap = acquire()
            self.local.version = snap.version
            return snap

        store.acquire = probed

    @property
    def version(self) -> int:
        return self.local.version


class Window:
    def __init__(self, engine, cfg, mix, traffic, reads, seconds: float,
                 open_events=None, annotate=None):
        self.engine, self.cfg, self.mix = engine, cfg, mix
        self.traffic, self.reads, self.seconds = traffic, reads, seconds
        self.open_events = open_events
        self.annotate = annotate
        self.batch = int(cfg["batch"])
        self.num_shards = int(cfg["serve"]["num_shards"])
        self.probe = EpochProbe(engine.store)
        self.acked = engine.store.version
        self.observes = []            # (version, src, dst) in publish order
        self.obs_calls = []           # (start, end) host clock
        self.events = 0
        self.lags = None
        self.errors = []
        n = reads.due.size
        self.read_lat = np.full(n, np.nan)
        self.read_dur = np.full(n, np.nan)
        self.read_fail = np.zeros(n, bool)
        self.checked = {}
        self.gc_pauses = []           # seconds of each collection in the window
        self._gc_t = None
        self._next = 0
        self._lock = threading.Lock()

    def _span(self, name):
        if self.annotate is None:
            return contextlib.nullcontext()
        return self.annotate(name)

    # ------------------------------------------------------------------
    def _observe(self, src, dst):
        ts = time.perf_counter()
        try:
            with self._span("bench.observe"):
                self.engine.observe(src, dst)
        except Exception:
            self.errors.append(traceback.format_exc())
            return ts, time.perf_counter(), False
        te = time.perf_counter()
        self.acked = self.engine.store.version
        self.observes.append((self.acked, src, dst))
        self.obs_calls.append((ts, te))
        return ts, te, True

    def _ingest_closed(self):
        i = 0
        while time.perf_counter() - self.t0 < self.seconds:
            src, dst = self.traffic.closed_batch(i)
            _, te, ok = self._observe(src, dst)
            self.events += src.size
            if not ok:
                self.failed_events += src.size
            self.t_end = te
            i += 1

    def _ingest_open(self):
        due, src, dst = self.open_events
        n, b, p = due.size, self.batch, 0
        lags = np.full(n, np.nan)
        while p < n:
            now = time.perf_counter() - self.t0
            if due[p] > now:
                time.sleep(min(due[p] - now, 0.002))
                continue
            q = min(int(np.searchsorted(due, now, side="right")), p + b)
            bs = np.full(b, -1, np.int32)
            bd = np.zeros(b, np.int32)
            bs[:q - p], bd[:q - p] = src[p:q], dst[p:q]
            _, te, ok = self._observe(bs, bd)
            lags[p:q] = te - self.t0 - due[p:q]
            if not ok:
                self.failed_events += q - p
            self.events += q - p
            p = q
        self.lags = lags
        self.t_end = time.perf_counter()

    def _reader(self):
        rd = self.reads
        thr = float(self.cfg["serve"]["threshold"])
        topn = int(self.cfg["serve"]["topn"])
        while True:
            with self._lock:
                i = self._next
                self._next += 1
            if i >= rd.due.size:
                return
            due = self.t0 + rd.due[i]
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            acked = self.acked
            ts = time.perf_counter()
            try:
                if rd.is_topn[i]:
                    with self._span("bench.topn"):
                        out = self.engine.topn(topn)
                else:
                    with self._span("bench.query"):
                        out = self.engine.query(rd.srcs[i], threshold=thr)
            except Exception:
                self.errors.append(traceback.format_exc())
                self.read_fail[i] = True
                continue
            te = time.perf_counter()
            self.read_lat[i] = te - due
            self.read_dur[i] = te - ts
            routed_out = None
            if not rd.is_topn[i]:
                routed_out = (route_drops(rd.srcs[i], self.num_shards,
                                          self.cfg["serve"]["bucket_factor"])
                              if self.num_shards > 1
                              else np.zeros(rd.srcs.shape[1], bool))
                self.read_fail[i] = bool(routed_out.any())
            if rd.checked[i]:
                self.checked[i] = Read(
                    self.probe.version, acked,
                    None if rd.is_topn[i] else rd.srcs[i],
                    tuple(np.asarray(x) for x in out), routed_out)

    def _gc_hook(self, phase, info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            self.gc_pauses.append(time.perf_counter() - self._gc_t)

    # ------------------------------------------------------------------
    def run(self, threads: int):
        gc.callbacks.append(self._gc_hook)
        try:
            return self._run(threads)
        finally:
            gc.callbacks.remove(self._gc_hook)

    def _run(self, threads: int):
        self.failed_events = 0
        self.t0 = time.perf_counter()
        self.t_end = self.t0
        pool = [threading.Thread(target=self._reader, daemon=True)
                for _ in range(threads)]
        for t in pool:
            t.start()
        if self.mix["ingest"]["loop"] == "closed":
            self._ingest_closed()
        else:
            self._ingest_open()
        for t in pool:
            t.join()
        self.t_reads_end = time.perf_counter()
        return self

