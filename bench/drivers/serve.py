"""Driver ``serve``: the FFT service under an open-loop Zipf mix.

Set-up starts ``FFTService(Session(), ServeConfig(**config["serve"]))``,
prewarms every mix entry (each power-of-two batch bucket up to
``max_batch``), makes a pool of ``payload_pool`` payloads per entry on the
device from the seed and copies them to the host (the service takes host
arrays), and sends one burst of each bucket size per entry so that every
path has run.

The window submits the tape of :mod:`traffic_tape` open loop: each request
at its due time, whatever the service is doing.  A request's latency runs
from its due time to its ``t_complete``; one that fails, or has no answer
60 s after the window closed, counts as failed and as an infinite latency.

The check compares the answers, as delivered, of a sample of requests
drawn from the seed with the float64 reference of their payloads.
"""

from __future__ import annotations

import time

import numpy as np

import inputs
import reference
import traffic_tape
from yardstick import quantile

#: Seconds after the window's close that a request may still answer.
GRACE_S = 60.0


def make_pool(seed: int, entry: int, extents, kind: str, size: int
              ) -> np.ndarray:
    """Mix entry ``entry``'s payloads, made on the device from the seed and
    copied to the host."""
    return np.asarray(inputs.make(inputs.key(seed, 1000 + entry),
                                  (size, *extents),
                                  kind.endswith("Complex")))


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 log):
        from repro.core.suite import Session
        from repro.serve.engine import FFTService, ServeConfig

        self.config, self.traffic, self.seed, self.log = (config, traffic,
                                                          seed, log)
        self.limits = config["limits"]
        self.precision = config["precision"]
        self.mix = traffic_tape.mix(traffic)
        self.pool = int(traffic["payload_pool"])
        self.svc = FFTService(Session(), ServeConfig(**config["serve"]))
        warm = sum(self.svc.prewarm(ext, kind, self.precision)
                   for ext, kind in self.mix)
        log(f"prewarmed {warm} executables over {len(self.mix)} entries")
        self.payloads = [make_pool(seed, i, ext, kind, self.pool)
                         for i, (ext, kind) in enumerate(self.mix)]
        self.svc.start()
        try:
            self._warm_paths()
        except BaseException:
            self.svc.stop(drain=False)
            raise

    def _submit(self, entry: int, j: int):
        ext, kind = self.mix[entry]
        return self.svc.submit(self.payloads[entry][j % self.pool],
                               kind=kind, precision=self.precision,
                               rank=len(ext))

    def _warm_paths(self) -> None:
        bucket = 1
        while bucket <= self.config["serve"]["max_batch"]:
            reqs = [self._submit(e, j) for e in range(len(self.mix))
                    for j in range(bucket)]
            for r in reqs:
                r.result(timeout=GRACE_S)
            bucket *= 2

    # --- the window ---------------------------------------------------------
    def window(self, seconds: float, span) -> dict:
        due_s, entries = traffic_tape.tape(self.traffic, seconds, self.seed)
        n = len(due_s)
        sample = set(inputs.rng(self.seed, 3).choice(
            n, size=min(n, int(self.traffic["check_requests"])),
            replace=False).tolist())
        lat = np.full(n, np.inf)
        queue = np.full(n, np.inf)
        late = np.zeros(n)
        ok = np.zeros(n, bool)
        live: dict[int, object] = {}
        self.checked: list[tuple[int, int, object]] = []
        counts = [0] * len(self.mix)
        m = self.svc.metrics
        batches0, completed0 = m.batches, m.completed

        def harvest(final: bool) -> None:
            for i in [i for i, r in live.items() if final or r.done()]:
                r = live.pop(i)
                due = t0 + due_s[i]
                if r.t_dispatch:
                    queue[i] = (r.t_dispatch - due) * 1e3
                if r.ok:
                    ok[i] = True
                    lat[i] = (r.t_complete - due) * 1e3
                    if i in sample:
                        self.checked.append(
                            (int(entries[i]), picks[i], r.result()))

        picks = np.zeros(n, np.int64)
        backlog_mid = 0
        t0 = time.perf_counter()
        for i in range(n):
            due = t0 + due_s[i]
            wait = due - time.perf_counter()
            if wait > 0:
                with span("arrival_wait"):
                    time.sleep(wait)
            e = int(entries[i])
            picks[i] = counts[e]
            counts[e] += 1
            with span("submit"):
                live[i] = self._submit(e, int(picks[i]))
            late[i] = (time.perf_counter() - due) * 1e3
            if i % 256 == 255 or i == n // 2:
                harvest(False)
            if i == n // 2:
                backlog_mid = len(live)
        harvest(False)
        backlog_close = len(live)
        close = t0 + seconds
        window_s = max(time.perf_counter(), close) - t0
        with span("result_wait"):
            while live and time.perf_counter() < close + GRACE_S:
                harvest(False)
                time.sleep(0.002)
        unanswered = len(live)
        harvest(True)
        self.failed = int(n - ok.sum())
        batches = m.batches - batches0
        completed = m.completed - completed0
        self.log(f"generator requests={n} late_p50_ms={quantile(late, .5)!r}"
                 f" late_p95_ms={quantile(late, .95)!r} "
                 f"late_max_ms={float(late.max()) if n else 0.0!r} "
                 f"backlog_mid={backlog_mid} backlog_close={backlog_close} "
                 f"unanswered_at_grace={unanswered} "
                 f"failed={self.failed} batches={batches} "
                 f"completed={completed}")
        return {"window_s": window_s, "attempted": n,
                "failed": self.failed,
                "metrics": {"serve_p50_ms": quantile(lat, 0.5),
                            "serve_p95_ms": quantile(lat, 0.95)},
                "queue_ms": queue, "batches": batches,
                "completed": completed, "backlog_mid": backlog_mid,
                "backlog_close": backlog_close}

    # --- the check ----------------------------------------------------------
    def check(self) -> list[tuple[str, float, float]]:
        refs: dict[tuple[int, int], np.ndarray] = {}
        worst = 0.0
        for entry, pick, got in self.checked:
            ext, kind = self.mix[entry]
            j = pick % self.pool
            ref = refs.get((entry, j))
            if ref is None:
                ref = refs[(entry, j)] = reference.forward(
                    self.payloads[entry][j:j + 1], len(ext),
                    kind.endswith("Real"))
            worst = max(worst, float(reference.rel_l2_rows(
                np.asarray(got).reshape(ref.shape), ref).max()))
        self.log(f"check answers={len(self.checked)} "
                 f"distinct_payloads={len(refs)} serve_rel_l2={worst!r}")
        return [("serve_rel_l2", worst, self.limits["serve_rel_l2"]),
                ("failed", float(self.failed), 0.0)]

    def close(self) -> None:
        self.svc.stop(drain=False)
