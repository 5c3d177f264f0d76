"""The one load generator: a closed or an open loop, as a traffic file
says.

    closed   one caller sends back-to-back ``batch``-query
             ``Server.query`` calls and reads every result to the host.
    open     single-query ``ServingRuntime.submit`` calls on a fixed
             schedule (``arrivals`` "poisson" at ``rate_qps``).  Each
             request is timed from when it was due, so a stall is
             charged to every request it delays; a refused or failed
             request counts as missing.

Queries are drawn from a pool of ``pool`` distinct queries in an order
drawn from the seed.  The open loop's arrival schedule is the traffic
file's alone: its gaps are the quantiles of the exponential distribution
in one fixed order, so every seed offers the same load at the same
moments and a seed changes only the corpus and the queries.
"""
from __future__ import annotations

import math
import time
from typing import NamedTuple, Optional

import numpy as np

#: how long past the window's close a request may still complete
DRAIN_S = 60.0


class Served(NamedTuple):
    """The answers of a window, in request order."""
    query: np.ndarray        # (n,) pool index of each request
    ids: list                # per request (R,) i32, or None if missing
    scores: list             # per request (R,) f32, or None
    n_candidates: np.ndarray  # (n,) live candidates, -1 if missing


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


#: the one order of the schedule's gaps
SCHEDULE_SEED = 0


def arrivals(traffic: dict, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of every request."""
    rate = float(traffic["rate_qps"])
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = np.random.default_rng(SCHEDULE_SEED).permutation(
        -np.log1p(-q) / rate)
    return np.cumsum(gaps)


class ClosedLoop:
    def __init__(self, traffic: dict, server, qe: np.ndarray,
                 qt: np.ndarray, rng):
        self.server = server
        self.batch = int(traffic["batch"])
        self.qe, self.qt = qe, qt
        self.order = rng.permutation(len(qe))
        self.stats: dict = {}

    def _rows(self, k: int) -> np.ndarray:
        n = len(self.order)
        return self.order[(np.arange(self.batch) + k * self.batch) % n]

    def _step(self, rows):
        with _annotate("bench.call"):
            res = self.server.query(self.qe[rows], self.qt[rows])
        with _annotate("bench.read"):
            return (np.asarray(res.doc_ids), np.asarray(res.scores),
                    np.asarray(res.n_candidates))

    def warmup(self) -> None:
        self._step(self._rows(0))

    def run(self, seconds: float) -> Served:
        ids, scores, cand, query = [], [], [], []
        t0 = time.perf_counter()
        t_end = t0
        k = 0
        with _annotate("bench.window"):
            while time.perf_counter() - t0 < seconds:
                rows = self._rows(k)
                i, s, c = self._step(rows)
                t_end = time.perf_counter()
                ids.extend(i)
                scores.extend(s)
                cand.append(c)
                query.append(rows)
                k += 1
        n = len(ids)
        self.stats = {"steps": k, "elapsed_s": t_end - t0,
                      "qps": n / (t_end - t0), "attempted": n, "failed": 0}
        return Served(np.concatenate(query), ids, scores,
                      np.concatenate(cand))


class OpenLoop:
    def __init__(self, traffic: dict, server, qe: np.ndarray,
                 qt: np.ndarray, rng):
        from repro.launch import runtime as rt_mod

        if traffic.get("arrivals", "poisson") != "poisson":
            raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
        self.traffic = traffic
        self.rt_mod = rt_mod
        self.runtime = rt_mod.ServingRuntime(
            server, rt_mod.RuntimeConfig(**traffic.get("runtime", {})))
        self.qe, self.qt = qe, qt
        self.order = rng.permutation(len(qe))
        self.stats: dict = {}

    def warmup(self) -> None:
        self.runtime.warmup(self.qe.shape[1], self.qt.shape[1])
        self.runtime.submit(self.qe[0], self.qt[0]).result()

    def close(self) -> None:
        self.runtime.close(drain=True)

    def run(self, seconds: float) -> Served:
        due = arrivals(self.traffic, seconds)
        n = len(due)
        query = self.order[np.arange(n) % len(self.order)]
        done = np.full(n, np.nan)
        late = np.zeros(n)
        futures: list = [None] * n
        refused = 0
        before = self.runtime.stats()

        def finished(i):
            def cb(_):
                done[i] = time.perf_counter()
            return cb

        t0 = time.perf_counter()
        with _annotate("bench.window"):
            for i in range(n):
                lead = t0 + due[i] - time.perf_counter()
                if lead > 0:
                    with _annotate("bench.sleep"):
                        time.sleep(lead)
                late[i] = time.perf_counter() - (t0 + due[i])
                with _annotate("bench.submit"):
                    try:
                        f = self.runtime.submit(self.qe[query[i]],
                                                self.qt[query[i]])
                    except self.rt_mod.RuntimeOverloaded:
                        refused += 1
                        continue
                futures[i] = f
                f.add_done_callback(finished(i))
            deadline = t0 + seconds + DRAIN_S
            with _annotate("bench.drain"):
                for f in futures:
                    if f is not None:
                        _wait(f, deadline - time.perf_counter())
        after = self.runtime.stats()
        ids, scores, cand = [], [], np.full(n, -1)
        failed = refused
        for i, f in enumerate(futures):
            row = _result(f)
            if row is None or math.isnan(done[i]):
                ids.append(None)
                scores.append(None)
                failed += f is not None
                continue
            ids.append(np.asarray(row.doc_ids))
            scores.append(np.asarray(row.scores))
            cand[i] = int(row.n_candidates)
        latency = done - (t0 + due)
        batches = after["n_batches"] - before["n_batches"]
        self.stats = {
            "attempted": n, "failed": failed, "refused": refused,
            "latency_s": latency, "late_s": late,
            "served": after["n_served"] - before["n_served"],
            "batches": batches,
            "post_warmup_traces": after["post_warmup_traces"],
            "elapsed_s": float(np.nanmax(done) - t0) if n else 0.0,
        }
        return Served(query, ids, scores, cand)


def _wait(future, timeout: float) -> None:
    try:
        future.result(timeout=max(timeout, 0.0))
    except Exception:  # noqa: BLE001 — a failed request counts as missing
        pass


def _result(future) -> Optional[object]:
    if future is None or not future.done():
        return None
    try:
        return future.result(timeout=0)
    except Exception:  # noqa: BLE001 — a failed request counts as missing
        return None


def percentile(values: np.ndarray, q: float) -> float:
    """The q-th percentile, a missing value (NaN) counting as infinite."""
    v = np.where(np.isnan(values), np.inf, values)
    return float(np.percentile(v, q, method="higher")) if len(v) else 0.0


def make(traffic: dict, server, qe, qt, rng):
    kind = traffic["loop"]
    if kind == "closed":
        return ClosedLoop(traffic, server, qe, qt, rng)
    if kind == "open":
        return OpenLoop(traffic, server, qe, qt, rng)
    raise ValueError(f"unknown loop kind {kind!r}")
