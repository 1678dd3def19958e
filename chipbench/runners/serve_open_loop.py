"""Runner kind ``serve_open_loop``: greedy decision serving under open-loop arrivals.

A ``DecisionEngine`` with a fixed pool of slots serves one episode per
request; weights are drawn on the device from the seed.  Requests come
from ``arrivals.poisson_schedule`` at the traffic file's fixed rate, due
in wall seconds from the start of the window, and are submitted when due
whatever the engine's state (open loop).  A request's first-decision
latency runs from its due time to the return of the tick that served its
first decision, so queueing and stalls count.  After the window the
engine drains what is left, so that every request due in the window is
answered (its latency counts the wait) and can be checked.

Traffic keys: slots, streams, rate_per_s, checked_requests, trace_seconds,
drain_seconds.
"""
from __future__ import annotations

import tempfile
import time

import jax
import numpy as np

import arrivals
import devtrace
import harness
from reference import ppo, serve_check
from repro.obs.profile import RetraceCounter, profile_trace
from repro.serve.engine import DecisionEngine, ServeRequest


def p95(values):
    return float(np.percentile(np.asarray(values), 95))


class Server:
    """The engine, its weights and the arrival schedule of one run."""

    def __init__(self, cell, seed, seconds, system=None):
        t = cell.traffic
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.system = system if system is not None else harness.build_system(cell.config)
        self.k_train, k_req = jax.random.split(harness.program_key(seed))
        train = jax.jit(self.system.init_train)(self.k_train)
        self.engine = DecisionEngine(self.system, train, max_slots=t["slots"], mode="greedy",
                                     seed=seed & 0x7FFFFFFF, record_actions=True)
        self.due, stream, index = arrivals.poisson_schedule(
            seed, t["streams"], t["rate_per_s"], seconds)
        keys = jax.vmap(lambda s, j: jax.random.fold_in(jax.random.fold_in(k_req, s), j))(
            stream, index)
        self.requests = [ServeRequest(uid=i, key=k) for i, k in enumerate(keys)]
        jax.block_until_ready(keys)

    def serve(self, drain_seconds, annotate=False):
        """Submit each request when due; tick while anything is live; drain after the window.

        With ``annotate`` the window (not the drain) is marked for the trace.
        """
        eng, due, n = self.engine, self.due, len(self.requests)
        first = np.full(n, np.nan)
        admitted = np.full(n, np.nan)
        decisions, ticks, i = 0, [], 0
        backlog, quarter = [], self.seconds / 4
        span = jax.profiler.TraceAnnotation(devtrace.WINDOW) if annotate else None
        if span is not None:
            span.__enter__()
        t0 = time.perf_counter()
        deadline = self.seconds + drain_seconds
        while True:
            now = time.perf_counter() - t0
            if span is not None and now >= self.seconds:
                span.__exit__(None, None, None)
                span = None
            while i < n and due[i] <= now:
                eng.submit(self.requests[i])
                i += 1
            if len(backlog) < 4 and now >= quarter * (len(backlog) + 1):
                backlog.append(len(eng.queue))
            if now > deadline or (eng.idle() and i >= n):
                break
            if eng.idle():
                time.sleep(max(0.0, min(due[i] - now, 1e-3)))
                continue
            start = time.perf_counter() - t0
            emitted = eng.tick()
            end = time.perf_counter() - t0
            if end <= self.seconds:
                decisions += len(emitted)
                ticks.append(end - start)
            for uid in emitted:
                if np.isnan(first[uid]):
                    first[uid] = end
                    admitted[uid] = start
        end = time.perf_counter() - t0
        if span is not None:
            span.__exit__(None, None, None)
        missing = np.isnan(first)
        # a request never answered counts as waiting to the end of the drain
        latency = np.where(missing, end, first) - due
        return {
            "latency_s": latency,
            "queue_s": (admitted - due)[~missing],
            "decisions": decisions,
            "missing": int(missing.sum()),
            "tick_seconds": ticks,
            "backlog": backlog,
        }

    def check(self, control=False):
        eng = self.engine
        done = sorted(eng.finished, key=lambda r: r.uid)
        if not done:
            return {k: None for k in serve_check.NUMBERS}
        rng = np.random.default_rng(self.seed)
        k = min(self.cell.traffic["checked_requests"], len(done))
        pick = set(rng.choice(len(done), size=k, replace=False).tolist())
        pick.add(int(np.argmax([r.length for r in done])))  # the longest is always in
        reqs = [done[j] for j in sorted(pick)]
        ids = list(self.system.spec.agent_ids)
        steps = self.cell.config["env_kwargs"]["horizon"]
        actions = np.zeros((len(reqs), steps, len(ids)), np.int32)
        for j, r in enumerate(reqs):
            for t, d in enumerate(r.actions):
                actions[j, t] = [d[a] for a in ids]
        spec = ppo.Spec.from_config(self.cell.config)
        params = ppo.init_params(spec, self.k_train)
        keys = jax.numpy.stack([r.key for r in reqs])
        return serve_check.numbers(
            self.cell.config, params, keys, actions,
            np.array([r.length for r in reqs]), np.array([r.episode_return for r in reqs]),
            control=control)


def run(cell, *, seed, seconds, trace, devices, clock, peaks):
    t = cell.traffic
    window = t["trace_seconds"] if trace else seconds
    server = Server(cell, seed, window)
    setup_s = clock.now()
    if not trace:
        with RetraceCounter() as rc:
            got = server.serve(t["drain_seconds"])
        out = {"window_compiles": rc.backend_compiles}
        out["end_to_end"] = {
            "serve_first_decision_p95_ms": 1e3 * p95(got["latency_s"]),
            "serve_decisions_per_s": got["decisions"] / seconds,
            "setup_s": setup_s,
        }
    else:
        out = {}
        with tempfile.TemporaryDirectory() as d:
            with profile_trace(d):
                got = server.serve(t["drain_seconds"], annotate=True)
            reduced = devtrace.reduce(d, len(devices))
        out["trace_ctx"] = dict(reduced, tick_seconds=got["tick_seconds"],
                                queue_seconds=list(got["queue_s"]))
    out["attempted"] = len(server.requests)
    out["failed"] = got["missing"]
    out["memory_peak_bytes"] = harness.memory_peak_bytes(devices)
    window_end = clock.now()
    numbers = server.check()
    out["checks"] = {k: {"value": numbers[k], "limit": v} for k, v in cell.limits.items()}
    out["timing"] = {"setup_s": setup_s, "window_end_s": window_end,
                     "check_s": clock.now() - window_end,
                     "window_compiles": out.pop("window_compiles", None)}
    out["correct"] = got["missing"] == 0 and all(
        c["value"] is not None and c["value"] <= c["limit"] for c in out["checks"].values())
    return out
