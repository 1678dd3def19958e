"""Runner kind ``anakin_seeds``: the many-seed training sweep users run.

One fused program, ``make_anakin(system, chunk, envs, num_seeds=S)``, holds
S independent runs of E envs each.  Set-up builds it and its state from
the seed and drives it through ``checked_chunks`` chunks by the window's
own donated call, keeping what a sample of lanes left after each (rows,
weights, Adam state) for the check.  The window then calls the same
program on the same state, chunk after chunk, back to back, for the given
seconds.  Every chunk is a whole number of rollouts, so each ends in PPO
updates.

Traffic keys: num_seeds, envs_per_seed, chunk_iterations, checked_chunks,
checked_lanes, trace_chunks.
"""
from __future__ import annotations

import collections
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

import devtrace
import harness
from repro.core.system import make_anakin
from repro.obs.profile import RetraceCounter, profile_trace

# Chunks queued on the device ahead of the host: the host stalls for up to
# 1.4 s in some runs on the chip (one chunk seen done 1.0-1.4 s after the
# last, about one run in ten), and with one chunk queued the device sat idle
# through it.  Eight chunks (about 1.5 s of work) keep it busy.
IN_FLIGHT = 8


class Sweep:
    """The compiled program and its state, from set-up through the window."""

    def __init__(self, cell, seed, system=None, program=None):
        t = cell.traffic
        self.cell, self.seed = cell, seed
        self.S, self.E, self.T = t["num_seeds"], t["envs_per_seed"], t["chunk_iterations"]
        self.system = system if system is not None else harness.build_system(cell.config)
        self.program = program if program is not None else make_anakin(
            self.system, self.T, self.E, num_seeds=self.S)
        self.key = harness.program_key(seed)
        rng = np.random.default_rng(seed)
        self.lanes = np.sort(rng.choice(self.S, size=t["checked_lanes"], replace=False))
        self._take = jax.jit(lambda tree, idx: jax.tree_util.tree_map(lambda x: x[idx], tree))

    def steps_per_chunk(self) -> int:
        return self.S * self.E * self.T

    def setup(self):
        """Build the state and run the checked chunks through the window's call."""
        lanes = jnp.asarray(self.lanes)
        st = self.program.init_fn(self.key)
        snaps = [{"params": self._take(st.train.params, lanes)}]
        for c in range(self.cell.traffic["checked_chunks"]):
            st, metrics = self.program.fused(st)
            snap = {"params": self._take(st.train.params, lanes),
                    "rows": self._take(st.buffer.storage, lanes)}
            if c == 0:
                snap["mu1"] = self._take(st.train.opt_state[1].mu, lanes)
            snaps.append(snap)
        self.snaps = jax.device_get(snaps)
        self.st = st
        jax.block_until_ready(metrics)

    def run_chunks(self, seconds=None, count=None):
        """Chunks back to back until ``seconds`` or ``count``, ``IN_FLIGHT`` queued at a time.

        Returns (chunks, elapsed, ends): ``ends`` holds the host time at
        which each chunk was seen done, from the window's start.
        """
        st, ends, pending = self.st, [], collections.deque()
        t0 = time.perf_counter()
        while True:
            st, metrics = self.program.fused(st)
            pending.append(metrics)
            if len(pending) == IN_FLIGHT:
                jax.block_until_ready(pending.popleft())
                ends.append(time.perf_counter() - t0)
            elapsed = time.perf_counter() - t0
            if (seconds is not None and elapsed >= seconds) or (
                count is not None and len(ends) + len(pending) >= count
            ):
                break
        while pending:
            jax.block_until_ready(pending.popleft())
            ends.append(time.perf_counter() - t0)
        self.st = st
        return len(ends), ends[-1], ends

    def free(self):
        self.st = None

    def check(self, control=False, detail=None):
        """The compared numbers, worst over the checked lanes, read by the configuration's module.

        ``harness.reference`` finds the module.  With ``control`` its
        reference in bfloat16 takes the program's place; ``detail`` (a
        list) receives per-leaf gaps.
        """
        return harness.reference(self.cell.config).numbers(self, control, detail)


def run(cell, *, seed, seconds, trace, devices, clock, peaks):
    sweep = Sweep(cell, seed)
    sweep.setup()
    setup_s = clock.now()
    out = {"attempted": 0, "failed": 0}
    if not trace:
        with RetraceCounter() as rc:
            chunks, elapsed, ends = sweep.run_chunks(seconds=seconds)
        out["window_compiles"] = rc.backend_compiles
        gaps = np.diff(ends)  # from the second chunk on, each chunk's time
        out["chunk_s"] = {"median": float(np.median(gaps)), "max": float(gaps.max()),
                          "argmax": int(gaps.argmax()) + 1}
        out["end_to_end"] = {
            "train_steps_per_s": chunks * sweep.steps_per_chunk() / elapsed,
            "setup_s": setup_s,
        }
    else:
        sweep.run_chunks(count=2)
        with tempfile.TemporaryDirectory() as d:
            with profile_trace(d):
                with jax.profiler.TraceAnnotation(devtrace.WINDOW):
                    chunks, elapsed, _ = sweep.run_chunks(count=cell.traffic["trace_chunks"])
            reduced = devtrace.reduce(d, len(devices))
        out["trace_ctx"] = dict(
            reduced,
            steps_per_s=chunks * sweep.steps_per_chunk() / elapsed,
            chips=len(devices),
            updates_traced=chunks * (sweep.T // cell.config["system_overrides"]["rollout_len"]),
            lanes=sweep.S,
            envs_per_seed=sweep.E,
        )
    out["attempted"] = chunks
    out["memory_peak_bytes"] = harness.memory_peak_bytes(devices)
    window_end = clock.now()
    sweep.free()
    numbers = sweep.check()
    out["checks"] = {k: {"value": numbers[k], "limit": v} for k, v in cell.limits.items()}
    out["timing"] = {"setup_s": setup_s, "window_end_s": window_end,
                     "check_s": clock.now() - window_end,
                     "window_compiles": out.pop("window_compiles", None),
                     "chunk_s": out.pop("chunk_s", None)}
    out["correct"] = all(c["value"] <= c["limit"] for c in out["checks"].values())
    return out
