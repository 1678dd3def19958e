"""Slot-based decision-serving engine for trained MARL policies.

The MARL twin of the LM side's continuous-batching engine
(`repro.serving.engine`): a fixed pool of ``max_slots`` *episode slots*
shares one batched env/carry state, per-user episode requests are admitted
into free slots, and one jitted tick advances **all** live slots — policy
forward pass, env step and carry bookkeeping fused into a single program
whose shapes never change, so the jit compiles once per pool size.

Per-slot recurrent state is exactly the typed `repro.core.types.Carry` the
memory-core protocol provides: one row per slot, zeroed on admission and
at episode boundaries through the protocol's one masking rule
(`repro.nn.recurrent.reset_carry`).  A feed-forward policy's carry is the
empty pytree and all of this is free.

Action modes map onto the executor's existing faces:

* ``greedy``  — ``select_actions(..., training=False)``: the same
  deterministic argmax path as `repro.eval`'s fused evaluator, which is
  what makes served decisions bitwise-comparable to offline eval;
* ``sample``  — ``training=True``: the stochastic behaviour policy
  (eps-greedy / categorical sampling), for serving exploratory traffic.

Simplifications vs a production server (documented, not hidden — same
discipline as the LM engine):

* free slots still burn forward-pass and env-step FLOPs (their outputs
  are discarded); fine at these pool sizes, masking would fix it at scale;
* admission resets one env per request (a tiny jitted call per admit)
  rather than batching arrivals into one reset.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import TrainState
from repro.envs.api import StepType
from repro.nn.recurrent import reset_carry


@dataclasses.dataclass
class ServeRequest:
    """One user's episode: a reset key in, decisions and a return out."""

    uid: int
    key: Any  # jax PRNG key seeding the episode's env.reset
    arrival_tick: int = 0  # when the traffic trace makes this request arrive
    # filled by the engine
    slot: Optional[int] = None
    episode_return: float = 0.0             # team return (mean over agents)
    agent_returns: Dict[str, float] = dataclasses.field(default_factory=dict)
    length: int = 0
    done: bool = False
    actions: List[Dict[str, Any]] = dataclasses.field(default_factory=list)


def _strong(tree):
    """Strip weak types so pool state keeps one aval across jit boundaries.

    ``env.step`` and ``env.reset`` disagree on weak-typedness for some
    leaves (e.g. rewards); without canonicalising, the admit and tick jits
    would each recompile once when state produced by one flows into the
    other — a latency spike BENCH_serve would wrongly report as a slow
    steady-state tick.
    """
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, x.dtype), tree)


def _as_train_state(train_or_params) -> TrainState:
    """Accept a full TrainState or bare params (wrapped with zero steps)."""
    if isinstance(train_or_params, TrainState):
        return train_or_params
    return TrainState(
        params=train_or_params,
        target_params=train_or_params,
        opt_state=None,
        steps=jnp.zeros((), jnp.int32),
    )


class DecisionEngine:
    """Serve per-user episodes of ``system``'s env from a fixed slot pool.

        env, system, train = load_policy("results/ckpts/rec_ippo-lbf")
        engine = DecisionEngine(system, train, max_slots=8)
        engine.submit(ServeRequest(uid=0, key=jax.random.key(7)))
        while not engine.idle():
            decisions = engine.tick()   # {uid: {agent: action}} this tick

    ``tick()`` admits queued requests into free slots (lowest slot index
    first, FIFO queue — deterministic recycling), runs the one jitted
    select-actions + env-step program over the whole pool, returns the
    live slots' joint actions, and retires episodes that hit LAST (the
    slot is freed for the next admission, its carry already zeroed by the
    in-tick boundary reset).  Per-tick wall time and live-slot counts are
    appended to ``tick_log`` for the BENCH_serve latency/throughput stats,
    with the tick split into ``admit_s``, ``decide_s`` (the decision
    program's dispatch and its device-to-host copies) and ``bookkeep_s``
    (the live-slot loop); the same three parts are ``serve.admit``,
    ``serve.decide`` and ``serve.bookkeep`` spans on a profiler trace.
    """

    def __init__(
        self,
        system,
        train,
        max_slots: int = 8,
        mode: str = "greedy",
        seed: int = 0,
        record_actions: bool = False,
        warmup: bool = True,
    ):
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        if mode not in ("greedy", "sample"):
            raise ValueError(f"mode must be 'greedy' or 'sample', got {mode!r}")
        self.system = system
        self.env = system.env  # raw env: LAST retires the slot, no auto-reset
        self.train = jax.device_put(_as_train_state(train))
        self.max_slots = max_slots
        self.mode = mode
        self.record_actions = record_actions
        self._ids = list(system.spec.agent_ids)
        k_pool, k_warm, k_act = jax.random.split(jax.random.key(seed), 3)
        self._warm_key = k_warm
        self._act_base = k_act
        self._t = 0  # tick counter (drives the sample-mode key stream)

        self.queue: Deque[ServeRequest] = deque()
        self.slots: List[Optional[ServeRequest]] = [None] * max_slots
        self.finished: List[ServeRequest] = []
        self.tick_log: List[Dict[str, float]] = []  # per-tick wall split + live count

        # the pool: batched env state / timestep / carry, one row per slot
        # (free rows hold placeholder episodes that are stepped and ignored)
        env_state, ts = jax.vmap(self.env.reset)(
            jax.random.split(k_pool, max_slots)
        )
        self._env_state, self._ts = _strong((env_state, ts))
        self._carry = system.initial_carry((max_slots,))
        self._live = np.zeros(max_slots, dtype=bool)

        self._admit_jit = jax.jit(self._admit_fn)
        self._tick_jit = jax.jit(self._tick_fn)
        if warmup:
            self.warmup()

    # ---------------------------------------------------------- jitted core

    def _admit_fn(self, env_state, ts, carry, key, slot):
        """Reset one episode into pool row ``slot`` and zero its carry.

        ``slot`` is a traced scalar, so one compiled program serves every
        admission.  The carry reset routes through `reset_carry` — the
        memory-core protocol's single masking rule — with a one-hot slot
        mask, exactly as the training runners reset at FIRST boundaries.
        """
        one_state, one_ts = self.env.reset(key)
        merge = lambda pool, one: pool.at[slot].set(one)
        env_state = jax.tree_util.tree_map(merge, env_state, one_state)
        ts = jax.tree_util.tree_map(merge, ts, one_ts)
        mask = jnp.arange(self.max_slots) == slot
        carry = reset_carry(
            carry, mask, initial=self.system.initial_carry((self.max_slots,))
        )
        return _strong((env_state, ts, carry))

    def _tick_fn(self, train, env_state, ts, carry, key):
        """One fused decision tick over the whole pool.

        Policy forward pass (greedy or sampled), vectorised env step, and
        the episode-boundary carry reset (rows whose step hit LAST restart
        from zero memory, so a recycled slot can never leak the previous
        user's state) — all inside one jit.
        """
        gs = jax.vmap(self.env.global_state)(env_state)
        actions, carry, _ = self.system.select_actions(
            train, ts.observation, gs, carry, key,
            training=(self.mode == "sample"),
        )
        new_env_state, new_ts = jax.vmap(self.env.step)(env_state, actions)
        ended = new_ts.step_type == StepType.LAST
        carry = reset_carry(
            carry, ended,
            initial=self.system.initial_carry((self.max_slots,)),
        )
        return _strong(
            (actions, new_env_state, new_ts, carry, new_ts.reward, ended)
        )

    def warmup(self) -> None:
        """Compile the admit/tick programs off the latency-critical path.

        Both are pure functions, so running them on the current pool state
        and discarding the outputs changes nothing; BENCH_serve latencies
        then measure steady-state decisions, not first-call compilation.
        """
        jax.block_until_ready(
            self._admit_jit(
                self._env_state, self._ts, self._carry,
                self._warm_key, jnp.asarray(0),
            )
        )
        jax.block_until_ready(
            self._tick_jit(
                self.train, self._env_state, self._ts, self._carry,
                jax.random.fold_in(self._warm_key, 1),
            )
        )

    # ------------------------------------------------------------ admission

    def submit(self, req: ServeRequest) -> None:
        """Queue one episode request (FIFO; admitted on the next tick)."""
        self.queue.append(req)

    def _admit(self) -> None:
        """Fill free slots from the queue: lowest slot first, FIFO order."""
        for slot in range(self.max_slots):
            if self.slots[slot] is not None or not self.queue:
                continue
            req = self.queue.popleft()
            req.slot = slot
            self.slots[slot] = req
            self._live[slot] = True
            self._env_state, self._ts, self._carry = self._admit_jit(
                self._env_state, self._ts, self._carry,
                req.key, jnp.asarray(slot),
            )
            req.agent_returns = {a: np.float32(0.0) for a in self._ids}

    # ----------------------------------------------------------------- tick

    def idle(self) -> bool:
        """True when no request is queued or being served."""
        return not self.queue and not self._live.any()

    def tick(self) -> Dict[int, Dict[str, int]]:
        """Admit, decide one joint action for every live slot, retire LASTs.

        Returns ``{uid: {agent_id: action}}`` for the slots that were live
        this tick — the decisions a server would ship back to its users.
        """
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("serve.admit"):
            self._admit()
        t_admit = time.perf_counter()
        live = [i for i, r in enumerate(self.slots) if r is not None]
        if not live:
            return {}
        with jax.profiler.TraceAnnotation("serve.decide"):
            k_act = jax.random.fold_in(self._act_base, self._t)
            self._t += 1
            actions, self._env_state, self._ts, self._carry, rewards, ended = (
                self._tick_jit(
                    self.train, self._env_state, self._ts, self._carry, k_act
                )
            )
            actions = {a: np.asarray(v) for a, v in actions.items()}
            rewards = {a: np.asarray(v, np.float32) for a, v in rewards.items()}
            ended = np.asarray(ended)
        t_decide = time.perf_counter()

        emitted: Dict[int, Dict[str, int]] = {}
        with jax.profiler.TraceAnnotation("serve.bookkeep"):
            for i in live:
                req = self.slots[i]
                decision = {a: actions[a][i] for a in self._ids}
                emitted[req.uid] = decision
                if self.record_actions:
                    req.actions.append(decision)
                for a in self._ids:
                    # float32 accumulation, same order as the evaluator's scan
                    req.agent_returns[a] = np.float32(
                        req.agent_returns[a] + rewards[a][i]
                    )
                req.length += 1
                if ended[i]:
                    req.episode_return = float(
                        np.mean(
                            np.stack(
                                [req.agent_returns[a] for a in self._ids]
                            ).astype(np.float32)
                        )
                    )
                    req.done = True
                    self.finished.append(req)
                    self.slots[i] = None
                    self._live[i] = False
        t_end = time.perf_counter()
        self.tick_log.append({
            "seconds": t_end - t0,
            "live": len(live),
            "admit_s": t_admit - t0,
            "decide_s": t_decide - t_admit,
            "bookkeep_s": t_end - t_decide,
        })
        return emitted

    def run_until_drained(self, max_ticks: int = 100_000) -> List[ServeRequest]:
        """Tick until the queue and every slot are empty; return finished."""
        for _ in range(max_ticks):
            if self.idle():
                break
            self.tick()
        return self.finished

    # -------------------------------------------------------- introspection

    @property
    def carry(self):
        """The pool's executor memory (one row per slot) — for tests."""
        return self._carry

    @property
    def num_live(self) -> int:
        """How many slots currently hold a running episode."""
        return int(self._live.sum())
