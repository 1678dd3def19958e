"""Plain SMAX-lite: the env's transition, observation and reset, written afresh.

The semantics of ``repro.envs.smax_lite.SmaxLite`` as documented there
(allies move or attack enemy j with action 5+j; scripted enemies approach
and hit the nearest living ally; shared reward of damage + 10 per kill +
200 for the win, over the largest return, times 20), on batches of envs
as plain arrays.  It imports nothing of the program.  ``dt`` is the
computing dtype: float32 for the reference, bfloat16 for the control.

Global state layout (the rows the rollout stores): ally positions (n*2),
ally hp / max_hp (n), enemy positions (n*2), enemy hp / max_hp (n).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MOVES = ((0.0, 0.0), (1.0, 0.0), (-1.0, 0.0), (0.0, 1.0), (0.0, -1.0))


def split_state(p, gs, dt=jnp.float32):
    """``(..., 6n)`` global state -> ally_pos, ally_hp, enemy_pos, enemy_hp."""
    n = p["num_agents"]
    gs = gs.astype(dt)
    ally_pos = gs[..., : 2 * n].reshape(gs.shape[:-1] + (n, 2))
    ally_hp = gs[..., 2 * n : 3 * n] * p["max_hp"]
    enemy_pos = gs[..., 3 * n : 5 * n].reshape(gs.shape[:-1] + (n, 2))
    enemy_hp = gs[..., 5 * n : 6 * n] * p["max_hp"]
    return ally_pos, ally_hp, enemy_pos, enemy_hp


def join_state(p, ally_pos, ally_hp, enemy_pos, enemy_hp):
    """The inverse of `split_state`."""
    lead = ally_hp.shape[:-1]
    return jnp.concatenate(
        [ally_pos.reshape(lead + (-1,)), ally_hp / p["max_hp"],
         enemy_pos.reshape(lead + (-1,)), enemy_hp / p["max_hp"]], axis=-1,
    )


def observe(p, ally_pos, ally_hp, enemy_pos, enemy_hp):
    """Per-agent observations ``(..., n, 6n)`` of a batch of states."""
    n = p["num_agents"]
    hp_scale = p["max_hp"]
    ally_alive = (ally_hp > 0).astype(ally_pos.dtype)
    enemy_alive = (enemy_hp > 0).astype(ally_pos.dtype)
    out = []
    for i in range(n):
        feats = [ally_pos[..., i, :], ally_hp[..., i : i + 1] / hp_scale]
        for j in range(n):
            if j != i:
                rel = (ally_pos[..., j, :] - ally_pos[..., i, :]) * ally_alive[..., j : j + 1]
                feats += [rel, ally_hp[..., j : j + 1] / hp_scale]
        for j in range(n):
            rel = (enemy_pos[..., j, :] - ally_pos[..., i, :]) * enemy_alive[..., j : j + 1]
            feats += [rel, enemy_hp[..., j : j + 1] / hp_scale]
        out.append(jnp.concatenate(feats, axis=-1) * ally_alive[..., i : i + 1])
    return jnp.stack(out, axis=-2)


def step(p, ally_pos, ally_hp, enemy_pos, enemy_hp, t, actions):
    """One transition of a batch ``(B, ...)``; ``actions`` is ``(B, n)`` ints.

    Returns the next (ally_pos, ally_hp, enemy_pos, enemy_hp, t), the
    shared reward and the episode-end flag.
    """
    n = p["num_agents"]
    dt = ally_pos.dtype
    B = actions.shape[0]
    rows = jnp.arange(B)[:, None]
    ally_alive = ally_hp > 0
    enemy_alive = enemy_hp > 0

    moves = jnp.asarray(MOVES, dt)[jnp.clip(actions, 0, 4)]
    moves = moves * (actions < 5)[..., None].astype(dt) * jnp.asarray(p["move_step"], dt)
    ally_pos = jnp.clip(ally_pos + moves * ally_alive[..., None].astype(dt),
                        -p["arena"], p["arena"])

    target = jnp.clip(actions - 5, 0, n - 1)
    target_pos = enemy_pos[rows, target]
    in_range = jnp.sqrt(jnp.sum(jnp.square(ally_pos - target_pos), -1)) <= p["attack_range"]
    hit = (actions >= 5) & ally_alive & in_range & enemy_alive[rows, target]
    damage = jnp.zeros((B, n), dt).at[rows, target].add(
        jnp.where(hit, jnp.asarray(p["damage"], dt), jnp.zeros((), dt))
    )
    new_enemy_hp = jnp.maximum(enemy_hp - damage, 0.0)
    killed = (enemy_hp > 0) & (new_enemy_hp <= 0)

    # enemies: nearest living ally (by the allies' new positions)
    d = jnp.sqrt(jnp.sum(jnp.square(enemy_pos[:, :, None] - ally_pos[:, None]), -1))
    d = jnp.where(ally_alive[:, None, :], d, jnp.asarray(1e9, d.dtype))
    nearest = jnp.argmin(d, axis=-1)
    nd = jnp.min(d, axis=-1)
    attack = (nd <= p["attack_range"]) & enemy_alive
    hurt = jnp.zeros((B, n), dt).at[rows, nearest].add(
        jnp.where(attack & (nd < 1e8), jnp.asarray(p["damage"], dt), jnp.zeros((), dt))
    )
    new_ally_hp = jnp.maximum(ally_hp - hurt, 0.0)
    to_ally = ally_pos[rows, nearest] - enemy_pos
    norm = jnp.sqrt(jnp.sum(jnp.square(to_ally), -1, keepdims=True)) + 1e-9
    stay = (attack | ~enemy_alive)[..., None]
    new_enemy_pos = jnp.where(
        stay, enemy_pos,
        jnp.clip(enemy_pos + to_ally / norm * p["move_step"], -p["arena"], p["arena"]),
    )

    t = t + 1
    won = jnp.all(new_enemy_hp <= 0, -1)
    done = won | jnp.all(new_ally_hp <= 0, -1) | (t >= p["horizon"])
    best = (p["max_hp"] + 10.0) * n + 200.0
    reward = (jnp.sum(damage, -1) + 10.0 * jnp.sum(killed, -1) + 200.0 * won) / best * 20.0
    return (ally_pos, new_ally_hp, new_enemy_pos, new_enemy_hp, t), reward.astype(dt), done


def reset(p, keys, dt=jnp.float32):
    """Fresh episodes for a batch of keys: allies in [-1, -0.5]^2, enemies in [0.5, 1]^2."""
    n = p["num_agents"]

    def one(key):
        k_ally, k_enemy = jax.random.split(key)
        ally = jax.random.uniform(k_ally, (n, 2), minval=-1.0, maxval=-0.5)
        enemy = jax.random.uniform(k_enemy, (n, 2), minval=0.5, maxval=1.0)
        return ally, enemy

    ally, enemy = jax.vmap(one)(keys)
    full = jnp.full(ally.shape[:-1], p["max_hp"], dt)
    return ally.astype(dt), full, enemy.astype(dt), full
