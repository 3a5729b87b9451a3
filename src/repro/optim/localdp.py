"""CoCoA-DP: the paper's additive-aggregation insight transplanted to
non-convex data-parallel training (BEYOND-PAPER, clearly labeled; no convex
theory claimed -- see DESIGN.md section 3).

Per round, every DP group runs H local optimizer steps on its own shard
starting from the shared params theta, with the sigma'-analogue proximal
damping in the local objective:

    L_k(theta_k) = loss_k(theta_k) + (prox/2)||theta_k - theta||^2 ,
    prox = prox0 * sigma'        (sigma' = gamma*K, the paper's safe bound)

then the driver aggregates the deltas ADDITIVELY:

    theta <- theta + gamma * sum_k (theta_k - theta)

gamma=1/K, prox=0 recovers vanilla local-SGD averaging; gamma=1 with the
damped subproblem is the CoCoA+-style rule. Communication is one delta per
round instead of one gradient per step: H x fewer syncs (the paper's point).
Optional top-k / int8 compression with error feedback on the deltas.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.comm import compress as C


@dataclasses.dataclass(frozen=True)
class LocalDPConfig:
    K: int
    H: int = 8
    gamma: float = 1.0
    prox0: float = 0.5             # prox = prox0 * sigma' (under-damping diverges, mirroring the paper's naive-adding failure)
    sigma_p: Optional[float] = None   # None -> gamma * K (safe bound)
    inner_lr: float = 1e-2
    compress: str = "none"

    def resolved_sigma(self) -> float:
        return self.sigma_p if self.sigma_p is not None else self.gamma * self.K

    @staticmethod
    def averaging(K: int, **kw) -> "LocalDPConfig":
        return LocalDPConfig(K=K, gamma=1.0 / K, prox0=0.0, sigma_p=1.0, **kw)

    @staticmethod
    def adding(K: int, **kw) -> "LocalDPConfig":
        return LocalDPConfig(K=K, gamma=1.0, sigma_p=float(K), **kw)


class LocalDPState(NamedTuple):
    params: object
    ef: object            # error-feedback state (or None)
    rounds: jnp.ndarray


def init_state(params, cfg: LocalDPConfig) -> LocalDPState:
    ef = C.ef_init(params) if cfg.compress != "none" else None
    return LocalDPState(params, ef, jnp.zeros((), jnp.int32))


def make_round_fn(loss_fn: Callable, cfg: LocalDPConfig):
    """loss_fn(params, batch) -> scalar. Batches: pytree with leading (K, ...)
    per-worker axis. Simulation backend (vmap); the shard_map production path
    mirrors core.cocoa.make_round_sharded (one psum of deltas per round)."""
    prox = cfg.prox0 * cfg.resolved_sigma()

    def local_solve(theta, batch_k):
        def damped(p):
            base = loss_fn(p, batch_k)
            reg = sum(jnp.sum((a - b) ** 2)
                      for a, b in zip(jax.tree.leaves(p),
                                      jax.tree.leaves(theta)))
            return base + 0.5 * prox * reg

        def step(p, _):
            g = jax.grad(damped)(p)
            p = jax.tree.map(lambda w, gg: w - cfg.inner_lr * gg, p, g)
            return p, None

        pk, _ = jax.lax.scan(step, theta, None, length=cfg.H)
        return jax.tree.map(lambda a, b: a - b, pk, theta)   # delta_k

    def round_fn(state: LocalDPState, batches) -> LocalDPState:
        deltas = jax.vmap(lambda b: local_solve(state.params, b))(batches)
        # (compression with error feedback happens per worker in production;
        # simulated here on the summed delta for simplicity when enabled)
        summed = jax.tree.map(lambda d: jnp.sum(d, axis=0), deltas)
        if cfg.compress != "none":
            summed, ef = C.compress(summed, state.ef, cfg.compress)
        else:
            ef = state.ef
        new_params = jax.tree.map(lambda p, d: p + cfg.gamma * d,
                                  state.params, summed)
        return LocalDPState(new_params, ef, state.rounds + 1)

    return round_fn


def make_round_sharded(loss_fn: Callable, cfg: LocalDPConfig, mesh,
                       data_axis: str = "data"):
    """Production path: shard_map over the data axis; one psum of the
    (optionally compressed) delta per round."""
    from jax.sharding import PartitionSpec as P

    prox = cfg.prox0 * cfg.resolved_sigma()

    def per_shard(params, batch):
        batch = jax.tree.map(lambda b: b[0], batch)

        def damped(p):
            base = loss_fn(p, batch)
            reg = sum(jnp.sum((a - b) ** 2)
                      for a, b in zip(jax.tree.leaves(p),
                                      jax.tree.leaves(params)))
            return base + 0.5 * prox * reg

        def step(p, _):
            g = jax.grad(damped)(p)
            return jax.tree.map(lambda w, gg: w - cfg.inner_lr * gg, p, g), None

        pk, _ = jax.lax.scan(step, params, None, length=cfg.H)
        delta = jax.tree.map(lambda a, b: a - b, pk, params)
        delta = jax.tree.map(
            lambda d: jax.lax.psum(d, data_axis), delta)
        return jax.tree.map(lambda p, d: p + cfg.gamma * d, params, delta)

    def round_fn(params, batches):
        bspec = jax.tree.map(lambda _: P(data_axis), batches)
        pspec = jax.tree.map(lambda _: P(), params)
        return jax.shard_map(per_shard, mesh=mesh,
                             in_specs=(pspec, bspec), out_specs=pspec,
                             check_vma=False)(params, batches)

    return round_fn
