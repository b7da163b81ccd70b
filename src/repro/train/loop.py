"""Training loop: jit'd step + checkpoint manager + fault supervision.

This is the single-process entry used by examples and tests; the launcher
(:mod:`repro.launch.train`) wraps it with mesh setup.  Under an active
mesh (``distributed.sharding.use_mesh``) the loop places params and
optimizer state by the logical-axis rules and shards each batch over the
data axes, the same distribution the dry-run compiles.
The loop is deliberately restart-pure: all state lives in (params,
opt_state, step), the data pipeline is a pure function of step, and the
checkpoint manager commits atomically — so `run()` after a crash resumes
bit-exactly.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointConfig, CheckpointManager
from repro.distributed.fault import StepFailure, StepWatchdog
from repro.distributed.sharding import current_mesh
from repro.models.registry import ModelApi
from repro.optim.adamw import AdamWConfig, init_adamw
from repro.train.step import init_train_state, make_train_step

log = logging.getLogger("repro.train")


@dataclasses.dataclass
class TrainConfig:
    total_steps: int = 100
    log_every: int = 10
    seed: int = 0
    checkpoint: Optional[CheckpointConfig] = None
    fail_on_nan: bool = True


def _shard_train_state(api: ModelApi, opt_cfg: AdamWConfig, mesh,
                       params, opt_state):
    """Place (params, opt_state) on ``mesh`` by the sharding rules and jit
    the step with matching in/out shardings.  Returns (params, opt_state,
    step_fn, place_batch)."""
    from repro.launch import shardings as shlib

    boxed = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    _, psh = shlib.params_shardings(boxed, mesh)
    osh = shlib.opt_shardings(
        jax.eval_shape(lambda p: init_adamw(p, opt_cfg), params), psh, mesh)
    step_fn = jax.jit(make_train_step(api, opt_cfg),
                      in_shardings=(psh, osh, None),
                      out_shardings=(psh, osh, None), donate_argnums=(0, 1))

    def place_batch(batch):
        bsh = shlib.batch_shardings(batch, mesh)
        return {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}

    return (jax.device_put(params, psh), jax.device_put(opt_state, osh),
            step_fn, place_batch)


def train(api: ModelApi, opt_cfg: AdamWConfig, train_cfg: TrainConfig,
          batch_fn: Callable[[int], Dict[str, np.ndarray]],
          *, hooks: Optional[list] = None) -> dict:
    """Run the loop; returns {final_params, opt_state, history}."""
    key = jax.random.PRNGKey(train_cfg.seed)
    params, opt_state, _axes = init_train_state(api, opt_cfg, key)

    mgr = (CheckpointManager(train_cfg.checkpoint)
           if train_cfg.checkpoint else None)
    start_step = 0
    if mgr is not None and mgr.latest_step() is not None:
        (params, opt_state), start_step = mgr.restore((params, opt_state))
        log.info("resumed from step %d", start_step)

    place_batch = None
    mesh = current_mesh()
    if mesh is None:
        step_fn = jax.jit(make_train_step(api, opt_cfg),
                          donate_argnums=(0, 1))
    else:
        params, opt_state, step_fn, place_batch = _shard_train_state(
            api, opt_cfg, mesh, params, opt_state)
    watchdog = StepWatchdog()
    history = []

    for step in range(start_step, train_cfg.total_steps):
        t0 = time.perf_counter()
        batch = {k: jnp.asarray(v) for k, v in batch_fn(step).items()}
        if place_batch is not None:
            batch = place_batch(batch)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        dt = time.perf_counter() - t0

        if train_cfg.fail_on_nan and not np.isfinite(loss):
            raise StepFailure(f"non-finite loss {loss} at step {step}")
        if watchdog.observe(step, dt):
            log.warning("straggler step %d: %.3fs (trend %.3fs)", step, dt,
                        watchdog._mean)

        history.append({"step": step, "loss": loss, "seconds": dt})
        if step % train_cfg.log_every == 0:
            log.info("step %d loss %.4f (%.3fs)", step, loss, dt)
        if hooks:
            for h in hooks:
                h(step, params, metrics)
        if mgr is not None:
            mgr.maybe_save(step + 1, (params, opt_state))

    if mgr is not None:
        mgr.save(train_cfg.total_steps, (params, opt_state))
        mgr.wait()
    return {"params": params, "opt_state": opt_state, "history": history}
