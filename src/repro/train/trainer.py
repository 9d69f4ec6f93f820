"""Training loop with checkpoint/restart fault tolerance.

The loop is crash-equivalent: state = (params, opt_state) is checkpointed
every ``ckpt_every`` steps (async), the data stream is a pure function of the
step index, and any step-time failure (injected or real) triggers restore of
the newest verified checkpoint and replay. ``FailureInjector`` simulates node
failures at chosen steps to test the path (tests/test_trainer.py).
"""
from __future__ import annotations

import dataclasses
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs.base import ArchConfig, ShardingPlan
from repro.data.pipeline import DataConfig, DataLoader
from repro.models import param_defs
from repro.models.layers import ParamDef, init_params
from repro.train import checkpoint as ckpt
from repro.train.optimizer import OptConfig, init_opt_state, opt_state_defs

IS_DEF = lambda t: isinstance(t, ParamDef)  # noqa: E731


class FailureInjector:
    """Raises once at each configured step — a stand-in for a node loss."""

    def __init__(self, fail_at: tuple[int, ...] = ()):
        self.pending = set(fail_at)
        self.fired: list[int] = []

    def maybe_fail(self, step: int):
        if step in self.pending:
            self.pending.discard(step)
            self.fired.append(step)
            raise RuntimeError(f"injected node failure at step {step}")


@dataclasses.dataclass
class TrainerConfig:
    num_steps: int = 200
    ckpt_every: int = 50
    ckpt_dir: str = "checkpoints"
    keep: int = 3
    log_every: int = 10
    seed: int = 0
    async_ckpt: bool = True


class Trainer:
    def __init__(self, arch: ArchConfig, mesh, plan: ShardingPlan,
                 data_cfg: DataConfig, opt_cfg: OptConfig | None = None,
                 tcfg: TrainerConfig | None = None,
                 injector: FailureInjector | None = None):
        self.arch, self.mesh, self.plan = arch, mesh, plan
        self.data_cfg = data_cfg
        self.opt_cfg = opt_cfg or OptConfig()
        self.tcfg = tcfg or TrainerConfig()
        self.injector = injector
        self.pdefs = param_defs(arch)
        self.param_specs = jax.tree.map(
            lambda d: plan.spec(d.dims, d.shape), self.pdefs, is_leaf=IS_DEF)
        odefs = opt_state_defs(self.pdefs, self.opt_cfg)
        self.opt_specs = jax.tree.map(
            lambda d: plan.spec(d.dims, d.shape), odefs, is_leaf=IS_DEF)
        # local import: launch.steps imports repro.train.optimizer, so a
        # module-level import here would be circular via repro.train.__init__
        from repro.launch.steps import make_train_step
        self._step_fn = jax.jit(
            make_train_step(arch, plan, self.opt_cfg),
            donate_argnums=(0, 1))
        self.history: list[dict] = []
        self.restarts = 0

    # -- state ------------------------------------------------------------
    def init_state(self):
        with jax.set_mesh(self.mesh):
            params = init_params_sharded(self.pdefs, self.mesh,
                                         self.param_specs, self.tcfg.seed)
            opt_state = init_opt_state(params, self.opt_cfg)
        return params, opt_state

    def save(self, step, params, opt_state):
        tree = {"params": params, "opt": opt_state}
        if self.tcfg.async_ckpt:
            self._ckpt_thread = ckpt.save_async(
                self.tcfg.ckpt_dir, step, tree, keep=self.tcfg.keep)
        else:
            ckpt.save(self.tcfg.ckpt_dir, step, tree, keep=self.tcfg.keep)

    def restore(self):
        specs = {"params": self.param_specs, "opt": self.opt_specs}
        step, tree = ckpt.restore(self.tcfg.ckpt_dir, mesh=self.mesh,
                                  specs=specs)
        if step is None:
            return 0, *self.init_state()
        return step, tree["params"], tree["opt"]

    # -- loop ---------------------------------------------------------------
    def run(self, num_steps: int | None = None):
        num_steps = num_steps or self.tcfg.num_steps
        step, params, opt_state = self.restore()
        loader = DataLoader(self.data_cfg, self.mesh, self.plan, self.arch,
                            start_step=step)
        t0 = time.time()
        while step < num_steps:
            try:
                if self.injector:
                    self.injector.maybe_fail(step)
                batch = next(loader)
                with jax.set_mesh(self.mesh):
                    params, opt_state, metrics = self._step_fn(
                        params, opt_state, batch)
                step += 1
                if step % self.tcfg.log_every == 0 or step == num_steps:
                    m = {k: float(v) for k, v in metrics.items()}
                    m.update(step=step, wall=round(time.time() - t0, 2))
                    self.history.append(m)
                if step % self.tcfg.ckpt_every == 0 or step == num_steps:
                    self.save(step, params, opt_state)
            except RuntimeError as e:
                if "injected node failure" not in str(e):
                    raise
                # node loss: restore newest verified ckpt, replay stream
                self.restarts += 1
                step, params, opt_state = self.restore()
                loader = DataLoader(self.data_cfg, self.mesh, self.plan,
                                    self.arch, start_step=step)
        if getattr(self, "_ckpt_thread", None) is not None:
            self._ckpt_thread.join()
        return params, opt_state


def init_params_sharded(pdefs, mesh, specs, seed: int):
    """Initialize parameters directly with their target shardings."""
    from jax.sharding import NamedSharding
    flat_defs: dict[str, ParamDef] = {}

    def rec(prefix, node):
        if isinstance(node, dict):
            for k, v in node.items():
                rec(f"{prefix}/{k}", v)
        else:
            flat_defs[prefix] = node

    rec("", pdefs)
    flat_specs: dict = {}
    rec2 = lambda prefix, node: (  # noqa: E731
        [rec2(f"{prefix}/{k}", v) for k, v in node.items()]
        if isinstance(node, dict) else flat_specs.__setitem__(prefix, node))
    rec2("", specs)

    out: dict = {}
    key = jax.random.key(seed)
    for i, (name, d) in enumerate(sorted(flat_defs.items())):
        k = jax.random.fold_in(key, i)
        arr = d.initializer(k)
        arr = jax.device_put(arr, NamedSharding(mesh, flat_specs[name]))
        node = out
        parts = name.strip("/").split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = arr
    return out
