"""Drive the sim engine's compiled trial: ``repro.fl.sim.make_trial_fn``,
vmapped over cases × strategies × seeds in ``grid_arrays``' order, lowered
and compiled once in set-up, then called back to back by the window.

``run()`` and ``grid_arrays`` trace and compile on every call, so a window
of ``run()`` calls would time compiles; this entry times rounds.  A change
to how ``grid_arrays`` batches trials is therefore not seen here.
"""
from __future__ import annotations

import time
import types
from typing import Any, Callable, Dict

import numpy as np


def fl_config(cfg: Dict[str, Any], traffic: Dict[str, Any]):
    """The FL settings the trial reads, from the configuration and the mix."""
    return types.SimpleNamespace(
        num_clients=cfg["num_clients"],
        clients_per_round=cfg["clients_per_round"],
        global_epochs=traffic["rounds_per_call"],
        local_epochs=cfg["local_epochs"], batch_size=cfg["batch_size"],
        lr=cfg["lr"], optimizer=cfg["optimizer"],
        aggregation=traffic["aggregation"], server_lr=cfg["server_lr"])


def grid_fn(cfg: Dict[str, Any], traffic: Dict[str, Any], ds, workload):
    """The trial vmapped as ``grid_arrays`` nests it: (plans, strategy ids,
    seeds, availability) → (case, strategy, seed, round) trajectories."""
    import jax

    from repro.fl.sim import make_trial_fn
    trial = make_trial_fn(
        fl_config(cfg, traffic), ds, aggregation=traffic["aggregation"],
        rounds=traffic["rounds_per_call"],
        eval_n_per_class=cfg["eval_n_per_class"],
        strategies=tuple(traffic["strategies"]), workload=workload)
    # The grid_arrays vmap nest: seeds (per-seed plans), strategies, cases.
    f = jax.vmap(trial, in_axes=(0, None, 0, None))
    f = jax.vmap(f, in_axes=(None, 0, None, None))
    return jax.vmap(f, in_axes=(0, None, None, 0))


class Engine:
    """Set-up builds the program once; ``call`` runs one grid of trials.

    ``plans(i)`` gives call ``i``'s (R, T, N, n) host plans; each is drawn
    when its call is first made and kept for the check.

    The client model is set up by three methods, and only they know it:
    :meth:`workload`, :meth:`dataset` and :meth:`check_model`.  These build
    the paper CNN; an engine for another model subclasses this class in a
    file of its own and overrides them."""

    def __init__(self, cfg: Dict[str, Any], traffic: Dict[str, Any],
                 plans: Callable[[int], np.ndarray]):
        self.cfg, self.traffic = cfg, traffic
        self.strategies = tuple(traffic["strategies"])
        self._draw = plans
        self.plans: Dict[int, np.ndarray] = {}
        self.spans: Dict[str, float] = {}
        self.memory: Dict[str, int] = {}
        self.compiled = None

    def setup(self, warm: int) -> None:
        """Lower and compile for the shapes of call ``warm``'s plans."""
        import jax
        import jax.numpy as jnp

        cfg, traffic = self.cfg, self.traffic
        ds = self.dataset()
        self.check_model(ds)
        f = grid_fn(cfg, traffic, ds, self.workload())
        t, n = self.plan(warm, 0).shape[:2]
        self.avail = jnp.ones((1, t, n), jnp.float32)
        self.sids = jnp.arange(len(self.strategies), dtype=jnp.int32)
        seeds = jnp.zeros((traffic["seeds_per_call"],), jnp.int32)
        t0 = time.perf_counter()
        lowered = jax.jit(f).lower(self._device_plans(warm), self.sids, seeds,
                                   self.avail)
        t1 = time.perf_counter()
        self.compiled = lowered.compile()
        t2 = time.perf_counter()
        self.spans = {"trace_lower_s": t1 - t0, "compile_s": t2 - t1}
        ma = self.compiled.memory_analysis()
        if ma is not None:
            self.memory = {k: int(getattr(ma, k)) for k in (
                "temp_size_in_bytes", "argument_size_in_bytes",
                "output_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(ma, k)}

    def workload(self):
        """The client workload the program trains: a registered name, or a
        ``repro.fl.workloads.Workload``."""
        return self.cfg["workload"]

    def dataset(self):
        """The dataset the workload draws its clients' samples from."""
        from repro.data import ImageDataset
        cfg = self.cfg
        return ImageDataset(num_classes=cfg["num_classes"],
                            image_size=cfg["image_size"],
                            channels=cfg["channels"], noise=cfg["noise"],
                            seed=cfg["template_seed"])

    def check_model(self, ds) -> None:
        """The program builds the model the configuration states, or we stop."""
        from repro.fl.workloads import get_workload
        cfg = self.cfg
        shapes = get_workload(self.workload()).param_shapes(ds)
        flat = (cfg["image_size"] // 4) ** 2 * cfg["conv2"]
        want = {"conv1": (3, 3, cfg["channels"], cfg["conv1"]),
                "conv2": (3, 3, cfg["conv1"], cfg["conv2"]),
                "fc1": (flat, cfg["hidden"]),
                "fc2": (cfg["hidden"], cfg["num_classes"])}
        got = {k: tuple(v["w"].shape) for k, v in shapes.items()}
        if got != want:
            raise RuntimeError(f"the program's CNN is {got}, the configuration "
                               f"states {want}")

    def _host_plans(self, i: int) -> np.ndarray:
        if i not in self.plans:
            self.plans[i] = self._draw(i)
        return self.plans[i]

    def _device_plans(self, i: int):
        import jax.numpy as jnp
        return jnp.asarray(self._host_plans(i)[None], jnp.int32)

    def call(self, i: int, seeds: np.ndarray) -> Dict[str, np.ndarray]:
        """Call ``i``: its plans with trial ``seeds``; returns the (S, R, T)
        trajectories on the host."""
        import jax
        import jax.numpy as jnp
        out = self.compiled(self._device_plans(i), self.sids,
                            jnp.asarray(seeds, jnp.int32), self.avail)
        acc, loss, nsel, msum = jax.device_get(out)
        return {"accuracy": acc[0], "loss": loss[0], "num_selected": nsel[0],
                "mask_sum": msum[0]}

    def plan(self, i: int, r: int) -> np.ndarray:
        """The (T, N, n) plan that seed index ``r`` of call ``i`` ran."""
        return self._host_plans(i)[r]

    def free(self) -> None:
        self.compiled = None
