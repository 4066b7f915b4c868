"""FedELMY's sequential chain through the system's front door.

Set-up makes the weights and every client's data on the device from the
seed, builds one `Experiment` (the strategy `fedelmy`, device-resident
`DataPlan` streams, so each client's local phase is one scanned program),
and runs it once through `repro.api.launch`: that first unit compiles every
program, and its first client's visit is what the check compares with the
plain reference. A unit is one `launch` of the whole chain, from the same
weights, on the streams' next rows.

The check, once the window has closed and the program's state is freed,
replays that first visit in the reference (bench/reference/fedelmy.py over
the configuration's reference loss) in f32 and compares:

- loss_gap: the largest relative gap of the S slots' last task losses;
- change_gap: over the leaves, the largest gap between the norms of the
  change that the visit made to the leaf, program against reference, over
  the larger of that leaf's reference norm and the median leaf's. Leaves
  whose first reference gradient is under a thousandth of the median
  leaf's are left out (they move by round-off alone).
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict

import jax
import numpy as np

from bench import harness
from bench.harness import arch_config, seed_key


def stream_seed(seed: int, client: int) -> int:
    return seed * 1000 + client


def hyper(config, traffic) -> Dict[str, Any]:
    return {k: traffic[k] for k in (
        "pool_size", "e_local", "e_warmup", "learning_rate", "weight_decay",
        "alpha", "beta")} | {"pool": config["pool"],
                             "pool_rank": config.get("pool_rank", 8)}


class Driver:
    def __init__(self, config, traffic, seed, devices, limits, seconds):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.limits = limits
        self.ref = harness.load_module("reference", config["reference"])
        self.hp = hyper(config, traffic)
        self.samples_per_unit = traffic["batch"] * (
            traffic["e_warmup"] + traffic["clients"]
            * traffic["pool_size"] * traffic["e_local"])

    # -- the program -------------------------------------------------------

    def _weights(self, shapes):
        return self.ref.init_params(shapes, jax.random.fold_in(
            seed_key(self.seed), 1))

    def _data(self):
        return self.ref.make_data(self.config, self.traffic, jax.random.fold_in(
            seed_key(self.seed), 2))

    def setup(self) -> None:
        from repro.api import Experiment
        from repro.api.engine import Callbacks
        from repro.configs import FedConfig
        from repro.data import DataPlan
        from repro.models import build_model
        t, hp = self.traffic, self.hp
        t0 = time.perf_counter()
        self.model = build_model(arch_config(self.config))
        self.shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        params = self._weights(self.shapes)
        plans = [DataPlan(arrays, t["batch"], seed=stream_seed(self.seed, i))
                 for i, arrays in enumerate(self._data())]
        jax.block_until_ready((params, plans[-1].arrays))
        t1 = time.perf_counter()
        fed = FedConfig(n_clients=t["clients"], pool_size=hp["pool_size"],
                        e_local=hp["e_local"], e_warmup=hp["e_warmup"],
                        alpha=hp["alpha"], beta=hp["beta"],
                        learning_rate=hp["learning_rate"],
                        weight_decay=hp["weight_decay"], optimizer="adam",
                        pool_backend=hp["pool"], pool_rank=hp["pool_rank"],
                        seed=0)
        self.first = {}
        self.exp = Experiment(
            model=self.model, client_iters=plans, fed=fed, strategy="fedelmy",
            init_params=params,
            callbacks=Callbacks(on_client_end=self._first_client))
        res = self._launch()
        harness.say(f"setup: weights and data {t1 - t0:.3f} s, first launch "
                    f"{time.perf_counter() - t1:.3f} s")
        self.first["slot_losses"] = [m.task_loss
                                     for m in res.clients[0].models]
        del res
        gc.collect()

    def _first_client(self, rec, params) -> None:
        if rec.rank == 0 and "handoff" not in self.first:
            self.first["handoff"] = params

    def _launch(self):
        from repro.api import launch
        with harness.span("launch"):
            res = launch(self.exp)
            jax.block_until_ready(res.params)
        return res

    def unit(self) -> int:
        self._launch()
        return self.samples_per_unit

    def release(self) -> None:
        handoff = self.first.pop("handoff")
        self.first["handoff_leaves"] = [np.asarray(x, np.float32)
                                        for x in jax.tree.leaves(handoff)]
        del handoff
        self.exp = None
        gc.collect()

    # -- the check ---------------------------------------------------------

    def reference_visit(self, control: str = None) -> Dict[str, Any]:
        """Client 0's first visit (warmup included) in the reference; with
        `control`, the reference computed with a fault of its own:
        "half_batch" takes each step's loss over the first half of its
        batch (of its tokens, where the batch is one sequence); any other
        name is the precision of every product (`products.product`)."""
        params = self._weights(self.shapes)
        data = self._data()[0]
        rows = self.ref_rows(len(next(iter(data.values()))))
        half = control == "half_batch"
        mode = None if half else control

        def loss(p, batch):
            if half:       # a batch of one sequence halves its tokens
                batch = {k: v[:v.shape[0] // 2] if v.shape[0] > 1
                         else v[:, :v.shape[1] // 2]
                         for k, v in batch.items()}
            return self.ref.loss(self.config, p, batch, mode)

        out = self.fedelmy.visit(loss, params, data, rows, self.hp,
                                 warmup=True)
        out["init_leaves"] = [np.asarray(x, np.float32)
                              for x in jax.tree.leaves(params)]
        return out

    @property
    def fedelmy(self):
        return harness.load_module("reference", "fedelmy")

    def ref_rows(self, n_rows: int) -> np.ndarray:
        t, hp = self.traffic, self.hp
        n_steps = hp["e_warmup"] + hp["pool_size"] * hp["e_local"]
        return self.fedelmy.schedule(stream_seed(self.seed, 0), n_rows,
                                     t["batch"], n_steps)

    def compare(self, got_losses, got_leaves, ref) -> Dict[str, float]:
        ref_losses = np.asarray(ref["slot_losses"])
        loss_gap = float(np.max(np.abs(np.asarray(got_losses) - ref_losses)
                                / np.abs(ref_losses)))
        init = ref["init_leaves"]
        ref_change = [float(np.linalg.norm((np.asarray(h, np.float32) - i)
                                           .ravel()))
                      for h, i in zip(jax.tree.leaves(ref["handoff"]), init)]
        got_change = [float(np.linalg.norm((g - i).ravel()))
                      for g, i in zip(got_leaves, init)]
        grads = np.asarray(jax.tree.leaves(ref["first_grad_norms"]),
                           np.float64)
        keep = grads >= 1e-3 * np.median(grads)
        med = float(np.median(np.asarray(ref_change)[keep]))
        gaps = [abs(g - r) / max(r, med)
                 for g, r, k in zip(got_change, ref_change, keep) if k]
        slot_gaps = np.abs(np.asarray(got_losses) - ref_losses) / np.abs(
            ref_losses)
        self.detail = {"slot_gaps": slot_gaps.tolist(),
                       "leaf_gaps_sorted": sorted(gaps)}
        return {"loss_gap": loss_gap,
                "loss_gap_median": float(np.median(slot_gaps)),
                "change_gap": float(max(gaps)),
                "change_gap_median": float(np.median(gaps))}

    def readings(self, control: str = None) -> Dict[str, float]:
        """The numbers the check compares: the program's first visit
        against the reference's, or with `control` the control's."""
        if not hasattr(self, "_ref"):
            self._ref = self.reference_visit()
        if control is None:
            return self.compare(self.first["slot_losses"],
                                self.first["handoff_leaves"], self._ref)
        low = self.reference_visit(control)
        return self.compare(low["slot_losses"],
                            [np.asarray(x, np.float32)
                             for x in jax.tree.leaves(low["handoff"])],
                            self._ref)
