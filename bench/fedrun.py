"""Drives the program for one cell: set-up as ``launch/train.py`` builds
its server, the first rounds whose results the reference checks, and the
timed window of whole federated rounds."""
from __future__ import annotations

import contextlib
import gc
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import generate as G
from reference import RoundRecord
from spec import Cell, derive_seeds

WINDOW_SPAN, ROUND_SPAN = "bench.window", "bench.round"


class CompileCounter:
    """Counts JAX traces and backend compiles through jax.monitoring."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        self.counts = {"traces": 0, "compiles": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)


class Session:
    """One cell's server, built from ``seed``.  ``spans`` collects the
    harness's host-clock spans (seconds) by name."""

    def __init__(self, cell: Cell, seed: int):
        self.cell, self.seed = cell, seed
        self.seeds = derive_seeds(seed)
        self.tr = cell.traffic
        self.spans: Dict[str, float] = {}
        self.server = None

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        yield
        self.spans[name] = time.perf_counter() - t0

    # -- set-up --------------------------------------------------------------
    def model_config(self):
        from repro.configs import get_config
        return get_config(self.cell.config["arch"]).replace(
            **self.cell.config.get("program", {}))

    def task(self) -> G.Task:
        t = self.tr["task"]
        return G.Task(vocab=t.get("vocab") or self.cell.published["vocab_size"],
                      seq_len=self.tr["seq_len"], n_classes=t["n_classes"],
                      topic_tokens=t["topic_tokens"], noise=t["noise"],
                      seed=self.seeds["data"])

    def client_data(self):
        """Each client's rows, in the order the client consumes them."""
        task, tr = self.task(), self.tr
        data = G.sample_dataset(task, tr["examples"], seed=self.seeds["data"])
        p = tr["partition"]
        parts = G.mixed_partition(data["label"], tr["clients"],
                                  p["dirichlet_alpha"], p["dirichlet_share"],
                                  self.seeds["partition"])
        return [{k: v[ix] for k, v in data.items()} for ix in parts]

    def build(self):
        from repro.configs.base import FLConfig
        from repro.core import Client, FederatedZO, sensitivity_mask
        from repro.data.synthetic import TaskSpec, make_task_fns
        from repro.models import Model

        cfg, tr, fam = self.model_config(), self.tr, self.cell.family
        dtype = self.cell.config["dtype"]
        bad = fam.program_mismatches(cfg, self.cell.published, dtype)
        if bad:
            raise SystemExit(f"{self.cell.config_name}: " + "; ".join(bad))
        model = Model(cfg)
        with self.span("weights"):
            weights = fam.make_weights(self.cell.published,
                                       self.seeds["weights"], dtype)
            jax.block_until_ready(weights)
        want = jax.tree.map(lambda a: (a.shape, a.dtype),
                            model.abstract_params())
        have = jax.tree.map(lambda a: (a.shape, a.dtype), weights)
        if want != have:
            raise SystemExit(f"the program's parameter layout for "
                             f"{cfg.name} differs from the benchmark's")
        task = self.task()
        pre = G.pretrain_batches(
            task, tr["pretrain"]["batches"],
            tr["pretrain"]["tokens_per_batch"] // tr["seq_len"],
            seed=self.seeds["pretrain"])
        self.pretrain_tokens = [b["tokens"] for b in pre]
        with self.span("mask_calibration"):
            space = sensitivity_mask(lambda p, b: model.loss(p, b), weights,
                                     pre, tr["density"])
            jax.block_until_ready(space.idx_tree)
        self.client_rows = self.client_data()
        clients = [Client(k, rows, tr["batch_size"])
                   for k, rows in enumerate(self.client_rows)]
        loss, _, _ = make_task_fns(model, TaskSpec(
            vocab=task.vocab, n_classes=task.n_classes,
            seq_len=tr["seq_len"]))
        fl = FLConfig(n_clients=tr["clients"], local_steps=tr["local_steps"],
                      lr=tr["lr"], eps=tr["eps"], density=tr["density"],
                      seed=self.seeds["fl"], batch_size=tr["batch_size"],
                      zo_backend=tr["zo_backend"], quantize=tr["uplink"])
        self.server = FederatedZO(loss, weights, space, fl, clients)
        self.idx = [np.asarray(i) for i in jax.tree.leaves(space.idx_tree)]
        self.cfg = cfg

    # -- the rounds the reference follows ------------------------------------
    def _mask_values(self) -> np.ndarray:
        idx = jax.tree.leaves(self.server.space.idx_tree)
        vals = [l.reshape(-1)[i].astype(jnp.float32)
                for l, i in zip(jax.tree.leaves(self.server.params), idx)]
        return np.asarray(jnp.concatenate(vals))

    def _round(self) -> np.ndarray:
        gs = self.server.run_round()
        jax.block_until_ready(self.server.params)
        return np.stack([np.asarray(gs[c.cid], np.float32)
                         for c in self.server.clients])

    def first_rounds(self) -> RoundRecord:
        """The first rounds, through the window's own call: the first of
        them loads every program the window runs."""
        p, gs = [self._mask_values()], []
        for r in range(self.tr["check"]["rounds"]):
            with self.span("round_%d" % r):
                gs.append(self._round())
            if r == 0:
                p.append(self._mask_values())
        p.append(self._mask_values())
        return RoundRecord(
            idx=self.idx, gs=gs, p=p,
            client_tokens=[r["tokens"] for r in self.client_rows],
            client_labels=[r["label"] for r in self.client_rows],
            pretrain_tokens=self.pretrain_tokens)

    # -- the window ----------------------------------------------------------
    def window(self, seconds: float, min_rounds: int = 1,
               traced: bool = False) -> dict:
        """Whole rounds until ``seconds`` have passed; each round ends in
        ``block_until_ready`` of the server's parameters."""
        ann = (jax.profiler.TraceAnnotation if traced
               else lambda _: contextlib.nullcontext())
        round_s: List[float] = []
        failed = 0
        start = time.perf_counter()
        with ann(WINDOW_SPAN):
            while True:
                t0 = time.perf_counter()
                with ann(ROUND_SPAN):
                    gs = self.server.run_round()
                    jax.block_until_ready(self.server.params)
                round_s.append(time.perf_counter() - t0)
                failed += int(not all(np.all(np.isfinite(np.asarray(g)))
                                      for g in gs.values()))
                if (time.perf_counter() - start >= seconds
                        and len(round_s) >= min_rounds):
                    break
        tr = self.tr
        steps = tr["clients"] * tr["local_steps"]
        return {"round_s": round_s, "failed": failed,
                "steps_per_round": steps,
                "tokens_per_round": steps * tr["batch_size"] * tr["seq_len"]}

    def routes(self) -> dict:
        from repro.models.layers import resolve_attn_backend
        return {"zo_routes": {f"T={k[0]},clients={k[1]}": v
                              for k, v in self.server.zo_routes.items()},
                "attn_route": resolve_attn_backend(
                    "auto", self.cfg, None, S=self.tr["seq_len"])}

    def close(self):
        """Free the program's state on the device."""
        self.server = None
        gc.collect()


def forward_flops_per_step(cell: Cell) -> float:
    """Model FLOPs one ZO step requires: two forwards of the batch."""
    tr = cell.traffic
    return 2 * tr["batch_size"] * cell.family.forward_flops(
        cell.published, tr["seq_len"])
