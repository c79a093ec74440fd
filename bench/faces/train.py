"""Train face: RANL deep-net training through ``optim.ranl_llm``.

Composes what ``launch/train.py`` composes (the registered config cut as
the configuration file states, ``build_loss``, ``init_state``,
``jax.jit(train_step)``), with weights and batches from the benchmark's
own generators.  Closed loop: each step starts when the last one is
queued, with at most two in flight.

Set-up drives the step through its first three rounds with the window's
own call and feed; their losses, the gradient memory after round one and
the parameter change after round three are what the reference checks.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import statistics
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp

from bench import gen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHECK_STEPS = 3
# jitted (init, step) per configuration, optimizer, traffic and loss
# wrapper: several seeds in one process trace and compile once
_PROGRAMS = {}


def program_config(model: dict):
    """The program's ModelConfig for the configuration file's keys."""
    from repro.configs import get_config
    cfg = get_config(model["program_arch"])
    return dataclasses.replace(
        cfg, num_layers=model["num_hidden_layers"],
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        d_ff=model["intermediate_size"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"], rope_theta=model["rope_theta"],
        tie_embeddings=model["tie_word_embeddings"])


@jax.jit
def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in jax.tree_util.tree_leaves(tree)]


@jax.jit
def diff_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
            for x, y in zip(jax.tree_util.tree_leaves(a),
                            jax.tree_util.tree_leaves(b))]


def load_limits(workload: str):
    path = os.path.join(ROOT, "bench", "limits", workload + ".json")
    with open(path) as f:
        return json.load(f)["limits"]


def gaps(prog: dict, ref: dict) -> dict:
    """The compared numbers: the loss of each checked step (largest
    relative gap), and by the worst leaf the gap between the program's and
    the reference's norms of the gradient memory after round one and of
    the parameter change after round three, each over the larger of that
    leaf's reference norm and the median leaf's.  Leaves whose init
    gradient is under a thousandth of the median leaf's are left out of
    the change (round-off alone moves them)."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                   ref["losses"]))

    def worst(key, names):
        med = statistics.median(ref[key][n] for n in names)
        return max(abs(prog[key][n] - ref[key][n]) / max(ref[key][n], med)
                   for n in names)

    names = list(ref["grad_norms"])
    gmed = statistics.median(ref["grad_norms"].values())
    moved = [n for n in names if ref["grad_norms"][n] >= 1e-3 * gmed]
    return {"loss_gap": loss, "grad_gap": worst("memory_norms", names),
            "update_gap": worst("update_norms", moved)}


class Face:
    def __init__(self, config, traffic, devices, seed, workload=None):
        self.config, self.traffic = config, traffic
        self.devices, self.seed = devices, seed
        self.workload = workload
        self.model = config["model"]
        self.opt = config["optimizer"]
        self.tokens_per_step = traffic["batch"] * traffic["seq"]
        self.k_data = gen.stream(seed, "data")
        self.k_rng = gen.stream(seed, "rng")
        self.k_weights = gen.stream(seed, "weights")
        # hooks for the fault tests: None leaves the program as it is
        self.loss_wrapper = None

    # ---------------------------------------------------------------- set-up
    def _batch(self, index):
        return gen.train_batch(self.traffic, self.model["vocab_size"],
                               self.k_data, index)

    def setup(self, split):
        from repro.launch.train import build_loss
        from repro.models import init_model
        from repro.optim import RanlLLMConfig, init_state, train_step

        t = time.perf_counter()
        cfg = program_config(self.model)
        seq = self.traffic["seq"]
        loss_fn = build_loss(cfg, q_chunk=min(1024, seq),
                             kv_chunk=min(1024, seq))
        if self.loss_wrapper is not None:
            loss_fn = self.loss_wrapper(loss_fn)
        o = self.opt
        rcfg = RanlLLMConfig(
            num_workers=o["num_workers"], keep_prob=o["keep_prob"],
            heterogeneous=o["heterogeneous"], tau_star=o["tau_star"],
            mu=o["mu"], mu_rel=o["mu_rel"], lr=o["lr"],
            trust_ratio=o["trust_ratio"], protect_glue=True,
            memory_dtype=o["memory_dtype"])
        params = gen.transformer_weights(self.model, self.k_weights)
        want = jax.tree.map(lambda a: (a.shape, a.dtype),
                            jax.eval_shape(partial(init_model, cfg),
                                           jax.random.PRNGKey(0)))
        have = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        if want != have:
            raise ValueError("the benchmark's weights do not fit the "
                             f"program's layout: {have} != {want}")
        batch0 = self._batch(0)
        jax.block_until_ready((params, batch0))
        split["weights_data_s"] = time.perf_counter() - t

        t = time.perf_counter()
        key = (json.dumps([self.model, o, self.traffic], sort_keys=True),
               self.loss_wrapper)
        if key not in _PROGRAMS:
            _PROGRAMS[key] = (
                jax.jit(partial(init_state, loss_fn=loss_fn, cfg=rcfg)),
                jax.jit(partial(train_step, loss_fn=loss_fn, cfg=rcfg)))
        init, self.step_fn = _PROGRAMS[key]
        state = init(params, batch=batch0, key=self.k_rng)
        losses = []
        params, state, m = self.step_fn(params, state, self._batch(1),
                                        self.k_rng)
        losses.append(m["loss"])
        mem = leaf_norms(state["memory"])
        jax.block_until_ready((params, state, mem))
        split["compile_or_cache_s"] = time.perf_counter() - t

        t = time.perf_counter()
        for i in range(2, CHECK_STEPS + 1):
            params, state, m = self.step_fn(params, state, self._batch(i),
                                            self.k_rng)
            losses.append(m["loss"])
        p0 = gen.transformer_weights(self.model, self.k_weights)
        upd = diff_norms(params, p0)
        del p0
        names = [jax.tree_util.keystr(k) for k, _ in
                 jax.tree_util.tree_leaves_with_path(params)]
        self.checked = {
            "losses": [float(x) for x in losses],
            "memory_norms": dict(zip(names, map(float, mem))),
            "update_norms": dict(zip(names, map(float, upd)))}
        self.params, self.state = params, state
        self.next_index = CHECK_STEPS + 1
        split["warmup_s"] = time.perf_counter() - t

    # ---------------------------------------------------------------- window
    def window(self, seconds):
        params, state = self.params, self.state
        losses, prev, n = [], None, 0
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.batch"):
                batch = self._batch(self.next_index + n)
            with jax.profiler.TraceAnnotation("bench.step"):
                params, state, m = self.step_fn(params, state, batch,
                                                self.k_rng)
            losses.append(m["loss"])
            if prev is not None:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    prev.block_until_ready()
            prev = m["loss"]
            n += 1
            if time.perf_counter() - t0 >= seconds:
                break
        prev.block_until_ready()
        elapsed = time.perf_counter() - t0
        self.params, self.state = params, state
        finite = [math.isfinite(float(x)) for x in losses]
        return {"attempted": n, "failed": finite.count(False), "steps": n,
                "elapsed_s": elapsed, "tokens": n * self.tokens_per_step,
                "batch_fn": "bigram_batch", "step_fn": "train_step"}

    def end_to_end(self, counts):
        return {"train_tokens_per_s": counts["tokens"] / counts["elapsed_s"]}

    def release(self):
        self.params = self.state = self.step_fn = None
        gc.collect()

    # ------------------------------------------------------------ reference
    def reference(self, dtype=jnp.float32):
        from bench.reference import ranl_llm
        params = gen.transformer_weights(self.model, self.k_weights)
        batches = [self._batch(i) for i in range(CHECK_STEPS + 1)]
        return ranl_llm.run(params, batches, self.k_rng, self.model,
                            self.opt, steps=CHECK_STEPS, dtype=dtype)

    def control(self):
        """The reference one precision lower, in the program's place."""
        self.checked = self.reference(jnp.bfloat16)

    def verify(self, limits=None):
        limits = limits or load_limits(self.workload)
        ref = self.reference()
        got = gaps(self.checked, ref)
        gmed = statistics.median(ref["grad_norms"].values())
        left_out = [n for n, v in ref["grad_norms"].items()
                    if v < 1e-3 * gmed]
        print(f"leaves left out of update_gap: {left_out}", file=sys.stderr)
        return [{"name": k, "value": v, "limit": limits[k]}
                for k, v in got.items()]
