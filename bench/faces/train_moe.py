"""Train face for an MoE configuration on a ("data", "model") mesh: RANL
deep-net training through ``optim.ranl_llm``.

Composes what ``launch/train.py`` composes under ``--data-shards D
--model-shards M`` (the registered config cut as the configuration file
states, ``build_loss`` with the attention block it derives from the
sequence, ``init_state`` and ``jax.jit(train_step)`` with the mesh, run
inside the ambient mesh the model code reads), with weights and batches
from the benchmark's own generators; the weights are laid out on the mesh
by the program's rules and checked against its layout.  Closed
loop: each step starts when the last one is queued, with at most two in
flight.  The step's routing counters stay on the device through the
window and are read after it.

Set-up drives the step through its first three rounds, as ``train.py``'s
face does; the reference, computed sharded over the cell's chips, checks
their losses, the gradient memory after round one and the parameter
change after round three, and no routed row may be dropped.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import math
import statistics
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen
from bench import moe as moe_layout
from bench.faces.train import (CHECK_STEPS, diff_norms, gaps, leaf_norms,
                               load_limits)

COUNTERS = ("held_rows", "absent_rows", "dropped_rows", "load_max_ratio")
# planted faults: the held experts' offset one model shard off, the
# shared experts left out, the routed weights left unscaled, the expert
# outputs' exchange between model shards left out
FAULTS = ("offset_shift", "no_shared", "unscaled", "no_model_sum")
_PROGRAMS = {}


def program_config(c: dict):
    """The program's ModelConfig for the configuration file's keys."""
    from repro.configs import get_config
    cfg = get_config(c["program_arch"])
    return dataclasses.replace(
        cfg, num_layers=c["num_hidden_layers"], vocab_size=c["vocab_size"],
        d_model=c["hidden_size"], d_ff=c["intermediate_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        num_experts=c["router_experts"], experts_held=c["n_routed_experts"],
        experts_per_token=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"],
        num_shared_experts=c["n_shared_experts"],
        first_dense_layers=c["first_k_dense_replace"],
        router_score=c["scoring_func"],
        routed_scale=c["routed_scaling_factor"],
        tie_embeddings=c["tie_word_embeddings"])


@contextlib.contextmanager
def planted(kind: str | None):
    """Plant fault ``kind`` in the program for the block (None: none)."""
    import repro.models.moe as moe_mod
    patches = {
        "offset_shift": ("held_offset",
                         lambda n_local, shard: (shard + 1) * n_local),
        "no_shared": ("apply_swiglu", lambda p, x: jnp.zeros_like(x)),
        "no_model_sum": ("sum_shares", lambda out: out),
    }
    saved = None
    if kind in patches:
        name, new = patches[kind]
        saved = (name, getattr(moe_mod, name))
        setattr(moe_mod, name, new)
    try:
        yield
    finally:
        if saved is not None:
            setattr(moe_mod, *saved)


class Face:
    def __init__(self, config, traffic, devices, seed, workload=None):
        self.config, self.traffic = config, traffic
        self.devices, self.seed = devices, seed
        self.workload = workload
        self.opt = config["optimizer"]
        self.tokens_per_step = traffic["batch"] * traffic["seq"]
        self.k_data = gen.stream(seed, "data")
        self.k_rng = gen.stream(seed, "rng")
        self.k_weights = gen.stream(seed, "weights")
        shape = (config["mesh"]["data"], config["mesh"]["model"])
        self.mesh = jax.sharding.Mesh(
            np.array(devices[:shape[0] * shape[1]]).reshape(shape),
            ("data", "model"))
        # hook for the fault tests: one of FAULTS, or None
        self.fault = None

    def _batch(self, index):
        return gen.train_batch(self.traffic, self.config["vocab_size"],
                               self.k_data, index)

    def _program(self, cfg, rcfg):
        from repro.launch.train import attn_block, build_loss
        from repro.optim import init_state, train_step
        key = (json.dumps([self.config, self.traffic], sort_keys=True),
               self.fault, self.mesh)
        if key not in _PROGRAMS:
            chunk = attn_block(self.traffic["seq"])
            loss_fn = build_loss(cfg, q_chunk=chunk, kv_chunk=chunk)
            _PROGRAMS[key] = (
                jax.jit(partial(init_state, loss_fn=loss_fn, cfg=rcfg,
                                mesh=self.mesh)),
                jax.jit(partial(train_step, loss_fn=loss_fn, cfg=rcfg,
                                mesh=self.mesh)))
        return _PROGRAMS[key]

    def _weights(self, cfg):
        from repro.launch.shard import params_pspecs, to_shardings
        from repro.models import init_model
        want = jax.eval_shape(partial(init_model, cfg),
                              jax.random.PRNGKey(0))
        shardings = to_shardings(
            params_pspecs(want, model_shards=self.mesh.shape["model"]),
            self.mesh)
        params = moe_layout.weights(self.config, self.k_weights, shardings)
        have = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        if jax.tree.map(lambda a: (a.shape, a.dtype), want) != have:
            raise ValueError("the benchmark's weights do not fit the "
                             f"program's layout: {have} != {want}")
        return params, shardings

    # ---------------------------------------------------------------- set-up
    def setup(self, split):
        from repro.models.sharding import use_mesh
        from repro.optim import RanlLLMConfig

        t = time.perf_counter()
        cfg = program_config(self.config)
        if self.fault == "unscaled":
            cfg = dataclasses.replace(cfg, routed_scale=1.0)
        o = self.opt
        rcfg = RanlLLMConfig(
            num_workers=o["num_workers"], keep_prob=o["keep_prob"],
            heterogeneous=o["heterogeneous"], tau_star=o["tau_star"],
            mu=o["mu"], mu_rel=o["mu_rel"], lr=o["lr"],
            trust_ratio=o["trust_ratio"], protect_glue=True,
            memory_dtype=o["memory_dtype"])
        params, self.shardings = self._weights(cfg)
        batch0 = self._batch(0)
        jax.block_until_ready((params, batch0))
        split["weights_data_s"] = time.perf_counter() - t

        t = time.perf_counter()
        with use_mesh(self.mesh), planted(self.fault):
            init, self.step_fn = self._program(cfg, rcfg)
            state = init(params, batch=batch0, key=self.k_rng)
            counters, losses = [], []
            params, state, m = self.step_fn(params, state, self._batch(1),
                                            self.k_rng)
            losses.append(m["loss"])
            counters.append({k: m[k] for k in COUNTERS})
            mem = leaf_norms(state["memory"])
            jax.block_until_ready((params, state, mem))
            split["compile_or_cache_s"] = time.perf_counter() - t

            t = time.perf_counter()
            for i in range(2, CHECK_STEPS + 1):
                params, state, m = self.step_fn(params, state,
                                                self._batch(i), self.k_rng)
                losses.append(m["loss"])
                counters.append({k: m[k] for k in COUNTERS})
        p0 = moe_layout.weights(self.config, self.k_weights, self.shardings)
        upd = diff_norms(params, p0)
        del p0
        names = [jax.tree_util.keystr(k) for k, _ in
                 jax.tree_util.tree_leaves_with_path(params)]
        self.checked = {
            "losses": [float(x) for x in losses],
            "memory_norms": dict(zip(names, map(float, mem))),
            "update_norms": dict(zip(names, map(float, upd))),
            "dropped_rows": float(sum(c["dropped_rows"] for c in counters))}
        self.params, self.state = params, state
        self.next_index = CHECK_STEPS + 1
        split["warmup_s"] = time.perf_counter() - t

    # ---------------------------------------------------------------- window
    def window(self, seconds):
        from repro.models.sharding import use_mesh
        params, state = self.params, self.state
        losses, counters, prev, n = [], [], None, 0
        # the ambient mesh is part of the step's jit cache key
        with use_mesh(self.mesh):
            t0 = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("bench.batch"):
                    batch = self._batch(self.next_index + n)
                with jax.profiler.TraceAnnotation("bench.step"):
                    params, state, m = self.step_fn(params, state, batch,
                                                    self.k_rng)
                losses.append(m["loss"])
                counters.append({k: m[k] for k in COUNTERS})
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
        read = {k: [float(c[k]) for c in counters] for k in COUNTERS}
        self.checked["dropped_rows"] += sum(read["dropped_rows"])
        return {"attempted": n, "failed": finite.count(False), "steps": n,
                "elapsed_s": elapsed, "tokens": n * self.tokens_per_step,
                "batch_fn": "bigram_batch", "step_fn": "train_step",
                "held_rows": sum(read["held_rows"]),
                "absent_rows": sum(read["absent_rows"]),
                "dropped_rows": sum(read["dropped_rows"]),
                "load_max_ratio": statistics.fmean(read["load_max_ratio"]),
                "model_shards": self.mesh.shape["model"]}

    def end_to_end(self, counts):
        return {"train_tokens_per_s": counts["tokens"] / counts["elapsed_s"]}

    def release(self):
        self.params = self.state = self.step_fn = None
        gc.collect()

    # ------------------------------------------------------------ reference
    def reference(self, dtype=jnp.float32):
        from bench.reference import moonlight
        from repro.launch.train import attn_block
        mesh = jax.sharding.Mesh(np.array(self.devices), ("x",))
        params = moe_layout.weights(self.config, self.k_weights,
                                    moonlight.shardings(self.config, mesh))
        batches = [self._batch(i) for i in range(CHECK_STEPS + 1)]
        return moonlight.run(params, batches, self.k_rng, self.config,
                             self.opt, steps=CHECK_STEPS, dtype=dtype,
                             q_chunk=attn_block(self.traffic["seq"]))

    def control(self):
        """The reference one precision lower, in the program's place."""
        self.checked = dict(self.reference(jnp.bfloat16), dropped_rows=0.0)

    def verify(self, limits=None):
        limits = limits or load_limits(self.workload)
        got = gaps(self.checked, self.reference())
        got["dropped_rows"] = self.checked["dropped_rows"]
        return [{"name": k, "value": v, "limit": limits[k]}
                for k, v in got.items()]

