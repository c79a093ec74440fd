"""Convex face: distributed logistic regression through ``repro.run``.

Closed loop of whole jobs: each job is one ``repro.run(engine=...)`` call
with a fresh key, timed from the call until its results are ready, init
phase and whatever the program compiles per call included.  The data
(X on the device, labels, x*) are made once in set-up from the seed.

Once the window has closed, a sample of its jobs drawn from the seed is
run again by the plain reference on the same data and keys, and their
iterates, losses and uplink counts are compared.
"""

from __future__ import annotations

import gc
import json
import os
import random
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_limits(workload: str):
    path = os.path.join(ROOT, "bench", "limits", workload + ".json")
    with open(path) as f:
        return json.load(f)["limits"]


def gaps(jobs, refs) -> dict:
    """Worst over the sampled jobs and their rounds: the gap between a
    checked iterate and the reference's answer from the same previous
    iterate, relative to the reference's step; the relative gap of the
    loss at each checked iterate; and the number of rounds whose uplink
    count differs (masks are exact)."""
    it, lo, cm = 0.0, 0.0, 0
    for job, ref in zip(jobs, refs):
        xs, rxs = job["xs"], ref["xs"]
        step = np.linalg.norm(rxs[1:] - xs[:-1], axis=1)
        err = np.linalg.norm(xs[1:] - rxs[1:], axis=1) / np.maximum(
            step, 1e-30)
        it = max(it, float(err.max()))
        lo = max(lo, float(np.max(np.abs(job["losses"] - ref["losses"])
                                  / np.abs(ref["losses"]))))
        cm += int(np.sum(job["comm"] != ref["comm"]))
    return {"step_gap": it, "loss_gap": lo, "comm_mismatch": float(cm)}


class Face:
    def __init__(self, config, traffic, devices, seed, workload=None):
        self.config, self.traffic = config, traffic
        self.devices, self.seed = devices, seed
        self.workload = workload
        self.p = config["problem"]
        self.k_data = gen.stream(seed, "data")
        self.k_jobs = gen.stream(seed, "jobs")
        # hooks for the fault tests: None leaves the program as it is
        self.result_hook = None

    def options(self):
        import repro
        from repro.core.masks import PolicyConfig
        a = self.config["algorithm"]
        return repro.RanlOptions(
            num_rounds=self.traffic["num_rounds"],
            num_regions=a["num_regions"],
            policy=PolicyConfig(name=a["policy"], keep_prob=a["keep_prob"],
                                heterogeneous=a["heterogeneous"]),
            mu=self.p["mu"], curvature=a["curvature"], lr=a["lr"],
            projection=a["projection"])

    def make_data(self):
        p = self.p
        return gen.logistic_data(
            self.k_data, workers=p["num_workers"], rows=p["rows_per_worker"],
            dim=p["dim"], shift=p["heterogeneity"],
            logit_scale=p["logit_scale"])

    def setup(self, split):
        from repro.core.convex import Logistic
        t = time.perf_counter()
        X, y = self.make_data()
        x_star = gen.logistic_optimum(X, y, self.p["lam"],
                                      iters=self.p["newton_iters"])
        jax.block_until_ready((X, y, x_star))
        # rows have unit length, so each logistic term's Hessian is at
        # most x x^T / 4 and L_g <= 1/4 + lam
        self.problem = Logistic(X=X, y=y, lam=self.p["lam"], grad_noise=0.0,
                                hess_noise=0.0, x_star=x_star,
                                mu=self.p["mu"], L_g=0.25 + self.p["lam"])
        self.opts = self.options()
        split["data_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self._job(-1)
        split["compile_or_cache_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self._job(-2)
        split["warmup_s"] = time.perf_counter() - t

    def _key(self, j):
        return jax.random.fold_in(self.k_jobs, np.uint32(j % 2 ** 32))

    def _job(self, j):
        import repro
        res = repro.run(self.problem, self._key(j), engine=self.config[
            "engine"], options=self.opts)
        if self.result_hook is not None:
            res = self.result_hook(res)
        jax.block_until_ready((res.xs, res.losses, res.comm_floats))
        return res

    def window(self, seconds):
        self.jobs, walls = [], []
        t0 = time.perf_counter()
        while True:
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.job"):
                res = self._job(len(self.jobs))
            walls.append(time.perf_counter() - t)
            self.jobs.append(res)
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        T = self.traffic["num_rounds"]
        failed = sum(not bool(jnp.all(jnp.isfinite(r.xs)))
                     for r in self.jobs)
        comm = float(sum(float(jnp.sum(r.comm_floats)) for r in self.jobs))
        return {"attempted": len(self.jobs), "failed": failed,
                "jobs": len(self.jobs), "rounds": len(self.jobs) * T,
                "elapsed_s": elapsed, "job_walls_s": walls,
                "comm_floats": comm,
                "rows_per_worker": self.p["rows_per_worker"],
                "num_workers": self.p["num_workers"], "dim": self.p["dim"],
                "round_module": "_scan_rounds"}

    def end_to_end(self, counts):
        return {"convex_rounds_per_s": counts["rounds"]
                / sum(counts["job_walls_s"])}

    def release(self):
        rng = random.Random(self.seed)
        n = min(self.traffic["check_jobs"], len(self.jobs))
        # the sample always holds the last job: the one due at the close
        picks = sorted(set(rng.sample(range(len(self.jobs) - 1), n - 1))
                       | {len(self.jobs) - 1}) if n > 1 else \
            [len(self.jobs) - 1]
        self.checked = [
            {"index": j, "xs": np.asarray(self.jobs[j].xs),
             "losses": np.asarray(self.jobs[j].losses),
             "comm": np.asarray(self.jobs[j].comm_floats)} for j in picks]
        self.jobs = None
        self.problem = None
        gc.collect()

    def reference(self, checked, dtype=jnp.float32, free=False):
        """The reference's answers for each checked job ({"index", "xs"})."""
        from bench.reference import convex_ranl
        X, y = self.make_data()
        a = self.config["algorithm"]
        out = []
        for job in checked:
            xs, losses, comm, ok = convex_ranl.run(
                X, y, self._key(job["index"]), jnp.asarray(job["xs"]),
                num_regions=a["num_regions"], keep_prob=a["keep_prob"],
                lam=self.p["lam"], mu=self.p["mu"], lr=a["lr"], dtype=dtype,
                free=free)
            if not bool(ok):
                raise ValueError("H - mu I is not positive definite: the "
                                 "reference's projection would not be "
                                 "the identity")
            out.append({"index": job["index"], "xs": np.asarray(xs),
                        "losses": np.asarray(losses),
                        "comm": np.asarray(comm)})
        return out

    def control(self):
        """The reference one precision lower, run as whole jobs in the
        program's place, on the first ``check_jobs`` jobs."""
        T, d = self.traffic["num_rounds"], self.p["dim"]
        jobs = [{"index": j, "xs": np.zeros((T + 2, d), np.float32)}
                for j in range(self.traffic["check_jobs"])]
        self.checked = self.reference(jobs, jnp.bfloat16, free=True)

    def verify(self, limits=None):
        limits = limits or load_limits(self.workload)
        refs = self.reference(self.checked)
        got = gaps(self.checked, refs)
        return [{"name": k, "value": v, "limit": limits[k]}
                for k, v in got.items()]
