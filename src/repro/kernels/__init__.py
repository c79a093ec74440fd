"""Pallas TPU kernels for the perf-critical compute layers.

  region_aggregate / ranl_update — the paper's server aggregation
      (Algorithm 1 lines 15–22), fused; ranl_update also folds in the
      projected-Newton parameter update (one HBM pass).
  logistic_grads — every worker's logistic-regression gradient in one
      pass over its design matrix (the convex round loop's worker step).
  flash_attention — causal GQA flash attention with sliding window.
  rwkv_wkv — RWKV-6 recurrence with VMEM-resident state.

Each kernel but logistic_grads has a pure-jnp oracle in ref.py (its
oracle is ``vmap(Logistic.worker_grad)``); ops.py wraps with
interpret-mode defaults for CPU validation.
"""

from . import ref  # noqa: F401
from .logistic_grad import logistic_grads  # noqa: F401
from .ops import (  # noqa: F401
    flash_attention,
    ranl_update,
    region_aggregate,
    rwkv_wkv,
)
