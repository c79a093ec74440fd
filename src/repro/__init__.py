"""RANL reproduction: adaptive pruning-based Newton for distributed
learning.

The supported engine surface is ``repro.run`` / ``repro.lower`` with a
:class:`repro.RanlOptions` record — see ``repro.api``.  Subpackages
(``repro.core``, ``repro.hetero``, ``repro.kernels``, ``repro.launch``,
...) import as before.  ``repro.obs`` is the observability layer:
``repro.run(..., journal=path)`` leaves a structured JSONL run journal,
``repro.obs.tracing()`` activates span tracing, and
``python -m repro.obs.report`` renders/diffs journals.
"""

from . import obs  # noqa: F401
from .api import ENGINES, lower, run, trace  # noqa: F401
from .core.options import (  # noqa: F401
    QuorumSpec,
    RanlOptions,
)
from .core.ranl import RanlResult  # noqa: F401
