"""presto_tpu — a TPU-native distributed SQL query engine.

A from-scratch rebuild of the capabilities of Presto (reference:
``johnnypav/presto``; see SURVEY.md for the structural analysis) designed
TPU-first rather than ported:

- host-side Python control plane: parser -> analyzer -> logical planner ->
  rule/cost optimizer -> fragmenter -> scheduler (reference layers L0-L3,
  SURVEY.md §1)
- device-side data plane: whole plan fragments compile to ``jax.jit`` /
  ``shard_map`` programs over fixed-shape, dictionary-encoded columnar pages
  (reference layers L4-L6 collapsed into XLA)
- shuffle = ``all_to_all`` over ICI inside a slice; token-acked paged
  exchange over DCN between hosts (reference: HTTP paged exchange,
  SURVEY.md §2.5)

x64 is enabled globally: SQL BIGINT/DECIMAL semantics require 64-bit
integers, and exact decimal arithmetic runs on scaled int64 (verified to
work on TPU v5e, where int64 is emulated on int32 lanes by XLA).
"""

import os as _os

import jax as _jax

_jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: sort-heavy query programs cost tens
# of seconds to minutes of TPU compile; the cache makes that a
# once-per-shape cost across processes (reference analogue: compiled
# PageProcessor caches, SURVEY.md §2.1 "Expression JIT"). Where
# JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and no directory
# is set in code; otherwise the cache sits at a fixed path inside the
# checkout (the path is part of the cache key, so it must not move).
# JAX_ENABLE_COMPILATION_CACHE=0 is JAX's own off switch.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(
            _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
            ".jax_cache",
        ),
    )
_jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

__version__ = "0.1.0"

from presto_tpu.session import Session  # noqa: E402,F401
from presto_tpu.utils import telemetry as _telemetry  # noqa: E402

# true XLA compiles, process-wide (device_snapshot()["xla_compiles"])
_telemetry.install_xla_listeners()
