"""SPMD plumbing shared by the sharded checker kernels.

One place answers three questions every sharded launch site asks:

  - *May I shard, and over how many devices?* `spmd_devices()` reads
    the process's device count through the JEPSEN_TPU_SPMD /
    JEPSEN_TPU_SPMD_DEVICES knobs (0/1 = single-device path). The
    gate is re-read per call so tests and benches can flip it without
    re-importing anything.
  - *How do the kernel's arguments lay out over the mesh?* The
    regex partition-rule table (the fmengine/EasyLM idiom, SNIPPETS.md
    [1]): arg names match rules, rules name PartitionSpecs. The
    lint registry reads the same table, so graftlint R4 prices the
    layout the launch sites actually use — not a parallel description
    that can drift.
  - *Where is the XLA compilation cache?* `enable_compile_cache()`
    points jax's persistent cache at JAX_COMPILATION_CACHE_DIR, or at
    `<checkout>/.jax_cache` when that is unset; every jit factory
    calls it lazily.

The mesh itself is 1-D over the batch axis ("b"): every kernel family
here is embarrassingly parallel in exactly one axis (histories in the
ensemble, segments x start-states in WGL, edges-by-key-block in SCC —
P-compositionality, PAPERS.md arXiv:1504.00204), so one axis name
serves all of them and `mesh_for(n)` memoizes one Mesh per size.
"""

from __future__ import annotations

import functools
import os
import re
from pathlib import Path

# The mesh's single batch axis name, shared by every sharded kernel.
AXIS = "b"

# Below this many independent rows a sharded launch is pure overhead.
MIN_ROWS = 2


def spmd_enabled() -> bool:
    """SPMD launches may be disabled outright (JEPSEN_TPU_SPMD=0):
    the single-device kernels are the fallback and the differential
    reference."""
    return os.environ.get("JEPSEN_TPU_SPMD", "1") != "0"


def spmd_devices() -> int:
    """How many devices a sharded launch may span right now: the
    process's device count, capped by JEPSEN_TPU_SPMD_DEVICES (tests
    parametrize mesh sizes through it), 0 when sharding is disabled.
    Values <= 1 mean 'take the single-device path'."""
    if not spmd_enabled():
        return 0
    import jax

    n = len(jax.devices())
    cap = os.environ.get("JEPSEN_TPU_SPMD_DEVICES")
    if cap:
        try:
            n = min(n, max(int(cap), 0))
        except ValueError:
            pass
    return n


@functools.lru_cache(maxsize=None)
def mesh_for(n_devices: int):
    """The memoized 1-D ('b',) mesh over the first n devices. One Mesh
    object per size keeps the jit factories' lru_cache keys stable
    (jax Meshes hash by devices + axis names, but identity-stable
    objects avoid rebuilding device arrays per launch)."""
    import jax
    import numpy as np

    from . import dist

    dist.ensure_initialized()
    devs = jax.devices()[:n_devices]
    return jax.sharding.Mesh(np.array(devs), (AXIS,))


# ---------------------------------------------------------------------------
# Regex partition rules (SNIPPETS.md [1]: match_partition_rules)
# ---------------------------------------------------------------------------

# WGL kernel family: the packed segment tensors are laid out in
# per-device blocks (ensemble.shard_layout), so their leading axis
# shards with the search rows — nothing big is replicated. Only the
# tiny result-ordering permutation stays replicated.
WGL_RULES = (
    (r"^(inv_t|ret_t|trans|mseg|sufmin)$", (AXIS,)),
    (r"^(row_seg|st0)$", (AXIS,)),
    (r"^inv_perm$", ()),
)

# SCC coloring kernel: the edge layouts (the big operand — the color
# array is n_pad ints) shard over the mesh, one block of edges and its
# run ends a device; colors stay replicated and are pmax-combined per
# sweep.
SCC_RULES = (
    (r"^(fwd|bwd)_", (AXIS,)),
    (r"^active$", ()),
)


def match_partition_rules(rules, names):
    """PartitionSpec per arg name via the first matching regex rule
    (re.search, like the reference snippet). Raises on an unmatched
    name — a silently-replicated new argument is exactly the bug the
    table exists to prevent."""
    from jax.sharding import PartitionSpec as P

    out = []
    for name in names:
        for rule, axes in rules:
            if re.search(rule, name):
                out.append(P(*axes))
                break
        else:
            raise ValueError(f"no partition rule for arg {name!r}")
    return tuple(out)


def describe_partition(rules, names) -> dict:
    """The lint-facing view of a rule table: which args shard over
    the mesh axis and which stay replicated (graftlint R4's input —
    jepsen_tpu.analysis.registry reads the table the launch sites
    use)."""
    sharded, replicated = [], []
    for name in names:
        for rule, axes in rules:
            if re.search(rule, name):
                (sharded if axes else replicated).append(name)
                break
        else:
            # same contract as match_partition_rules: an arg the lint
            # view can't place would silently escape R4 pricing
            raise ValueError(f"no partition rule for arg {name!r}")
    return {"axis": AXIS, "sharded": sharded, "replicated": replicated}


# ---------------------------------------------------------------------------
# Persistent XLA compilation cache
# ---------------------------------------------------------------------------

# Where the cache lives when JAX_COMPILATION_CACHE_DIR is unset: one
# fixed path inside the checkout, resolved from this file and not from
# the working directory — the path is part of what makes a cache hit.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Points jax's persistent compilation cache at its one directory
    and returns it: JAX_COMPILATION_CACHE_DIR when set (jax reads it at
    import, and this leaves it alone), else DEFAULT_CACHE_DIR. Called
    lazily by every kernel jit factory, before the first compile and
    never at import. A warm cache makes first-check compile ~0."""
    import jax

    d = jax.config.jax_compilation_cache_dir
    if not d:
        d = str(DEFAULT_CACHE_DIR)
        os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", 0.5)
    return d
