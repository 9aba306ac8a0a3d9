"""Device-mesh sharding of the WGL search: many histories (or many
segments of one long history) checked concurrently across chips.

The reference's scaling story for checking is host-side only: bounded
pmap over per-key subhistories (jepsen/src/jepsen/independent.clj:271-377)
and fork-join folds over history chunks (checker.clj:139-200). Here the
batch dimension of the WGL kernel — independent keys, ensemble histories,
or segments x start-states of one long history — is laid out over a 1-D
`jax.sharding.Mesh` as a TRUE SPMD program:

  - `shard_layout` blocks the packed segment tensors into per-device
    groups (LPT-balanced by search work, encode.balanced_groups), so
    each chip holds ONLY the segments its search rows reference —
    nothing big is replicated (graftlint R4 prices exactly this; the
    pre-SPMD path shipped 22 MiB of replicated tables per launch).
  - `shard_map` (SNIPPETS.md [1]-[3]; partition rules in
    tpu/spmd.py) runs one frontier search per chip over its local
    rows. Each shard's `lax.while_loop` exits as soon as ITS rows
    resolve — there is no per-BFS-level cross-chip sync at all; the
    only collectives are the end-of-search psum/pmax of the (tiny)
    search-shape stats and the gather that restores caller row order.
  - The blocked segment tensors are donated (wgl.DONATE_ARGNUMS):
    launch sites build them fresh per call, so XLA reuses the shards
    as scratch.

Per-row results are bit-identical to the single-device kernel for any
mesh size: a search row never reads another row's state, so blocking
and padding change nothing but the wall clock (tests/test_spmd.py
pins verdicts AND certificates across mesh 1/2/4/8).
"""

from __future__ import annotations

import time as _time
from functools import lru_cache, partial
from typing import Sequence

import numpy as np

from .. import telemetry
from . import profiler, spmd
from . import wgl as wgl_mod
from .encode import Encoded, balanced_groups
from .wgl import PackedBatch, _drain, _kernel, _next_pow2, _timed_launch

# The sharded program's argument names, in signature order (the
# partition-rule table in tpu/spmd.py keys off these; the lint
# registry traces the same layout).
SHARD_ARGS = ("inv_t", "ret_t", "trans", "mseg", "sufmin",
              "row_seg", "st0", "inv_perm")


@lru_cache(maxsize=None)
def _jitted_sharded(mesh, W: int, F: int, max_iters: int, reach: bool,
                    crash_free: bool = False):
    """One jitted shard_map program per (mesh, static config); jax.jit
    then caches compiled executables per array shape bucket."""
    import jax
    from jax.sharding import NamedSharding

    spmd.enable_compile_cache()
    wgl_mod.quiet_unusable_donation()
    kern = partial(_kernel, W=W, F=F, max_iters=max_iters, reach=reach,
                   crash_free=crash_free)
    n_res = 2 if reach else 1

    def local(inv_t, ret_t, trans, mseg, sufmin, row_seg, st0):
        # one per-chip frontier search over the chip's row shard; the
        # while_loop stops when the LOCAL rows resolve (no cross-chip
        # level sync). Only the search-shape stats cross the mesh.
        outs = kern(inv_t, ret_t, trans, mseg, sufmin, row_seg, st0)
        res, (it, *lvls) = outs[:n_res], outs[n_res:]
        it = jax.lax.pmax(it, spmd.AXIS)
        lvls = tuple(jax.lax.psum(lv, spmd.AXIS) for lv in lvls)
        return res + (it,) + lvls

    data_specs = spmd.match_partition_rules(spmd.WGL_RULES,
                                            SHARD_ARGS[:7])
    from jax.sharding import PartitionSpec as P

    out_specs = (P(spmd.AXIS),) * n_res + (P(),) * 4
    mapped = jax.shard_map(local, mesh=mesh, in_specs=data_specs,
                           out_specs=out_specs, check_vma=False)

    def run(inv_t, ret_t, trans, mseg, sufmin, row_seg, st0, inv_perm):
        outs = mapped(inv_t, ret_t, trans, mseg, sufmin, row_seg, st0)
        # restore caller row order: a gather over the per-row result
        # vector (1-4 bytes/row) — the only all-gather in the program
        res = tuple(o[inv_perm] for o in outs[:n_res])
        return res + outs[n_res:]

    shardings = tuple(
        NamedSharding(mesh, s) for s in
        spmd.match_partition_rules(spmd.WGL_RULES, SHARD_ARGS))
    return jax.jit(run, in_shardings=shardings,
                   donate_argnums=wgl_mod.DONATE_ARGNUMS)


def default_mesh(n_devices: int | None = None):
    """A 1-D mesh over the first n (default: all) devices; with the
    multi-host env set (tpu/dist.py), 'all' spans every host's chips
    and the batch axis shards across DCN."""
    import jax

    from . import dist

    dist.ensure_initialized()
    if n_devices is None:
        # honor the SPMD knobs (JEPSEN_TPU_SPMD / _SPMD_DEVICES): with
        # sharding disabled or capped, the explicitly-sharded entry
        # points degrade to a smaller mesh instead of silently running
        # shard_map over every device — JEPSEN_TPU_SPMD=0 really does
        # give the differential reference everywhere
        n_devices = max(1, spmd.spmd_devices())
    # clamp like the old devs[:n] slice: asking for more devices than
    # the process has yields the full mesh, not an error
    return spmd.mesh_for(min(n_devices, len(jax.devices())))


class _ShardLayout:
    """The per-device blocking of one launch: segment tensors gathered
    into [n_dev * (K_loc + 1), ...] blocks (each device's K_loc
    segments + its own sentinel empty row), rows rebased to local
    segment indices, and the inverse permutation that restores caller
    row order."""

    __slots__ = ("inv_t", "ret_t", "trans", "mseg", "sufmin",
                 "row_seg", "st0", "inv_perm", "n_dev", "n_rows",
                 "device_entries")


def shard_layout(pb: PackedBatch, rows: Sequence[tuple[int, int]],
                 n_dev: int) -> _ShardLayout:
    """Blocks a PackedBatch + its search rows onto n_dev devices.

    Segments are grouped by LPT over estimated search work
    (entries x rows referencing the segment); each device's block
    holds only its own segments, so H2D traffic and HBM footprint
    ship every byte ONCE across the mesh instead of once per chip.
    Segments no row references don't ship at all."""
    t0 = _time.monotonic_ns()
    rows = list(rows)
    B = pb.B
    n_rows_seg = np.zeros(B + 1, dtype=np.int32)
    for k, _s in rows:
        n_rows_seg[k] += 1
    used = [k for k in range(B) if n_rows_seg[k]]
    weights = [(int(pb.m[k]) + 1) * int(n_rows_seg[k]) for k in used]
    groups = [[used[i] for i in g]
              for g in balanced_groups(weights, n_dev)]
    K_loc = _next_pow2(max((len(g) for g in groups), default=1))
    # device-major gather map; unfilled slots and each device's local
    # sentinel (index K_loc) point at pb's empty row B
    gmap = np.full((n_dev, K_loc + 1), B, dtype=np.int32)
    loc: dict[int, tuple[int, int]] = {}
    for d, g in enumerate(groups):
        for j, k in enumerate(g):
            gmap[d, j] = k
            loc[k] = (d, j)
    flat = gmap.reshape(-1)
    lay = _ShardLayout()
    lay.inv_t = pb.inv_t[flat]
    lay.ret_t = pb.ret_t[flat]
    lay.trans = pb.trans[flat]
    lay.mseg = pb.m[flat]
    lay.sufmin = pb.sufmin[flat]
    # rows per device, caller order preserved within each device
    per: list[list[tuple[int, int]]] = [[] for _ in range(n_dev)]
    where: list[tuple[int, int]] = []
    for k, s in rows:
        d, j = loc[k]
        where.append((d, len(per[d])))
        per[d].append((j, int(s)))
    B_loc = _next_pow2(max((len(p) for p in per), default=1))
    row_seg = np.full(n_dev * B_loc, K_loc, dtype=np.int32)
    st0 = np.zeros(n_dev * B_loc, dtype=np.int32)
    for d, p in enumerate(per):
        for slot, (j, s) in enumerate(p):
            row_seg[d * B_loc + slot] = j
            st0[d * B_loc + slot] = s
    inv_perm = np.zeros(_next_pow2(max(len(rows), 1)), dtype=np.int32)
    for i, (d, slot) in enumerate(where):
        inv_perm[i] = d * B_loc + slot
    lay.row_seg, lay.st0, lay.inv_perm = row_seg, st0, inv_perm
    lay.n_dev, lay.n_rows = n_dev, len(rows)
    lay.device_entries = [
        int(sum(int(pb.m[k]) * int(n_rows_seg[k]) for k in g))
        for g in groups]
    profiler.get().record_host("shard-layout",
                               _time.monotonic_ns() - t0)
    return lay


def sharded_launch(pb: PackedBatch, rows: Sequence[tuple[int, int]],
                   W: int, F: int, reach: bool, mesh=None,
                   kernel: str = "wgl-sharded"):
    """Dispatches one SPMD launch (async; drain with wgl._drain).
    Outputs answer rows in CALLER order — trim to len(rows).

    Profiling meta carries the per-device work attribution
    (`device_entries`, entries of search work per chip) and the
    mean/max `balance` figure — with zero replicated bytes, an uneven
    `balance` is what's left to explain a flat device sweep."""
    import jax
    from jax.sharding import NamedSharding

    prof = profiler.get()
    if mesh is None:
        n = max(1, min(spmd.spmd_devices(), len(rows)))
        # pow2 mesh sizes only: _jitted_sharded caches per mesh, so a
        # stray 3-row launch must not mint a mesh3 compile family
        mesh = spmd.mesh_for(1 << (n.bit_length() - 1))
    n_dev = mesh.devices.size
    lay = shard_layout(pb, rows, n_dev)
    fn = _jitted_sharded(mesh, W, F, pb.M + 4, reach,
                         not pb.has_crashed)
    host_args = (lay.inv_t, lay.ret_t, lay.trans, lay.mseg,
                 lay.sufmin, lay.row_seg, lay.st0, lay.inv_perm)
    specs = spmd.match_partition_rules(spmd.WGL_RULES, SHARD_ARGS)
    t0 = _time.monotonic_ns()
    args = tuple(jax.device_put(a, NamedSharding(mesh, s))
                 for a, s in zip(host_args, specs))
    h2d_ns = _time.monotonic_ns() - t0
    bucket = (mesh, lay.inv_t.shape, lay.trans.shape[2],
              len(lay.row_seg), len(lay.inv_perm), W, F, pb.M + 4,
              reach, pb.has_crashed)
    telemetry.count("wgl.spmd.launches")
    telemetry.gauge_max("wgl.spmd.devices", n_dev)
    balance = profiler.work_balance(lay.device_entries)
    meta = {"h2d_ns": h2d_ns, "rows": len(lay.row_seg),
            "batch": pb.B, "m": pb.M, "states": pb.S,
            "devices": n_dev, "device_entries": lay.device_entries,
            "balance": balance}
    return _timed_launch(bucket, lambda: fn(*args), kernel=kernel, meta=meta)


def check_batch_sharded(encs: Sequence[Encoded], mesh=None, W: int = 32,
                        F: int = 64, reach: bool = False, rows=None):
    """check_batch/check_batch_reach across a device mesh. Search rows
    — (segment, start-state) pairs, default one per history — AND the
    packed segment tensors both shard over the mesh's 'b' axis via the
    blocked layout (see module docstring)."""
    if mesh is None:
        mesh = default_mesh()
    pb = PackedBatch(encs)
    if rows is None:
        rows = [(i, e.init_state) for i, e in enumerate(encs)]
    n_rows = len(rows)
    telemetry.count("wgl.ensemble.launches")
    out = sharded_launch(pb, rows, W, F, reach=reach, mesh=mesh,
                         kernel="wgl-sharded")
    if reach:
        mask, unk = _drain(out, reach=True)
        return mask[:n_rows], unk[:n_rows]
    return _drain(out, reach=False)[:n_rows]


def analysis_batch_sharded(model, hists, mesh=None, W: int | None = None,
                           F: int | None = None) -> list[dict]:
    """analysis_batch across a mesh: the ensemble benchmark path
    (BASELINE config 5: 1024 generated histories checked concurrently)."""
    from . import wgl as wgl_mod
    from ..history import History
    from .encode import EncodingError, encode

    encs, idx_map, results = [], [], [None] * len(hists)
    unencodable = []
    # one encode span for the whole batch, not one per history
    with telemetry.span("wgl.encode"):
        for i, hh in enumerate(hists):
            if not isinstance(hh, History):
                hh = History(hh)
            try:
                encs.append(encode(model, hh))
                idx_map.append(i)
            except EncodingError:
                unencodable.append((i, hh))
    for i, hh in unencodable:
        out = wgl_mod.search_host_model(model, hh, witness=True)
        out["analyzer"] = "model"
        results[i] = out
    if encs:
        from .wgl import RangeError
        try:
            res = check_batch_sharded(encs, mesh=mesh,
                                      W=W if W is not None else 32,
                                      F=F if F is not None else 64)
        except RangeError:
            res = [wgl_mod.UNKNOWN] * len(encs)
        for j, i in enumerate(idx_map):
            r = int(res[j])
            if r == wgl_mod.VALID:
                results[i] = {"valid?": True, "analyzer": "tpu-sharded"}
            else:
                # Bounded anomaly path: localize the failing segment on
                # device instead of re-searching the whole history on
                # host (unbounded at 1M-op scale). No certificate here,
                # so an UNKNOWN member's order is dropped.
                if r == wgl_mod.INVALID:
                    out = wgl_mod.extract_witness(encs[j], W=W, F=F)
                else:
                    out = wgl_mod.decide_unknown(encs[j], W=W, F=F)
                    out.pop(wgl_mod.ORDER_KEY, None)
                out["analyzer"] = ("tpu-sharded" if r == wgl_mod.INVALID
                                   else "tpu+host-fallback")
                results[i] = wgl_mod._search_stats(out)
    return results
