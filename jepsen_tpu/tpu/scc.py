"""Strongly-connected components as a batched device kernel.

Capability reference: elle 0.2.1 runs Tarjan's SCC on the JVM over the
inferred dependency graph (consumed via jepsen/src/jepsen/tests/cycle/
append.clj:6-27); SURVEY §2.2 plans its replacement as "vectorized edge
inference + iterative/batched SCC (forward-backward reachability) on
int32 adjacency tensors".

Tarjan is inherently sequential, so the device formulation is Orzan's
coloring algorithm, whose primitives are pure data-parallel segment
ops that XLA maps well:

  repeat until no active nodes:
    1. forward pass — propagate the max node id ("color") along active
       edges to a fixpoint: c[v] = max(c[v], max_{u->v} c[u]); the
       fixpoint runs in a lax.while_loop on device.
    2. backward pass — for every color root r (c[r]==r), mark the
       nodes that reach r inside r's color class; again a max
       fixpoint, all roots in parallel. Marked nodes = the exact SCC
       of each root (they reach r and r reaches them).
    3. retire every marked SCC; survivors recolor next round.

Each sweep is a pull, not a scatter. Once per launch the host lays the
edge list out twice (`_layout`, a counting sort in native/order.c): by
dst for the forward pass, by src for the backward one, so each node's
edges in that direction form one run. A sweep then reads each edge's
sender color (a gather), takes a segmented max along the runs by
log-doubling, and reads each node's maximum at its run's end. Every
edge reads and none writes to a shared slot, as a scatter-max with
colliding indices does; the sort stays on the host, out of the
program's compile. The doubling levels are ceil(log2)
of the longest run, read from the launch's own input (levels past it
are skipped), and come back in the status word as `pull_levels`. Both
passes are the same Jacobi sweeps as a scatter-max over the previous
colors, so labels, sweeps and rounds do not depend on the formulation.

Node ids follow history order, so dependency edges point mostly
forward (u < v) and a forward sweep changes nothing for them: the
fixpoint converges in a handful of sweeps rather than O(diameter).
Both loops carry iteration caps; on non-convergence (adversarial
graphs) the caller falls back to the host path (scipy's compiled
Tarjan-equivalent), so results are always exact.

Edge subsets (elle checks cycles over WW, WW+WR, ... cumulative edge
classes) are expressed as boolean edge masks over ONE shared edge
array, so every subset reuses the same compiled kernel executable
instead of recompiling per subset shape.
"""

from __future__ import annotations

import logging
from functools import lru_cache
from time import monotonic_ns
from typing import Any, NamedTuple

import numpy as np

from .. import telemetry
from . import profiler

logger = logging.getLogger(__name__)

# Caps: sweeps per fixpoint and outer peeling rounds. Each fixpoint
# sweep is O(E) on device, so generous caps cost little; they exist to
# bound adversarial graphs, which then take the host fallback.
SWEEP_CAP = 512
ROUND_CAP = 64

# Below this edge count the host path wins on dispatch overhead alone.
DEVICE_MIN_EDGES = 20_000


def _next_pow2(x: int) -> int:
    n = 1
    while n < x:
        n <<= 1
    return n


def _scc_program(n_pad: int, sweep_cap: int, round_cap: int,
                 combine=None):
    """The ENTIRE Orzan peeling loop as one traceable program: rounds
    and fixpoints run in nested lax.while_loops, one download of
    (labels, status). A host-driven loop would sync device->host once
    per sweep to test convergence; here the loop never leaves the
    device.

    The edges arrive laid out by `_layout`: sorted by dst for the
    forward pass and by src for the backward one, masked-off edges
    turned into sentinel self-loops (node n_pad-1, never active).

    `combine` is the SPMD hook: the sharded path passes a pmax over
    the mesh axis. Each shard pulls over its own edge block, and one
    small all-reduce of the color array a sweep combines the shards'
    partial maxima — the edge list (the big operand) never
    replicates."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def node_ids():
        # built inside the trace: a closed-over array would be baked
        # into the executable as a constant
        return jnp.arange(n_pad, dtype=jnp.int32)

    def runs(send, off, end):
        # levels = ceil(log2(longest run)): shifts 1, 2, ...,
        # 2^(levels-1) reach back over a whole run
        longest = jnp.max(off) + 1
        levels = sum(((1 << d) < longest).astype(jnp.int32)
                     for d in range(_max_levels(off.shape[0])))
        return _Runs(send, off, end, levels)

    def pull(v, runs, neutral):
        """One sweep's incoming maximum per node from each edge's
        sender value `v`: a segmented max by log-doubling along the
        sorted runs, read at each run's end. No scatter: every edge
        reads, none writes to a shared slot."""
        for d in range(_max_levels(v.shape[0])):
            s = 1 << d

            def step(v, s=s):
                back = jnp.concatenate(
                    [jnp.full((s,), neutral, dtype=v.dtype), v[:-s]])
                return jnp.where(runs.off >= s, jnp.maximum(v, back), v)

            # levels past the launch's longest run are skipped
            v = lax.cond(d < runs.levels, step, lambda v: v, v)
        return jnp.where(runs.end >= 0, v[jnp.maximum(runs.end, 0)],
                         neutral)

    def fixpoint(carry_c, active, runs, in_class, neutral):
        """Pull propagation to fixpoint; returns (c, converged, sweeps
        run). An inactive node holds the neutral and keeps it, so a
        sender needs no mask, and masking the update at the node equals
        masking each edge by both ends' activity."""

        def cond(state):
            c, changed, it = state
            return jnp.logical_and(changed, it < sweep_cap)

        def body(state):
            c, _, it = state
            v = c[runs.send]
            if in_class is not None:
                v = jnp.where(in_class, v, neutral)
            prop = pull(v, runs, neutral)
            if combine is not None:
                prop = combine(prop)
            nc = jnp.where(active, jnp.maximum(c, prop), c)
            # c and (combined) prop are replicated on the sharded
            # path, so `changed` agrees across shards and the while
            # loops stay in lockstep
            return nc, jnp.any(nc != c), it + 1

        c, changed, it = jax.lax.while_loop(
            cond, body, (carry_c, jnp.bool_(True), jnp.int32(0)))
        return c, jnp.logical_not(changed), it

    def one_round(active, fwd, bwd, bwd_recv):
        """One coloring round. Returns (labels for nodes retired this
        round, new active mask, converged, sweeps run)."""
        # 1. forward colors, pulled along edges sorted by dst
        ids = node_ids()
        c0 = jnp.where(active, ids, jnp.int32(-1))
        c, ok_f, it_f = fixpoint(c0, active, fwd, None, jnp.int32(-1))
        # 2. backward membership within color classes, all roots at
        # once: m[v]=1 iff v reaches its color root inside the class.
        # Pulled along edges sorted by src: m[u] |= m[v] for edge u->v
        # in-class. An edge with an inactive end is in-class only if
        # both ends are inactive (colors -1), and then carries m = 0.
        in_class = c[bwd_recv] == c[bwd.send]
        m0 = jnp.where(jnp.logical_and(active, c == ids),
                       jnp.int32(1), jnp.int32(0))
        m, ok_b, it_b = fixpoint(m0, active, bwd, in_class, jnp.int32(0))
        member = jnp.logical_and(active, m > 0)
        labels = jnp.where(member, c, jnp.int32(-1))
        return labels, jnp.logical_and(active, ~member), \
            jnp.logical_and(ok_f, ok_b), it_f + it_b

    # the program's name in a device trace is jit_scc_peel
    def scc_peel(active0, fwd_send, fwd_off, fwd_end, bwd_send, bwd_recv,
                 bwd_off, bwd_end):
        fwd = runs(fwd_send, fwd_off, fwd_end)
        bwd = runs(bwd_send, bwd_off, bwd_end)
        levels = jnp.maximum(fwd.levels, bwd.levels)
        if combine is not None:
            levels = combine(levels)

        def cond(state):
            active, _out, ok, rounds, _sweeps = state
            return ok & jnp.any(active) & (rounds < round_cap)

        def body(state):
            active, out, ok, rounds, sweeps = state
            labels, new_active, converged, it = one_round(
                active, fwd, bwd, bwd_recv)
            return (new_active, jnp.where(labels >= 0, labels, out),
                    ok & converged, rounds + 1, sweeps + it)

        out0 = jnp.full((n_pad,), -1, dtype=jnp.int32)
        active, out, ok, rounds, sweeps = jax.lax.while_loop(
            cond, body, (active0, out0, jnp.bool_(True), jnp.int32(0),
                         jnp.int32(0)))
        done = ok & jnp.logical_not(jnp.any(active))
        # the ok flag and the work counts ride IN the labels array (slot
        # n_pad-1 is sentinel territory): one device->host transfer.
        status = pack_status(done, rounds, sweeps, levels)
        return lax.dynamic_update_slice(out, status[None], (n_pad - 1,))

    return scc_peel


class _Runs(NamedTuple):
    """One pass's edges on device, sorted by receiving node: per edge
    its sender and offset in its node's run; per node its run's last
    position or -1; the doubling levels the longest run needs."""

    send: Any
    off: Any
    end: Any
    levels: Any


def _max_levels(e: int) -> int:
    """Doubling levels that span any run of an e-edge block."""
    return max(1, (e - 1).bit_length())


def _layout(recv, send, on, n_pad: int, blocks: int = 1):
    """One pass's edge layout, built on the host once per launch: in
    each of `blocks` equal blocks (a shard's edges) the edges sorted by
    receiving node, so each node's edges form one run. Masked-off edges
    become self-loops on the sentinel node n_pad-1 (never active), each
    its own run of one. Returns (recv, send, off, end): per edge its
    receiver, sender and offset in its run; per block and node the
    run's last position in the block, or -1. native/order.c's counting
    sort does this in linear time; `_layout_py`'s stable sort is its
    reference and fallback."""
    from .. import native

    try:
        return native.edge_layout(recv, send, on, n_pad, blocks)
    except RuntimeError:
        return _layout_py(recv, send, on, n_pad, blocks)


def _layout_py(recv, send, on, n_pad: int, blocks: int = 1):
    sentinel = np.int32(n_pad - 1)
    recv = np.where(on, recv, sentinel).reshape(blocks, -1)
    send = np.where(on, send, sentinel).reshape(blocks, -1)
    order = np.argsort(recv, axis=1, kind="stable")
    recv = np.take_along_axis(recv, order, 1)
    send = np.take_along_axis(send, order, 1)
    live = recv != sentinel
    base = np.arange(blocks, dtype=np.int32)[:, None] * n_pad
    count = np.bincount((recv + base)[live], minlength=blocks * n_pad
                        ).reshape(blocks, n_pad)
    last = (np.cumsum(count, axis=1) - 1).astype(np.int32)
    end = np.where(count > 0, last, -1)
    first = last - count + 1
    off = np.arange(recv.shape[1], dtype=np.int32) - \
        np.take_along_axis(first, recv, 1)
    off[~live] = 0
    return recv.ravel(), send.ravel(), off.ravel(), end.ravel()


# The status word in the labels' sentinel slot: bit 0 the convergence
# flag, bits 1-7 the peeling rounds (<= ROUND_CAP), bits 8-24 the
# fixpoint sweeps, forward plus backward, summed over the rounds (<= 2 *
# SWEEP_CAP * ROUND_CAP = 65,536), bits 25-29 the pull's doubling levels
# (<= 31, the larger of the forward and backward layouts').
def pack_status(done, rounds, sweeps, levels):
    return (done.astype(rounds.dtype) | (rounds << 1) | (sweeps << 8)
            | (levels << 25))


def unpack_status(word: int) -> tuple[bool, int, int, int]:
    """(converged, rounds, sweeps, pull levels) from the sentinel
    slot's word."""
    word = int(word)
    return (bool(word & 1), (word >> 1) & 0x7F, (word >> 8) & 0x1FFFF,
            (word >> 25) & 0x1F)


# The two edge layouts (`_layout`: the big per-launch payload) are
# donated: scc_device builds fresh device arrays per call, so XLA may
# reuse them as scratch (graftlint R3). `active` stays live (tiny).
SCC_ARGS = ("active", "fwd_send", "fwd_off", "fwd_end", "bwd_send",
            "bwd_recv", "bwd_off", "bwd_end")
DONATE_ARGNUMS = tuple(range(1, len(SCC_ARGS)))


def scc_arg_shapes(n_pad: int, e_pad: int, blocks: int = 1):
    """(shape, dtype) of each of SCC_ARGS for a launch of `blocks`
    edge blocks (the mesh size on the sharded path): edge arrays hold
    e_pad slots, run ends n_pad a block."""
    edge, ends = ((e_pad,), np.int32), ((blocks * n_pad,), np.int32)
    return (((n_pad,), np.bool_), edge, edge, ends, edge, edge, edge,
            ends)


@lru_cache(maxsize=None)
def _jitted_scc(n_pad: int, e_pad: int, sweep_cap: int,
                round_cap: int):
    """Single-device compile of the peeling loop, one executable per
    (node, edge) shape bucket."""
    import jax

    from . import spmd
    from .wgl import quiet_unusable_donation

    spmd.enable_compile_cache()
    quiet_unusable_donation()
    return jax.jit(_scc_program(n_pad, sweep_cap, round_cap),
                   donate_argnums=DONATE_ARGNUMS)


@lru_cache(maxsize=None)
def _jitted_scc_sharded(mesh, n_pad: int, e_pad: int, sweep_cap: int,
                        round_cap: int):
    """SPMD compile: the edge list shards over the mesh's 'b' axis
    (in key blocks — see scc_device), the color array stays
    replicated, each shard sorts and pulls over its own block, and
    each sweep combines the shards' partial maxima with ONE pmax of
    n_pad ints. Per-sweep compute and H2D both scale ~1/N in edges;
    the collective moves node-count bytes, not edge-count."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from . import spmd
    from .wgl import quiet_unusable_donation

    spmd.enable_compile_cache()
    quiet_unusable_donation()
    full = _scc_program(
        n_pad, sweep_cap, round_cap,
        combine=lambda prop: jax.lax.pmax(prop, spmd.AXIS))
    specs = spmd.match_partition_rules(spmd.SCC_RULES, SCC_ARGS)
    mapped = jax.shard_map(full, mesh=mesh, in_specs=specs,
                           out_specs=P(), check_vma=False)
    shardings = tuple(NamedSharding(mesh, s) for s in specs)
    return jax.jit(mapped, in_shardings=shardings,
                   donate_argnums=DONATE_ARGNUMS)


def _edge_pad(e: int) -> int:
    """Edge shape buckets: multiples of 128Ki (capped pow2 below that)
    rather than next-pow2 — the padding is uploaded over the (slow)
    host->device link, so a 600k-edge graph shouldn't ship 1M slots."""
    if e <= (1 << 17):
        return _next_pow2(max(e, 1))
    step = 1 << 17
    return ((e + step - 1) // step) * step


def scc_device(n: int, src, dst, emask=None,
               ekey=None) -> np.ndarray | None:
    """SCC labels per node (label = the component's max node id), or
    None when iteration caps were hit (caller must take the host
    path). Singleton components get their own id, so callers test
    non-triviality by label multiplicity.

    On a multi-device process the edge list shards over the mesh
    (_jitted_scc_sharded). ekey — the per-edge key id from the elle
    edge-inference passes — orders the edge array into key blocks
    first, so each device's contiguous shard covers whole keys:
    same-key dependency edges (the bulk of ww/wr/rw) propagate inside
    one shard and only cross-key session/realtime edges ride the
    pmax. Labels are order-independent, so the layout cannot change
    the verdict."""
    import jax.numpy as jnp

    from . import spmd

    src = np.asarray(src, dtype=np.int32)
    dst = np.asarray(dst, dtype=np.int32)
    if n == 0:
        return np.empty(0, dtype=np.int32)
    n_dev = spmd.spmd_devices()
    if n_dev > 1:
        # pow2 mesh sizes only, like ensemble.sharded_launch: each
        # mesh size is its own compile family, and compile latency —
        # not FLOPs — is this kernel's budget
        n_dev = 1 << (n_dev.bit_length() - 1)
    shard = n_dev > 1 and len(src) >= DEVICE_MIN_EDGES
    if shard and ekey is not None and len(ekey) == len(src):
        ekey = np.asarray(ekey)
        if np.any(ekey[:-1] > ekey[1:]):
            # callers that launch several graded subsets over one edge
            # array (cycle_anomalies_arrays) pre-sort once; this sort
            # only runs for one-shot callers
            order = np.argsort(ekey, kind="stable")
            src, dst = src[order], dst[order]
            if emask is not None:
                emask = np.asarray(emask)[order]
        telemetry.count("scc.keyblock-layouts")
    n_pad = _next_pow2(n + 1)
    e_pad = _edge_pad(len(src))
    if shard and e_pad % n_dev:
        e_pad += n_dev - e_pad % n_dev
    # pad edges as self-loops on the sentinel (inactive) node n
    psrc = np.full(e_pad, n, dtype=np.int32)
    pdst = np.full(e_pad, n, dtype=np.int32)
    psrc[:len(src)] = src
    pdst[:len(dst)] = dst
    pmask = np.zeros(e_pad, dtype=bool)
    pmask[:len(src)] = True if emask is None else np.asarray(emask)
    active = np.zeros(n_pad, dtype=bool)
    active[:n] = True
    t0 = monotonic_ns()
    blocks = n_dev if shard else 1
    _, fsend, foff, fend = _layout(pdst, psrc, pmask, n_pad, blocks)
    brecv, bsend, boff, bend = _layout(psrc, pdst, pmask, n_pad, blocks)
    host = (active, fsend, foff, fend, bsend, brecv, boff, bend)
    layout_ns = monotonic_ns() - t0
    prof = profiler.get()
    if shard:
        mesh = spmd.mesh_for(n_dev)
        fn = _jitted_scc_sharded(mesh, n_pad, e_pad, SWEEP_CAP,
                                 ROUND_CAP)
        bucket = ("scc-sharded", n_dev, n_pad, e_pad)
        telemetry.gauge_max("scc.spmd.devices", n_dev)
    else:
        fn = _jitted_scc(n_pad, e_pad, SWEEP_CAP, ROUND_CAP)
        bucket = ("scc", n_pad, e_pad)
    rec = prof.begin("scc", bucket=bucket, nodes=n, edges=len(src),
                     devices=n_dev if shard else None)
    rec["encode_ns"] = layout_ns
    fresh = prof.bucket_fresh("scc", bucket)
    t0 = monotonic_ns()
    if shard:
        import jax
        from jax.sharding import NamedSharding

        specs = spmd.match_partition_rules(spmd.SCC_RULES, SCC_ARGS)
        args = tuple(
            jax.device_put(a, NamedSharding(mesh, s))
            for a, s in zip(host, specs))
    else:
        args = tuple(jnp.asarray(a) for a in host)
    rec["h2d_ns"] = monotonic_ns() - t0
    try:
        t0 = monotonic_ns()
        dev = fn(*args)
        rec["dispatch_ns"] = monotonic_ns() - t0
        if fresh:
            rec["compile_ns"] = rec["dispatch_ns"]
        t0 = monotonic_ns()
        labels = np.asarray(dev)
        rec["compute_ns"] = monotonic_ns() - t0
        (done, rec["rounds"], rec["sweeps"],
         rec["pull_levels"]) = unpack_status(labels[-1])
    except BaseException:
        if fresh:
            # failed first launch: release the claim so the retry's
            # real recompile records a miss, not a phantom hit
            prof.bucket_unclaim("scc", bucket)
        raise
    finally:
        prof.finish(rec)
    telemetry.count("scc.sweeps", rec["sweeps"])
    telemetry.count("scc.rounds", rec["rounds"])
    telemetry.count("scc.pull-levels", rec["pull_levels"])
    if not done:
        return None
    return labels[:n]


def _scc_host(n: int, src, dst) -> np.ndarray:
    """Exact host SCC via scipy (compiled Tarjan-equivalent), with
    labels normalized to the component's max node id so device and
    host paths are interchangeable."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    g = coo_matrix((np.ones(len(src), dtype=np.int8),
                    (np.asarray(src), np.asarray(dst))), shape=(n, n))
    _, comp = connected_components(g, directed=True, connection="strong")
    # int32 throughout: node ids are < 2^31 by construction, and the
    # int64 intermediates here doubled the representative-id pass's
    # memory traffic on million-node graphs (graftlint R2)
    ids = np.arange(n, dtype=np.int32)
    rep = np.full(int(comp.max()) + 1 if n else 0, -1, dtype=np.int32)
    np.maximum.at(rep, comp, ids)
    return rep[comp]


def scc(n: int, src, dst, emask=None, device: bool = True,
        ekey=None) -> np.ndarray:
    """SCC labels (component max-id per node); device kernel with host
    fallback on non-convergence, host path outright for small graphs
    (dispatch overhead dominates under DEVICE_MIN_EDGES edges).
    ekey: optional per-edge key ids for the sharded path's key-block
    layout (see scc_device)."""
    src = np.asarray(src)
    dst = np.asarray(dst)
    if emask is not None:
        emask = np.asarray(emask, dtype=bool)
    n_live = len(src) if emask is None else int(emask.sum())
    if n == 0 or n_live == 0:
        return np.arange(n, dtype=np.int32)
    telemetry.count("scc.nodes", n)
    telemetry.count("scc.edges", n_live)
    if device and n_live >= DEVICE_MIN_EDGES:
        try:
            labels = scc_device(n, src, dst, emask, ekey=ekey)
        except Exception as e:  # noqa: BLE001 — device ladder
            from .wgl import device_error_kind

            kind = device_error_kind(e)
            if kind is None:
                raise
            # counted and logged like the wgl ladder's rungs: the host
            # path below is exact, but a dead device must be visible
            telemetry.count(f"scc.ladder.{kind}")
            logger.error("scc device kernel failed (%s: %s); host "
                         "fallback", kind, str(e)[:200])
            labels = None
        if labels is not None:
            telemetry.count("scc.path.device")
            return labels
        telemetry.count("scc.device-nonconverged")
    if emask is not None:
        src, dst = src[emask], dst[emask]
    telemetry.count("scc.path.host")
    return _scc_host(n, src, dst)


def nontrivial_from_labels(labels: np.ndarray) -> list[np.ndarray]:
    """Member arrays of every component with >= 2 nodes (self-loops are
    not cycles in dependency graphs: a txn never depends on itself)."""
    uniq, inverse, counts = np.unique(labels, return_inverse=True,
                                      return_counts=True)
    big = counts > 1
    if not big.any():
        return []
    order = np.argsort(inverse, kind="stable")
    sorted_inv = inverse[order]
    bounds = np.concatenate([[0], np.cumsum(counts)])
    groups = [order[bounds[i]:bounds[i + 1]]
              for i in np.flatnonzero(big)]
    telemetry.count("scc.nontrivial-components", len(groups))
    telemetry.gauge_max("scc.largest-component",
                        int(max(len(g) for g in groups)))
    return groups


def nontrivial_sccs(n: int, src, dst, emask=None, device: bool = True,
                    ekey=None) -> list[np.ndarray]:
    if n == 0:
        return []
    return nontrivial_from_labels(scc(n, src, dst, emask,
                                      device=device, ekey=ekey))
