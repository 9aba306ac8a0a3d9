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
       edges to a fixpoint: c[v] = max(c[v], max_{u->v} c[u]). Each
       sweep is one scatter-max over the edge list; the fixpoint runs
       in a lax.while_loop on device.
    2. backward pass — for every color root r (c[r]==r), mark the
       nodes that reach r inside r's color class; again a scatter-max
       fixpoint, all roots in parallel. Marked nodes = the exact SCC
       of each root (they reach r and r reaches them).
    3. retire every marked SCC; survivors recolor next round.

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
    (labels, ok). A host-driven loop would sync device->host once per
    sweep to test convergence; here the loop never leaves the device.

    `combine` is the SPMD hook: the sharded path passes a pmax over
    the mesh axis, turning each sweep's scatter-max into local
    scatter over the shard's edge block + one small all-reduce of the
    color array — the edge list (the big operand) never replicates."""
    import jax
    import jax.numpy as jnp

    def fixpoint(carry_c, src, dst, live_e, neutral):
        """Scatter-max propagation to fixpoint; returns (c, converged,
        sweeps run)."""

        def cond(state):
            c, changed, it = state
            return jnp.logical_and(changed, it < sweep_cap)

        def body(state):
            c, _, it = state
            vals = jnp.where(live_e, c[src], neutral)
            prop = jnp.full((n_pad,), neutral, dtype=jnp.int32
                            ).at[dst].max(vals)
            if combine is not None:
                prop = combine(prop)
            nc = jnp.maximum(c, prop)
            # c and (combined) prop are replicated on the sharded
            # path, so `changed` agrees across shards and the while
            # loops stay in lockstep
            return nc, jnp.any(nc != c), it + 1

        c, changed, it = jax.lax.while_loop(
            cond, body, (carry_c, jnp.bool_(True), jnp.int32(0)))
        return c, jnp.logical_not(changed), it

    def one_round(active, src, dst, edge_on):
        """One coloring round. Returns (labels for nodes retired this
        round, new active mask, converged, sweeps run)."""
        node_ids = jnp.arange(n_pad, dtype=jnp.int32)
        live_e = jnp.logical_and(
            edge_on, jnp.logical_and(active[src], active[dst]))
        # 1. forward colors
        c0 = jnp.where(active, node_ids, jnp.int32(-1))
        c, ok_f, it_f = fixpoint(c0, src, dst, live_e, jnp.int32(-1))
        # 2. backward membership within color classes, all roots at
        # once: m[v]=1 iff v reaches its color root inside the class.
        same_color = jnp.logical_and(live_e, c[src] == c[dst])
        m0 = jnp.where(jnp.logical_and(active, c == node_ids),
                       jnp.int32(1), jnp.int32(0))
        # propagate backward: m[u] |= m[v] for edge u->v in-class
        m, ok_b, it_b = fixpoint(m0, dst, src, same_color, jnp.int32(0))
        member = jnp.logical_and(active, m > 0)
        labels = jnp.where(member, c, jnp.int32(-1))
        return labels, jnp.logical_and(active, ~member), \
            jnp.logical_and(ok_f, ok_b), it_f + it_b

    # the program's name in a device trace is jit_scc_peel
    def scc_peel(active0, src, dst, edge_on):
        def cond(state):
            active, _out, ok, rounds, _sweeps = state
            return ok & jnp.any(active) & (rounds < round_cap)

        def body(state):
            active, out, ok, rounds, sweeps = state
            labels, new_active, converged, it = one_round(
                active, src, dst, edge_on)
            return (new_active, jnp.where(labels >= 0, labels, out),
                    ok & converged, rounds + 1, sweeps + it)

        out0 = jnp.full((n_pad,), -1, dtype=jnp.int32)
        active, out, ok, rounds, sweeps = jax.lax.while_loop(
            cond, body, (active0, out0, jnp.bool_(True), jnp.int32(0),
                         jnp.int32(0)))
        done = ok & jnp.logical_not(jnp.any(active))
        # the ok flag and the work counts ride IN the labels array (slot
        # n_pad-1 is sentinel territory): one device->host transfer.
        return out.at[-1].set(pack_status(done, rounds, sweeps))

    return scc_peel


# The status word in the labels' sentinel slot: bit 0 the convergence
# flag, bits 1-7 the peeling rounds (<= ROUND_CAP), bits 8 and up the
# fixpoint sweeps, forward plus backward, summed over the rounds (<= 2 *
# SWEEP_CAP * ROUND_CAP).
def pack_status(done, rounds, sweeps):
    return done.astype(rounds.dtype) | (rounds << 1) | (sweeps << 8)


def unpack_status(word: int) -> tuple[bool, int, int]:
    """(converged, rounds, sweeps) from the sentinel slot's word."""
    word = int(word)
    return bool(word & 1), (word >> 1) & 0x7F, word >> 8


# The edge arrays (src/dst/edge_on — the big per-launch payload) are
# donated: scc_device builds fresh device arrays per call, so XLA may
# reuse them as scratch (graftlint R3). `active` stays live (tiny).
DONATE_ARGNUMS = (1, 2, 3)
SCC_ARGS = ("active", "src", "dst", "edge_on")


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
    replicated, and each sweep's fixpoint combines per-shard
    scatter-max results with ONE pmax of n_pad ints. Per-sweep
    compute and H2D both scale ~1/N in edges; the collective moves
    node-count bytes, not edge-count."""
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
    fresh = prof.bucket_fresh("scc", bucket)
    t0 = monotonic_ns()
    if shard:
        import jax
        from jax.sharding import NamedSharding

        specs = spmd.match_partition_rules(spmd.SCC_RULES, SCC_ARGS)
        args = tuple(
            jax.device_put(a, NamedSharding(mesh, s))
            for a, s in zip((active, psrc, pdst, pmask), specs))
    else:
        args = (jnp.asarray(active), jnp.asarray(psrc),
                jnp.asarray(pdst), jnp.asarray(pmask))
    rec["h2d_ns"] = monotonic_ns() - t0
    try:
        t0 = monotonic_ns()
        dev = fn(*args)
        rec["dispatch_ns"] = monotonic_ns() - t0
        if fresh:
            rec["compile_ns"] = rec["dispatch_ns"]
        rec.update(prof.bucket_cost(bucket, lambda: fn.lower(*args),
                                    fresh))
        t0 = monotonic_ns()
        labels = np.asarray(dev)
        rec["compute_ns"] = monotonic_ns() - t0
        done, rec["rounds"], rec["sweeps"] = unpack_status(labels[-1])
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
