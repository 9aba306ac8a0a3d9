"""Wing & Gong / Lowe linearizability checking, host + TPU.

The reference delegates this to knossos (`checker.clj:202-233`:
`knossos.competition/analysis`, `linear/analysis`, `wgl/analysis`). Both
of knossos's searches explore *configurations* — (set of linearized ops,
model state) pairs — memoizing visited configurations. We keep that
algorithm but re-shape it for SIMD:

- A configuration is `(p, window-bitmask, state)`: every entry below `p`
  (in invocation order) is linearized; the uint32 mask covers entries
  `[p, p+W)`; `state` indexes the model's pre-tabulated state space
  (encode.py). This fixed-width encoding is exact as long as no candidate
  entry falls `>= W` past the first unlinearized entry; when that happens
  the kernel flags the history and the caller falls back to the unbounded
  host search — the kernel is sound, never wrong.
- One BFS step linearizes exactly one entry in every live configuration,
  so the search is a `lax.while_loop` of at most `m` steps over a
  fixed-size frontier `[B, F]`, batched over `B` histories (vmap over
  keys/histories is the TPU win: jepsen shards its keyspace precisely so
  histories stay short — independent.clj:2-7).
- Candidate entries: `j` may linearize next iff
  `inv_t[j] < min(ret_t[unlinearized])` — the standard minimal-op rule.
  Crashed (`:info`) entries never block (`ret_t = INF`) and may either
  take effect (a normal transition) or never happen (a "discard" action:
  mark linearized, keep the state).
- Deduplication is a sort + unique-compaction on packed config keys each
  step (the memo set of the sequential algorithm becomes per-step
  frontier dedup; BFS levels never revisit earlier levels because every
  config at level k has exactly k entries linearized).
"""

from __future__ import annotations

import functools
import logging
import os as _os
import threading as _threading
import time as _time
import warnings as _warnings
from typing import Any, Sequence

import numpy as np

from .. import history as h
from .. import telemetry
from ..checker import models as model_mod
from ..history import History
from . import profiler
from .encode import INF, Encoded, EncodingError, encode

BIG = int(INF)

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# Host search (unbounded window; correctness reference and fallback)
# ---------------------------------------------------------------------------

def _min_ret(p: int, wmask: int, m, sufmin, ret_t) -> int:
    """Earliest completion among unlinearized entries at config (p,
    wmask): the candidate cutoff — only entries invoked before it may
    linearize next. Shared by the searches and witness extraction."""
    span = wmask.bit_length()
    mr = int(sufmin[min(p + span, m)])
    for i in range(span):
        if not (wmask >> i) & 1 and p + i < m:
            r = int(ret_t[p + i])
            if r < mr:
                mr = r
    return mr


def search_host(enc: Encoded, witness: bool = False) -> dict:
    """Exhaustive WGL over an Encoded history. Returns {'valid?': bool}
    plus witness info (furthest entry reached, pending ops, states) when
    witness=True and the history is invalid."""
    m = enc.m
    if m == 0:
        return {"valid?": True}
    inv_t = enc.inv_t
    ret_t = enc.ret_t
    crashed = enc.crashed
    trans = enc.trans
    sufmin = enc.suffix_min_ret()

    # config = (p, wmask, state); wmask bit i == entry p+i linearized;
    # bit 0 always clear (p is the first unlinearized entry).
    s0 = enc.init_state
    seen: set[tuple[int, int, int]] = set()
    stack: list[tuple[int, int, int]] = [(0, 0, s0)]
    seen.add((0, 0, s0))
    best_p = 0
    best_cfgs: list[tuple[int, int, int]] = [(0, 0, s0)]

    while stack:
        p, wmask, st = stack.pop()
        if p >= m:
            return {"valid?": True}
        if p > best_p:
            best_p, best_cfgs = p, []
        if p == best_p and len(best_cfgs) < 8:
            best_cfgs.append((p, wmask, st))
        min_ret = _min_ret(p, wmask, m, sufmin, ret_t)
        # candidates: unlinearized j with inv_t[j] < min_ret (inv_t sorted)
        i = 0
        while p + i < m and int(inv_t[p + i]) < min_ret:
            if not (wmask >> i) & 1:
                e = p + i
                nmask = wmask | (1 << i)
                # advance past the linearized prefix
                t = _trailing_ones(nmask)
                np_, nmask_ = p + t, nmask >> t
                s2 = int(trans[e, st])
                if s2 >= 0:
                    cfg = (np_, nmask_, s2)
                    if cfg not in seen:
                        seen.add(cfg)
                        stack.append(cfg)
                if crashed[e]:
                    cfg = (np_, nmask_, st)
                    if cfg not in seen:
                        seen.add(cfg)
                        stack.append(cfg)
            i += 1

    out: dict = {"valid?": False}
    if witness:
        out["op"] = enc.entry_ops[best_p] if best_p < m else None
        # search-dynamics telemetry: where in the history the search
        # got stuck — the witness-position percentile feeding the
        # coverage atlas and ROADMAP-3's early-exit tuning
        out["witness-entry"] = int(best_p)
        out["entry-count"] = int(m)
        cfgs = []
        for p, wmask, st in best_cfgs:
            # pending = every unlinearized entry in flight at the stuck
            # point: invoked before the earliest completion among
            # unlinearized entries (can lie well past the mask span).
            min_ret = _min_ret(p, wmask, m, sufmin, ret_t)
            pending = []
            i = 0
            while p + i < m and int(inv_t[p + i]) < min_ret:
                if not (wmask >> i) & 1:
                    pending.append(enc.entry_ops[p + i])
                    if len(pending) >= 4:
                        break
                i += 1
            cfgs.append({"model": enc.states[st], "pending": pending})
        out["configs"] = cfgs
        out["previous-ok"] = enc.entry_ops[best_p - 1] if best_p else None
    return out


def search_host_reach(enc: Encoded) -> int:
    """Exhaustive host search returning the bitmask of model states the
    history can end in (0 = not linearizable). Host analog of the
    kernel's reach mode, for per-segment fallback. Every row the device
    leaves to the host comes through here: one `wgl.host-reach` span
    and one `wgl.host-reach.rows` count per call."""
    telemetry.count("wgl.host-reach.rows")
    with telemetry.span("wgl.host-reach"):
        return _search_host_reach(enc)


def _search_host_reach(enc: Encoded) -> int:
    m = enc.m
    if m == 0:
        return 1 << enc.init_state
    inv_t, ret_t, crashed, trans = (enc.inv_t, enc.ret_t, enc.crashed,
                                    enc.trans)
    sufmin = enc.suffix_min_ret()
    seen = {(0, 0, enc.init_state)}
    stack = [(0, 0, enc.init_state)]
    out = 0
    while stack:
        p, wmask, st = stack.pop()
        if p >= m:
            out |= 1 << st
            continue
        min_ret = _min_ret(p, wmask, m, sufmin, ret_t)
        i = 0
        while p + i < m and int(inv_t[p + i]) < min_ret:
            if not (wmask >> i) & 1:
                e = p + i
                nmask = wmask | (1 << i)
                t = _trailing_ones(nmask)
                np_, nmask_ = p + t, nmask >> t
                s2 = int(trans[e, st])
                nexts = [s2] if s2 >= 0 else []
                if crashed[e]:
                    nexts.append(st)
                for s_next in nexts:
                    cfg = (np_, nmask_, s_next)
                    if cfg not in seen:
                        seen.add(cfg)
                        stack.append(cfg)
            i += 1
    return out


def _trailing_ones(x: int) -> int:
    t = 0
    while x & 1:
        x >>= 1
        t += 1
    return t


def search_host_model(model, hist: History, witness: bool = False) -> dict:
    """Object-model WGL for models whose state space can't be tabulated
    (mirrors knossos stepping model values directly)."""
    from .encode import entries as entries_fn

    ents = entries_fn(hist)
    m = len(ents)
    if m == 0:
        return {"valid?": True}
    inv_t = [e[0] for e in ents]
    ret_t = [e[1] for e in ents]
    crashed = [e[2] for e in ents]
    ops = [e[3] for e in ents]
    sufmin = [BIG] * (m + 1)
    for i in range(m - 1, -1, -1):
        sufmin[i] = min(sufmin[i + 1], ret_t[i])

    seen: set = set()
    start = (0, 0, model)
    stack = [start]
    seen.add((0, 0, model))
    best_p = 0
    best: list = [start]
    while stack:
        p, wmask, st = stack.pop()
        if p >= m:
            return {"valid?": True}
        if p > best_p:
            best_p, best = p, []
        if p == best_p and len(best) < 8:
            best.append((p, wmask, st))
        span = wmask.bit_length()
        min_ret = sufmin[min(p + span, m)]
        for i in range(span):
            if not (wmask >> i) & 1 and p + i < m:
                min_ret = min(min_ret, ret_t[p + i])
        i = 0
        while p + i < m and inv_t[p + i] < min_ret:
            if not (wmask >> i) & 1:
                e = p + i
                nmask = wmask | (1 << i)
                t = _trailing_ones(nmask)
                np_, nmask_ = p + t, nmask >> t
                st2 = st.step(ops[e])
                if not model_mod.is_inconsistent(st2):
                    cfg = (np_, nmask_, st2)
                    if cfg not in seen:
                        seen.add(cfg)
                        stack.append(cfg)
                if crashed[e]:
                    cfg = (np_, nmask_, st)
                    if cfg not in seen:
                        seen.add(cfg)
                        stack.append(cfg)
            i += 1
    out: dict = {"valid?": False}
    if witness:
        out["op"] = ops[best_p] if best_p < m else None
        out["witness-entry"] = int(best_p)
        out["entry-count"] = int(m)
        out["configs"] = [{"model": st, "pending":
                           [ops[p + i] for i in range(wmask.bit_length() + 1)
                            if p + i < m and not (wmask >> i) & 1][:4]}
                          for p, wmask, st in best]
    return out


# ---------------------------------------------------------------------------
# Batched device kernel
# ---------------------------------------------------------------------------

VALID = 1
INVALID = 0
UNKNOWN = -1
RUNNING = -2


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


class RangeError(Exception):
    """History too large for the kernel's f32-exact position range;
    callers fall back to the host search."""


# ---------------------------------------------------------------------------
# Device-failure degradation ladder
# ---------------------------------------------------------------------------
#
# The host searches are exact, so a device that can't run the kernel is
# a performance problem, never a correctness one: analysis steps DOWN —
# halve the batch (smaller launches fit where one big one OOMed), then
# halve the search width, then the host search as the floor — instead
# of dying. "Faster linearizability checking via P-compositionality"
# (PAPERS.md) is what makes the intermediate rungs sound: decomposed
# searches answer the same question. Every rung is counted in telemetry
# (wgl.ladder.*) and the verdict carries the path taken.

def device_error_kind(e: BaseException) -> str | None:
    """Classifies an exception from a kernel launch: 'oom' (XLA
    RESOURCE_EXHAUSTED / allocator failure — retry smaller), 'compile'
    (compilation failure — this shape is unrunnable, don't re-attempt
    it per sub-batch), 'device' (any other XLA/jax runtime failure —
    INTERNAL, device lost; degradable, the host floor is exact), or
    None (not a device failure: re-raise, it's a bug)."""
    if isinstance(e, (RangeError, EncodingError)):
        return None
    s = str(e)
    if ("RESOURCE_EXHAUSTED" in s or "Out of memory" in s
            or "out of memory" in s or "OOM" in s):
        return "oom"
    if ("error during compilation" in s or "Compilation failure" in s
            or "UNIMPLEMENTED" in s or "FAILED_PRECONDITION" in s):
        return "compile"
    if type(e).__name__ in ("XlaRuntimeError", "JaxRuntimeError"):
        # XlaRuntimeError is jax's RUNTIME error type too, not only
        # compilation: a kernel regression lands here, so this kind is
        # logged loudly (error, not warning) — verdicts stay correct
        # via the host floor, but a silently dead device path would
        # cost 10-100x per analysis
        return "device"
    return None


_ladder_local = _threading.local()


def _ladder_steps() -> list | None:
    return getattr(_ladder_local, "steps", None)


def _ladder_note(step: str) -> None:
    """Counts a degradation rung and records it on the ambient ladder
    scope (analysis attaches the path to its verdict)."""
    telemetry.count(f"wgl.ladder.{step}")
    steps = _ladder_steps()
    if steps is not None and (not steps or steps[-1] != step):
        steps.append(step)


class _ladder_scope:
    """Collects the degradation rungs walked during one analysis call
    (thread-local; nested scopes share the outermost list)."""

    def __enter__(self):
        self.own = _ladder_steps() is None
        if self.own:
            _ladder_local.steps = []
        return _ladder_local.steps

    def __exit__(self, *exc):
        if self.own:
            _ladder_local.steps = None
        return False


class _ladder_fork:
    """Records rungs on a fresh list — one result's OWN provenance —
    then merges them back onto the enclosing scope. Slicing the shared
    scope list instead would let its consecutive-dedup suppress a rung
    that belongs to a different result (e.g. chunk B's OOM after chunk
    A's in analysis_batch_streamed). Telemetry counts happen inside
    _ladder_note, so the merge only appends, never re-counts."""

    def __enter__(self):
        self.outer = _ladder_steps()
        _ladder_local.steps = []
        return _ladder_local.steps

    def __exit__(self, *exc):
        forked = _ladder_local.steps
        _ladder_local.steps = self.outer
        if self.outer is not None:
            for s in forked:
                if not self.outer or self.outer[-1] != s:
                    self.outer.append(s)
        return False


def _ladder_classify(e: BaseException, what: str) -> str:
    """One device failure becomes one counted, logged ladder rung; a
    non-device exception re-raises (a bug, not a rung)."""
    kind = device_error_kind(e)
    if kind is None:
        raise e
    _ladder_note(kind)
    log = logger.error if kind == "device" else logger.warning
    log("%s failed on device (%s: %s); degrading",
        what, kind, str(e)[:200])
    return kind


class PackedBatch:
    """A bucket of Encoded histories padded to common (M, S).

    Positions are rank-compressed per history: the kernel only compares
    invocation/completion positions, so each history's finite positions
    are remapped to their dense rank. Ranks stay < 2m < 2^22, keeping
    them exact through the kernel's f32 one-hot contractions for any
    history up to 2^21 entries (RangeError beyond)."""

    __slots__ = ("inv_t", "ret_t", "trans", "m", "sufmin",
                 "st0", "M", "S", "B", "has_crashed")

    def __init__(self, encs: Sequence[Encoded]):
        with telemetry.span("wgl.pack"):
            self._pack(encs)

    def _pack(self, encs: Sequence[Encoded]) -> None:
        _pack_t0 = _time.monotonic_ns()
        B = len(encs)
        self.has_crashed = any(bool(e.crashed.any()) for e in encs)
        M = max((e.m for e in encs), default=0)
        # Bucket to powers of two so the jitted kernel compiles once per
        # bucket rather than once per history length. Generous floors keep
        # the number of compiled variants small; padding compute is cheap.
        M = _next_pow2(max(M, 64))
        S = _next_pow2(max((e.n_states for e in encs), default=1) or 1)
        S = max(S, 8)
        # One packed row per distinct history/segment, plus a sentinel
        # empty row at index K that batch-padding rows point at. Search
        # rows reference these via the kernel's row->segment indirection,
        # so checking the same segment from S start states shares one
        # copy of its tensors.
        K = B + 1
        self.B, self.M, self.S = B, M, S
        self.inv_t = np.full((K, M), BIG, dtype=np.int32)
        self.ret_t = np.full((K, M), BIG, dtype=np.int32)
        self.trans = np.full((K, M, S), -1, dtype=np.int32)
        self.m = np.zeros(K, dtype=np.int32)
        self.sufmin = np.full((K, M + 1), BIG, dtype=np.int32)
        for b, e in enumerate(encs):
            mm = e.m
            self.m[b] = mm
            if not mm:
                continue
            if 2 * mm >= (1 << 21):
                raise RangeError(
                    f"history with {mm} entries exceeds the kernel's "
                    "f32-exact rank range")
            fin = e.ret_t < INF
            order = np.unique(np.concatenate([e.inv_t, e.ret_t[fin]]))
            inv_r = np.searchsorted(order, e.inv_t).astype(np.int32)
            ret_r = np.full(mm, BIG, dtype=np.int32)
            ret_r[fin] = np.searchsorted(order, e.ret_t[fin])
            self.inv_t[b, :mm] = inv_r
            self.ret_t[b, :mm] = ret_r
            self.trans[b, :mm, :e.n_states] = e.trans
            self.sufmin[b, mm] = BIG
            self.sufmin[b, :mm] = np.minimum.accumulate(ret_r[::-1])[::-1]
        # batch shape profile: real entries vs padded slots — the
        # bucketing waste a tuning loop needs to see
        used = int(self.m.sum())
        slots = int(B * M)
        tel = telemetry.get()
        tel.count("wgl.batch.histories", B)
        tel.count("wgl.batch.entries", used)
        tel.count("wgl.batch.slots", slots)
        if slots:
            tel.gauge("wgl.batch.occupancy", round(used / slots, 4))
            tel.gauge("wgl.batch.padding-waste",
                      round(1 - used / slots, 4))
        # host-side packing is part of the launch pipeline's "encode"
        # wall time; aggregate-only (ensembles pack hundreds of times)
        profiler.get().record_host(
            "pack", _time.monotonic_ns() - _pack_t0, entries=used)

    def rows(self, rows: Sequence[tuple[int, int]]):
        """(row_seg, st0) int32 arrays for (segment, start-state) search
        rows, padded to a power of two with sentinel rows."""
        B = len(rows)
        Bp = _next_pow2(max(B, 1))
        row_seg = np.full(Bp, self.B, dtype=np.int32)  # sentinel = empty
        st0 = np.zeros(Bp, dtype=np.int32)
        for i, (k, s) in enumerate(rows):
            row_seg[i] = k
            st0[i] = s
        return row_seg, st0


# The packed segment tensors (inv_t/ret_t/trans/mseg/sufmin — the big
# per-launch H2D payload) are donated: every launch site converts its
# numpy PackedBatch fields to fresh device arrays per call, so nothing
# reads them after dispatch, and donation hands XLA the buffers as
# scratch instead of keeping them live across the whole search
# (graftlint R3; jepsen_tpu.analysis). Backends that can't alias them
# (CPU) just ignore the donation, with an advisory warning per
# compile — quieted by quiet_unusable_donation() below.
DONATE_ARGNUMS = (0, 1, 2, 3, 4)


def quiet_unusable_donation() -> None:
    """Narrow filter for jax's 'Some donated buffers were not usable'
    advisory, registered by the jit FACTORIES (not at import: merely
    importing this library must not mutate global warning filters).
    The filter is still process-global once a donated kernel is
    built — a per-dispatch catch_warnings would race across the
    checker thread pools — but it only fires for processes that
    actually launch these kernels, and only for this one message.
    (pytest resets filters per test; tests/conftest.py re-asserts
    it.)"""
    _warnings.filterwarnings(
        "ignore", message="Some donated buffers were not usable")


@functools.lru_cache(maxsize=None)
def _jitted_kernel():
    import jax

    from . import spmd

    spmd.enable_compile_cache()
    quiet_unusable_donation()
    return jax.jit(_kernel, static_argnames=("W", "F", "max_iters",
                                             "reach", "debug",
                                             "crash_free"),
                   donate_argnums=DONATE_ARGNUMS)


def _kernel(inv_t, ret_t, trans, mseg, sufmin, row_seg, st0,
            W: int, F: int, max_iters: int, reach: bool = False,
            debug: bool = False, crash_free: bool = False):
    """The batched WGL frontier search.

    Packed data is per-*segment* ([K, M] / [K, M, S]); search rows are
    (row_seg[b], st0[b]) pairs so many rows (e.g. S start states) share
    one segment's tensors.

    reach=False: returns int8 results [B]: 1 valid / 0 invalid /
    -1 unknown (fall back to host); stops each history at first success.

    reach=True: exhausts each history's search and returns
    (out_mask uint32 [B] — bit s set iff final state s is reachable —
    and unknown bool [B]); used by the segment-parallel long-history
    path, which composes per-segment reachability. Requires S <= 32.

    Search-dynamics telemetry: both modes also return three int32
    [max_iters] level-series — live frontier configs entering each BFS
    level, unique successor states produced by it, and dedup hits
    (generated minus unique) — summed over the batch. _drain folds
    them into profiler records (profiler.<kernel>.* counters); cost is
    three scalar reductions + dynamic_update_slice per level."""
    import jax
    import jax.numpy as jnp

    B = row_seg.shape[0]
    K, M = inv_t.shape
    INFi = jnp.int32(BIG)
    u1 = jnp.uint32(1)
    m = mseg[row_seg]                                          # [B]
    S = trans.shape[2]

    # TPU gathers cost ~8ns/element; a naive window gather dominates the
    # whole search. Instead exploit the BFS invariant: at iteration `it`
    # every live config has linearized exactly `it` entries, so its
    # prefix pointer p lies in [it-W, it]. All values any config needs
    # this step live in the contiguous entry slab [it-W, it+2W): fetch
    # it with one dynamic_slice per table and extract per-config windows
    # with one-hot einsum contractions on the MXU — no gathers at all.
    # Positions are clamped to KINF = 2^22 so f32 accumulation is exact.
    L = 3 * W + 8
    KINF = jnp.int32(1 << 22)
    kinf = jnp.float32(1 << 22)
    pad_lo, pad_hi = W, max_iters + L
    inv_p = jnp.pad(jnp.minimum(inv_t, KINF), ((0, 0), (pad_lo, pad_hi)),
                    constant_values=1 << 22).astype(jnp.float32)
    ret_p = jnp.pad(jnp.minimum(ret_t, KINF), ((0, 0), (pad_lo, pad_hi)),
                    constant_values=1 << 22).astype(jnp.float32)
    suf_p = jnp.pad(jnp.minimum(sufmin, KINF), ((0, 0), (pad_lo, pad_hi)),
                    constant_values=1 << 22).astype(jnp.float32)
    trans_p = jnp.pad(trans, ((0, 0), (pad_lo, pad_hi), (0, 0)),
                      constant_values=-1).astype(jnp.float32)
    rows_oh = (row_seg[:, None] ==
               jnp.arange(K)[None, :]).astype(jnp.float32)     # [B,K]
    # Positions/state codes ride through these one-hot contractions as
    # f32 integers up to 2^22; default TPU matmul precision is bf16
    # (8 mantissa bits) which silently rounds them. HIGHEST keeps the
    # products exact.
    PREC = jax.lax.Precision.HIGHEST
    ein = functools.partial(jnp.einsum, precision=PREC)
    iota_w = jnp.arange(W, dtype=jnp.int32)
    iota_l = jnp.arange(L, dtype=jnp.int32)

    def body(carry):
        (p, mask, st, result, out_mask, ovf, it,
         lvl_live, lvl_new, lvl_dup) = carry
        live = p < INFi                                       # [B, F]
        # slab absolute entry range [it-W, it+2W+8)
        slab_iv = jax.lax.dynamic_slice(inv_p, (0, it), (K, L))
        slab_rt = jax.lax.dynamic_slice(ret_p, (0, it), (K, L))
        slab_sf = jax.lax.dynamic_slice(suf_p, (0, it), (K, L))
        slab_tr = jax.lax.dynamic_slice(trans_p, (0, it, 0), (K, L, S))
        row_iv = ein("bk,kl->bl", rows_oh, slab_iv)           # [B, L]
        row_rt = ein("bk,kl->bl", rows_oh, slab_rt)
        row_sf = ein("bk,kl->bl", rows_oh, slab_sf)
        row_tr = ein("bk,kls->bls", rows_oh, slab_tr)         # [B,L,S]

        rel = p - (it - W)                                    # [B,F]
        oh_w = ((rel[:, :, None, None] + iota_w[None, None, :, None])
                == iota_l).astype(jnp.float32)                # [B,F,W,L]
        inv_w = ein("bfwl,bl->bfw", oh_w, row_iv)
        ret_w = ein("bfwl,bl->bfw", oh_w, row_rt)
        cra_w = ret_w >= kinf
        bit = (mask[:, :, None] >> jnp.arange(W, dtype=jnp.uint32)) & u1
        unlin = (bit == 0) & (inv_w < kinf)
        minret_w = jnp.min(jnp.where(unlin, ret_w, kinf), axis=2)  # [B,F]
        oh_t = ((rel[:, :, None] + W) == iota_l).astype(
            jnp.float32)                                      # [B,F,L]
        tail_min = ein("bfl,bl->bf", oh_t, row_sf)
        minret = jnp.minimum(minret_w, tail_min)
        cand = unlin & (inv_w < minret[:, :, None])           # [B,F,W]
        # window overflow: entry p+W would itself be a candidate
        tail_inv = ein("bfl,bl->bf", oh_t, row_iv)
        cfg_ovf = live & (tail_inv < minret)                  # [B,F]

        # next state per candidate: trans[seg, e, st] via two one-hot
        # contractions (window, then current state)
        st_w = ein("bfwl,bls->bfws", oh_w, row_tr)            # [B,F,W,S]
        st_oh = (st[:, :, None] == jnp.arange(S)[None, None, :]
                 ).astype(jnp.float32)                        # [B,F,S]
        st_nxt = ein("bfws,bfs->bfw", st_w, st_oh
                     ).astype(jnp.int32)                      # [B,F,W]
        apply_ok = cand & (st_nxt >= 0)
        disc_ok = cand & cra_w

        # successors [B, F, W, 2]: action 0 = apply, 1 = discard
        nmask = mask[:, :, None] | (u1 << jnp.arange(W, dtype=jnp.uint32))
        invm = ~nmask
        t_ones = jnp.where(
            invm == 0, jnp.uint32(W),
            jax.lax.population_count((invm & (jnp.uint32(0) - invm))
                                     - u1)).astype(jnp.int32)  # [B,F,W]
        s_p = p[:, :, None] + t_ones
        s_mask = jnp.where(t_ones >= W, jnp.uint32(0),
                           nmask >> t_ones.astype(jnp.uint32))
        running = (result == RUNNING)[:, None, None]
        ok0 = apply_ok & live[:, :, None] & ~cfg_ovf[:, :, None] & running
        gen_n = jnp.sum(ok0)
        if crash_free:
            # no crashed entries anywhere in the batch: the discard
            # action never fires, so successors are half as wide and
            # the dedup sorts process half the candidates
            N = F * W
            sp = jnp.where(ok0, s_p, INFi).reshape(B, N)
            sm = jnp.where(ok0, s_mask, 0).reshape(B, N)
            ss = jnp.where(ok0, st_nxt, 0).reshape(B, N)
        else:
            ok1 = disc_ok & live[:, :, None] & ~cfg_ovf[:, :, None] & running
            gen_n = gen_n + jnp.sum(ok1)
            sp = jnp.stack([jnp.where(ok0, s_p, INFi),
                            jnp.where(ok1, s_p, INFi)], axis=3)
            sm = jnp.stack([jnp.where(ok0, s_mask, 0),
                            jnp.where(ok1, s_mask, 0)], axis=3)
            ss = jnp.stack([jnp.where(ok0, st_nxt, 0),
                            jnp.where(ok1, st[:, :, None], 0)], axis=3)
            N = F * W * 2
            sp = sp.reshape(B, N)
            sm = sm.reshape(B, N)
            ss = ss.reshape(B, N)

        # sort + dedup + compact to F slots: two fused multi-key sorts
        # (lax.sort with num_keys compares tuples in ONE pass — far
        # cheaper on TPU than lexsort's per-key stable passes).
        sp, sm, ss = jax.lax.sort((sp, sm, ss), dimension=-1, num_keys=3)
        prev_ne = ((sp != jnp.roll(sp, 1, axis=1))
                   | (sm != jnp.roll(sm, 1, axis=1))
                   | (ss != jnp.roll(ss, 1, axis=1)))
        first = jnp.zeros_like(prev_ne).at[:, 0].set(True)
        uniq = (prev_ne | first) & (sp < INFi)
        n_uniq = jnp.sum(uniq, axis=1)                        # [B]
        sp, sm, ss = jax.lax.sort(
            (jnp.where(uniq, sp, INFi), jnp.where(uniq, sm, 0),
             jnp.where(uniq, ss, 0)), dimension=-1, num_keys=3)
        sp, sm, ss = sp[:, :F], sm[:, :F], ss[:, :F]
        kept = sp < INFi

        # resolution
        done_cfg = kept & (sp >= m[:, None]) & (sp < INFi)    # [B,F]
        succeeded = jnp.any(done_cfg, axis=1)
        new_ovf = ovf | jnp.any(cfg_ovf & live, axis=1) | (n_uniq > F)
        was_running = result == RUNNING
        if reach:
            # accumulate reachable final states; retire success configs
            reached = jnp.any(
                done_cfg[:, :, None]
                & (ss[:, :, None] == jnp.arange(S)[None, None, :]),
                axis=1)                                        # [B,S]
            bits = jnp.sum(
                jnp.where(reached,
                          u1 << jnp.arange(min(S, 32), dtype=jnp.uint32)
                          [None, :S],
                          jnp.uint32(0)), axis=1, dtype=jnp.uint32)
            out_mask = jnp.where(was_running, out_mask | bits, out_mask)
            sp = jnp.where(done_cfg, INFi, sp)
            empty = ~jnp.any(sp < INFi, axis=1)
            result = jnp.where(
                was_running & empty,
                jnp.where(new_ovf, UNKNOWN, INVALID).astype(result.dtype),
                result)
        else:
            empty = n_uniq == 0
            result = jnp.where(was_running & succeeded, VALID, result)
            result = jnp.where(
                was_running & ~succeeded & empty,
                jnp.where(new_ovf, UNKNOWN, INVALID).astype(result.dtype),
                result)
        # freeze resolved histories
        frozen = (result != RUNNING)[:, None]
        sp = jnp.where(frozen, INFi, sp)
        # per-level search-shape samples (batch-summed)
        new_n = jnp.sum(n_uniq).astype(jnp.int32)
        lvl_live = lvl_live.at[it].set(jnp.sum(live).astype(jnp.int32))
        lvl_new = lvl_new.at[it].set(new_n)
        lvl_dup = lvl_dup.at[it].set(
            jnp.maximum(gen_n.astype(jnp.int32) - new_n, 0))
        return (sp, sm, ss, result, out_mask, new_ovf, it + 1,
                lvl_live, lvl_new, lvl_dup)

    def cond(carry):
        result, it = carry[3], carry[6]
        return jnp.any(result == RUNNING) & (it < max_iters)

    p0 = jnp.full((B, F), BIG, dtype=jnp.int32).at[:, 0].set(0)
    mask0 = jnp.zeros((B, F), dtype=jnp.uint32)
    sts0 = jnp.zeros((B, F), dtype=jnp.int32).at[:, 0].set(st0)
    res0 = jnp.where(m == 0, VALID, RUNNING).astype(jnp.int8)
    ovf0 = jnp.zeros(B, dtype=bool)
    out0 = jnp.where(m == 0, u1 << jnp.minimum(
        st0.astype(jnp.uint32), 31), jnp.uint32(0))
    p0 = jnp.where((res0 != RUNNING)[:, None], jnp.int32(BIG), p0)
    lvl0 = jnp.zeros(max_iters, dtype=jnp.int32)
    carry = (p0, mask0, sts0, res0, out0, ovf0, jnp.int32(0),
             lvl0, lvl0, lvl0)
    carry = jax.lax.while_loop(cond, body, carry)
    (p, mask, st, result, out_mask, ovf, it,
     lvl_live, lvl_new, lvl_dup) = carry
    if debug:
        return p, mask, st, result, out_mask, ovf, it
    result = jnp.where(result == RUNNING, UNKNOWN, result)
    # `it` + the level series ride along so callers can account
    # while-loop iterations and search shape without a debug launch
    # (see _drain)
    if reach:
        unknown = (result == UNKNOWN) | ovf
        return out_mask, unknown, it, lvl_live, lvl_new, lvl_dup
    return result, it, lvl_live, lvl_new, lvl_dup


# kernel shape buckets this process has already compiled: first launch
# of a bucket is timed synchronously as compile (trace + XLA compile +
# first execute); later launches stay async and cost only dispatch here
_compiled_buckets: set = set()
_buckets_lock = _threading.Lock()


def _timed_launch(bucket, dispatch, kernel: str = "wgl",
                  meta: dict | None = None):
    """Runs a kernel-dispatch thunk with first-launch-per-bucket
    compile accounting. Shared by the single-device path below and the
    mesh-sharded path (tpu/ensemble.py); their bucket tuples differ in
    shape so one seen-set serves both. The bucket is CLAIMED under a
    lock before measuring: concurrent checkers (compose fans out over
    a thread pool) racing on the same bucket must record one compile,
    not two — the loser's wait lands in execute time, where it
    belongs.

    Profiling: opens a per-launch profiler record (kernel/bucket/meta,
    dispatch + compile phases) and parks it against the dispatched
    output; _drain closes it with the device-wait and readback
    phases."""
    import jax

    with _buckets_lock:
        fresh = bucket not in _compiled_buckets
        if fresh:
            _compiled_buckets.add(bucket)
        n_buckets = len(_compiled_buckets)
    tel = telemetry.get()
    prof = profiler.get()
    rec = prof.begin(kernel, bucket=bucket, **(meta or {}))
    prof.cache_event(kernel, fresh)
    if fresh:
        # distinct-bucket cardinality (set size, not the miss count:
        # a failed first launch unclaims and retries without growing
        # it) — graftlint R5's runtime cross-check. The wgl launch
        # family (wgl/wgl-reach/wgl-sharded) shares one claim set, so
        # each kernel's gauge reads the family total.
        tel.gauge(f"profiler.{kernel}.bucket_cardinality", n_buckets)
    t0 = _time.monotonic_ns()
    try:
        out = dispatch()
    except BaseException:
        # the claimed bucket never compiled: release it, or the real
        # first compile on retry would be misrecorded as a plain launch
        if fresh:
            with _buckets_lock:
                _compiled_buckets.discard(bucket)
        prof.finish(rec)
        raise
    rec["dispatch_ns"] = _time.monotonic_ns() - t0
    if fresh:
        jax.block_until_ready(out)
        rec["compile_ns"] = _time.monotonic_ns() - t0
    return prof.attach(out, rec)


def _launch(pb: PackedBatch, rows: Sequence[tuple[int, int]], W: int,
            F: int, reach: bool):
    """Dispatches one batched search. On a multi-device process the
    rows (and their segment tensors, blocked per device — nothing
    replicated) shard over the mesh via the SPMD program in
    tpu/ensemble.py; single-device processes (and JEPSEN_TPU_SPMD=0)
    take the plain jit path below. Every wgl entry point —
    check_batch / check_batch_reach / check_segmented / check_slices,
    and through the last one the fleet scheduler's cross-tenant
    launches — funnels through here, so they all scale with the mesh
    (and the chaos tests' monkeypatch seam stays this one function)."""
    from . import spmd

    rows = list(rows)
    if spmd.spmd_devices() > 1 and len(rows) >= spmd.MIN_ROWS:
        from . import ensemble

        return ensemble.sharded_launch(
            pb, rows, W, F, reach=reach,
            kernel="wgl-reach" if reach else "wgl")
    import jax.numpy as jnp

    prof = profiler.get()
    row_seg, st0 = pb.rows(rows)
    t0 = _time.monotonic_ns()
    args = (jnp.asarray(pb.inv_t), jnp.asarray(pb.ret_t),
            jnp.asarray(pb.trans), jnp.asarray(pb.m),
            jnp.asarray(pb.sufmin), jnp.asarray(row_seg),
            jnp.asarray(st0))
    h2d_ns = _time.monotonic_ns() - t0
    bucket = (pb.inv_t.shape, pb.trans.shape[2], len(row_seg), W, F,
              pb.M + 4, reach, pb.has_crashed)
    kw = dict(W=W, F=F, max_iters=pb.M + 4, reach=reach,
              crash_free=not pb.has_crashed)
    meta = {"h2d_ns": h2d_ns, "rows": len(row_seg), "batch": pb.B,
            "m": pb.M, "states": pb.S}
    return _timed_launch(
        bucket, lambda: _jitted_kernel()(*args, **kw),
        kernel="wgl-reach" if reach else "wgl", meta=meta)


def _downsample(xs, n: int = 32) -> list[int]:
    """At most n evenly-spaced samples of a level series (profiler
    span attrs carry the curve; a segment search can run 8k levels)."""
    xs = list(xs)
    if len(xs) <= n:
        return [int(x) for x in xs]
    step = len(xs) / n
    return [int(xs[int(i * step)]) for i in range(n)]


def _drain(out, reach: bool):
    """Materializes a launch's outputs (blocking on the device) and
    closes the launch's profiler record with the device-compute wait,
    the D2H readback, the kernel's while-loop iteration count and its
    search-shape series (frontier occupancy / states explored / dedup
    hits per BFS level); the profiler turns them into
    profiler.<kernel>.* counters. Returns result [B] (reach=False) or
    (out_mask, unknown) arrays (reach=True)."""
    prof = profiler.get()
    rec = prof.take(out)
    t0 = _time.monotonic_ns()
    try:
        import jax

        jax.block_until_ready(out)
    except Exception:  # noqa: BLE001 — numpy outs (mocked launches)
        pass
    t_ready = _time.monotonic_ns()
    if reach:
        mask, unk, it, lvl_live, lvl_new, lvl_dup = out
        res = (np.asarray(mask), np.asarray(unk))
    else:
        r, it, lvl_live, lvl_new, lvl_dup = out
        res = np.asarray(r)
    n_it = int(it)
    live = np.asarray(lvl_live)[:n_it]
    new = np.asarray(lvl_new)[:n_it]
    dup = np.asarray(lvl_dup)[:n_it]
    peak = int(live.max()) if live.size else 0
    states = int(new.sum())
    dedup = int(dup.sum())
    t1 = _time.monotonic_ns()
    # the per-launch series ride in the kernel:<k> span attrs
    if rec is not None:
        rec["compute_ns"] = t_ready - t0
        rec["d2h_ns"] = t1 - t_ready
        rec["iterations"] = n_it
        rec["frontier_peak"] = peak
        rec["states_explored"] = states
        rec["dedup_hits"] = dedup
        rec["frontier_curve"] = _downsample(live)
        prof.finish(rec)
    return res


"""Floor of the width-halving rung: below this window the kernel mostly
answers UNKNOWN anyway, so the ladder goes straight to host."""
MIN_LADDER_W = 8


def check_batch(encs: Sequence[Encoded], W: int = 32,
                F: int = 64) -> np.ndarray:
    """Checks a batch of encoded histories on device. Returns int8 [B]
    (VALID/INVALID/UNKNOWN). UNKNOWN means the fixed-width search couldn't
    decide (window or frontier overflow) — fall back to search_host.

    Device failures (OOM, compile) walk the degradation ladder instead
    of raising: halve the batch, then halve the search width, then
    report UNKNOWN for the affected histories so callers take the host
    floor. The result is therefore never *wrong* on device failure,
    only less decisive."""
    try:
        pb = PackedBatch(encs)
        rows = [(i, e.init_state) for i, e in enumerate(encs)]
        res = _drain(_launch(pb, rows, W, F, reach=False), reach=False)
        return res[:pb.B]
    except Exception as e:  # noqa: BLE001 — device ladder
        kind = _ladder_classify(e, "batched kernel")
    # a compile failure is deterministic for the shape: re-attempting
    # compilation on every halved sub-batch would just fail B more
    # times, so that rung is skipped (width-halving below DOES change
    # the compiled shape and still applies)
    if kind != "compile" and len(encs) > 1:
        # smaller launches fit where one big one OOMed (and isolate a
        # poisoned shape bucket to half the batch)
        _ladder_note("batch-halved")
        mid = len(encs) // 2
        return np.concatenate([check_batch(encs[:mid], W, F),
                               check_batch(encs[mid:], W, F)])
    if W > MIN_LADDER_W:
        # a narrower window/frontier shrinks every per-step tensor;
        # histories needing the wider window come back UNKNOWN, which
        # is sound (host fallback decides)
        _ladder_note("width-halved")
        return check_batch(encs, max(W // 2, MIN_LADDER_W),
                           max(F // 2, 2 * MIN_LADDER_W))
    _ladder_note("host-floor")
    return np.full(len(encs), UNKNOWN, dtype=np.int8)


def check_batch_reach(encs: Sequence[Encoded], W: int = 32,
                      F: int = 32) -> tuple[np.ndarray, np.ndarray]:
    """Exhaustive reachability over a batch: returns (out_mask uint32 [B]
    — bit s set iff the whole history can linearize ending in state s —
    and unknown bool [B]). Requires every n_states <= 32. Device
    failures degrade like check_batch: smaller launches, then all-
    unknown (callers host-search unknown rows)."""
    assert max((e.n_states for e in encs), default=1) <= 32, \
        "reach mode packs states into a uint32"
    try:
        pb = PackedBatch(encs)
        rows = [(i, e.init_state) for i, e in enumerate(encs)]
        out, unk = _drain(_launch(pb, rows, W, F, reach=True),
                          reach=True)
        return out[:pb.B], unk[:pb.B]
    except Exception as e:  # noqa: BLE001 — device ladder
        kind = _ladder_classify(e, "batched reach kernel")
    if kind != "compile" and len(encs) > 1:  # see check_batch
        _ladder_note("batch-halved")
        mid = len(encs) // 2
        a_out, a_unk = check_batch_reach(encs[:mid], W, F)
        b_out, b_unk = check_batch_reach(encs[mid:], W, F)
        return (np.concatenate([a_out, b_out]),
                np.concatenate([a_unk, b_unk]))
    _ladder_note("host-floor")
    return (np.zeros(len(encs), dtype=np.uint32),
            np.ones(len(encs), dtype=bool))


def check_slices(slices: Sequence[tuple[Encoded, int]],
                 W: int = 24, F: int = 48
                 ) -> tuple[np.ndarray, np.ndarray]:
    """The fleet's cross-run batching entry point: packs (encoded
    slice, start state) rows from MANY tenants' streams into ONE reach
    launch (jepsen_tpu.fleet.scheduler — continuous batching, as in
    inference serving). Distinct rows may share an Encoded (one
    segment searched from several live start states costs one packed
    history, several rows), so slices dedupe by identity before
    packing. Returns (out_mask uint32 [len(slices)], unknown bool
    [len(slices)]), row i answering slices[i]. Requires every
    n_states <= 32 (reach packs states into a uint32).

    Device failures walk the same ladder as check_batch_reach —
    smaller launches, then the HOST floor, which here computes real
    per-row masks (search_host_reach) instead of all-unknown: a fleet
    under device pressure gets slower, never less decisive."""
    slices = list(slices)
    if not slices:
        return (np.empty(0, dtype=np.uint32),
                np.empty(0, dtype=bool))
    assert max(e.n_states for e, _s in slices) <= 32, \
        "reach mode packs states into a uint32"
    encs: list[Encoded] = []
    idx: dict[int, int] = {}
    rows: list[tuple[int, int]] = []
    for enc, s in slices:
        j = idx.get(id(enc))
        if j is None:
            j = idx[id(enc)] = len(encs)
            encs.append(enc)
        rows.append((j, int(s)))
    try:
        pb = PackedBatch(encs)
        out, unk = _drain(_launch(pb, rows, W, F, reach=True),
                          reach=True)
        return (np.asarray(out[:len(rows)], dtype=np.uint32),
                np.asarray(unk[:len(rows)], dtype=bool))
    except Exception as e:  # noqa: BLE001 — device ladder
        kind = _ladder_classify(e, "slices kernel")
    if kind != "compile" and len(slices) > 1:  # see check_batch
        _ladder_note("batch-halved")
        mid = len(slices) // 2
        a_out, a_unk = check_slices(slices[:mid], W, F)
        b_out, b_unk = check_slices(slices[mid:], W, F)
        return (np.concatenate([a_out, b_out]),
                np.concatenate([a_unk, b_unk]))
    _ladder_note("host-floor")
    out = np.fromiter(
        (search_host_reach(e.with_init(s)) for e, s in slices),
        dtype=np.uint32, count=len(slices))
    return out, np.zeros(len(slices), dtype=bool)


# ---------------------------------------------------------------------------
# Segment-parallel checking of long histories
# ---------------------------------------------------------------------------

def valid_cut_points(enc: Encoded) -> np.ndarray:
    """Entry indices where a compositional cut is sound: every earlier
    entry completed before this entry invoked (zero ops span the cut),
    so real-time order forces all pre-cut ops before all post-cut ops
    in ANY linearization. Crashed entries (ret=INF) forbid all later
    cuts."""
    m = enc.m
    if m == 0:
        return np.empty(0, dtype=np.int32)
    prefix_max = np.maximum.accumulate(enc.ret_t)
    valid = np.zeros(m, dtype=bool)
    valid[1:] = prefix_max[:-1] < enc.inv_t[1:]
    # int32: entry indices stay < 2^21 (the kernel's rank range), so
    # the 8-byte default index type just doubles the memory traffic
    return np.flatnonzero(valid).astype(np.int32)


def segment_cuts(enc: Encoded, target_len: int = 2048,
                 vcuts: np.ndarray | None = None) -> list[int]:
    """Cut points for compositional checking (see valid_cut_points);
    segments come out a little over target_len, degrading gracefully to
    bigger trailing segments when few cuts exist. Pass vcuts to reuse
    an already-computed valid_cut_points array."""
    m = enc.m
    if m == 0:
        return [0, 0]
    idx = valid_cut_points(enc) if vcuts is None else vcuts
    cuts = [0]
    want = target_len
    while want < m:
        j = np.searchsorted(idx, want)
        if j >= len(idx):
            break
        e = int(idx[j])
        cuts.append(e)
        want = e + target_len
    cuts.append(m)
    return cuts


class _SegmentCheckpoint:
    """CRC-framed (k, s) -> mask log keyed by a fingerprint of the
    history + transition tables + cut layout, so a checkpoint written
    for different data OR a different model never poisons a check."""

    def __init__(self, path, enc: Encoded, cuts):
        import zlib as _z
        from pathlib import Path as _P

        self.path = _P(path)
        h = _z.crc32(enc.inv_t.tobytes())
        h = _z.crc32(enc.ret_t.tobytes(), h)
        h = _z.crc32(enc.trans.tobytes(), h)  # model semantics
        h = _z.crc32(np.asarray(cuts, dtype=np.int64).tobytes(), h)
        self.fingerprint = int(h)
        self._known: set = set()
        self._reset_needed = False
        self._opened = False

    def load(self) -> dict:
        import json as _json

        from ..store import format as sformat

        out: dict = {}
        if not self.path.exists():
            return out
        try:
            for payload, _end in sformat._scan_path(self.path):
                d = _json.loads(payload)
                if d.get("fp") != self.fingerprint:
                    # different history/model/cuts: restart the file
                    # on the next write, or mixed-fingerprint records
                    # would poison every later load
                    self._reset_needed = True
                    self._known = set()
                    return {}
                out[(d["k"], d["s"])] = d["m"]
        except (OSError, ValueError):
            self._reset_needed = True
            return {}
        self._known = set(out)
        telemetry.count("wgl.checkpoint.loaded", len(out))
        return out

    def _prepare(self):
        """First write: restart a stale/corrupt file, or truncate a
        torn tail so appends stay reachable (the HistoryWriter reopen
        rule — appending after a torn record hides everything later)."""
        from ..store import format as sformat

        self.path.parent.mkdir(parents=True, exist_ok=True)
        if self._reset_needed or not self.path.exists():
            with open(self.path, "wb") as f:
                f.write(sformat.MAGIC)
            self._reset_needed = False
        else:
            end = sformat._valid_prefix_end(self.path)
            if end == 0:
                with open(self.path, "wb") as f:
                    f.write(sformat.MAGIC)
            elif end < self.path.stat().st_size:
                with open(self.path, "r+b") as f:
                    f.truncate(end)
        self._opened = True

    def save_one(self, k: int, s: int, mask: int) -> None:
        import json as _json
        import struct as _struct
        import zlib as _z

        if (k, s) in self._known:
            return
        if not self._opened:
            self._prepare()
        with open(self.path, "ab") as f:
            payload = _json.dumps(
                {"fp": self.fingerprint, "k": k, "s": s,
                 "m": int(mask)}).encode()
            f.write(_struct.pack("<II", len(payload),
                                 _z.crc32(payload)))
            f.write(payload)
        self._known.add((k, s))
        telemetry.count("wgl.checkpoint.saved")

    def save(self, resolved: dict) -> None:
        for (k, s), m in resolved.items():
            if m is not None:
                self.save_one(k, s, m)


def _wave_bounds(K: int, early: bool) -> list[tuple[int, int]]:
    """Segment-index waves for early-exit composition: geometric
    doubling from 4, so a witness at fraction p of the history costs
    O(p) launches + one wave of overshoot, while a valid history pays
    only ~log2(K/4) extra dispatches over the single-launch path.
    Without early exit (or for small K) everything is one wave."""
    if not early or K < 8:
        return [(0, K)]
    out = []
    lo, w = 0, 4
    while lo < K:
        out.append((lo, min(K, lo + w)))
        lo += w
        w *= 2
    return out


def _screen_wave(enc: Encoded, segs, cuts, vcuts, lo: int, hi: int,
                 S: int, W: int, F: int, prefix_screen: int,
                 resolved: dict) -> list[tuple[int, int]]:
    """The device prefix screen of one wave (see check_segmented's
    prefix_screen): resolves the rows it can into `resolved` and
    returns the (segment, start-state) rows left for the main launch."""
    rows: list[tuple[int, int]] = []
    # Screening runs ON DEVICE: all (segment, start-state) prefix
    # rows go up in one small batched reach launch (the prefixes
    # bucket to one tiny kernel shape), replacing K x S sequential
    # host searches. Rare UNKNOWN prefix rows fall back to the
    # exact host search.
    screen_rows: list[tuple[int, int]] = []
    screen_segs: dict[int, tuple] = {}  # k -> (pre_enc, exact)
    for k in range(lo, hi):
        klo, khi = cuts[k], cuts[k + 1]
        j = np.searchsorted(vcuts, klo + prefix_screen)
        pre_end = int(vcuts[j]) if (j < len(vcuts)
                                    and vcuts[j] < khi) else khi
        if (pre_end - klo > 2 * prefix_screen
                or enc.crashed[klo:pre_end].any()):
            # No NEARBY interior cut (one such "prefix" would pad
            # the whole screen batch up to its length), or crashed
            # entries in the prefix: screening can't shrink the
            # work cheaply — leave every state to the main launch
            # (minus checkpoint-restored entries).
            rows.extend((k, s) for s in range(S)
                        if resolved.get((k, s)) is None)
            continue
        exact = pre_end == khi
        pre = segs[k] if exact else enc.segment(klo, pre_end)
        screen_segs[k] = (pre, exact)
        screen_rows.extend((k, s) for s in range(S)
                           if resolved.get((k, s)) is None)
    if screen_rows:
        ks = sorted(screen_segs)
        kidx = {k: i for i, k in enumerate(ks)}
        launch_rows = [(kidx[k], s) for k, s in screen_rows]
        try:
            pre_pb = PackedBatch([screen_segs[k][0] for k in ks])
            p_out, p_unk = _drain(
                _launch(pre_pb, launch_rows, W, F, reach=True),
                reach=True)
            p_out = p_out[:len(launch_rows)]
            p_unk = p_unk[:len(launch_rows)]
        except Exception as e:  # noqa: BLE001 — ladder rung
            # screen launch failed: every screened row resolves on
            # host (the exact search — sound, just slower)
            _ladder_classify(e, "segmented prefix screen")
            _ladder_note("segment-host-screen")
            p_out = np.zeros(len(launch_rows), dtype=np.uint32)
            p_unk = np.ones(len(launch_rows), dtype=bool)
        for i, (k, s) in enumerate(screen_rows):
            pre, exact = screen_segs[k]
            mask = (search_host_reach(pre.with_init(s))
                    if p_unk[i] else int(p_out[i]))
            if exact:
                resolved[(k, s)] = mask
            elif mask == 0:
                resolved[(k, s)] = 0
            else:
                rows.append((k, s))
    return rows


def _launch_ready(out) -> bool:
    """Whether a dispatched launch has finished on the device (outputs
    without is_ready, a mocked launch's numpy arrays, are ready)."""
    is_ready = getattr(out[0], "is_ready", None)
    return is_ready is None or bool(is_ready())


def _resolve_wave(enc: Encoded, segs, cuts, vcuts, lo: int, hi: int,
                  S: int, W: int, F: int, prefix_screen: int,
                  resolved: dict, pad_to: int | None = None,
                  prefetch=None) -> None:
    """Resolves every unresolved (segment, start-state) reach mask for
    segments [lo, hi): the device prefix screen first (rows whose
    time-complete prefix proves mask 0 never reach the main launch),
    then ONE batched reach launch over the survivors. Device failures
    leave rows at None for the caller's lazy host floor. pad_to pads
    the wave's packed batch with empty segments so wave launches
    bucket to a fixed set of compile shapes. prefetch (a
    certify.Prefetch) works on the host while the main launch runs."""
    if prefix_screen:
        with telemetry.span("wgl.screen"):
            rows = _screen_wave(enc, segs, cuts, vcuts, lo, hi, S, W, F,
                                prefix_screen, resolved)
    else:
        rows = [(k, s) for k in range(lo, hi) for s in range(S)
                if resolved.get((k, s)) is None]
    if not rows:
        return
    # One packed copy per segment; rows share it via the kernel's
    # row->segment indirection. Device failure marks every row
    # unresolved: the composition host-searches ONLY the states it
    # actually reaches (the lazy floor), and each result still
    # checkpoints, so a retry resumes instead of re-searching.
    wave_segs = list(segs[lo:hi])
    if pad_to and len(wave_segs) < pad_to:
        empty = enc.segment(cuts[lo], cuts[lo])
        wave_segs += [empty] * (pad_to - len(wave_segs))
    try:
        pb = PackedBatch(wave_segs)
        launch_rows = [(k - lo, s) for k, s in rows]
        dev = _launch(pb, launch_rows, W, F, reach=True)
        if prefetch is not None:
            prefetch.work(lambda: _launch_ready(dev))
        out, unk = _drain(dev, reach=True)
        out = out[:len(launch_rows)]
        unk = unk[:len(launch_rows)]
        for i, (k, s) in enumerate(rows):
            resolved[(k, s)] = None if unk[i] else int(out[i])
    except Exception as e:  # noqa: BLE001 — ladder rung
        _ladder_classify(e, "segmented main launch")
        _ladder_note("segment-host-floor")
        for k, s in rows:
            resolved.setdefault((k, s), None)


def check_segmented(enc: Encoded, target_len: int | None = None,
                    W: int = 24,
                    F: int = 48, witness: bool = False,
                    prefix_screen: int = 96,
                    checkpoint_path=None,
                    checkpoint_dir=None,
                    early_exit: bool | None = None,
                    prefetch=None) -> dict | None:
    """Checks one long history by cutting it into segments, computing
    per-(segment, start-state) final-state reachability in ONE batched
    device launch, and composing reachability masks across segments.
    Returns None when the history doesn't segment usefully (caller uses
    the plain kernel).

    checkpoint_path / checkpoint_dir: persists every resolved
    (segment, start-state)
    reachability mask to a CRC-framed log as it lands, and reloads it
    on entry — a crashed or interrupted long check resumes without
    re-searching finished segments (SURVEY §5: long-running checker
    jobs checkpoint search state; the history itself checkpoints the
    same way in the store). Entries are keyed by history fingerprint
    so a stale checkpoint for different data is ignored.
    checkpoint_path names one exact file (single-check usage);
    checkpoint_dir derives a per-fingerprint filename, so concurrent
    checkers (per-key independent checks, composed checkers) sharing a
    store directory never fight over one file.

    prefix_screen: before the main launch, each (segment, start-state)
    row is screened over the segment's first ~prefix_screen entries
    ENDING AT A VALID CUT — a time-complete sub-history, so
    reach(prefix) == 0 soundly proves reach(segment) == 0 (an
    arbitrary entry-prefix would NOT be: a pending read may observe a
    later write). The screen itself is one batched device reach
    launch over all prefix rows (a tiny kernel bucket); rare UNKNOWN
    rows fall back to the exact host search. Wrong start states die
    in the prefix, so the main launch runs ~half the rows.

    early_exit (default on; JEPSEN_TPU_EARLY_EXIT=0 disables):
    segments resolve in geometric waves composed as they land, so an
    invalid history witnessed at fraction p of the search costs ~p of
    the check (PR 9's `search.witness-position` proves where the
    anomaly localizes; doc/spmd.md documents the semantics). Verdicts,
    masks, witnesses and certificates are identical either way — a
    wave resolves exactly the masks the single launch would have, and
    composition stops at the same failed segment.

    prefetch: wgl.analysis's certify.Prefetch when the verdict will
    carry a certificate, else None. It is offered the reach data after
    each wave's composition and works on the host while a wave's main
    launch runs; the verdict is the same either way.

    Stage spans: `wgl.segment` (cut points and segments), then per
    wave `wgl.screen` and `wgl.compose` (the composition, with its
    lazy host floor); `wgl.host-reach.rows` starts at 0 here, so a
    verdict that sent no row to the host search still reports it."""
    telemetry.count("wgl.host-reach.rows", 0)
    if enc.n_states > 32:
        # the per-(segment, state) reach masks are uint32 bitmasks; a
        # bigger state space silently fell back to the whole-history
        # path before — make the bail observable (telemetry counter +
        # a warning naming the model), since the fallback forfeits
        # segment-level checkpointing and anomaly localization
        telemetry.count("wgl.segmented.fallback-states")
        model_name = (type(enc.states[enc.init_state]).__name__
                      if len(enc.states) else "?")
        logger.warning(
            "check_segmented: %s model has %d states (> 32, the "
            "reach-mask width); falling back to the whole-history "
            "search path", model_name, enc.n_states)
        return None
    if target_len is None:
        # Adaptive: long segments amortize kernel latency best (the
        # sweep puts ~8192 at the single-chip sweet spot), but small
        # histories still need >= ~8 segments for the batch dimension
        # (and for checkpointing) to exist at all
        target_len = min(8192, max(256, enc.m // 8))
    with telemetry.span("wgl.segment"):
        vcuts = valid_cut_points(enc)
        cuts = segment_cuts(enc, target_len, vcuts=vcuts)
        K = len(cuts) - 1
        if K < 2:
            return None
        if 2 * max(cuts[k + 1] - cuts[k] for k in range(K)) >= (1 << 21):
            return None  # a segment alone exceeds the kernel range
        segs = [enc.segment(cuts[k], cuts[k + 1]) for k in range(K)]
    S = enc.n_states
    # resolved mask per (segment, start-state); None = device said
    # UNKNOWN, resolve lazily on host ONLY if the composition actually
    # reaches that state (unknown rows are the hardest searches).
    resolved: dict[tuple[int, int], int | None] = {}
    ckpt = None
    if checkpoint_path is not None:
        ckpt = _SegmentCheckpoint(checkpoint_path, enc, cuts)
    elif checkpoint_dir is not None:
        probe = _SegmentCheckpoint("/dev/null", enc, cuts)
        from pathlib import Path as _P

        ckpt = _SegmentCheckpoint(
            _P(checkpoint_dir)
            / f"frontier-{probe.fingerprint & 0xffffffff:08x}.jlog",
            enc, cuts)
    if ckpt is not None:
        resolved.update(ckpt.load())
    early = early_exit if early_exit is not None else \
        _os.environ.get("JEPSEN_TPU_EARLY_EXIT", "1") != "0"
    waves = _wave_bounds(K, early)
    reach = 1 << enc.init_state
    reaches = [reach]  # reachable-state mask entering each segment
    wstate = 0
    failed_k = None
    for lo, hi in waves:
        _resolve_wave(enc, segs, cuts, vcuts, lo, hi, S, W, F,
                      prefix_screen, resolved,
                      pad_to=(_next_pow2(hi - lo)
                              if len(waves) > 1 else None),
                      prefetch=prefetch)
        if ckpt is not None:
            ckpt.save(resolved)
        with telemetry.span("wgl.compose"):
            for k in range(lo, hi):
                nreach = 0
                for s in range(S):
                    if (reach >> s) & 1:
                        mask = resolved.get((k, s))
                        if mask is None:
                            mask = search_host_reach(
                                segs[k].with_init(s))
                            resolved[(k, s)] = mask
                            if ckpt is not None:
                                ckpt.save_one(k, s, mask)
                        nreach |= mask
                if nreach == 0:
                    failed_k = k
                    wstate = next(s for s in range(S)
                                  if (reach >> s) & 1)
                    break
                reach = nreach
                reaches.append(reach)
        if prefetch is not None:
            prefetch.offer(cuts, resolved, reaches)
        if failed_k is not None:
            if hi < K:
                # the early-exit payoff: segments past the witness's
                # wave were never launched — an anomaly at 12% of the
                # history cost ~12% of the search
                telemetry.count("wgl.segmented.early-exit")
                telemetry.gauge(
                    "wgl.segmented.early-exit-frac",
                    round(cuts[hi] / max(enc.m, 1), 4))
            break
    if failed_k is not None:
        k = failed_k
        res: dict = {"valid?": False, "failed-segment": k,
                     "segment-range": [cuts[k], cuts[k + 1]]}
        chain = _reach_chain(resolved, reaches, k, wstate)
        if chain is not None:
            # the reach/choice data a certificate re-derives the
            # pre-witness linearization from (jepsen_tpu.tpu
            # .certify); also where the witness sits in the
            # history — the early-exit signal (ROADMAP item 3)
            res["search-chain"] = {"cuts": [int(c) for c in cuts],
                                   "chain": chain}
        if witness:
            w = search_host(segs[k].with_init(wstate),
                            witness=True)
            res.update({kk: v for kk, v in w.items()
                        if kk != "valid?"})
            if "witness-entry" in res:
                # globalize the segment-local stuck entry
                res["witness-entry"] = int(
                    cuts[k] + res["witness-entry"])
                res["entry-count"] = int(enc.m)
        return res
    final_state = next(s for s in range(S) if (reach >> s) & 1)
    chain = _reach_chain(resolved, reaches, K, final_state)
    res = {"valid?": True, "segments": K}
    if chain is not None:
        res["search-chain"] = {"cuts": [int(c) for c in cuts],
                               "chain": chain}
    return res


def _reach_chain(resolved: dict, reaches: list[int], upto: int,
                 final_state: int) -> list[int] | None:
    """A concrete per-segment start-state chain out of the resolved
    reach masks: chain[j] is segment j's start state, chain[upto] =
    final_state, and resolved[(j, chain[j])] contains chain[j+1] for
    every j — the choice data certificates compose per-segment
    linearization orders along. Backward reconstruction; None when a
    mask is missing (shouldn't happen after composition resolved
    them)."""
    chain = [0] * (upto + 1)
    chain[upto] = int(final_state)
    for j in range(upto - 1, -1, -1):
        nxt = chain[j + 1]
        for s in range(32):
            if (reaches[j] >> s) & 1:
                mask = resolved.get((j, s))
                if mask is not None and (mask >> nxt) & 1:
                    chain[j] = s
                    break
        else:
            return None
    return chain


# ---------------------------------------------------------------------------
# Checkpoint-and-extend: incremental re-checking of grown histories
# ---------------------------------------------------------------------------

# The extend path's FIXED cut stride. check_segmented's adaptive
# target_len (m//8-ish) moves the cut layout whenever the history
# grows, which would orphan every checkpointed mask; a fixed stride
# makes the greedy cut schedule prefix-stable (entries below a valid
# cut are frozen by real time, so the same cuts — and the same reach
# masks — fall out of the grown history), which is the whole game.
EXTEND_STRIDE = 512


def _extend_fingerprint(enc: Encoded) -> int:
    """Model-semantics fingerprint for wgl-extend records: the model
    class and initial state (via the models' value-based reprs). Entry
    digests key the HISTORY prefix; this keys the MODEL, so a
    checkpoint written for a different model (or initial value) never
    poisons a resume. Deliberately NOT the transition-table bytes:
    those depend on the whole history's distinct-op set, which grows
    with the suffix — state identity is carried per-state by the
    record's "states" reprs instead."""
    import zlib as _z

    init = enc.states[enc.init_state]
    return int(_z.crc32(
        f"{type(init).__name__}:{init!r}".encode()))


def _remap_record_masks(record: dict, enc: Encoded,
                        reused_segments: int
                        ) -> dict[tuple[int, int], int] | None:
    """Translates a record's (segment, state) -> mask entries into
    THIS encoding's state indices. A grown history can discover new
    distinct ops, which reorders state discovery — indices move, but
    the states themselves (value-carrying model objects with stable
    reprs) do not, and a reach mask is semantically a SET of model
    states. Returns None when any recorded state is unknown to this
    encoding (not a superset — stale record)."""
    new_idx = {repr(s): i for i, s in enumerate(enc.states)}
    old_keys = record["states"]
    mapping = []
    for key in old_keys:
        i = new_idx.get(key)
        if i is None:
            return None
        mapping.append(i)
    out: dict[tuple[int, int], int] = {}
    for key, mask in record["masks"].items():
        k_str, s_str = key.split(":")
        k, s = int(k_str), int(s_str)
        if k >= reused_segments or s >= len(mapping):
            continue
        new_mask = 0
        m = int(mask)
        for j in range(len(mapping)):
            if (m >> j) & 1:
                new_mask |= 1 << mapping[j]
        out[(k, mapping[s])] = new_mask
    return out


def check_extend(enc: Encoded, record: dict | None = None,
                 stride: int = EXTEND_STRIDE, W: int = 24,
                 F: int = 48) -> tuple[dict | None, dict | None]:
    """Segment-composed check with a prefix-stable cut schedule and a
    reusable (segment, state) -> reach-mask frontier. Returns
    (result, new_record); (None, None) when the history doesn't
    segment (caller falls back to the plain paths).

    `record` is a ckpt.py "wgl-extend" record from a previous check of
    a PREFIX of this history. Reuse is earned, never assumed: the
    record's cuts must match this history's greedy schedule position by
    position AND digest by digest (sha256 over the encoded entries
    below each cut) — so a torn, stale, or wrong-history record
    degrades to a full re-check, with `ckpt.stale` counted when a
    record was offered and nothing matched. Masks for the matched
    prefix segments are reused verbatim; only suffix segments launch.
    Fresh and resumed runs compose the SAME exact masks through the
    SAME deterministic composition, so verdicts, search chains — and
    therefore certificates (certify.attach_wgl derives them from the
    search chain alone) — are identical by construction."""
    from . import ckpt as ckpt_mod

    telemetry.count("wgl.host-reach.rows", 0)  # see check_segmented
    if enc.n_states > 32:
        return None, None
    vcuts = valid_cut_points(enc)
    cuts = segment_cuts(enc, stride, vcuts=vcuts)
    K = len(cuts) - 1
    if K < 2:
        return None, None
    if 2 * max(cuts[k + 1] - cuts[k] for k in range(K)) >= (1 << 21):
        return None, None  # a segment alone exceeds the kernel range
    S = enc.n_states
    digests = ckpt_mod.entry_digest_chain(enc, cuts)
    fp = _extend_fingerprint(enc)

    resolved: dict[tuple[int, int], int] = {}
    reused_segments = 0
    if record is not None:
        ok = (record.get("stride") == stride
              and record.get("model_fp") == fp)
        matched = 0
        if ok:
            rcuts = record["cuts"]
            rdigs = record["digests"]
            limit = min(len(rcuts), len(cuts))
            while matched < limit and rcuts[matched] == cuts[matched] \
                    and rdigs[matched] == digests[matched]:
                matched += 1
        # a segment is reusable when BOTH its cut endpoints matched
        reused_segments = max(0, matched - 1)
        remapped = (_remap_record_masks(record, enc, reused_segments)
                    if reused_segments else None)
        if remapped:
            resolved.update(remapped)
            telemetry.count("ckpt.extend.reused-masks",
                            len(resolved))
            telemetry.count("ckpt.extend.resumed")
        else:
            reused_segments = 0
            telemetry.count("ckpt.stale")

    segs = [enc.segment(cuts[k], cuts[k + 1]) for k in range(K)]
    need = [(k, s) for k in range(K) for s in range(S)
            if (k, s) not in resolved]
    if need:
        out, unk = check_slices([(segs[k], s) for k, s in need],
                                W, F)
        for i, (k, s) in enumerate(need):
            # UNKNOWN rows get the exact host search — resolved masks
            # are always exact, so resumed composition is bit-stable
            resolved[(k, s)] = (search_host_reach(
                segs[k].with_init(s)) if unk[i] else int(out[i]))
    telemetry.count("ckpt.extend.computed-masks", len(need))

    reach = 1 << enc.init_state
    reaches = [reach]
    failed_k = None
    wstate = 0
    for k in range(K):
        nreach = 0
        for s in range(S):
            if (reach >> s) & 1:
                mask = resolved.get((k, s))
                if mask is None:
                    # a reused segment can miss a state the old
                    # encoding never had; the exact host search fills
                    # it deterministically
                    mask = int(search_host_reach(
                        segs[k].with_init(s)))
                    resolved[(k, s)] = mask
                nreach |= mask
        if nreach == 0:
            failed_k = k
            wstate = next(s for s in range(S) if (reach >> s) & 1)
            break
        reach = nreach
        reaches.append(reach)

    new_record = {
        "v": ckpt_mod.VERSION, "kind": "wgl-extend",
        "stride": int(stride), "model_fp": fp,
        "cuts": [int(c) for c in cuts], "digests": digests,
        "states": [repr(s) for s in enc.states],
        "masks": {f"{k}:{s}": int(m)
                  for (k, s), m in sorted(resolved.items())},
        "n_ops": int(enc.m), "digest": digests[-1],
    }
    if failed_k is not None:
        k = failed_k
        res: dict = {"valid?": False, "failed-segment": k,
                     "segment-range": [cuts[k], cuts[k + 1]]}
        chain = _reach_chain(resolved, reaches, k, wstate)
        if chain is not None:
            res["search-chain"] = {"cuts": [int(c) for c in cuts],
                                   "chain": chain}
        w = search_host(segs[k].with_init(wstate), witness=True)
        res.update({kk: v for kk, v in w.items() if kk != "valid?"})
        if "witness-entry" in res:
            res["witness-entry"] = int(cuts[k] + res["witness-entry"])
            res["entry-count"] = int(enc.m)
        return res, new_record
    final_state = next(s for s in range(S) if (reach >> s) & 1)
    chain = _reach_chain(resolved, reaches, K, final_state)
    res = {"valid?": True, "segments": K}
    if chain is not None:
        res["search-chain"] = {"cuts": [int(c) for c in cuts],
                               "chain": chain}
    return res, new_record


# ---------------------------------------------------------------------------
# Public analysis API (knossos-analysis-shaped results)
# ---------------------------------------------------------------------------

# Below this many entries the whole-history kernel/host search is cheap
# enough that segment-localized witness extraction isn't worth a launch.
SEGMENT_MIN_M = 4096


def _witness_op_indices(out: dict) -> dict:
    """Attaches the participating op (invocation) indices to an
    invalid analysis as out['op-indices'] — anomaly provenance: the
    stuck op, its predecessor, and every pending op in the surviving
    configs. entry_ops are merged invocations, so the indices join the
    per-op trace (optrace.jsonl) and timeline anchors directly."""
    if out.get("valid?") is not False or "op-indices" in out:
        return out
    idxs = set()

    def add(o):
        i = getattr(o, "index", None)
        if i is None and isinstance(o, dict):
            i = o.get("index")
        if isinstance(i, int) and i >= 0:
            idxs.add(i)

    add(out.get("op"))
    add(out.get("previous-ok"))
    for cfg in out.get("configs") or []:
        for o in (cfg.get("pending") or []) if isinstance(cfg, dict) \
                else []:
            add(o)
    out["op-indices"] = sorted(idxs)
    return out


def _seg_kwargs(W: int | None, F: int | None, **extra) -> dict:
    """check_segmented kwargs: only overrides the leaner segmented
    defaults (W=24/F=48) when the caller tuned W/F explicitly."""
    kw = dict(extra)
    if W is not None:
        kw["W"] = W
    if F is not None:
        kw["F"] = F
    return kw


def extract_witness(enc: Encoded, W: int | None = None,
                    F: int | None = None) -> dict:
    """Bounded witness extraction for a history the device kernel
    flagged INVALID or UNKNOWN.

    For long histories, localizes the FIRST failing segment by
    reach-mask composition (one batched device launch over
    segment x start-state rows) and host-searches only that segment —
    replacing the whole-history `search_host` fallback whose cost is
    unbounded at 1M-op scale (the anomaly path the reference pays hours
    for, jepsen/src/jepsen/checker.clj:202-233). Small or unsegmentable
    histories fall through to the exact whole-history host search.

    Sets result["witness-extraction"] to 'segmented' or 'host' so
    callers (and tests) can see which path ran."""
    if enc.m >= SEGMENT_MIN_M:
        seg = check_segmented(enc, witness=True, **_seg_kwargs(W, F))
        if seg is not None:
            seg["witness-extraction"] = "segmented"
            return _witness_op_indices(seg)
    out = search_host(enc, witness=True)
    out["witness-extraction"] = "host"
    return _witness_op_indices(out)


# Where decide_unknown's order rides on a valid result until
# certify.attach_wgl takes it off as the key's certificate order.
ORDER_KEY = "_order"


def decide_unknown(enc: Encoded, W: int | None = None,
                   F: int | None = None) -> dict:
    """Decides a batch member the kernel returned UNKNOWN.

    A history below SEGMENT_MIN_M first gets the certificate's order
    search (certify._order_search: search_host's configurations and
    moves, walked lazily, earliest candidate first, apply before
    discard), which finds a valid history's linearization long before
    search_host's latest-first DFS does. An order found decides the
    member valid (`witness-extraction` 'order') and rides on the result
    under ORDER_KEY for the certificate. No order, the search's node
    budget spent, or a longer history falls through to
    extract_witness, so every invalid result is extract_witness's.
    Counters: `wgl.batch.order` (decided by the order search),
    `wgl.batch.order-miss` (fell through)."""
    if enc.m < SEGMENT_MIN_M:
        from . import certify

        try:
            found, actions = certify._order_search(enc)
        except certify._Budget:
            found = False
        if found:
            telemetry.count("wgl.batch.order")
            return {"valid?": True, "witness-extraction": "order",
                    ORDER_KEY: actions}
    telemetry.count("wgl.batch.order-miss")
    return extract_witness(enc, W=W, F=F)


def _search_stats(out: dict) -> dict:
    """Attaches out['search'] — the witness-position percentile
    ("nonlinearizable witnessed at 12% of the history") for invalid
    verdicts: the direct input for segment-level early-exit (ROADMAP
    item 3) and the coverage atlas's anomaly-localization ranking."""
    if out.get("valid?") is not False:
        return out
    we = out.get("witness-entry")
    m = out.get("entry-count")
    if we is None and "segment-range" in out:
        we = out["segment-range"][0]
    if we is not None and m:
        out["search"] = {"witness-entry": int(we),
                         "entries": int(m),
                         "witness-position": round(int(we) / int(m),
                                                   4)}
    return out


def analysis(model, hist, algorithm: str = "tpu", W: int | None = None,
             F: int | None = None, checkpoint_path=None,
             checkpoint_dir=None, certify: bool = False) -> dict:
    """Checks a single history against a model.

    algorithm: 'tpu'  — device kernel, host fallback on UNKNOWN
               'wgl'  — host search over encoded tables
               'model' — host search stepping model objects
    Result mirrors knossos analysis maps: {'valid?': bool, 'op': ...,
    'configs': [...], 'analyzer': ...}. When the device kernel failed
    (OOM / compile) and analysis stepped down the degradation ladder,
    the verdict carries the rungs walked as result['degradation'].

    certify=True (the checker entry points pass it; raw kernel paths
    don't) additionally attaches a machine-checkable proof of the
    verdict as result['certificate'] (jepsen_tpu.tpu.certify) — for
    valid, a per-segment linearization order re-derived from the reach
    chain; for invalid, the replayable blocked-frontier witness.

    The verdict is one `wgl.analysis` span (its stages nest in it:
    `wgl.encode`, then check_segmented's); certificate extraction
    follows it as `certify.attach`. A segmented verdict's certificate
    is begun inside it, while the chip runs a wave's main launch
    (`certify.prefetch` spans; certify.Prefetch)."""
    certify_mod = prefetch = None
    if certify:
        from . import certify as certify_mod

        if certify_mod.enabled():
            prefetch = certify_mod.Prefetch(hist)
    with _ladder_scope() as steps:
        enc_box: list = [None]
        with telemetry.span("wgl.analysis"):
            out = _analysis(model, hist, algorithm, W, F,
                            checkpoint_path, checkpoint_dir, enc_box,
                            prefetch)
            if steps:
                out["degradation"] = list(steps)
            _search_stats(out)
        if certify_mod is not None:
            certify_mod.attach_wgl(model, hist, enc_box[0], out,
                                   prefetch)
        return out


def analysis_extend(model, hist, store_path=None,
                    stride: int = EXTEND_STRIDE, W: int | None = None,
                    F: int | None = None,
                    certify: bool = False) -> dict:
    """analysis(), resumable: checks via check_extend's prefix-stable
    segmentation, loading the previous frontier from the ckpt.py store
    at `store_path` and persisting the grown frontier back after the
    verdict. Re-checking a grown history costs O(suffix); a missing,
    torn, stale, or wrong-model record costs a full re-check — never a
    wrong verdict. Histories that don't segment (too short, > 32
    states, unencodable) fall through to plain analysis(), so this is
    always safe to call where analysis() was. Spans as analysis()'s."""
    with _ladder_scope() as steps:
        if not isinstance(hist, History):
            hist = History(hist)
        with telemetry.span("wgl.analysis"):
            enc, out = _analysis_extend(model, hist, store_path, stride,
                                        W, F, steps)
        if out is None:
            telemetry.count("ckpt.extend.fallback")
            return analysis(model, hist, algorithm="tpu", W=W, F=F,
                            certify=certify)
        if certify:
            from . import certify as certify_mod

            certify_mod.attach_wgl(model, hist, enc, out)
        return out


def _analysis_extend(model, hist: History, store_path, stride: int,
                     W: int | None, F: int | None,
                     steps: list) -> tuple[Encoded | None, dict | None]:
    """analysis_extend's verdict: (enc, result), result None where the
    history does not encode or segment."""
    from . import ckpt as ckpt_mod

    try:
        with telemetry.span("wgl.encode"):
            enc = encode(model, hist)
    except EncodingError:
        return None, None
    record = None
    if store_path is not None:
        record = ckpt_mod.load(store_path, "wgl-extend")
    out, new_rec = check_extend(enc, record=record, stride=stride,
                                **_seg_kwargs(W, F))
    if out is None:
        return enc, None
    out["analyzer"] = "tpu-extend"
    _witness_op_indices(out)
    if steps:
        out["degradation"] = list(steps)
    _search_stats(out)
    if store_path is not None and new_rec is not None:
        # best-effort durability: a failed write (ENOSPC/EIO)
        # leaves the previous record in place — degraded, not
        # wrong — and the verdict still stands
        ckpt_mod.try_write(store_path, new_rec)
    return enc, out


def _analysis(model, hist, algorithm, W, F, checkpoint_path,
              checkpoint_dir, enc_box: list | None = None,
              prefetch=None) -> dict:
    if not isinstance(hist, History):
        hist = History(hist)
    try:
        with telemetry.span("wgl.encode"):
            enc = encode(model, hist)
    except EncodingError:
        out = search_host_model(model, hist, witness=True)
        out["analyzer"] = "model"
        return _witness_op_indices(out)
    if enc_box is not None:
        enc_box[0] = enc  # certificate extraction reuses the encode
    if prefetch is not None:
        prefetch.enc = enc

    if algorithm == "model":
        out = search_host_model(model, hist, witness=True)
        out["analyzer"] = "model"
        return _witness_op_indices(out)
    if algorithm == "wgl":
        out = search_host(enc, witness=True)
        out["analyzer"] = "wgl"
        return _witness_op_indices(out)

    # Long histories: segment-parallel path (one batched launch over
    # segments x start-states instead of m sequential frontier steps).
    # W/F default per path: the prefix-screened segmented search runs
    # leaner (24/48, unknowns fall back soundly) than the whole-history
    # kernel (32/64).
    if enc.m >= SEGMENT_MIN_M:
        seg_kw = _seg_kwargs(W, F)
        if checkpoint_path is not None:
            seg_kw["checkpoint_path"] = checkpoint_path
        if checkpoint_dir is not None:
            seg_kw["checkpoint_dir"] = checkpoint_dir
        seg = check_segmented(enc, witness=True, prefetch=prefetch,
                              **seg_kw)
        if seg is not None:
            seg["analyzer"] = "tpu-segmented"
            return _witness_op_indices(seg)

    try:
        res = int(check_batch([enc],
                              W=W if W is not None else 32,
                              F=F if F is not None else 64)[0])
    except RangeError:
        out = search_host(enc, witness=True)
        out["analyzer"] = "wgl"
        return _witness_op_indices(out)
    if res == VALID:
        return {"valid?": True, "analyzer": "tpu"}
    if res == INVALID:
        out = search_host(enc, witness=True)  # witness extraction
        out["analyzer"] = "tpu"
        return _witness_op_indices(out)
    out = search_host(enc, witness=True)
    out["analyzer"] = "tpu+host-fallback"
    if _ladder_steps():
        _ladder_note("host-fallback")
    return _witness_op_indices(out)


def analysis_batch_streamed(model, hists: Sequence, chunk: int = 256,
                            W: int | None = None,
                            F: int | None = None,
                            certify: bool = False) -> list[dict]:
    """analysis_batch with host->HBM pipelining (SURVEY P7): histories
    are encoded and launched chunk by chunk, and because JAX dispatch
    is asynchronous, chunk i+1's host-side encoding overlaps chunk i's
    device search. A one-chunk drain lag keeps at most two chunks of
    packed tensors live on the host while preserving the overlap.
    certify=True attaches a per-result verdict certificate (the
    checker batch path passes it; a raw call doesn't).

    Spans: `wgl.encode` and `wgl.pack` per chunk, `wgl.batch.drain`
    (the wait for a chunk's launch and the decode of its results),
    `wgl.batch.witness` (the host work on each member the kernel did
    not decide VALID, unencodable members' model search included),
    `certify.attach` per result. Counters: `wgl.batch.rows` (members
    launched), `wgl.batch.unknown` (rows the kernel returned UNKNOWN),
    `wgl.batch.host` (members the host decided: UNKNOWN rows, rows
    of a failed launch and unencodable members) and decide_unknown's
    `wgl.batch.order` and `wgl.batch.order-miss`, which sum to
    `wgl.batch.unknown` where no launch failed."""
    hists = list(hists)
    results: list[dict] = [None] * len(hists)  # type: ignore
    for name in ("wgl.batch.rows", "wgl.batch.unknown", "wgl.batch.host",
                 "wgl.batch.order", "wgl.batch.order-miss"):
        telemetry.count(name, 0)

    def launch(group, start):
        encs = []
        idx_map = []
        unencodable = []
        # one encode span per chunk, not per history
        with telemetry.span("wgl.encode"):
            for off, hh in enumerate(group):
                i = start + off
                if not isinstance(hh, History):
                    hh = History(hh)
                try:
                    encs.append(encode(model, hh))
                    idx_map.append(i)
                except EncodingError:
                    unencodable.append((i, hh))
        for i, hh in unencodable:
            telemetry.count("wgl.batch.host")
            with telemetry.span("wgl.batch.witness"):
                out = search_host_model(model, hh, witness=True)
            out["analyzer"] = "model"
            results[i] = _witness_op_indices(_search_stats(out))
            if certify_mod is not None:
                certify_mod.attach_wgl(model, hh, None, results[i])
        if not encs:
            return None
        try:
            pb = PackedBatch(encs)
            rows = [(j, e.init_state) for j, e in enumerate(encs)]
            dev = _launch(pb, rows, W if W is not None else 32,
                          F if F is not None else 64, reach=False)
            telemetry.count("wgl.batch.rows", len(rows))
            return dev, encs, idx_map, []
        except RangeError:
            return None, encs, idx_map, []
        except Exception as e:  # noqa: BLE001 — device ladder
            return (None, encs, idx_map,
                    [_ladder_classify(e, "streamed launch")])

    certify_mod = None
    if certify:
        from . import certify as certify_mod  # noqa: PLC0415

    def drain(entry):
        dev, encs, idx_map, rungs = entry
        with telemetry.span("wgl.batch.drain"):
            res = [UNKNOWN] * len(encs)
            if dev is not None:
                try:
                    res = _drain(dev, reach=False)[:len(encs)]
                    telemetry.count("wgl.batch.unknown", int(
                        np.count_nonzero(res == UNKNOWN)))
                except Exception as e:  # noqa: BLE001 — async dispatch
                    # defers device failure to the blocking drain
                    rungs = rungs + [_ladder_classify(e,
                                                      "streamed drain")]
        for j, i in enumerate(idx_map):
            r = int(res[j])
            own = list(rungs)
            if r == VALID:
                results[i] = {"valid?": True, "analyzer": "tpu"}
            else:
                if r != INVALID:
                    telemetry.count("wgl.batch.host")
                # Bounded: long invalid/unknown members are localized
                # segment-wise instead of re-searched whole on host,
                # keeping the caller's W/F tuning.
                with telemetry.span("wgl.batch.witness"), \
                        _ladder_fork() as sub:
                    # rungs the witness extraction itself walked (e.g.
                    # a segmented-search device failure) belong to
                    # THIS result too
                    out = (extract_witness(encs[j], W=W, F=F)
                           if r == INVALID
                           else decide_unknown(encs[j], W=W, F=F))
                own += sub
                out["analyzer"] = ("tpu" if r == INVALID
                                   else "tpu+host-fallback")
                results[i] = out
            if own:
                # only this chunk's own failures, not the cumulative
                # call-wide list: the pipelining means other chunks'
                # rungs may already be on the ladder scope
                own = [s for k, s in enumerate(own)
                       if k == 0 or own[k - 1] != s]
                results[i].setdefault("degradation", own)
            _search_stats(results[i])
            if certify_mod is not None:
                certify_mod.attach_wgl(model, hists[i], encs[j],
                                       results[i])
            else:
                results[i].pop(ORDER_KEY, None)

    with _ladder_scope():
        pending = None
        for start in range(0, len(hists), chunk):
            entry = launch(hists[start:start + chunk], start)
            # drain the PREVIOUS chunk now: the current one is already
            # dispatched, so the device keeps working while we decode
            if pending is not None:
                drain(pending)
            pending = entry
        if pending is not None:
            drain(pending)
    return results


def analysis_batch(model, hists: Sequence, W: int | None = None,
                   F: int | None = None,
                   certify: bool = False) -> list[dict]:
    """Checks many histories at once (the ensemble path: one device
    launch for the whole batch, host fallback only for UNKNOWNs)."""
    hists = list(hists)
    return analysis_batch_streamed(model, hists,
                                   chunk=max(len(hists), 1), W=W, F=F,
                                   certify=certify)