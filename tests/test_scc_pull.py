"""The SCC kernel's sorted pull (tpu/scc.py): every fixpoint sweep reads
each node's incoming maximum off its own run of edges, sorted once per
launch, with no scatter.

Each graph is held to three things: the kernel's labels equal the host
Tarjan's (`_scc_host`); its sweeps and rounds equal those of the plain
numpy Jacobi reference of Orzan's colouring below (the scatter-max
formulation, sweep for sweep); and its `pull_levels` is ceil(log2) of
the longest run of live edges into or out of one node. The sharded
case is in tests/test_spmd.py beside the other sharded SCC tests.
"""

import numpy as np
import pytest

from jepsen_tpu import telemetry
from jepsen_tpu.tpu import lint, scc


def orzan_reference(n, src, dst, on):
    """(labels, sweeps, rounds) of Orzan's colouring as Jacobi sweeps of
    a scatter-max, the kernel's semantics before the pull: forward max
    colours along live edges to a fixpoint, backward membership within
    colour classes, retire, repeat."""
    ids = np.arange(n)
    active = np.ones(n, dtype=bool)
    out = np.full(n, -1)
    sweeps = rounds = 0

    def fixpoint(c, frm, to, live, neutral):
        k = 0
        while True:
            prop = np.full(n, neutral)
            np.maximum.at(prop, to[live], c[frm[live]])
            nc = np.maximum(c, prop)
            k += 1
            if np.array_equal(nc, c):
                return nc, k
            c = nc

    while active.any():
        live = on & active[src] & active[dst]
        c, kf = fixpoint(np.where(active, ids, -1), src, dst, live, -1)
        same = live & (c[src] == c[dst])
        m, kb = fixpoint(np.where(active & (c == ids), 1, 0), dst, src,
                         same, 0)
        member = active & (m > 0)
        out[member] = c[member]
        active &= ~member
        sweeps += kf + kb
        rounds += 1
    return out, sweeps, rounds


def ceil_log2(x: int) -> int:
    return max(0, (int(x) - 1).bit_length())


def longest_run_levels(src, dst, on) -> int:
    """ceil(log2) of the most live edges into or out of one node."""
    if not on.any():
        return 0
    runs = max(np.bincount(dst[on]).max(), np.bincount(src[on]).max())
    return ceil_log2(runs)


# Every graph below has n <= 1023 and 2049..4096 edges: one shape bucket
# (n_pad 1024, e_pad 4096), so the kernel compiles once for the file.

def _random(rng, n=700, e=3000):
    return n, rng.integers(0, n, e), rng.integers(0, n, e), None


def _history_like(rng, n=900, e=3600):
    """Mostly forward edges within a window, some back: small SCCs."""
    src = rng.integers(0, n, e)
    dst = np.minimum(src + rng.integers(1, 40, e), n - 1)
    back = rng.random(e) < 0.05
    src[back], dst[back] = dst[back], src[back]
    return n, src, dst, None


def _star(rng, n=1001, spokes=1100):
    """A hub with in- and out-degree 1100 > 2^10: 11 doubling levels."""
    leaf = 1 + np.arange(spokes) % (n - 1)
    hub = np.zeros(spokes, dtype=np.int64)
    src = np.concatenate([leaf, hub])
    dst = np.concatenate([hub, leaf])
    return n, src, dst, None


def _isolated(rng, n=1000, e=2500):
    """Edges only among the first 300 nodes, and 100 of those never
    receive one: isolated nodes and nodes with no in-edges."""
    src = rng.integers(0, 300, e)
    dst = rng.integers(100, 300, e)
    return n, src, dst, None


def _self_loops(rng, n=600, e=2000):
    n, src, dst, _ = _random(rng, n, e)
    loops = rng.integers(0, n, 800)
    return n, np.concatenate([src, loops]), np.concatenate([dst, loops]), \
        None


def _sentinel_padding(rng, n=1000):
    """2,049 edges in a 4,096-edge bucket: 2,047 padding self-loops on
    the sentinel node, which must join no run (11 levels if they did)."""
    src = rng.integers(0, n, 2049)
    dst = rng.integers(0, n, 2049)
    return n, src, dst, None


def _emask(rng, n=700, e=3200):
    n, src, dst, _ = _random(rng, n, e)
    return n, src, dst, rng.random(e) < 0.6


def _nested(rng, depth=None):
    """Rings of 8 nodes (inner edges, kind 0), joined ring to ring into
    rings of 4 rings (middle edges, kind 1), joined block to block into
    one ring of all blocks (outer edges, kind 2): SCCs nested three
    deep. A depth masks the edges of higher kinds out."""
    n, ring = 1000, 8
    rings = n // ring
    src, dst, kind = [], [], []
    for r in range(rings):
        base = r * ring
        for i in range(ring):
            src.append(base + i)
            dst.append(base + (i + 1) % ring)
            kind.append(0)
    for r in range(rings):
        blk, pos = divmod(r, 4)
        nxt = blk * 4 + (pos + 1) % 4
        if nxt < rings:
            src.append(r * ring)
            dst.append(nxt * ring + 3)
            kind.append(1)
    blocks = (rings + 3) // 4
    for b in range(blocks):
        src.append(b * 4 * ring + 5)
        dst.append(((b + 1) % blocks) * 4 * ring + 6)
        kind.append(2)
    kind = np.array(kind)
    # chords inside each ring keep the count in the bucket
    extra = 2049 - len(kind)
    r = rng.integers(0, rings, extra)
    a, b = rng.integers(0, ring, extra), rng.integers(0, ring, extra)
    src = np.concatenate([src, r * ring + a])
    dst = np.concatenate([dst, r * ring + b])
    kind = np.concatenate([kind, np.zeros(extra, dtype=np.int64)])
    return n, src, dst, None if depth is None else kind <= depth


GRAPHS = {
    "random": _random,
    "history-like": _history_like,
    "star": _star,
    "isolated": _isolated,
    "self-loops": _self_loops,
    "sentinel-padding": _sentinel_padding,
    "emask": _emask,
    "nested": _nested,
    "nested-inner-emask": lambda rng: _nested(rng, depth=0),
    "nested-middle-emask": lambda rng: _nested(rng, depth=1),
}


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_pull_matches_host_and_reference(name):
    rng = np.random.default_rng(sorted(GRAPHS).index(name))
    n, src, dst, emask = GRAPHS[name](rng)
    src, dst = np.asarray(src), np.asarray(dst)
    assert n < 1024 and 2049 <= len(src) <= 4096
    on = np.ones(len(src), dtype=bool) if emask is None else emask
    telemetry.reset()
    labels = scc.scc_device(n, src, dst, emask)
    assert labels is not None
    c = telemetry.get().counters()
    assert labels.tolist() == scc._scc_host(n, src[on], dst[on]).tolist()
    ref, sweeps, rounds = orzan_reference(n, src, dst, on)
    assert ref.tolist() == labels.tolist()
    assert (c["scc.sweeps"], c["scc.rounds"]) == (sweeps, rounds)
    levels = longest_run_levels(src, dst, on)
    assert c["scc.pull-levels"] == levels
    if name == "star":
        assert levels == 11
    if name == "sentinel-padding":
        assert levels < 11


def test_launch_record_carries_pull_levels():
    from jepsen_tpu.tpu import profiler

    rng = np.random.default_rng(3)
    n, src, dst, _ = _star(rng)
    before = len(profiler.get().records())
    scc.scc_device(n, src, dst)
    rec = profiler.get().records()[before:][-1]
    assert rec["kernel"] == "scc" and rec["pull_levels"] == 11


def test_status_word_round_trips_its_fields():
    import jax.numpy as jnp

    for done, rounds, sweeps, levels in ((True, 64, 65536, 31),
                                         (False, 9, 48, 5),
                                         (True, 0, 0, 0)):
        word = scc.pack_status(jnp.bool_(done), jnp.int32(rounds),
                               jnp.int32(sweeps), jnp.int32(levels))
        assert scc.unpack_status(int(word)) == (done, rounds, sweeps,
                                                levels)


def test_program_holds_no_scatter():
    """No sweep scatters: the whole program, fixpoint bodies included,
    holds no scatter primitive (its one status write is a dynamic
    update slice), and no sort: the host lays the edges out."""
    import jax

    prog = scc._scc_program(1024, scc.SWEEP_CAP, scc.ROUND_CAP)
    jaxpr = jax.make_jaxpr(prog)(*(
        jax.ShapeDtypeStruct(s, d)
        for s, d in scc.scc_arg_shapes(1024, 4096)))
    prims = {e.primitive.name for e in lint.iter_eqns(jaxpr)}
    assert "while" in prims and "sort" not in prims
    assert not {p for p in prims if p.startswith("scatter")}, prims


def _padded(n, src, dst, emask, e_pad=4096):
    """The edge arrays as scc_device pads them: sentinel self-loops on
    node n past the last edge, masked off."""
    psrc = np.full(e_pad, n, dtype=np.int32)
    pdst = np.full(e_pad, n, dtype=np.int32)
    psrc[:len(src)], pdst[:len(dst)] = src, dst
    on = np.zeros(e_pad, dtype=bool)
    on[:len(src)] = True if emask is None else emask
    return psrc, pdst, on


@pytest.mark.parametrize("blocks", [1, 4])
@pytest.mark.parametrize("name", ["random", "star", "emask",
                                  "sentinel-padding"])
def test_native_layout_matches_reference(name, blocks):
    """order.c's counting sort gives the numpy reference's layout, array
    for array, in one block and in four (the sharded path's)."""
    from jepsen_tpu import native

    rng = np.random.default_rng(sorted(GRAPHS).index(name))
    n, src, dst, emask = GRAPHS[name](rng)
    psrc, pdst, on = _padded(n, src, dst, emask)
    for recv, send in ((pdst, psrc), (psrc, pdst)):
        got = native.edge_layout(recv, send, on, 1024, blocks)
        ref = scc._layout_py(recv, send, on, 1024, blocks)
        for g, r in zip(got, ref):
            assert g.dtype == np.int32 and g.tolist() == r.tolist()
    recv, send, off, end = ref
    # every block's live edges are sorted, each run's offsets count up
    # from 0, and each node's run ends where `end` says
    step = len(recv) // blocks
    for b in range(blocks):
        r = recv[b * step:(b + 1) * step]
        assert (np.diff(r) >= 0).all()
        live = r < 1023
        o = off[b * step:(b + 1) * step]
        head = np.diff(r[live], prepend=-1) > 0
        assert (o[~live] == 0).all() and (o[live][head] == 0).all()
        assert (o[live][~head] == o[live][np.flatnonzero(~head) - 1]
                + 1).all()
        e = end[b * 1024:(b + 1) * 1024]
        counts = np.bincount(r[live], minlength=1024)
        assert (e[counts == 0] == -1).all()
        assert (r[e[counts > 0]] == np.flatnonzero(counts)).all()


def test_native_layout_rejects_an_out_of_range_receiver():
    from jepsen_tpu import native

    recv = np.array([0, 1023, 2, 3], dtype=np.int32)
    with pytest.raises(RuntimeError, match="failed"):
        native.edge_layout(recv, recv, np.ones(4, dtype=bool), 1024, 1)


def test_layout_falls_back_without_the_native_codec(monkeypatch):
    """Without the compiled codec `_layout` takes the numpy path and
    the kernel's labels and work are unchanged."""
    from jepsen_tpu import native

    rng = np.random.default_rng(5)
    n, src, dst, _ = _history_like(rng)
    work = []
    for broken in (False, True):
        if broken:
            def unavailable(*a, **k):
                raise RuntimeError("native edge layout unavailable")
            monkeypatch.setattr(native, "edge_layout", unavailable)
        telemetry.reset()
        labels = scc.scc_device(n, src, dst)
        c = telemetry.get().counters()
        work.append((labels.tolist(), c["scc.sweeps"], c["scc.rounds"],
                     c["scc.pull-levels"]))
    assert work[0] == work[1]
