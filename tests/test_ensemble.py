"""Mesh-sharded ensemble checking tests: the data-parallel and
segment-parallel (reach) paths of tpu/ensemble.py on the virtual
8-device CPU mesh set up by conftest.py.

Differential strategy mirrors test_wgl.py: the sharded kernel must agree
with the single-device kernel and the exhaustive host search on both
valid-by-construction and corrupted histories. This is the coverage the
driver's dryrun_multichip exercises (SURVEY §2.5: shard the batch dim
over a 1-D Mesh; independent.clj:271-377 is the host-side analog).
"""

import os

import numpy as np
import pytest

from jepsen_tpu.checker import models as model
from jepsen_tpu.history import History, op
from jepsen_tpu.tpu import ensemble, synth, wgl
from jepsen_tpu.tpu.encode import encode


@pytest.fixture(scope="module")
def mesh():
    import jax

    devs = jax.devices()
    if len(devs) < 8:  # real-device run (JEPSEN_TPU_TEST_REAL_DEVICE=1)
        pytest.skip(f"needs 8 devices, have {len(devs)}")
    return ensemble.default_mesh(8)


def corrupt(hist):
    """Flip one ok-read's value so the history becomes non-linearizable."""
    ops = list(hist)
    for i in range(len(ops) - 1, -1, -1):
        o = ops[i]
        if o.type == "ok" and o.f == "read" and o.value is not None:
            ops[i] = o.copy(value=o.value + 1000)
            return History(ops, assign_indices=False)
    raise AssertionError("no ok read to corrupt")


def test_default_mesh_shape(mesh):
    assert mesh.devices.size == 8
    assert mesh.axis_names == ("b",)


def test_data_parallel_valid(mesh):
    m = model.cas_register()
    hists = [synth.register_history(32, n_procs=3, seed=i)
             for i in range(16)]
    encs = [encode(m, h) for h in hists]
    res = ensemble.check_batch_sharded(encs, mesh=mesh, W=16, F=16)
    assert res.shape == (16,)
    assert all(int(r) == wgl.VALID for r in res)


def test_data_parallel_mixed_validity(mesh):
    m = model.cas_register()
    hists = [synth.register_history(32, n_procs=3, seed=100 + i)
             for i in range(8)]
    bad_idx = {1, 4, 6}
    hists = [corrupt(h) if i in bad_idx else h
             for i, h in enumerate(hists)]
    encs = [encode(m, h) for h in hists]
    res = ensemble.check_batch_sharded(encs, mesh=mesh, W=16, F=32)
    for i, (e, r) in enumerate(zip(encs, res)):
        expect = wgl.search_host(e)["valid?"]
        if int(r) == wgl.UNKNOWN:
            continue  # sound: kernel may punt, never lie
        assert (int(r) == wgl.VALID) == expect, f"history {i}"
    # at least the corrupted ones must not come back VALID
    for i in bad_idx:
        assert int(res[i]) != wgl.VALID


def test_data_parallel_matches_unsharded(mesh):
    m = model.cas_register()
    hists = [synth.register_history(24, n_procs=3, seed=200 + i)
             for i in range(12)]
    hists[3] = corrupt(hists[3])
    encs = [encode(m, h) for h in hists]
    sharded = ensemble.check_batch_sharded(encs, mesh=mesh, W=16, F=16)
    plain = wgl.check_batch(encs, W=16, F=16)
    assert list(map(int, sharded)) == list(map(int, plain))


def test_ragged_batch_not_multiple_of_devices(mesh):
    """Row padding: 5 histories over 8 devices still answers 5 rows."""
    m = model.cas_register()
    hists = [synth.register_history(16, n_procs=2, seed=300 + i)
             for i in range(5)]
    encs = [encode(m, h) for h in hists]
    res = ensemble.check_batch_sharded(encs, mesh=mesh, W=16, F=16)
    assert res.shape == (5,)
    assert all(int(r) == wgl.VALID for r in res)


def test_reach_segments_compose(mesh):
    """Segment-parallel long history: sharded reach rows compose through
    boundary states to the same verdict as the host search."""
    m = model.cas_register()
    hist = synth.register_history(300, n_procs=4, seed=7)
    enc = encode(m, hist)
    cuts = wgl.segment_cuts(enc, target_len=32)
    K = len(cuts) - 1
    assert K >= 2
    segs = [enc.segment(cuts[k], cuts[k + 1]) for k in range(K)]
    S = enc.n_states
    rows = [(k, s) for k in range(K) for s in range(S)]
    out, unk = ensemble.check_batch_sharded(
        segs, mesh=mesh, W=16, F=16, reach=True, rows=rows)
    assert out.shape == (len(rows),)
    reach = 1 << enc.init_state
    for k in range(K):
        nreach = 0
        for s in range(S):
            if (reach >> s) & 1:
                i = k * S + s
                nreach |= (wgl.search_host_reach(segs[k].with_init(s))
                           if unk[i] else int(out[i]))
        assert nreach, f"segment {k} should stay reachable"
        reach = nreach


def test_reach_rows_match_host(mesh):
    """Every (segment, start-state) reach mask the kernel resolves must
    equal the exhaustive host reachability for that row."""
    m = model.cas_register()
    hist = synth.register_history(120, n_procs=3, seed=11)
    enc = encode(m, hist)
    cuts = wgl.segment_cuts(enc, target_len=24)
    K = len(cuts) - 1
    segs = [enc.segment(cuts[k], cuts[k + 1]) for k in range(K)]
    S = enc.n_states
    rows = [(k, s) for k in range(K) for s in range(S)]
    out, unk = ensemble.check_batch_sharded(
        segs, mesh=mesh, W=16, F=32, reach=True, rows=rows)
    for i, (k, s) in enumerate(rows):
        if unk[i]:
            continue
        host = wgl.search_host_reach(segs[k].with_init(s))
        assert int(out[i]) == host, f"row {(k, s)}"


def test_scaling_equivalence_across_mesh_sizes(mesh):
    """The sharded checker is a pure function of the histories: its
    answers must be bit-identical whether the mesh has 1, 2, or 8
    devices (VERDICT r3 #10 — turns 'wired' multi-chip into
    'verified'). Covers both the data-parallel ensemble path and the
    segment x start-state reach path."""
    m = model.cas_register()
    hists = [synth.register_history(28, n_procs=3, seed=500 + i)
             for i in range(10)]
    hists[2] = corrupt(hists[2])
    hists[7] = corrupt(hists[7])
    encs = [encode(m, h) for h in hists]

    long_hist = synth.register_history(220, n_procs=4, seed=77)
    enc = encode(m, long_hist)
    cuts = wgl.segment_cuts(enc, target_len=32)
    K = len(cuts) - 1
    assert K >= 2
    segs = [enc.segment(cuts[k], cuts[k + 1]) for k in range(K)]
    S = enc.n_states
    rows = [(k, s) for k in range(K) for s in range(S)]

    ens_by_n, reach_by_n = {}, {}
    for n in (1, 2, 8):
        sub = ensemble.default_mesh(n)
        assert sub.devices.size == n
        ens_by_n[n] = list(map(int, ensemble.check_batch_sharded(
            encs, mesh=sub, W=16, F=16)))
        out, unk = ensemble.check_batch_sharded(
            segs, mesh=sub, W=16, F=16, reach=True, rows=rows)
        reach_by_n[n] = (list(map(int, out)), list(map(bool, unk)))

    assert ens_by_n[1] == ens_by_n[2] == ens_by_n[8]
    assert reach_by_n[1] == reach_by_n[2] == reach_by_n[8]
    # and the 1-device answer equals the unsharded kernel's
    assert ens_by_n[1] == list(map(int, wgl.check_batch(
        encs, W=16, F=16)))


def test_analysis_batch_sharded(mesh):
    m = model.cas_register()
    hists = [synth.register_history(24, n_procs=3, seed=400 + i)
             for i in range(8)]
    hists[2] = corrupt(hists[2])
    res = ensemble.analysis_batch_sharded(m, hists, mesh=mesh, W=16, F=32)
    assert len(res) == 8
    for i, r in enumerate(res):
        assert r["valid?"] == (i != 2)
    assert res[2]["op"] is not None or res[2].get("configs")


@pytest.mark.skipif(
    os.environ.get("JEPSEN_TPU_TEST_REAL_DEVICE") == "1",
    reason="dryrun forces the virtual CPU platform mid-session")
def test_graft_entry_dryrun():
    """The driver's multichip dryrun must pass end-to-end in-process."""
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        import __graft_entry__ as ge
    finally:
        sys.path.pop(0)
    ge.dryrun_multichip(8)


class TestStreamedBatch:
    def test_matches_one_shot_batch(self):
        from jepsen_tpu.checker import models
        from jepsen_tpu.tpu import synth, wgl

        hists = [synth.register_history(60, n_procs=3, seed=100 + i,
                                        crash_p=0.1 if i % 3 else 0.0)
                 for i in range(40)]
        # corrupt one history so valid/invalid both flow through
        bad = hists[7]
        ops = list(bad)
        from jepsen_tpu.history import History, op as mkop
        ops.append(mkop(type="invoke", process=0, f="read", value=None))
        ops.append(mkop(type="ok", process=0, f="read", value=424242))
        hists[7] = History(ops)
        model = models.cas_register()
        one = wgl.analysis_batch(model, hists)
        streamed = wgl.analysis_batch_streamed(model, hists, chunk=16)
        assert [r["valid?"] for r in one] == \
            [r["valid?"] for r in streamed]
        assert streamed[7]["valid?"] is False


def test_sharded_batch_decides_unknown_members_alike(monkeypatch):
    """The sharded batch path decides the members its launch left
    UNKNOWN through wgl.decide_unknown, as the streamed path does: the
    same calls, the same order counters, the same results."""
    from jepsen_tpu import telemetry

    m = model.cas_register()
    hists = [synth.register_history(40, n_procs=5, seed=s, crash_p=0.2)
             for s in range(1, 7)]
    hists[2] = corrupt(hists[2])
    hists[5] = corrupt(hists[5])
    calls = []
    real = wgl.decide_unknown

    def spy(enc, W=None, F=None):
        calls.append(enc.m)
        return real(enc, W=W, F=F)

    monkeypatch.setattr(wgl, "decide_unknown", spy)
    monkeypatch.setattr(wgl, "_launch", lambda pb, rows, *a, **kw: len(rows))
    monkeypatch.setattr(wgl, "_drain",
                        lambda n, reach: np.full(n, wgl.UNKNOWN, np.int32))
    monkeypatch.setattr(ensemble, "check_batch_sharded",
                        lambda encs, **kw: np.full(len(encs), wgl.UNKNOWN))
    names = ("wgl.batch.order", "wgl.batch.order-miss")
    runs = []
    for check in (wgl.analysis_batch_streamed,
                  ensemble.analysis_batch_sharded):
        telemetry.reset()
        calls.clear()
        res = check(m, hists)
        c = telemetry.get().counters()
        runs.append((res, [c.get(n, 0) for n in names], list(calls)))
    (streamed, c_streamed, k_streamed), (sharded, c_sharded, k_sharded) = runs
    assert sharded == streamed
    assert c_sharded == c_streamed == [4, 2]
    assert k_sharded == k_streamed and len(k_sharded) == len(hists)
    assert [r["valid?"] for r in sharded] == [True, True, False, True,
                                              True, False]
    assert all(wgl.ORDER_KEY not in r for r in sharded)
