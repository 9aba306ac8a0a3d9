"""SPMD sharding tests (ISSUE-15): the checker kernels as true
multi-device programs.

Pins the whole acceptance surface of the SPMD rebuild:

  - sharded-vs-unsharded verdict AND certificate equivalence on seeded
    valid/invalid histories at mesh caps 0/1/2/4/8 (the conftest gives
    every test process a virtual 8-device CPU mesh; the caps ride the
    JEPSEN_TPU_SPMD / JEPSEN_TPU_SPMD_DEVICES knobs the launch sites
    re-read per call);
  - segment-level early exit: identical results with the waves on or
    off, and an early witness costs a fraction of the full search;
  - degradation-ladder behavior when the sharded program OOMs (the
    ladder steps down to single-device launches, verdicts stay right);
  - fleet `check_slices` cross-tenant parity;
  - the sharded SCC coloring kernel against the host union-find;
  - a fast fake-8-device smoke with per-device work attribution — the
    CI tripwire that fails sharding regressions before hardware does.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

from jepsen_tpu import telemetry
from jepsen_tpu.checker import models
from jepsen_tpu.history import History
from jepsen_tpu.tpu import certify, ensemble, profiler, scc, spmd, \
    synth, wgl
from jepsen_tpu.tpu.encode import balanced_groups, encode


@pytest.fixture(scope="module")
def devices8():
    import jax

    if len(jax.devices()) < 8:  # real-device run
        pytest.skip(f"needs 8 devices, have {len(jax.devices())}")
    return 8


def corrupt(hist, frac=1.0):
    """Flip one ok-read's value so the history becomes
    non-linearizable; frac places the flipped read at roughly that
    fraction of the history (early witnesses for the early-exit
    tests, late ones for everything else)."""
    ops = list(hist)
    idx = [i for i, o in enumerate(ops)
           if o.type == "ok" and o.f == "read" and o.value is not None]
    assert idx, "no ok read to corrupt"
    i = idx[min(int(len(idx) * frac), len(idx) - 1)]
    ops[i] = ops[i].copy(value=ops[i].value + 1000)
    return History(ops, assign_indices=False)


def _cap(monkeypatch, n: int) -> None:
    """Pin the sharded launch sites to an n-device mesh (0 = SPMD
    off: the plain single-device jit path, the differential
    reference)."""
    if n == 0:
        monkeypatch.setenv("JEPSEN_TPU_SPMD", "0")
    else:
        monkeypatch.delenv("JEPSEN_TPU_SPMD", raising=False)
        monkeypatch.setenv("JEPSEN_TPU_SPMD_DEVICES", str(n))


# ---------------------------------------------------------------------------
# plumbing: knobs, rule table, layout packing
# ---------------------------------------------------------------------------

class TestPlumbing:
    def test_spmd_knobs(self, monkeypatch, devices8):
        monkeypatch.setenv("JEPSEN_TPU_SPMD", "0")
        assert spmd.spmd_devices() == 0
        monkeypatch.delenv("JEPSEN_TPU_SPMD", raising=False)
        assert spmd.spmd_devices() >= 8
        monkeypatch.setenv("JEPSEN_TPU_SPMD_DEVICES", "4")
        assert spmd.spmd_devices() == 4
        monkeypatch.setenv("JEPSEN_TPU_SPMD_DEVICES", "junk")
        assert spmd.spmd_devices() >= 8  # bad cap ignored

    def test_mesh_memoized(self, devices8):
        assert spmd.mesh_for(2) is spmd.mesh_for(2)
        assert spmd.mesh_for(2).devices.size == 2
        assert spmd.mesh_for(2).axis_names == (spmd.AXIS,)

    def test_partition_rules_cover_kernel_args(self):
        from jax.sharding import PartitionSpec as P

        specs = spmd.match_partition_rules(spmd.WGL_RULES,
                                           ensemble.SHARD_ARGS)
        assert specs[ensemble.SHARD_ARGS.index("trans")] == \
            P(spmd.AXIS)
        assert specs[ensemble.SHARD_ARGS.index("inv_perm")] == P()
        specs = spmd.match_partition_rules(spmd.SCC_RULES,
                                           scc.SCC_ARGS)
        assert specs[scc.SCC_ARGS.index("active")] == P()
        assert specs[scc.SCC_ARGS.index("fwd_send")] == P(spmd.AXIS)
        assert specs[scc.SCC_ARGS.index("bwd_end")] == P(spmd.AXIS)

    def test_unmatched_arg_raises(self):
        with pytest.raises(ValueError, match="no partition rule"):
            spmd.match_partition_rules(spmd.WGL_RULES,
                                       ("trans", "mystery_arg"))

    def test_describe_partition_is_the_lint_view(self):
        d = spmd.describe_partition(spmd.WGL_RULES,
                                    ensemble.SHARD_ARGS)
        assert d["axis"] == spmd.AXIS
        # the R4 acceptance: every big tensor sharded, only the tiny
        # result permutation replicated
        assert set(d["sharded"]) == {"inv_t", "ret_t", "trans",
                                     "mseg", "sufmin", "row_seg",
                                     "st0"}
        assert d["replicated"] == ["inv_perm"]

    def test_compile_cache_placement(self, tmp_path):
        import subprocess
        import sys
        from pathlib import Path

        repo = Path(__file__).resolve().parents[1]
        assert spmd.DEFAULT_CACHE_DIR == repo / ".jax_cache"
        probe = ("import jax; from jepsen_tpu.tpu import spmd; "
                 "print(spmd.enable_compile_cache()); "
                 "print(jax.config.jax_compilation_cache_dir)")

        def run(env_dir):
            env = {k: v for k, v in os.environ.items()
                   if k != "JAX_COMPILATION_CACHE_DIR"}
            if env_dir is not None:
                env["JAX_COMPILATION_CACHE_DIR"] = env_dir
            # from another working directory: the default must not
            # move with cd
            out = subprocess.run(
                [sys.executable, "-c", probe], cwd=tmp_path, env=env
                | {"PYTHONPATH": str(repo)}, capture_output=True,
                text=True, timeout=120, check=True)
            return out.stdout.split()

        assert run(None) == [str(repo / ".jax_cache")] * 2
        outside = str(tmp_path / "cache")
        assert run(outside) == [outside] * 2

    def test_balanced_groups(self):
        groups = balanced_groups([10, 1, 9, 2, 8, 3], 2)
        assert sorted(i for g in groups for i in g) == list(range(6))
        assert all(g == sorted(g) for g in groups)
        loads = [sum([10, 1, 9, 2, 8, 3][i] for i in g)
                 for g in groups]
        assert max(loads) <= min(loads) + 10  # LPT bound
        # fewer items than groups: every group still exists
        groups = balanced_groups([5], 4)
        assert len(groups) == 4
        assert sum(len(g) for g in groups) == 1
        assert balanced_groups([], 3) == [[], [], []]

    def test_shard_layout_restores_caller_order(self, devices8):
        m = models.cas_register()
        encs = [encode(m, synth.register_history(
            16 + 8 * i, n_procs=3, seed=i)) for i in range(5)]
        pb = wgl.PackedBatch(encs)
        rows = [(i, e.init_state) for i, e in enumerate(encs)]
        n_dev = 4
        lay = ensemble.shard_layout(pb, rows, n_dev)
        assert lay.n_dev == n_dev and lay.n_rows == len(rows)
        assert len(lay.device_entries) == n_dev
        k_blk = lay.mseg.shape[0] // n_dev      # K_loc + 1
        b_loc = len(lay.row_seg) // n_dev
        seen = set()
        for i, (k, _s) in enumerate(rows):
            pos = int(lay.inv_perm[i])
            assert pos not in seen  # a permutation, not a collapse
            seen.add(pos)
            d, slot = divmod(pos, b_loc)
            j = int(lay.row_seg[pos])
            assert j < k_blk - 1  # real segment, not the sentinel
            # the local block row really is caller segment k
            assert int(lay.mseg[d * k_blk + j]) == int(pb.m[k])

    def test_shard_layout_ships_only_referenced_segments(self,
                                                         devices8):
        m = models.cas_register()
        encs = [encode(m, synth.register_history(
            20 + 4 * i, n_procs=3, seed=40 + i)) for i in range(4)]
        pb = wgl.PackedBatch(encs)
        rows = [(0, encs[0].init_state), (2, encs[2].init_state)]
        lay = ensemble.shard_layout(pb, rows, 2)
        # only segments 0 and 2 ship; everything else in the blocked
        # tensor is the zero-length sentinel row
        assert int(lay.mseg.sum()) == int(pb.m[0]) + int(pb.m[2])


# ---------------------------------------------------------------------------
# sharded vs unsharded: verdicts and certificates
# ---------------------------------------------------------------------------

CAPS = (0, 1, 2, 4, 8)


class TestShardedParity:
    def test_check_batch_across_mesh_caps(self, monkeypatch,
                                          devices8):
        m = models.cas_register()
        hists = [synth.register_history(26, n_procs=3, seed=700 + i)
                 for i in range(12)]
        hists[3] = corrupt(hists[3])
        hists[9] = corrupt(hists[9])
        encs = [encode(m, h) for h in hists]
        by_cap = {}
        for n in CAPS:
            _cap(monkeypatch, n)
            by_cap[n] = list(map(int, wgl.check_batch(encs, W=16,
                                                      F=16)))
        for n in CAPS[1:]:
            assert by_cap[n] == by_cap[0], f"mesh cap {n} diverged"

    def test_check_segmented_and_certificates_across_caps(
            self, monkeypatch, devices8):
        m = models.cas_register()
        valid = synth.register_history(360, n_procs=4, seed=31)
        invalid = corrupt(synth.register_history(360, n_procs=4,
                                                 seed=32), frac=0.6)
        for hist in (valid, invalid):
            enc = encode(m, hist)
            results = {}
            for n in CAPS:
                _cap(monkeypatch, n)
                res = wgl.check_segmented(enc, target_len=48,
                                          witness=True)
                assert res is not None
                certify.attach_wgl(m, hist, enc, res)
                results[n] = res
            for n in CAPS[1:]:
                # the whole result — verdict, masks-derived chain,
                # witness AND certificate — bit-identical per cap
                assert results[n] == results[0], \
                    f"mesh cap {n} diverged on {hist is valid}"
            cert = results[0]["certificate"]
            assert "absent" not in cert, cert
            certify.validate(hist, cert)  # proof actually checks

    def test_analysis_certificates_across_caps(self, monkeypatch,
                                               devices8):
        m = models.cas_register()
        hists = [synth.register_history(30, n_procs=3, seed=55),
                 corrupt(synth.register_history(30, n_procs=3,
                                                seed=56))]
        for hist in hists:
            by_cap = {}
            for n in (0, 2, 8):
                _cap(monkeypatch, n)
                res = wgl.analysis(m, hist, certify=True)
                by_cap[n] = (res["valid?"], res["certificate"])
            assert by_cap[2] == by_cap[0]
            assert by_cap[8] == by_cap[0]
            certify.validate(hist, by_cap[0][1])

    def test_check_slices_cross_tenant_parity(self, monkeypatch,
                                              devices8):
        """The fleet scheduler's cross-run batching entry point: many
        tenants' (slice, start-state) rows in ONE launch must answer
        exactly what each tenant's solo single-device launch would."""
        m = models.cas_register()
        tenants = [encode(m, synth.register_history(
            40 + 10 * i, n_procs=3, seed=900 + i)) for i in range(4)]
        slices = [(enc, s) for enc in tenants
                  for s in range(min(enc.n_states, 3))]
        _cap(monkeypatch, 0)
        ref_out, ref_unk = wgl.check_slices(slices, W=16, F=16)
        for n in (2, 8):
            _cap(monkeypatch, n)
            out, unk = wgl.check_slices(slices, W=16, F=16)
            assert out.tolist() == ref_out.tolist()
            assert unk.tolist() == ref_unk.tolist()


# ---------------------------------------------------------------------------
# segment-level early exit
# ---------------------------------------------------------------------------

class TestEarlyExit:
    def test_wave_bounds(self):
        assert wgl._wave_bounds(5, True) == [(0, 5)]  # small K
        assert wgl._wave_bounds(20, False) == [(0, 20)]
        waves = wgl._wave_bounds(100, True)
        assert waves[0] == (0, 4)
        assert waves[-1][1] == 100
        for (alo, ahi), (blo, bhi) in zip(waves, waves[1:]):
            assert ahi == blo  # contiguous cover
            assert (bhi - blo) >= (ahi - alo)  # geometric growth

    def _rows_launched(self, monkeypatch):
        counted = []
        real = wgl._launch

        def counting(pb, rows, W, F, reach):
            counted.append(len(list(rows)))
            return real(pb, rows, W, F, reach)

        monkeypatch.setattr(wgl, "_launch", counting)
        return counted

    def test_early_witness_costs_a_fraction(self, monkeypatch,
                                            devices8):
        m = models.cas_register()
        hist = corrupt(synth.register_history(800, n_procs=4,
                                              seed=61), frac=0.1)
        enc = encode(m, hist)
        telemetry.reset()
        counted = self._rows_launched(monkeypatch)
        full = wgl.check_segmented(enc, target_len=24, witness=True,
                                   early_exit=False)
        rows_full = sum(counted)
        counted.clear()
        early = wgl.check_segmented(enc, target_len=24, witness=True,
                                    early_exit=True)
        rows_early = sum(counted)
        assert early == full  # verdict, witness, chain — identical
        assert full["valid?"] is False
        # an anomaly at ~10% of the history must cost a fraction of
        # the full search (the waves after the witness never launch)
        assert rows_early < rows_full * 0.7, (rows_early, rows_full)
        c = telemetry.get().counters()
        assert c.get("wgl.segmented.early-exit", 0) >= 1

    def test_valid_history_waves_match_single_launch(self,
                                                     monkeypatch,
                                                     devices8):
        m = models.cas_register()
        enc = encode(m, synth.register_history(500, n_procs=4,
                                               seed=62))
        full = wgl.check_segmented(enc, target_len=24,
                                   early_exit=False)
        early = wgl.check_segmented(enc, target_len=24,
                                    early_exit=True)
        assert early == full
        assert full["valid?"] is True

    def test_env_knob_disables(self, monkeypatch, devices8):
        monkeypatch.setenv("JEPSEN_TPU_EARLY_EXIT", "0")
        m = models.cas_register()
        enc = encode(m, synth.register_history(300, n_procs=3,
                                               seed=63))
        counted = self._rows_launched(monkeypatch)
        res = wgl.check_segmented(enc, target_len=24)
        assert res["valid?"] is True
        # one screen launch + one main launch, no waves
        assert len(counted) <= 2


# ---------------------------------------------------------------------------
# degradation ladder under shard failure
# ---------------------------------------------------------------------------

class TestShardOOMLadder:
    def test_sharded_oom_steps_down_to_single_device(
            self, monkeypatch, devices8):
        """The SPMD program OOMing must not cost correctness: the
        batch ladder halves down to single-row launches, which fall
        under spmd.MIN_ROWS and take the plain single-device path —
        slower, never wrong, and the rungs are counted."""
        m = models.cas_register()
        hists = [synth.register_history(24, n_procs=3, seed=80 + i)
                 for i in range(4)]
        hists[1] = corrupt(hists[1])
        encs = [encode(m, h) for h in hists]
        ref = list(map(int, wgl.check_batch(encs, W=16, F=16)))

        def boom(*a, **k):
            raise RuntimeError("RESOURCE_EXHAUSTED: fake shard OOM")

        telemetry.reset()
        monkeypatch.setattr(ensemble, "sharded_launch", boom)
        got = list(map(int, wgl.check_batch(encs, W=16, F=16)))
        assert got == ref
        c = telemetry.get().counters()
        assert c.get("wgl.ladder.batch-halved", 0) >= 1

    def test_segmented_survives_shard_failure(self, monkeypatch,
                                              devices8):
        """A dead SPMD program under the segmented check: the wave
        resolver walks its host rungs (screen + floor) and composes
        the SAME masks the device would have produced."""
        m = models.cas_register()
        enc = encode(m, synth.register_history(300, n_procs=3,
                                               seed=85))
        ref = wgl.check_segmented(enc, target_len=32)

        def boom(*a, **k):
            raise RuntimeError("RESOURCE_EXHAUSTED: fake shard OOM")

        telemetry.reset()
        monkeypatch.setattr(ensemble, "sharded_launch", boom)
        res = wgl.check_segmented(enc, target_len=32)
        assert res == ref
        c = telemetry.get().counters()
        assert any(k.startswith("wgl.ladder.segment-host")
                   for k in c), c


# ---------------------------------------------------------------------------
# sharded SCC coloring kernel
# ---------------------------------------------------------------------------

class TestSccSharded:
    def _graph(self, seed, n=2500, e=30_000):
        rng = np.random.default_rng(seed)
        src = rng.integers(0, n, e)
        dst = rng.integers(0, n, e)
        # a few guaranteed cycles so nontrivial SCCs exist
        ring = np.arange(40)
        src = np.concatenate([src, ring])
        dst = np.concatenate([dst, np.roll(ring, -1)])
        return n, src, dst

    def test_sharded_labels_match_host(self, monkeypatch, devices8):
        n, src, dst = self._graph(5)
        host = scc._scc_host(n, src, dst)
        _cap(monkeypatch, 8)
        dev = scc.scc_device(n, src, dst)
        assert dev is not None
        assert dev[:n].tolist() == host.tolist()

    def test_keyblock_layout_cannot_change_labels(self, monkeypatch,
                                                  devices8):
        n, src, dst = self._graph(6)
        ekey = np.random.default_rng(1).integers(-1, 5, len(src))
        _cap(monkeypatch, 8)
        telemetry.reset()
        with_key = scc.scc_device(n, src, dst, ekey=ekey)
        plain = scc.scc_device(n, src, dst)
        assert with_key is not None and plain is not None
        assert with_key[:n].tolist() == plain[:n].tolist()
        c = telemetry.get().counters()
        assert c.get("scc.keyblock-layouts", 0) >= 1

    def test_emask_subsets_survive_sharding(self, monkeypatch,
                                            devices8):
        n, src, dst = self._graph(7)
        emask = np.random.default_rng(2).random(len(src)) < 0.7
        _cap(monkeypatch, 0)
        ref = scc.scc(n, src, dst, emask=emask)
        _cap(monkeypatch, 8)
        got = scc.scc(n, src, dst, emask=emask)
        assert got.tolist() == ref.tolist()

    def test_sharded_pull_matches_single(self, monkeypatch, devices8):
        """Each shard sorts and pulls over its own edge block: labels,
        sweeps and rounds equal the single-device launch's, and the
        pull levels are those of the longest run inside one block."""
        n, src, dst = self._graph(8)
        emask = np.random.default_rng(3).random(len(src)) < 0.8
        work = {}
        for cap in (0, 8):
            _cap(monkeypatch, cap)
            telemetry.reset()
            labels = scc.scc_device(n, src, dst, emask)
            c = telemetry.get().counters()
            work[cap] = (labels.tolist(), c["scc.sweeps"],
                         c["scc.rounds"], c["scc.pull-levels"])
        assert work[8][:3] == work[0][:3]
        assert work[0][0] == scc._scc_host(n, src[emask],
                                           dst[emask]).tolist()
        # shard i holds edges [i * step, (i + 1) * step) of the padded
        # array; the padding is masked off
        step = scc._edge_pad(len(src)) // 8
        longest = max(np.bincount(ends[i:i + step][emask[i:i + step]]).max()
                      for ends in (src, dst)
                      for i in range(0, len(src), step))
        assert work[8][3] == (int(longest) - 1).bit_length()
        assert work[8][3] <= work[0][3]


# ---------------------------------------------------------------------------
# the tier-1 fake-8-device smoke (CI tripwire)
# ---------------------------------------------------------------------------

class TestFake8Smoke:
    def test_sharded_launch_spreads_work_over_8_devices(self,
                                                        devices8):
        """The regression tripwire: a sharded ensemble launch on the
        fake 8-device mesh must actually attribute work to all 8
        shards with a sane balance — if a refactor quietly
        re-serializes or re-replicates the launch, this fails in CI,
        not on hardware (doc/spmd.md)."""
        profiler.reset()
        telemetry.reset()
        m = models.cas_register()
        encs = [encode(m, synth.register_history(
            24, n_procs=3, seed=300 + i)) for i in range(16)]
        mesh = ensemble.default_mesh(8)
        res = ensemble.check_batch_sharded(encs, mesh=mesh, W=16,
                                           F=16)
        assert all(int(r) == wgl.VALID for r in res)
        recs = [r for r in profiler.get().records()
                if r["kernel"] == "wgl-sharded"]
        assert recs, "sharded launch left no profiler record"
        r = recs[0]
        assert r["devices"] == 8
        assert len(r["device_entries"]) == 8
        assert all(w > 0 for w in r["device_entries"]), \
            r["device_entries"]  # every shard got real rows
        assert r["balance"] and r["balance"] >= 0.5
        g = telemetry.get().gauges()
        assert g.get("wgl.spmd.devices") == 8

    def test_segmented_path_rides_the_mesh(self, devices8):
        """check_segmented (and through _launch, every wgl entry
        point) must land on the SPMD program when the process has
        devices — the headline 1M-event path scales only if this
        stays true."""
        telemetry.reset()
        m = models.cas_register()
        enc = encode(m, synth.register_history(400, n_procs=4,
                                               seed=71))
        res = wgl.check_segmented(enc, target_len=32)
        assert res is not None and res["valid?"] is True
        c = telemetry.get().counters()
        assert c.get("wgl.spmd.launches", 0) >= 1
