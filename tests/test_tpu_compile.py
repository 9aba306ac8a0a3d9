"""The device programs compiled for a described TPU v5e, without a chip.

The TPU compiler is installed with jax and compiles for a chip that is
described rather than attached, so these tests refuse, at no chip time,
what the chip's compiler would refuse: an unsupported op, a program
that does not fit the chip's HBM, a sharding that cannot be
partitioned. Nothing runs, so they say nothing about results or times.

The shapes are the launches that `chip_smoke.py` makes at the BASELINE
configs' widths, recorded from a CPU run of the same phases (PR 21):

  - config 2 (1M-event CAS register, checker.linearizable): the largest
    segmented main launch packs 33 segments of 16384 entries, 8 model
    states, 128 search rows, W=24/F=48, reach mode, no crashed ops;
  - config 5 (1024 x 400-op histories, 15% crashes): each 128-history
    chunk packs 129 x 512 entries, 8 states, 128 rows, W=32/F=64;
  - the indep-register-410k-partition cell (one 819k-event history of
    4,038 independent keys, crashed ops): one launch of every key,
    4,039 x 512 entries, 8 states, 4096 rows, W=32/F=64;
  - config 3 (100k list-append txns): the SCC graph pads to 131072
    nodes and 655360 edges;
  - on a 2x2 mesh, the blocked layouts of the same launches.

The topology is described inside a module fixture: only one process at
a time may load the TPU library, and xdist workers import every test
file (see the on-chip-measurement guide).
"""

from __future__ import annotations

import numpy as np
import pytest

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo, no_persistent_cache):
    from jax.sharding import Mesh

    from jepsen_tpu.tpu import spmd

    return Mesh(np.array(topo.devices[:4]), (spmd.AXIS,))


def _fits(compiled) -> int:
    """Bytes the program needs on one chip; asserts they fit its HBM."""
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, total
    return total


def _wgl_shapes(K, M, S, rows):
    # inv_t, ret_t, trans, mseg, sufmin, row_seg, st0
    return ((K, M), (K, M), (K, M, S), (K,), (K, M + 1), (rows,),
            (rows,))


# (K segments incl. sentinel, M, S, rows, W, F, reach, crash_free)
WGL_LAUNCHES = {
    "config2-segmented": (33, 16384, 8, 128, 24, 48, True, True),
    "config5-batch": (129, 512, 8, 128, 32, 64, False, False),
    "indep-410k-batch": (4039, 512, 8, 4096, 32, 64, False, False),
}


@pytest.mark.parametrize("name", sorted(WGL_LAUNCHES))
def test_wgl_kernel_compiles_for_v5e(one_chip, name):
    import jax
    import jax.numpy as jnp

    from jepsen_tpu.tpu import wgl

    K, M, S, rows, W, F, reach, crash_free = WGL_LAUNCHES[name]
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in _wgl_shapes(K, M, S, rows)]
    compiled = wgl._jitted_kernel().lower(
        *args, W=W, F=F, max_iters=M + 4, reach=reach,
        crash_free=crash_free).compile()
    _fits(compiled)


def _scc_args(n_pad, e_pad, shardings, blocks=1):
    import jax

    from jepsen_tpu.tpu import scc

    shapes = scc.scc_arg_shapes(n_pad, e_pad, blocks)
    return [jax.ShapeDtypeStruct(s, d, sharding=sh)
            for (s, d), sh in zip(shapes, shardings)]


SCC_CONFIG3 = (131072, 655360)  # (n_pad, e_pad) of 100k txns


def test_scc_compiles_for_v5e(one_chip):
    from jepsen_tpu.tpu import scc

    n_pad, e_pad = SCC_CONFIG3
    fn = scc._jitted_scc(n_pad, e_pad, scc.SWEEP_CAP, scc.ROUND_CAP)
    _fits(fn.lower(*_scc_args(n_pad, e_pad, [one_chip] * len(scc.SCC_ARGS))
                   ).compile())


def test_scc_sharded_compiles_for_v5e_2x2(mesh4):
    from jax.sharding import NamedSharding

    from jepsen_tpu.tpu import scc, spmd

    n_pad, e_pad = SCC_CONFIG3
    specs = spmd.match_partition_rules(spmd.SCC_RULES, scc.SCC_ARGS)
    fn = scc._jitted_scc_sharded(mesh4, n_pad, e_pad, scc.SWEEP_CAP,
                                 scc.ROUND_CAP)
    compiled = fn.lower(*_scc_args(
        n_pad, e_pad, [NamedSharding(mesh4, s) for s in specs],
        blocks=4)).compile()
    _fits(compiled)
    # the edge list stays sharded: the only collective is the per-sweep
    # pmax of the color array
    assert "all-gather" not in compiled.as_text()


# blocked layouts on 4 devices: (segment rows, M, S, search rows,
# inv_perm rows, W, F, reach, crash_free)
SHARDED_LAUNCHES = {
    "config2-segmented": (36, 16384, 8, 256, 128, 24, 48, True, True),
    "config5-batch": (132, 512, 8, 128, 128, 32, 64, False, False),
}


@pytest.mark.parametrize("name", sorted(SHARDED_LAUNCHES))
def test_wgl_sharded_compiles_for_v5e_2x2(mesh4, name):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from jepsen_tpu.tpu import ensemble, spmd

    K, M, S, rows, n_perm, W, F, reach, crash_free = \
        SHARDED_LAUNCHES[name]
    shapes = _wgl_shapes(K, M, S, rows) + ((n_perm,),)
    specs = spmd.match_partition_rules(spmd.WGL_RULES,
                                       ensemble.SHARD_ARGS)
    args = [jax.ShapeDtypeStruct(s, jnp.int32,
                                 sharding=NamedSharding(mesh4, p))
            for s, p in zip(shapes, specs)]
    fn = ensemble._jitted_sharded(mesh4, W, F, M + 4, reach, crash_free)
    per_device = _fits(fn.lower(*args).compile())
    # the segment tensors are blocked per device, not replicated: one
    # chip holds about a quarter of the packed trans table
    assert per_device < K * M * S * 4
