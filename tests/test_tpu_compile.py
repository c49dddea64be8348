"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached.

The TPU compiler refuses what interpret mode accepts: dynamic slices of
in-register values, primitives it has no lowering for (`erf`), 1-D row
reductions, tiles it cannot lay out, and more fast memory than a kernel may
use.  Each case compiles one kernel at the sizes `implementation="auto"`
hands it on the chip: Cholesky and trsv up to `MAX_PALLAS_N`, the fused EI
megakernel up to `MAX_ACQ_PALLAS_N` for every tile the autotuner may pick.

The topology is described inside a module fixture (never at import), and
the persistent compilation cache is off around these compiles: an
executable compiled for a described chip cannot be read back without one.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.acq import fused_ei_grad_pallas
from repro.kernels.chol import cholesky_pallas
from repro.kernels.matern import matern52_gram_pallas
from repro.kernels.mixed import mixed_gram_pallas
from repro.kernels.trsv import trsv_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n", [1024, ops.MAX_PALLAS_N])
def test_cholesky_compiles(one_chip, n):
    _compile(cholesky_pallas, one_chip, (n, n))


@pytest.mark.parametrize("n", [1024, ops.MAX_PALLAS_N])
@pytest.mark.parametrize("trans", [False, True])
@pytest.mark.parametrize("rhs", ["vector", "square"])
def test_trsv_compiles(one_chip, n, trans, rhs):
    # vector: ops pads an (n,) right-hand side to 128 lanes; square: the
    # re-anchor's triangular inverse solves against the identity.
    r = 128 if rhs == "vector" else n
    _compile(lambda l, b: trsv_pallas(l, b, trans=trans), one_chip,
             (n, n), (n, r))


def test_matern_gram_compiles(one_chip):
    _compile(lambda x, y: matern52_gram_pallas(x, y, 1.0, 0.3), one_chip,
             (1024, 128), (1024, 128))


def test_mixed_gram_compiles(one_chip):
    _compile(lambda a, b, c, d: mixed_gram_pallas(a, b, c, d, 1.0, 0.3),
             one_chip, (1024, 128), (1024, 128), (1024, 128), (1024, 128))


@pytest.mark.parametrize("n_pad", [128, ops.MAX_ACQ_PALLAS_N])
@pytest.mark.parametrize("block_r", ops.ACQ_BLOCK_R_CANDIDATES)
@pytest.mark.parametrize("mixed", [False, True])
def test_fused_ei_compiles(one_chip, n_pad, block_r, mixed):
    r, d = 2 * block_r, 128
    if mixed:
        fn = lambda xc, xk, xb, xbk, am, al, ab: fused_ei_grad_pallas(
            xc, xb, am, al, ab, 1.0, 0.25, 0.0, xk=xk, xbk=xbk,
            block_r=block_r)
        shapes = [(r, d), (r, d), (n_pad, d), (n_pad, d), (1, n_pad),
                  (1, n_pad), (n_pad, n_pad)]
    else:
        fn = lambda xc, xb, am, al, ab: fused_ei_grad_pallas(
            xc, xb, am, al, ab, 1.0, 0.25, 0.0, block_r=block_r)
        shapes = [(r, d), (n_pad, d), (1, n_pad), (1, n_pad),
                  (n_pad, n_pad)]
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("mixed", [False, True])
def test_fused_ei_batched_over_studies_compiles(one_chip, mixed):
    """The serving round vmaps the megakernel over its 64-slot study axis:
    the study axis becomes a grid dimension with double-buffered blocks."""
    s, n, block_r, d = 64, ops.MAX_ACQ_PALLAS_N, 128, 128
    if mixed:
        fn = jax.vmap(lambda xc, xk, xb, xbk, am, al, ab:
                      fused_ei_grad_pallas(xc, xb, am, al, ab, 1.0, 0.25,
                                           0.0, xk=xk, xbk=xbk,
                                           block_r=block_r))
        shapes = [(s, block_r, d), (s, block_r, d), (s, n, d), (s, n, d),
                  (s, 1, n), (s, 1, n), (s, n, n)]
    else:
        fn = jax.vmap(lambda xc, xb, am, al, ab: fused_ei_grad_pallas(
            xc, xb, am, al, ab, 1.0, 0.25, 0.0, block_r=block_r))
        shapes = [(s, block_r, d), (s, n, d), (s, 1, n), (s, 1, n),
                  (s, n, n)]
    _compile(fn, one_chip, *shapes)


@pytest.fixture(scope="module")
def served_engines():
    """The chip smoke run's two engines (n_max=1024, default AcqConfig)."""
    from repro.core.acquisition import AcqConfig
    from repro.hpo.engine import StudyEngine
    from repro.hpo.pool import SchedulerConfig
    from repro.hpo.space import MIXED_DEMO_SPACE, RESNET_SPACE
    cfg = SchedulerConfig(n_max=1024, acq=AcqConfig())
    return {"float": StudyEngine(RESNET_SPACE.dim, cfg, 48),
            "mixed": StudyEngine(MIXED_DEMO_SPACE.dim, cfg, 16,
                                 descs=[MIXED_DEMO_SPACE.descriptor()] * 16)}


@pytest.mark.parametrize("space", ["float", "mixed"])
@pytest.mark.parametrize("block_r", ops.ACQ_BLOCK_R_CANDIDATES)
def test_served_advance_compiles(one_chip, served_engines, monkeypatch,
                                 space, block_r):
    """The whole fused round, as `auto` builds it on a TPU, for every tile
    the autotuner may pick.  Inside a program XLA may place the batched
    kernel operands in VMEM as well, so a kernel that compiles alone can
    still overflow its scoped VMEM here."""
    jax.clear_caches()          # the tile is baked in at trace time
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "acq_tile_config",
                        lambda n_pad, d, s, interp, **kw: ops.AcqTileConfig(
                            block_r=block_r, d_pad=128, measured=False))
    eng = served_engines[space]
    s, d = eng.n_studies, eng.gp_cfg.dim
    spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    state = jax.tree.map(spec, eng.state)
    desc = (jax.tree.map(spec, eng.desc),) if eng.mixed else ()
    arg = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    hlo = eng._advance_all.lower(
        state, *desc, arg((s, d)), arg((s,)), arg((s,), bool),
        arg((s, 2), jnp.uint32), top_t=1).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("space", ["float", "mixed"])
def test_served_reanchor_and_fantasy_compile(one_chip, served_engines,
                                             monkeypatch, space):
    """The re-anchor (the Gram built on differences, then the Pallas
    Cholesky and inverse) and the q-ask's fantasy rows (covariance columns
    on differences through the Pallas gram, under two vmaps), as `auto`
    builds them on a TPU."""
    jax.clear_caches()
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    eng = served_engines[space]
    s, d = eng.n_studies, eng.gp_cfg.dim
    spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    state = jax.tree.map(spec, eng.state)
    desc = (jax.tree.map(spec, eng.desc),) if eng.mixed else ()
    i = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    hlo = eng._reanchor_at.lower(state, *desc, i).compile().as_text()
    assert "tpu_custom_call" in hlo
    xs = jax.ShapeDtypeStruct((4, d), jnp.float32, sharding=one_chip)
    hlo = eng._refantasize_at.lower(state, *desc, i, xs).compile().as_text()
    assert "tpu_custom_call" in hlo
