"""Multi-tenant StudyPool tests: batched suggest, routed/queued absorption,
per-study isolation (capacity, faults, lag, telemetry), pool checkpointing,
and the one-code-path contract with TrialScheduler."""
import dataclasses
import json
import tempfile

import numpy as np
import pytest

from repro.core import GPCapacityError
from repro.hpo.pool import SchedulerConfig, StudyPool
from repro.hpo.scheduler import TrialScheduler
from repro.hpo.space import LENET_SPACE, RESNET_SPACE


def quad(center):
    """Smooth per-study objective on the unit cube (maximize)."""
    def f(unit):
        return float(-np.sum((np.asarray(unit) - center) ** 2))
    return f


CENTERS = [np.asarray([0.3, 0.6, 0.5]), np.asarray([0.8, 0.2, 0.4]),
           np.asarray([0.5, 0.5, 0.9])]


def _drive(pool, rounds, t=1):
    """suggest_all -> evaluate -> absorb_many, completion-order shuffled."""
    rng = np.random.default_rng(0)
    for _ in range(rounds):
        suggestions = pool.suggest_all(t=t)
        events = [(sid, tr, quad(CENTERS[sid])(tr.unit))
                  for sid, trs in suggestions.items() for tr in trs]
        rng.shuffle(events)
        pool.absorb_many(events)


def test_pool_round_advances_every_study():
    cfg = SchedulerConfig(n_max=32, seed=0)
    pool = StudyPool([RESNET_SPACE] * 3, cfg)
    _drive(pool, rounds=4)
    for s in range(3):
        assert pool.engine.n(s) == 4
        assert pool.best(s) is not None
        units = np.stack([t.unit for t in pool.studies[s].trials])
        assert units.min() >= 0.0 and units.max() <= 1.0
    # ledgers are independent: ids restart per study
    assert [t.trial_id for t in pool.studies[1].trials[:2]] == [0, 1]


def test_pool_matches_independent_schedulers():
    """One code path, S-way: absorbing the same observations through the
    pool and through S independent TrialSchedulers yields identical
    posteriors (the batched-parity contract at the orchestration layer)."""
    cfg = SchedulerConfig(n_max=16, seed=0)
    pool = StudyPool([RESNET_SPACE] * 2, cfg)
    scheds = [TrialScheduler(RESNET_SPACE, cfg) for _ in range(2)]
    rng = np.random.default_rng(3)
    for k in range(5):
        for s in range(2):
            unit = rng.uniform(size=3).astype(np.float32)
            val = quad(CENTERS[s])(unit)
            pool.absorb(s, pool._make_trial(s, unit), val)
            scheds[s].absorb(scheds[s]._make_trial(unit), val)
    for s in range(2):
        got, want = pool.state(s), scheds[s].state
        assert int(got.n) == int(want.n) == 5
        np.testing.assert_allclose(np.asarray(got.l_buf),
                                   np.asarray(want.l_buf), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(got.alpha),
                                   np.asarray(want.alpha), rtol=1e-5,
                                   atol=1e-7)


def test_absorb_many_matches_routed_absorbs():
    """Masked batched rounds == per-event routed appends, including events
    with per-study multiplicity > 1 (spillover rounds)."""
    cfg = SchedulerConfig(n_max=16, seed=0)
    a = StudyPool([RESNET_SPACE] * 3, cfg)
    b = StudyPool([RESNET_SPACE] * 3, cfg)
    rng = np.random.default_rng(7)
    events_a, events_b = [], []
    # interleaved completion order, study 1 completes twice in the queue
    for sid in (1, 0, 1, 2, 0):
        unit = rng.uniform(size=3).astype(np.float32)
        val = quad(CENTERS[sid])(unit)
        events_a.append((sid, a._make_trial(sid, unit), val))
        events_b.append((sid, b._make_trial(sid, unit), val))
    a.absorb_many(events_a)
    for sid, tr, val in events_b:
        b.absorb(sid, tr, val)
    for s in range(3):
        np.testing.assert_allclose(np.asarray(a.state(s).l_buf),
                                   np.asarray(b.state(s).l_buf), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(a.state(s).alpha),
                                   np.asarray(b.state(s).alpha), rtol=1e-5,
                                   atol=1e-7)
        assert int(a.state(s).n) == int(b.state(s).n)
        assert a.studies[s].trials[-1].clamp_count is not None


def test_pool_capacity_fault_is_per_study():
    """Filling one tenant must raise for that tenant only and leave its
    neighbors absorbing normally."""
    cfg = SchedulerConfig(n_max=2, seed=0)
    pool = StudyPool([RESNET_SPACE] * 2, cfg)
    rng = np.random.default_rng(0)
    for _ in range(2):
        u = rng.uniform(size=3).astype(np.float32)
        pool.absorb(1, pool._make_trial(1, u), 0.5)
    with pytest.raises(GPCapacityError):
        pool.absorb(1, pool._make_trial(
            1, rng.uniform(size=3).astype(np.float32)), 0.1)
    # study 1 state not corrupted; study 0 unaffected
    assert pool.engine.n(1) == 2
    pool.absorb(0, pool._make_trial(
        0, rng.uniform(size=3).astype(np.float32)), 0.3)
    assert pool.engine.n(0) == 1


def test_absorb_many_capacity_fault_leaves_neighbors_consistent():
    """A GPCapacityError inside an absorb_many round must not mark a healthy
    neighbor's trial done without absorbing its observation (the round is
    capacity-checked before any ledger mutation)."""
    cfg = SchedulerConfig(n_max=2, seed=0)
    pool = StudyPool([RESNET_SPACE] * 2, cfg)
    rng = np.random.default_rng(0)
    for _ in range(2):
        u = rng.uniform(size=3).astype(np.float32)
        pool.absorb(0, pool._make_trial(0, u), 0.5)  # study 0 now full
    t_full = pool._make_trial(0, rng.uniform(size=3).astype(np.float32))
    t_ok = pool._make_trial(1, rng.uniform(size=3).astype(np.float32))
    with pytest.raises(GPCapacityError):
        pool.absorb_many([(1, t_ok, 0.7), (0, t_full, 0.9)])
    # neither trial entered the ledger-done/GP-absorbed state inconsistently
    assert t_ok.status == "pending" and pool.engine.n(1) == 0
    assert t_full.status == "pending" and pool.engine.n(0) == 2
    assert pool.best(1) is None
    # the healthy study keeps absorbing normally afterwards
    pool.absorb_many([(1, t_ok, 0.7)])
    assert t_ok.status == "done" and pool.engine.n(1) == 1


def test_absorb_many_whole_queue_capacity_check_covers_later_rounds():
    """Overflow queued for a LATER round (per-study multiplicity) must also
    raise before anything is absorbed — the drain is all-or-nothing with
    respect to capacity, so no event is ever silently dropped."""
    cfg = SchedulerConfig(n_max=2, seed=0)
    pool = StudyPool([RESNET_SPACE] * 2, cfg)
    rng = np.random.default_rng(0)
    u = lambda: rng.uniform(size=3).astype(np.float32)  # noqa: E731
    pool.absorb(0, pool._make_trial(0, u()), 0.5)  # study 0 at n_max - 1
    a, b = pool._make_trial(0, u()), pool._make_trial(0, u())
    c, d = pool._make_trial(1, u()), pool._make_trial(1, u())
    with pytest.raises(GPCapacityError):
        pool.absorb_many([(0, a, 0.1), (1, c, 0.2), (0, b, 0.3),
                          (1, d, 0.4)])
    # nothing from the queue was absorbed — no partial round, no lost event
    assert [t.status for t in (a, b, c, d)] == ["pending"] * 4
    assert pool.engine.n(0) == 1 and pool.engine.n(1) == 0


def test_pool_lag_refit_is_per_study():
    cfg = SchedulerConfig(n_max=16, seed=0, lag=2)
    pool = StudyPool([RESNET_SPACE] * 2, cfg)
    rng = np.random.default_rng(1)
    for k in range(2):
        u = rng.uniform(size=3).astype(np.float32)
        pool.absorb(0, pool._make_trial(0, u), float(k))
    # study 0 tripped its lag counter and refit; study 1 never absorbed
    assert pool.engine.since_refit(0) == 0
    assert pool.engine.n(0) == 2
    assert pool.engine.since_refit(1) == 0 and pool.engine.n(1) == 0
    # params diverge per study after the refit
    p = pool.engine.state.params
    assert p.rho.shape == (2,)
    assert float(p.rho[0]) != pytest.approx(float(p.rho[1])) or \
        float(p.sigma2[0]) != pytest.approx(float(p.sigma2[1]))


def test_pool_failure_policy_routed_to_owner():
    cfg = SchedulerConfig(n_max=16, seed=0, max_retries=1,
                          failure_penalty=-50.0)
    pool = StudyPool([RESNET_SPACE] * 2, cfg)
    tr = pool.seed_trials(1, 1)[0]
    retry = pool.record_failure(1, tr, "node lost")
    assert tr.status == "failed"
    assert retry is not None and retry.retries == 1
    # penalty pseudo-observation landed in study 1 only
    assert pool.engine.n(1) == 1 and pool.engine.n(0) == 0
    assert float(pool.state(1).y_buf[0]) == pytest.approx(-50.0)


def test_pool_checkpoint_restore_roundtrip():
    with tempfile.TemporaryDirectory() as d:
        cfg = SchedulerConfig(n_max=16, seed=0, ckpt_dir=d)
        pool = StudyPool([RESNET_SPACE] * 3, cfg)
        _drive(pool, rounds=3)
        states = [np.asarray(pool.state(s).alpha) for s in range(3)]

        pool2 = StudyPool([RESNET_SPACE] * 3, cfg)
        assert pool2.restore()
        for s in range(3):
            assert pool2.engine.n(s) == 3
            np.testing.assert_allclose(np.asarray(pool2.state(s).alpha),
                                       states[s], rtol=1e-6)
            assert len(pool2.studies[s].trials) == \
                len(pool.studies[s].trials)
            assert pool2.studies[s].next_id == pool.studies[s].next_id
        # restored pool keeps absorbing + suggesting
        _drive(pool2, rounds=1)
        assert all(pool2.engine.n(s) == 4 for s in range(3))


def test_restore_resumes_prng_streams_no_replayed_batches():
    """The per-study seed/EI PRNG streams ride the checkpoint: a restored
    pool must not re-draw random batches already drawn pre-crash."""
    with tempfile.TemporaryDirectory() as d:
        cfg = SchedulerConfig(n_max=16, seed=0, ckpt_dir=d)
        pool = StudyPool([RESNET_SPACE] * 2, cfg)
        # study 1 absorbs (firing a checkpoint) while study 0 is still at
        # n == 0 with its seed batch only in the ledger
        drawn = {tuple(t.unit.tolist()) for t in pool.seed_trials(0, 2)}
        tr = pool.seed_trials(1, 1)[0]
        pool.absorb(1, tr, 0.5)

        pool2 = StudyPool([RESNET_SPACE] * 2, cfg)
        assert pool2.restore()
        again = {tuple(t.unit.tolist()) for t in pool2.seed_trials(0, 2)}
        assert drawn.isdisjoint(again), \
            "restored pool replayed a pre-crash seed batch"


def test_pool_rejects_mismatched_dims_and_study_counts():
    with pytest.raises(ValueError, match="dimensionality"):
        StudyPool([RESNET_SPACE, LENET_SPACE], SchedulerConfig(n_max=8))
    with tempfile.TemporaryDirectory() as d:
        cfg = SchedulerConfig(n_max=8, seed=0, ckpt_dir=d)
        pool = StudyPool([RESNET_SPACE] * 2, cfg)
        pool.checkpoint()
        # the stacked-buffer shape guard fires before the registry count
        # check: the S axis is part of every leaf's shape
        with pytest.raises(ValueError, match="studies"):
            StudyPool([RESNET_SPACE] * 3, cfg).restore()
        # same-shape pool with a different n_max is also refused
        with pytest.raises(ValueError, match="shape mismatch"):
            StudyPool([RESNET_SPACE] * 2,
                      SchedulerConfig(n_max=12, seed=0, ckpt_dir=d)).restore()


def test_repeated_seeding_draws_fresh_points():
    """The per-study seed stream is persistent: a second seeding round (or
    a width top-up at n == 0) must not replay the same random batch."""
    pool = StudyPool([RESNET_SPACE], SchedulerConfig(n_max=16, seed=0))
    first = pool.suggest(0, 2)
    second = pool.suggest(0, 2)
    units = {tuple(t.unit.tolist()) for t in first + second}
    assert len(units) == 4, "seed batches repeated"


def test_parallel_width_topup_at_n0_has_no_duplicate_points():
    """run(parallel=4, n_seed=1): the pre-absorb top-up used to launch the
    identical seed point width times."""
    from repro.hpo.scheduler import TrialScheduler as TS
    sched = TS(RESNET_SPACE, SchedulerConfig(n_max=32, seed=0, parallel=4))
    sched.run(lambda hp: quad(CENTERS[0])(
        RESNET_SPACE.to_unit(hp)), budget=6, n_seed=1)
    launched = [tuple(t.unit.tolist()) for t in sched.trials]
    assert len(set(launched)) == len(launched), "duplicate launches"


def test_fully_lazy_inverse_reanchor_keeps_params():
    """lag=0 + inv_refresh: the drift guard refactors (since_refit resets)
    without touching the kernel params."""
    cfg = SchedulerConfig(n_max=16, seed=0, lag=0, inv_refresh=3)
    pool = StudyPool([RESNET_SPACE] * 2, cfg)
    rho_before = float(pool.engine.state.params.rho[0])
    rng = np.random.default_rng(0)
    for k in range(3):
        u = rng.uniform(size=3).astype(np.float32)
        pool.absorb(0, pool._make_trial(0, u), float(k) * 0.1)
    assert pool.engine.since_refit(0) == 0          # re-anchored
    assert pool.engine.since_refit(1) == 0 and pool.engine.n(1) == 0
    assert float(pool.engine.state.params.rho[0]) == pytest.approx(
        rho_before)                                  # params untouched
    assert pool.engine.n(0) == 3


def test_checkpoint_cadence_batches_snapshots():
    with tempfile.TemporaryDirectory() as d:
        from repro import checkpoint as ckpt_mod
        cfg = SchedulerConfig(n_max=16, seed=0, ckpt_dir=d, ckpt_every=3)
        pool = StudyPool([RESNET_SPACE], cfg)
        rng = np.random.default_rng(0)
        for k in range(2):
            u = rng.uniform(size=3).astype(np.float32)
            pool.absorb(0, pool._make_trial(0, u), float(k))
        assert ckpt_mod.latest_step(d) is None       # below cadence
        u = rng.uniform(size=3).astype(np.float32)
        pool.absorb(0, pool._make_trial(0, u), 0.9)
        assert ckpt_mod.latest_step(d) == 3          # cadence hit


def test_scheduler_is_one_study_pool():
    """The one-code-path contract: the scheduler's suggest/absorb ARE the
    pool's (same engine object, same ledger list)."""
    sched = TrialScheduler(RESNET_SPACE, SchedulerConfig(n_max=16, seed=0))
    assert isinstance(sched.pool, StudyPool)
    assert sched.trials is sched.pool.studies[0].trials
    tr = sched._make_trial(np.full(3, 0.4, np.float32))
    sched.absorb(tr, 1.0)
    assert sched.pool.engine.n(0) == 1
    assert int(sched.state.n) == 1


# -- the pool's key table: per-study PRNG streams ----------------------------

def _ref_split(ref: list, s: int) -> np.ndarray:
    """Advance the reference chain of study s by one plain split."""
    import jax
    ref[s], sub = jax.random.split(ref[s])
    return np.asarray(sub)


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_key_table_streams_match_per_study_split_chains(seed):
    """Every subkey the table hands out equals the one a study's own
    `jax.random.split` chain draws, under a random mix of single and
    batched splits over id subsets of changing width (empty and all S
    included), a `reset_study(seed=...)` and an export/import round trip."""
    import jax
    S = 5
    pool = StudyPool([RESNET_SPACE] * S, SchedulerConfig(n_max=8, seed=seed))
    ref = [jax.random.PRNGKey(seed + i) for i in range(S)]
    rng = np.random.default_rng(seed % 1000)
    widths = [0, S, 1, 3, S, 0, 2, 4, 1, S]
    for step, w in enumerate(widths):
        ids = sorted(rng.choice(S, size=w, replace=False).tolist())
        got = pool._split_many(ids)
        assert got.shape == (w, 2) and got.dtype == np.uint32
        for j, s in enumerate(ids):
            np.testing.assert_array_equal(got[j], _ref_split(ref, s))
        s = int(rng.integers(S))
        np.testing.assert_array_equal(pool._split(s), _ref_split(ref, s))
        if step == 3:
            pool.reset_study(2, seed=seed + 77)
            ref[2] = jax.random.PRNGKey(seed + 77)
        if step == 6:
            snap = pool.export_study(1)
            assert snap["meta"]["key"] == np.asarray(ref[1]).tolist()
            pool.import_study(4, snap["tree"], snap["meta"])
            ref[4] = ref[1]
    for s in range(S):
        np.testing.assert_array_equal(pool._split(s), _ref_split(ref, s))


def test_key_table_compiles_nothing_for_new_widths():
    """After the first round, serving a changing number of asking studies
    compiles no program: the split runs one fixed shape for every width."""
    import jax
    compiles = []

    def on_event(event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    cfg = SchedulerConfig(n_max=16, seed=0)
    pool = StudyPool([RESNET_SPACE] * 4, cfg)
    rng = np.random.default_rng(0)
    for s in range(4):       # every study past its seed trial
        u = rng.uniform(size=3).astype(np.float32)
        pool.absorb(s, pool._make_trial(s, u), float(s))
    pool.advance_round([], studies=[0, 1, 2, 3])      # the first round
    pool.suggest(0)                           # and the one-study program
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for ids in ([0], [1, 3], [0, 1, 2], [2], [0, 1, 2, 3], []):
            pool.advance_round([], studies=ids)
            pool._split_many(ids)
        pool.suggest(2)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiles == []


def test_checkpoint_key_format_restores_the_same_next_subkey():
    """The snapshot keeps each study's key as a two-item list, as it always
    has: a checkpoint written with a key in that form restores to the same
    next subkey as a plain split of that key."""
    import jax
    from repro import checkpoint as ckpt_mod
    with tempfile.TemporaryDirectory() as d:
        cfg = SchedulerConfig(n_max=8, seed=3, ckpt_dir=d)
        pool = StudyPool([RESNET_SPACE] * 3, cfg)
        pool._split_many([0, 2])
        pool.checkpoint()
        _, _, meta = ckpt_mod.restore_latest(
            d, dataclasses.asdict(pool.engine.state))
        keys = [rec["key"] for rec in json.loads(meta["studies"])]
        assert all(isinstance(k, list) and len(k) == 2 and
                   all(isinstance(v, int) for v in k) for k in keys)
        pool2 = StudyPool([RESNET_SPACE] * 3, cfg)
        assert pool2.restore()
        for s, k in enumerate(keys):
            want = np.asarray(jax.random.split(
                jax.numpy.asarray(k, jax.numpy.uint32))[1])
            np.testing.assert_array_equal(pool2._split(s), want)
            np.testing.assert_array_equal(pool._split(s), want)
