"""StudyBank: fleet serialization, kill->resume replay, and the bank's
picks at bucket boundaries against the float64 reference."""
import json
import os

import numpy as np
import pytest
from scipy import stats

from repro.core import AskTellOptimizer, StudyBank, StudyLedger
from repro.core.studybank import pack_rng_state, unpack_rng_state

SPACE = {"x": stats.uniform(0, 1), "y": stats.uniform(-1, 2)}
STRATS = ["bayesian", "tpe", "clustering"]


def _objective(p):
    return -(p["x"] - 0.3) ** 2 - (p["y"] - 0.5) ** 2


def _run(bank, steps, leave_pending=False):
    """Drive every study; returns the full proposal history.  With
    ``leave_pending`` every third ask stays in flight (async mode)."""
    hist = []
    for s in range(steps):
        trials = bank.ask_all(1)
        for b, ts in enumerate(trials):
            for t in ts:
                hist.append((b, t.id, dict(t.params)))
                if not (leave_pending and s % 3 == 2):
                    bank.tell(b, t.id, _objective(t.params))
    return hist


# --------------------------------------------------------------------------- #
# serialization
# --------------------------------------------------------------------------- #
def test_rng_state_pack_roundtrip():
    rng = np.random.default_rng(1234)
    rng.uniform(size=7)
    rng.integers(0, 10)  # leaves a cached uint32 in the bit generator
    clone = unpack_rng_state(pack_rng_state(rng))
    assert list(clone.uniform(size=5)) == list(rng.uniform(size=5))
    assert clone.bit_generator.state == rng.bit_generator.state


def test_fleet_state_dict_roundtrip_json():
    bank = StudyBank(SPACE, 4, seed=5, mc_samples=32)
    _run(bank, 4, leave_pending=True)
    sd = json.loads(json.dumps(bank.state_dict()))
    bank2 = StudyBank(SPACE, 4, seed=99, mc_samples=32)
    bank2.load_state_dict(sd)
    assert bank2.state_dict() == sd


def test_single_study_view_matches_v1_snapshot_format():
    """A bank study's snapshot entry IS the v1 single-study format: same
    keys, and byte-identical to an AskTellOptimizer replaying the same
    study stand-alone."""
    bank = StudyBank(SPACE, 3, seed=5, mc_samples=32)
    _run(bank, 3)
    entry = bank.state_dict()["studies"][1]
    assert set(entry) == {"version", "next_id", "ask_count", "n_failed",
                          "sign", "best_trace", "trials", "rng_state", "gp"}
    assert entry["version"] == 1
    # a stand-alone (bank-of-one) optimizer loads it and round-trips it
    solo = AskTellOptimizer(SPACE, seed=0)
    solo.load_state_dict(entry)
    assert solo.state_dict() == entry
    assert solo.n_observed == bank.study(1).n_observed
    assert [t.id for t in solo.observed_trials()] == \
        [t.id for t in bank.study(1).observed_trials()]


def test_npz_checkpoint_single_write(tmp_path):
    bank = StudyBank(SPACE, 4, seed=2, mc_samples=32)
    _run(bank, 4, leave_pending=True)
    path = tmp_path / "fleet.npz"
    bank.save(path, iteration=4)
    assert path.exists() and not (tmp_path / "fleet.tmp").exists()
    bank2 = StudyBank(SPACE, 4, seed=77, mc_samples=32)
    assert bank2.load(path) == 4
    assert bank2.state_dict() == bank.state_dict()
    for name in StudyLedger.ARRAY_FIELDS:
        np.testing.assert_array_equal(getattr(bank2.ledger, name),
                                      getattr(bank.ledger, name))


def test_checkpoint_study_count_mismatch_raises(tmp_path):
    bank = StudyBank(SPACE, 3, seed=2, mc_samples=32)
    path = tmp_path / "fleet.npz"
    bank.save(path)
    other = StudyBank(SPACE, 4, seed=2, mc_samples=32)
    with pytest.raises(ValueError):
        other.load(path)
    with pytest.raises(ValueError):
        other.load_state_dict(bank.state_dict())


# --------------------------------------------------------------------------- #
# kill -> resume replay (16-study bank, mid-flight)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("opt", STRATS)
@pytest.mark.parametrize("mode", ["sync", "async"])
def test_bank_kill_resume_replay(opt, mode, tmp_path):
    """A 16-study bank killed mid-flight resumes to the exact proposals of
    an uninterrupted run — sync (every trial told before the next ask) and
    async (a third of the asks still in flight at the kill point)."""
    pending = mode == "async"
    kw = dict(optimizer=opt, seed=11, mc_samples=32)
    ref = StudyBank(SPACE, 16, **kw)
    h_ref = _run(ref, 4, pending) + _run(ref, 3, pending)

    # kill via the one-write npz checkpoint ...
    a = StudyBank(SPACE, 16, **kw)
    _run(a, 4, pending)
    path = tmp_path / f"{opt}-{mode}.npz"
    a.save(path)
    b = StudyBank(SPACE, 16, **kw)
    b.load(path)
    h_npz = _run(b, 3, pending)
    assert h_npz == h_ref[len(h_ref) - len(h_npz):]

    # ... and via the JSON fleet state dict
    c = StudyBank(SPACE, 16, **kw)
    c.load_state_dict(json.loads(json.dumps(a.state_dict())))
    h_json = _run(c, 3, pending)
    assert h_json == h_ref[len(h_ref) - len(h_json):]


# --------------------------------------------------------------------------- #
# bucket boundaries, judged by the float64 reference
# --------------------------------------------------------------------------- #
# a view's ask of n = 2 runs at bucket 32 up to here and at 64 from here on
# (n_obs + pend_cap(4) + n(2) > 32)
EDGE = 27


def _seeded_bank(opt, n_obs_list, seed=31):
    """A bank with one study per requested observation count, frozen
    hypers (no fit runs during the ask under test), noise-floored values
    so the acquisition surfaces have no ties."""
    rng = np.random.default_rng(seed)
    bank = StudyBank(SPACE, len(n_obs_list), optimizer=opt, seed=seed,
                     mc_samples=64)
    led = bank.ledger
    for b, k in enumerate(n_obs_list):
        v = bank.study(b)
        for _ in range(k):
            p = {"x": float(rng.uniform(0, 1)),
                 "y": float(rng.uniform(-1, 1))}
            v.observe_params(p, float(rng.normal()))
        led.have_fit[b] = 1
        led.n_fit[b] = k
        led.log_ls[b] = np.log(0.5)
        led.log_var[b] = 0.1
        led.log_noise[b] = np.log(1e-2)
        led.y_mean[b] = 0.0
        led.y_std[b] = 1.0
    return bank


def _check_edge(opt, n_obs):
    """One study of ``n_obs`` observations asks for 2 through its view, at
    the bucket the edge puts it in; the reference judges its picks under
    the bank's own fit, so padding that leaks into a score shows as a
    gap."""
    from served_asks import GAP_LIMIT, gaps, served

    bank = _seeded_bank(opt, [n_obs])
    seen = []
    gather = bank._gather_obs
    bank._gather_obs = lambda k, na, rows: (seen.append(na),
                                            gather(k, na, rows))[1]
    _, ask = served(bank, bank.study(0), 2)
    assert seen == [32 if n_obs < EDGE else 64]
    assert len(ask["X"]) == n_obs and min(ask["picks"]) >= 0
    assert max(gaps(ask)) <= GAP_LIMIT[ask["family"]]


@pytest.mark.parametrize("n_obs", [EDGE - 1, EDGE, EDGE + 1])
def test_bucket_boundary_parity_bayesian(n_obs):
    _check_edge("bayesian", n_obs)


@pytest.mark.parametrize("n_obs", [EDGE - 1, EDGE, EDGE + 1])
def test_bucket_boundary_parity_tpe(n_obs):
    _check_edge("tpe", n_obs)


@pytest.mark.parametrize("n_obs", [EDGE - 1, EDGE, EDGE + 1])
def test_bucket_boundary_parity_clustering(n_obs):
    _check_edge("clustering", n_obs)


def test_mixed_bank_parity_with_homogeneous_banks():
    """One bank holding GP + TPE + clustering studies picks bit-equal to
    three homogeneous banks: the per-family sub-batching inside a single
    ``ask_all`` changes the dispatch grouping, never the math — every row
    of a vmap'd stage is independent of its neighbors, and all four banks
    draw the identical flat candidate stream from the same bank seed."""
    B = 9
    strats = STRATS * 3

    def build(opt):
        bank = StudyBank(SPACE, B, optimizer=opt, seed=11, mc_samples=48,
                         fit_steps=8)
        rng = np.random.default_rng(2)
        for b in range(B):
            for _ in range(8):
                p = {"x": float(rng.uniform(0, 1)),
                     "y": float(rng.uniform(-1, 1))}
                bank.study(b).observe_params(p, _objective(p))
        return bank

    mixed = build(strats)
    assert mixed.optimizer == "mixed"
    homos = {s: build(s) for s in STRATS}
    for rnd in range(3):
        got = mixed.ask_all(2)
        want = {s: homos[s].ask_all(2) for s in STRATS}
        for b in range(B):
            s = strats[b]
            assert [t.params for t in got[b]] \
                == [t.params for t in want[s][b]], (rnd, b, s)
            for tm, th in zip(got[b], want[s][b]):
                # identical params -> identical objective fed to both
                mixed.tell(b, tm.id, _objective(tm.params))
                homos[s].tell(b, th.id, _objective(th.params))


def test_bucket_shapes_shared_across_bank():
    """Studies of different sizes share one bucket: the bank ask pads every
    study to the same power-of-2 capacity, and the obs stage factors every
    row at it."""
    bank = _seeded_bank("bayesian", [EDGE - 1, EDGE, EDGE + 1])
    bank.ask_all(1)
    # all three studies proposed through one program at one bucket shape
    na = bank._gp_cache["key"][1]
    assert na >= 64
    assert bank._gp_cache["L"].shape == (3, na, na)
    for b in range(3):
        assert len(bank.study(b).pending_trials()) == 1


# --------------------------------------------------------------------------- #
# the fit runs the due rows alone, at a power-of-2 row bucket
# --------------------------------------------------------------------------- #
FIT_FIELDS = ("log_ls", "log_var", "log_noise", "y_mean", "y_std", "n_fit",
              "have_fit")
DUE_SETS = [(2,), (0, 4), (1, 3, 5)]


def _observe(bank, b, k, rng):
    for _ in range(k):
        p = {"x": float(rng.uniform(0, 1)), "y": float(rng.uniform(-1, 1))}
        bank.study(b).observe_params(p, _objective(p) + 0.01 * rng.normal())


def _gp_bank(seed=5):
    """Six GP-BUCB studies of 20..35 observations (bucket na = 64)."""
    rng = np.random.default_rng(seed)
    bank = StudyBank(SPACE, 6, optimizer="bayesian", seed=seed,
                     mc_samples=32)
    for b in range(6):
        _observe(bank, b, 20 + 3 * b, rng)
    return bank, rng


def _ask_and_tell(bank, rng):
    for b, ts in enumerate(bank.ask_all(1)):
        for t in ts:
            bank.tell(b, t.id, _objective(t.params) + 0.01 * rng.normal())


@pytest.mark.parametrize("due", DUE_SETS)
def test_due_row_fit_matches_whole_sub_batch_fit(due):
    """Fitting the gathered due rows, padded to their row bucket, gives
    each due row the hypers the fit over the whole sub-batch gives it;
    rows not due keep their fit state bit for bit."""
    from repro.core import gp as gp_lib
    from repro.core.studybank import _y_standardization, row_bucket

    bank, rng = _gp_bank()
    _ask_and_tell(bank, rng)                  # the first fit: every row
    led = bank.ledger
    for b in due:
        _observe(bank, b, bank.refit_every, rng)
    gpr = bank._gp_fam_rows
    ko = led.n_observed()[gpr].astype(np.int32)
    Xd, yraw, mask = bank._gather_obs(ko, 64, gpr)
    ym, ys = led.y_mean[gpr].copy(), led.y_std[gpr].copy()
    for i in due:
        ym[i], ys[i] = _y_standardization(yraw[i, :ko[i]])
    whole = [np.asarray(a) for a in gp_lib.fit_hypers_bank(
        Xd, yraw, mask, led.log_ls[gpr], led.log_var[gpr],
        led.log_noise[gpr], ym, ys, steps=bank.fit_steps)]
    before = {f: getattr(led, f).copy() for f in FIT_FIELDS}
    run0 = bank.counters.snapshot()["fit.rows_run"]
    assert bank._fit_if_due(Xd, yraw, mask, ko, gpr)
    assert (bank.counters.snapshot()["fit.rows_run"] - run0
            == row_bucket(len(due), len(gpr)))
    for i, b in enumerate(gpr):
        if i in due:
            for f, w in zip(("log_ls", "log_var", "log_noise"), whole):
                np.testing.assert_allclose(getattr(led, f)[b], w[i],
                                           rtol=1e-6, err_msg=f)
            assert (led.y_mean[b], led.y_std[b]) == (ym[i], ys[i])
            assert led.n_fit[b] == ko[i]
        else:
            for f in FIT_FIELDS:
                np.testing.assert_array_equal(getattr(led, f)[b],
                                              before[f][b], err_msg=f)


def test_due_row_fits_compile_nothing_after_the_first():
    """The first fit at a bucket compiles the fit at every row bucket;
    later fits of 1, 2 and 3 due rows add no jit cache entry.  The warm
    dispatches leave the ledger, the obs stamp, the RNG and the op
    sequence as they found them."""
    from repro.analysis.sanitizers import no_retrace
    from repro.core import gp as gp_lib
    from repro.core.studybank import row_buckets

    gp_lib.fit_hypers_bank.clear_cache()      # no bucket compiled before
    bank, rng = _gp_bank()
    led = bank.ledger
    warm = bank._warm_fit_buckets
    seen = []

    def state():
        return ({k: v.copy() for k, v in vars(led).items()
                 if isinstance(v, np.ndarray)},
                led.obs_stamp, bank._rng.bit_generator.state, bank.op_seq)

    def watched(*a):
        s0 = state()
        warm(*a)
        s1 = state()
        assert s1[0].keys() == s0[0].keys()
        for k in s0[0]:
            np.testing.assert_array_equal(s1[0][k], s0[0][k], err_msg=k)
        assert s1[1:] == s0[1:]
        seen.append(a[1])

    bank._warm_fit_buckets = watched
    _ask_and_tell(bank, rng)                  # the first fit: every row
    assert seen == [6]
    warmed = bank.counters.snapshot()["fit.warm_calls"]
    assert warmed == len(row_buckets(6)) - 1 == 3
    calls = bank.counters.snapshot()["fit.calls"]
    with no_retrace():
        for due in DUE_SETS:
            for b in due:
                _observe(bank, b, bank.refit_every, rng)
            _ask_and_tell(bank, rng)
    snap = bank.counters.snapshot()
    assert snap["fit.calls"] == calls + len(DUE_SETS)
    assert snap["fit.warm_calls"] == warmed


def test_factor_counters_add_up():
    """One factoring per obs-stage miss, of every GP-family row at the
    ask's bucket: ``factors.slots`` grows by rows x na on each miss and
    ``factors.obs`` by the rows' observations and pending trials."""
    from repro.core.studybank import _pow2

    bank, rng = _gp_bank()
    led = bank.ledger
    held = []
    for step in range(6):
        s0 = bank.counters.snapshot()
        ko, kp = led.n_observed(), led.n_pending()
        pend_cap = max(4, -(-int(kp.max()) // 4) * 4)
        na = _pow2(max(16, int(ko.max()) + pend_cap + 1))
        out = bank.ask_all(1)
        s1 = bank.counters.snapshot()
        miss = (s1["obs_stage.calls"] - s0["obs_stage.calls"]
                - (s1["obs_stage.hits"] - s0["obs_stage.hits"]))
        assert s1["factors.calls"] - s0["factors.calls"] == miss
        assert s1["factors.rows"] - s0["factors.rows"] == 6 * miss
        assert s1["factors.slots"] - s0["factors.slots"] == 6 * na * miss
        assert (s1["factors.obs"] - s0["factors.obs"]
                == miss * int(ko.sum() + kp.sum()))
        if miss:
            assert s1["factors.ns"] > s0["factors.ns"]
        for b, ts in enumerate(out):        # real tells, then held trials
            for t in ts:
                if step % 2:
                    held.append((b, t.id))
                else:
                    bank.tell(b, t.id, _objective(t.params))
    snap = bank.counters.snapshot()
    assert snap["factors.calls"] == (snap["obs_stage.calls"]
                                     - snap["obs_stage.hits"]) > 0
    assert snap["obs_stage.hits"] > 0 and held
    assert 0 < snap["factors.obs"] <= snap["factors.slots"]
    assert snap["factors.ill_conditioned"] == 0


@pytest.mark.parametrize("log_var", [0.0, 2.0])
def test_ill_conditioned_rows_are_counted_at_every_factoring(log_var):
    """A near-duplicate history with the noise at its floor passes the
    condition limit (``log_var`` 0) or breaks the float32 factorization
    (2, a NaN estimate); either counts at each factoring, and warns
    once."""
    import warnings

    rng = np.random.default_rng(9)
    bank = StudyBank(SPACE, 2, optimizer="bayesian", seed=9, mc_samples=32)
    _observe(bank, 0, 20, rng)
    for i in range(60):                       # 60 points 1e-7 apart
        bank.study(1).observe_params({"x": 0.5 + 1e-7 * i, "y": 0.1},
                                     0.01 * rng.normal())
    bank.ask_all(1)                           # the first fit of both
    assert bank.counters.snapshot()["factors.ill_conditioned"] == 0
    led = bank.ledger
    led.log_var[1], led.log_noise[1] = log_var, -30.0
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for k in (1, 2):                      # a tell short of a refit
            bank.study(1).observe_params({"x": 0.5, "y": 0.1}, 0.0)
            bank.ask_all(1)
            assert bank.counters.snapshot()["factors.ill_conditioned"] == k
    cond = bank._gp_cache["cond"]
    assert cond[0] < 1e7 and not cond[1] <= 1e7
    assert sum("condition estimate" in str(x.message) for x in w) == 1


# --------------------------------------------------------------------------- #
# the factors stay on the device
# --------------------------------------------------------------------------- #
def test_ledger_and_snapshot_hold_no_factors(tmp_path):
    """The Cholesky factors live in the obs-stage cache alone: after an
    ask at na = 64 the ledger has no ``L``/``Linv`` and a snapshot no
    ``led_L``/``led_Linv``."""
    bank, rng = _gp_bank()
    _ask_and_tell(bank, rng)
    assert bank._gp_cache["key"][1] >= 64
    assert not hasattr(bank.ledger, "L")
    assert not hasattr(bank.ledger, "Linv")
    path = tmp_path / "fleet.npz"
    bank.save(path)
    with np.load(path) as z:
        assert "led_X" in z.files
        assert "led_L" not in z.files and "led_Linv" not in z.files


def test_snapshot_with_factors_still_loads(tmp_path):
    """A snapshot written while the ledger held the factors carries
    ``led_L``/``led_Linv``; it loads, they are left unread, and the next
    ``ask_all`` equals that of the bank that wrote it, trial for trial."""
    bank, rng = _gp_bank()
    for _ in range(2):
        _ask_and_tell(bank, rng)
    path = tmp_path / "fleet.npz"
    bank.save(path, iteration=7)
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    B, na = bank.n_studies, bank._gp_cache["key"][1]
    arrays["led_L"] = rng.normal(size=(B, na, na)).astype(np.float32)
    arrays["led_Linv"] = rng.normal(size=(B, na, na)).astype(np.float32)
    old = tmp_path / "old.npz"
    np.savez(old, **arrays)
    fresh = StudyBank(SPACE, 6, optimizer="bayesian", seed=5,
                      mc_samples=32)
    assert fresh.load(old) == 7
    assert not hasattr(fresh.ledger, "L")
    want, got = bank.ask_all(2), fresh.ask_all(2)
    assert [[(t.id, t.params) for t in ts] for ts in got] \
        == [[(t.id, t.params) for t in ts] for ts in want]


def test_obs_stage_miss_copies_no_factors_to_the_host(monkeypatch):
    """An obs-stage miss brings only per-row numbers to the host (the
    condition estimates, the fit's hypers, the picks): no array of rank
    3, such as the ``(R, na, na)`` factors, passes ``jax.device_get``."""
    import jax

    bank, rng = _gp_bank()
    _ask_and_tell(bank, rng)
    shapes = []
    real = jax.device_get

    def recording(x):
        shapes.extend(np.shape(a) for a in jax.tree_util.tree_leaves(x))
        return real(x)

    monkeypatch.setattr(jax, "device_get", recording)
    calls = bank.counters.snapshot()["factors.calls"]
    _ask_and_tell(bank, rng)                 # after real tells: a miss
    assert bank.counters.snapshot()["factors.calls"] == calls + 1
    assert bank._gp_cache["L"].ndim == 3
    assert (6,) in shapes                    # the condition estimates
    assert max(len(s) for s in shapes) < 3, shapes


# --------------------------------------------------------------------------- #
# rng kind tag
# --------------------------------------------------------------------------- #
def test_pack_rng_state_rejects_non_pcg64():
    rng = np.random.Generator(np.random.MT19937(0))
    with pytest.raises(ValueError, match="PCG64"):
        pack_rng_state(rng)


def test_checkpoint_rng_kind_tag_validated(tmp_path):
    """Checkpoints carry the bit-generator kind; load refuses a mismatch
    (the 6-word packed rng rows are PCG64-specific) and treats legacy
    checkpoints without the tag as PCG64."""
    bank = StudyBank(SPACE, 2, seed=3, mc_samples=32)
    _run(bank, 2)
    path = tmp_path / "fleet.npz"
    bank.save(path, iteration=4)
    with np.load(path) as z:
        meta = json.loads(bytes(z["meta"]).decode())
        arrays = {k: z[k] for k in z.files if k != "meta"}
    assert meta["rng_kind"] == "PCG64"

    def rewrite(meta_dict, to):
        np.savez(to, meta=np.frombuffer(
            json.dumps(meta_dict).encode(), dtype=np.uint8), **arrays)

    bad = tmp_path / "bad.npz"
    rewrite({**meta, "rng_kind": "MT19937"}, bad)
    fresh = StudyBank(SPACE, 2, seed=3, mc_samples=32)
    with pytest.raises(ValueError, match="MT19937"):
        fresh.load(bad)
    # legacy (pre-tag) checkpoint: still loads as PCG64
    legacy_meta = {k: v for k, v in meta.items() if k != "rng_kind"}
    legacy = tmp_path / "legacy.npz"
    rewrite(legacy_meta, legacy)
    assert fresh.load(legacy) == 4
