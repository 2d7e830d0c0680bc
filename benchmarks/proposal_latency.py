"""Batch-proposal latency: seed Python-loop GP-BUCB vs the fused path.

Measures one steady-state tuner iteration of ``propose`` — exactly what the
tuner hot loop pays per iteration:

  * ``seed``: ``HallucinationStrategy`` — full O(fit_steps * n^3)
    hyperparameter refit, then a host-roundtripping Python loop over batch
    slots (posterior -> numpy UCB -> hallucinate) per proposal call.
  * ``fused``: ``FusedHallucinationStrategy`` — O(n^2) incremental Cholesky
    appends for the new observations plus one jit'd ``lax.fori_loop`` device
    program for the whole batch.

Grid: batch_size in {1, 4, 16} x n_obs in {16, 64, 256, 512}.  Emits the
repo's ``name,us_per_call,derived`` CSV rows: the fused row's headline
number is the steady-state propose call (the seed refits inside every
propose; the fused path doesn't — that *is* the optimization), and the
``amortized=`` field adds the periodic refit's share under the default
``refit_every=8`` schedule for the whole-loop view.  Acceptance target
(ISSUE 1): fused propose >= 3x at batch_size=4, n_obs=256.

Per n_obs it also emits ``refit_cold_n{n}`` vs ``refit_warm_n{n}``: the
refit-boundary hyperparameter re-tune from scratch vs warm-started from the
previous fit's log-params (ISSUE 2 — the warm path runs a short Adam polish,
``warm_fit_steps``, instead of the full ``fit_steps`` schedule); the
amortized number uses the warm cost, since that is what a steady-state
tuner loop pays.

ISSUE-3 sections (the finished on-device proposal stack):

  * ``pallas_pending_{host,fused}``: async replacement pick on the Pallas
    scorer with in-flight trials — host absorb loop (one device program per
    pending trial) vs the single fused program whose absorb phase tracks
    K^{-1} via in-program Schur appends.
  * ``pallas_rescore_{full,downdate}``: the per-slot rescore op across
    training-set size n — full scoring kernel (O(n^2 S)) vs the in-kernel
    rank-1 variance downdate (O(n S)); the growth across n rows is the
    point.
  * ``clustering_{host,fused}``: clustering batch proposal, host pipeline
    (acquisition surface + top-slice + k-means on host) vs the one-program
    device pipeline (wash on CPU; on accelerators it removes the (n_mc,)
    device->host transfer per ask).

ISSUE-5 section (the conditioning-hardened shared scoring core):

  * ``kinv_f32_schur_{n}`` vs ``kinv_f64_schur_{n}``: one per-slot rescore
    op (rank-1 system extension + variance downdate) on the legacy float32
    K^{-1} Schur path vs the hardened factor path (float64 Schur
    accumulation when x64 is enabled, one iterative-refinement step on
    float32-only backends).  Acceptance: hardened <10% over f32 at n=1024.

ISSUE-10 sections (bank-of-one: every single-study strategy now serves
asks through the bucketed ``StudyBank`` pipeline):

  * ``single_study_ask_{gp,tpe,clustering}``: one steady-state
    ``AskTellOptimizer.ask`` per strategy — the whole serving path the
    refactor unified (columnar candidate draw -> bucketed gather ->
    staged vmap'd device program -> one exit sync).  ``single_study_asks``
    is the mean of the three; it is the CI-gated row
    (``single_study_asks:1.25``), normalized by ``bench_delta`` against
    the same-run ``single_study_random`` row (a random-strategy ask —
    pure host work, so runner throttling moves both and the gate blocks
    only on the bank serving overhead itself regressing).
  * ``time_to_1000_asks``: measured ask+tell_failed rounds on the
    bank-of-one GP path, extrapolated to 1000 asks — the steady-state
    serving headline.
  * ``single_study_retrace``: the single-study zero-retrace proof.  Each
    of GP / TPE / clustering grows 64 -> 1024 observations through
    ``AskTellOptimizer``; every bank entry point (``gp.BANK_JITS`` +
    ``fused_tpe_propose_bank``) may compile once per power-of-2 bucket it
    is dispatched at, and the row's value is the summed excess jit-cache
    growth (nonzero exits 1 — the CI bench job fails).

All paired rows are timed with *interleaved* reps (``_interleaved_medians``)
so this container's bursty CPU-share throttling hits every path equally;
``bench_delta.py`` additionally normalizes derived rows against the same
run's baseline row before flagging regressions.

``--json PATH`` additionally writes every emitted row as JSON so CI can
archive the perf trajectory (``BENCH_*.json``).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

ROWS = []   # every emitted row, for --json


def _emit(name, us, derived):
    ROWS.append({"name": name, "us_per_call": round(us, 1),
                 "derived": derived})
    print(f"{name},{us:.1f},{derived}", flush=True)


def _interleaved_medians(calls, reps=3, setups=None):
    """Median seconds per call, with the calls *interleaved within each
    rep*: this container's CPU shares are throttled in bursts, so timing
    each path in its own contiguous window skews the *ratio* between paths
    — interleaving exposes every path to the same bursts (and the CI
    bench-delta job then normalizes derived rows against the same-run
    baseline row, so the trajectory comparison sees throttle-free ratios).
    ``setups[i]`` runs untimed before each timed ``calls[i]``.

    One untimed setup+call round runs first: the steady-state op sequence
    can differ from the caller's own warmup (e.g. the incremental GP's
    append programs only compile on the first post-reset call), and a
    stray compile inside a timed rep poisons small-reps medians.
    """
    samples = [[] for _ in calls]
    for i, c in enumerate(calls):        # warmup: compile the timed path
        if setups is not None and setups[i] is not None:
            setups[i]()
        c()
    for _ in range(reps):
        for i, c in enumerate(calls):
            if setups is not None and setups[i] is not None:
                setups[i]()
            t0 = time.perf_counter()
            c()
            samples[i].append(time.perf_counter() - t0)
    return [float(np.median(s)) for s in samples]


def _time_full_fit(strategy, X, y, reps=3):
    """Median seconds for a full from-scratch observe (hyperparameter tune)."""
    import jax

    times = []
    for _ in range(reps):
        strategy.gp.state = None
        strategy.gp.n_fit = 0
        strategy.gp._fit_params = None    # cold: default Adam init
        t0 = time.perf_counter()
        st = strategy.gp.observe(X, y)
        jax.block_until_ready((st.L, st.ls, st.var, st.noise))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _time_warm_refit(strategy, X, y, reps=3):
    """Median seconds for a refit-boundary re-tune: Adam warm-started from
    the previous fit's log-params (short polish run) instead of the full
    from-scratch schedule."""
    import jax

    n = len(y)
    times = []
    for _ in range(reps):
        strategy.gp.state = None
        strategy.gp.n_fit = 0
        strategy.gp._fit_params = None
        st = strategy.gp.fit(X[: n - 8], y[: n - 8])   # previous fit
        jax.block_until_ready((st.L, st.ls, st.var, st.noise))
        t0 = time.perf_counter()
        st = strategy.gp.fit(X, y)                     # warm refit
        jax.block_until_ready((st.L, st.ls, st.var, st.noise))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


DEFAULT_REFIT_EVERY = 8   # the Tuner default the amortized number models


def run_pallas_pending(n_obs_grid=(64, 256), n_pend=8, bs=4, n_cand=2000,
                       dim=4, fit_steps=40, reps=3, seed=0):
    """Async replacement pick on the Pallas scorer with in-flight trials.

    ``pallas_pending_host``: the seed path — one host round-trip
    (posterior + K^{-1} Schur append programs) per pending trial before the
    fused pick can even start.  ``pallas_pending_fused``: the absorb phase
    runs inside the one jit'd program (``fused_propose_pallas_pending``),
    and per-slot rescoring uses the in-kernel rank-1 variance downdate
    (O(n S) per slot, not O(n^2 S)).
    """
    from repro.core.strategies import FusedHallucinationStrategy

    rng = np.random.default_rng(seed)
    for n in n_obs_grid:
        X = rng.uniform(size=(n, dim)).astype(np.float32)
        y = np.sum(-(X - 0.5) ** 2, axis=-1).astype(np.float32)
        y += 0.05 * rng.normal(size=n).astype(np.float32)
        C = rng.uniform(size=(n_cand, dim)).astype(np.float32)
        P = rng.uniform(size=(n_pend, dim)).astype(np.float32)

        host = FusedHallucinationStrategy(dim, 1e6, fit_steps=fit_steps,
                                          refit_every=10 ** 9,
                                          use_pallas=True)
        fused = FusedHallucinationStrategy(dim, 1e6, fit_steps=fit_steps,
                                           refit_every=10 ** 9,
                                           use_pallas=True)

        def host_call():
            st = host.gp.observe(X, y)           # steady state: no-op pass
            st = host.gp.ensure_capacity(st, n_pend + bs)
            st = host._absorb_pending(st, P)     # one program per pending
            return host.pick_from_state(st, C, bs)

        def fused_call():
            return fused.propose(X, y, C, bs, pending=P)

        host_call()      # warm jit caches (and take the one-time GP fit)
        fused_call()
        t_host, t_fused = _interleaved_medians([host_call, fused_call],
                                               reps=reps)
        _emit(f"pallas_pending_host_bs{bs}_p{n_pend}_n{n}", t_host * 1e6,
              "speedup=1.0x")
        _emit(f"pallas_pending_fused_bs{bs}_p{n_pend}_n{n}", t_fused * 1e6,
              f"speedup={t_host / max(t_fused, 1e-12):.1f}x")


def run_perslot_rescore(n_grid=(64, 256, 1024), n_cand=2000, dim=4, reps=5,
                        seed=0):
    """The per-slot rescore op itself, old vs new, across training-set size.

    ``pallas_rescore_full``: re-run the full factor scoring kernel per slot
    (``t = k @ L^{-T}``: O(n^2 S)).  ``pallas_rescore_downdate``: the
    in-kernel rank-1 variance downdate (matvec against the cached cross-
    covariance block: O(n S)).  The *ratio across n rows* is the point:
    full rescoring grows ~quadratically with n, the downdate ~linearly.
    (The legacy K^{-1} UCB kernel these rows originally baselined was
    deleted with the K^{-1} path; the baseline is now the factor scorer.)
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.gp_acquisition.gp_acquisition import (
        score_cov_pallas, var_downdate_pallas)
    from repro.kernels.gp_acquisition.ref import matern52

    rng = np.random.default_rng(seed)
    dp = 8
    for n in n_grid:
        X = rng.uniform(size=(n, dim)).astype(np.float32) * 2.0
        Xs = np.zeros((n, dp), np.float32)
        Xs[:, :dim] = X
        mask = np.ones(n, np.float32)
        var, noise = 1.0, 0.05
        K = np.array(matern52(jnp.asarray(Xs), jnp.asarray(Xs), 1.0, var))
        K[np.diag_indices(n)] = var + noise
        import scipy.linalg as sla
        L = np.linalg.cholesky(K).astype(np.float32)
        Linv = sla.solve_triangular(L, np.eye(n, dtype=np.float32),
                                    lower=True).astype(np.float32)
        y = rng.normal(size=n).astype(np.float32)
        alpha = (Linv.T @ (Linv @ y)).astype(np.float32)
        Cs = np.zeros((n_cand, dp), np.float32)
        Cs[:, :dim] = rng.uniform(size=(n_cand, dim)).astype(np.float32) * 2

        args = (jnp.asarray(Cs), jnp.asarray(Xs), jnp.asarray(mask),
                jnp.asarray(Linv), jnp.asarray(alpha), jnp.float32(var),
                jnp.float32(noise))
        _, sig2, Kc = jax.block_until_ready(score_cov_pallas(*args))
        star = 7
        k_star = Kc[star]
        u = jnp.asarray(np.linalg.solve(K, np.asarray(k_star))
                        .astype(np.float32))
        schur = jnp.float32(var + noise) - k_star @ u

        def full_call():
            return jax.block_until_ready(score_cov_pallas(*args))

        def downdate_call():
            return jax.block_until_ready(var_downdate_pallas(
                jnp.asarray(Cs), jnp.asarray(Cs[star]), Kc, u, schur,
                sig2, jnp.float32(var)))

        full_call()
        downdate_call()
        t_full, t_dd = _interleaved_medians([full_call, downdate_call],
                                            reps=reps)
        _emit(f"pallas_rescore_full_n{n}", t_full * 1e6, "speedup=1.0x")
        _emit(f"pallas_rescore_downdate_n{n}", t_dd * 1e6,
              f"speedup={t_full / max(t_dd, 1e-12):.1f}x")


def run_kinv_hardening(n_grid=(256, 1024), n_cand=2000, dim=4, reps=5,
                       seed=0):
    """ISSUE-5 rows: the conditioning hardening's cost on the rescore path.

    One per-slot rescore op = rank-1 system extension + variance downdate
    against the cached cross-covariance block:

      * ``kinv_f32_schur_n{n}``: the legacy float32 K^{-1} Schur append
        (``gp._append_core_uv`` — triangular solves + full-matrix
        block-inverse rewrite) + the downdate kernel.  This is the PR-3
        path whose picks flipped on near-noiseless fits.
      * ``kinv_f64_schur_n{n}``: the hardened ``scoring.factor_append``
        (rank-1 (L, L^{-1}) extension; Schur solves accumulate in float64
        when the backend has x64 enabled, and carry one float32
        iterative-refinement step otherwise — the configuration measured
        here is whatever the running backend resolves to) + the same
        downdate kernel.

    Acceptance (ISSUE 5): the hardening costs <10% vs the float32 Schur
    path at n=1024.
    """
    import jax
    import jax.numpy as jnp
    import scipy.linalg as sla

    from repro.core import scoring
    from repro.core.gp import _append_core_uv
    from repro.kernels.gp_acquisition.gp_acquisition import (
        score_cov_pallas, var_downdate_pallas)
    from repro.kernels.gp_acquisition.ref import matern52

    rng = np.random.default_rng(seed)
    dp = 8
    out = []
    for n in n_grid:
        Xs = np.zeros((n, dp), np.float32)
        Xs[:, :dim] = rng.uniform(size=(n, dim)).astype(np.float32) * 2.0
        # last padded slot stays inactive: it is the slot both appends
        # extend into (identity row in L / Linv, zero in the mask)
        mask = np.ones(n, np.float32)
        mask[n - 1] = 0.0
        var, noise = 1.0, 0.05
        K = np.array(matern52(jnp.asarray(Xs), jnp.asarray(Xs), 1.0, var))
        K = K * mask[:, None] * mask[None, :]
        K[np.diag_indices(n)] = np.where(mask > 0, var + noise, 1.0)
        L = np.linalg.cholesky(K).astype(np.float32)
        Linv = sla.solve_triangular(L, np.eye(n, dtype=np.float32),
                                    lower=True).astype(np.float32)
        Kinv = np.linalg.inv(K).astype(np.float32)
        y = (rng.normal(size=n) * mask).astype(np.float32)
        alpha = (Linv.T @ (Linv @ y)).astype(np.float32)
        Cs = np.zeros((n_cand, dp), np.float32)
        Cs[:, :dim] = rng.uniform(size=(n_cand, dim)).astype(np.float32) * 2

        _, sig2, Kc = jax.block_until_ready(score_cov_pallas(
            jnp.asarray(Cs), jnp.asarray(Xs), jnp.asarray(mask),
            jnp.asarray(Linv), jnp.asarray(alpha), jnp.float32(var),
            jnp.float32(noise)))
        star = 7
        idx = jnp.int32(n - 1)   # extend into the inactive slot
        k_vec = Kc[star]         # masked cross-covariance row (zero at idx)

        @jax.jit
        def legacy_step(L, Kinv, Kc, sig2):
            L2, Kinv2, u, schur = _append_core_uv(L, Kinv, idx, k_vec,
                                                  jnp.float32(var),
                                                  jnp.float32(noise))
            sig2b, _ = var_downdate_pallas(jnp.asarray(Cs),
                                           jnp.asarray(Cs[star]), Kc, u,
                                           schur, sig2, jnp.float32(var))
            return L2, Kinv2, sig2b

        @jax.jit
        def hardened_step(L, Linv, Kc, sig2):
            L2, Linv2, u, schur = scoring.factor_append(
                L, Linv, idx, k_vec, jnp.float32(var), jnp.float32(noise))
            sig2b, _ = var_downdate_pallas(jnp.asarray(Cs),
                                           jnp.asarray(Cs[star]), Kc, u,
                                           schur, sig2, jnp.float32(var))
            return L2, Linv2, sig2b

        Lj, Linvj, Kinvj = (jnp.asarray(L), jnp.asarray(Linv),
                            jnp.asarray(Kinv))

        def legacy_call():
            return jax.block_until_ready(legacy_step(Lj, Kinvj, Kc, sig2))

        def hardened_call():
            return jax.block_until_ready(hardened_step(Lj, Linvj, Kc,
                                                       sig2))

        legacy_call()
        hardened_call()
        t_f32, t_hard = _interleaved_medians([legacy_call, hardened_call],
                                             reps=reps)
        overhead = (t_hard - t_f32) / t_f32 * 100.0
        _emit(f"kinv_f32_schur_n{n}", t_f32 * 1e6, "overhead=+0.0%")
        _emit(f"kinv_f64_schur_n{n}", t_hard * 1e6,
              f"overhead={overhead:+.1f}%")
        out.append((n, overhead))
    return out


def run_clustering(n_obs_grid=(64, 256), bs=4, n_cand=2000, dim=4,
                   fit_steps=40, reps=3, seed=0):
    """Clustering batch proposal: host pipeline (acquisition surface +
    top-slice + k-means all round-tripping through numpy) vs the fused
    device program (``fused_cluster_propose`` — only the ``(batch_size,)``
    indices leave the device)."""
    from repro.core.strategies import ClusteringStrategy

    rng = np.random.default_rng(seed)
    for n in n_obs_grid:
        X = rng.uniform(size=(n, dim)).astype(np.float32)
        y = np.sum(-(X - 0.5) ** 2, axis=-1).astype(np.float32)
        y += 0.05 * rng.normal(size=n).astype(np.float32)
        C = rng.uniform(size=(n_cand, dim)).astype(np.float32)

        host = ClusteringStrategy(dim, 1e6, fit_steps=fit_steps,
                                  refit_every=10 ** 9)
        fused = ClusteringStrategy(dim, 1e6, fit_steps=fit_steps,
                                   refit_every=10 ** 9)
        host.propose_host(X, y, C, bs, seed=0)   # warm jit + one-time fit
        fused.propose(X, y, C, bs, seed=0)
        t_host, t_fused = _interleaved_medians(
            [lambda: host.propose_host(X, y, C, bs, seed=0),
             lambda: fused.propose(X, y, C, bs, seed=0)], reps=reps)
        _emit(f"clustering_host_bs{bs}_n{n}", t_host * 1e6, "speedup=1.0x")
        _emit(f"clustering_fused_bs{bs}_n{n}", t_fused * 1e6,
              f"speedup={t_host / max(t_fused, 1e-12):.1f}x")


def run_tpe(n_cand_grid=(2048, 8192), n_obs_grid=(64, 256), bs=4, dim=4,
            reps=5, seed=0):
    """ISSUE-4 rows: the TPE baseline, host numpy vs device-resident.

    ``tpe_host``: the seed path — numpy good/bad split + the O(m n d)
    product-Parzen KDE materializing an (m, n, d) temporary per split, per
    propose call.  ``tpe_fused``: ``fused_tpe_propose`` — masked split,
    jnp KDE scoring and ``lax.top_k`` in one jit'd device program.
    ``tpe_pallas``: the same program scoring through the ``tpe_kde`` Pallas
    kernel (interpret mode on CPU — the correctness path; the row tracks
    the one-program contract, the CPU win belongs to ``tpe_fused``).

    The candidate grid starts at S=2048 because ``ParamSpace.mc_samples``
    floors at 2000 — a real ask never scores fewer; below ~1k candidates
    both paths sit in the ~2 ms dispatch/allocator-noise regime of this
    throttled 2-core container and the comparison measures the scheduler,
    not the algorithm.  Acceptance (ISSUE 4): fused >= 2x over host on
    every row with n_candidates >= 512.
    """
    from repro.core.tpe import TPEStrategy

    rng = np.random.default_rng(seed)
    out = []
    for n in n_obs_grid:
        X = rng.uniform(size=(n, dim)).astype(np.float32)
        y = np.sum(-(X - 0.5) ** 2, axis=-1).astype(np.float32)
        y += 0.05 * rng.normal(size=n).astype(np.float32)
        for S in n_cand_grid:
            C = rng.uniform(size=(S, dim)).astype(np.float32)
            host = TPEStrategy(dim, 1e6)
            fused = TPEStrategy(dim, 1e6)
            pallas = TPEStrategy(dim, 1e6, use_pallas=True)
            calls = [lambda: host.propose_host(X, y, C, bs),
                     lambda: fused.propose(X, y, C, bs),
                     lambda: pallas.propose(X, y, C, bs)]
            for c in calls:     # warm numpy allocator / jit caches
                c()
            t_host, t_fused, t_pal = _interleaved_medians(calls, reps=reps)
            _emit(f"tpe_host_bs{bs}_n{n}_S{S}", t_host * 1e6,
                  "speedup=1.0x")
            speedup = t_host / max(t_fused, 1e-12)
            _emit(f"tpe_fused_bs{bs}_n{n}_S{S}", t_fused * 1e6,
                  f"speedup={speedup:.1f}x")
            _emit(f"tpe_pallas_bs{bs}_n{n}_S{S}", t_pal * 1e6,
                  f"speedup={t_host / max(t_pal, 1e-12):.1f}x")
            out.append((n, S, speedup))
    return out


def _ask_space():
    from scipy import stats
    return {"x": stats.uniform(0, 1), "y": stats.uniform(-1, 2),
            "z": stats.uniform(0, 3)}


def _grow(opt, k, rng):
    for _ in range(k):
        p = {"x": float(rng.uniform(0, 1)), "y": float(rng.uniform(-1, 1)),
             "z": float(rng.uniform(0, 3))}
        opt.observe_params(p, float(rng.normal()))


def run_bank_of_one(n_obs=256, n_mc=64, reps=5, seed=0):
    """ISSUE-10 rows: the unified single-study serving path.

    Every bank-served strategy is timed on one steady-state
    ``AskTellOptimizer.ask`` (each rep's proposal is told *failed* in the
    untimed setup slot, so observation counts and every bucket shape stay
    frozen).  ``single_study_random`` — a random-strategy ask, pure host
    candidate draw with no device program — is the same-run normalization
    denominator for the gated ``single_study_asks`` mean:
    ``bench_delta`` compares the *ratio*, so shared-runner throttling
    (which moves host work and dispatch overhead together) stays
    advisory and the gate blocks only on the bank serving overhead
    itself regressing >25%.
    """
    from repro.core import AskTellOptimizer

    rng = np.random.default_rng(seed)
    names = [("random", "random"), ("gp", "bayesian"), ("tpe", "tpe"),
             ("clustering", "clustering")]
    opts, asked = {}, {}
    for label, strat in names:
        o = AskTellOptimizer(_ask_space(), optimizer=strat,
                             seed=seed + 1, mc_samples=n_mc)
        _grow(o, n_obs, rng)
        opts[label], asked[label] = o, []

    def setup(label):
        for t in asked[label]:
            opts[label].tell_failed(t.id)
        asked[label].clear()

    def call(label):
        asked[label].append(opts[label].ask(1)[0])

    import functools
    labels = [lb for lb, _ in names]
    meds = _interleaved_medians(
        [functools.partial(call, lb) for lb in labels], reps=reps,
        setups=[functools.partial(setup, lb) for lb in labels])
    t_rand = meds[0]
    _emit("single_study_random", t_rand * 1e6,
          f"baseline=1.0x,n_obs={n_obs}")
    for lb, t in zip(labels[1:], meds[1:]):
        _emit(f"single_study_ask_{lb}", t * 1e6,
              f"n_obs={n_obs},vs_random={t / max(t_rand, 1e-12):.1f}x")
    t_mean = float(np.mean(meds[1:]))
    _emit("single_study_asks", t_mean * 1e6,
          f"mean_of=gp/tpe/clustering,n_obs={n_obs},"
          f"vs_random={t_mean / max(t_rand, 1e-12):.1f}x")

    # time_to_1000_asks: real ask+tell_failed rounds (the tell is part of
    # what a serving loop pays), extrapolated from a measured burst
    gp_opt = opts["gp"]
    setup("gp")
    rounds = 20
    t = gp_opt.ask(1)[0]                # untimed settle round
    gp_opt.tell_failed(t.id)
    t0 = time.perf_counter()
    for _ in range(rounds):
        t = gp_opt.ask(1)[0]
        gp_opt.tell_failed(t.id)
    per_round = (time.perf_counter() - t0) / rounds
    _emit("time_to_1000_asks", per_round * 1000.0 * 1e6,
          f"per_round={per_round * 1e6:.0f}us,rounds_measured={rounds},"
          f"strategy=bayesian,n_obs={n_obs}")
    return t_mean


def run_single_study_retrace(max_obs=1024, n_mc=64, seed=0):
    """The single-study zero-retrace proof, one strategy at a time.

    The multi-study growth sweep (``multi_study.run_retrace_sweep``)
    pins the bucket schedule for ``ask_all``; this one pins the
    bank-of-one path those same programs now serve: three
    ``AskTellOptimizer`` instances (GP, clustering, TPE) each grow
    64 -> ``max_obs`` observations, asking twice at every bucket edge
    (edge-1 / edge / edge+1) and at interior points.  Each audited entry
    point — ``gp.BANK_JITS`` plus the TPE bank program — may compile
    once per power-of-2 bucket it is dispatched at; GP and clustering
    share the obs-stage programs (identical shapes -> cache hits for the
    second family), clustering adds only its pick head, TPE only its one
    fused program.  Emits the summed excess as ``single_study_retrace``
    and returns it (``main`` exits 1 when nonzero).
    """
    from repro.analysis.sanitizers import no_retrace
    from repro.core import AskTellOptimizer
    from repro.core import gp as gp_lib
    from repro.core import tpe as tpe_lib
    from repro.core.studybank import _pow2

    jits = dict(gp_lib.BANK_JITS)
    jits["fused_tpe_propose_bank"] = tpe_lib.fused_tpe_propose_bank

    rng = np.random.default_rng(seed)
    opts = {lb: AskTellOptimizer(_ask_space(), optimizer=strat,
                                 seed=seed + 1, mc_samples=n_mc)
            for lb, strat in [("gp", "bayesian"),
                              ("clustering", "clustering"),
                              ("tpe", "tpe")]}

    # same bucket-edge targets as the multi-study sweep: for each edge E
    # (na doubles at n_obs = E), visit E-1, E, E+1, plus mid-bucket
    pend_cap, n = 4, 1
    targets, na = [], 64
    while na <= max_obs:
        edge = na - pend_cap - n
        targets += [edge - 1, edge, edge + 1, edge + (edge // 2)]
        na *= 2
    targets = sorted(t for t in set(targets) if 58 <= t <= max_obs - 5)

    buckets, fit_buckets = set(), set()
    with no_retrace(jits=jits, raise_on_violation=False) as rep:
        for lb, opt in opts.items():
            for k in targets:
                _grow(opt, k - opt.n_observed, rng)
                na = _pow2(max(16, k + pend_cap + n))
                buckets.add(na)
                if lb != "tpe":
                    led = opt._led
                    if (led.have_fit[0] == 0
                            or k - int(led.n_fit[0]) >= opt.refit_every):
                        fit_buckets.add(na)
                # two asks per target: the first may compile (bucket
                # boundary), the second must be a pure cache hit
                for _ in range(2):
                    t = opt.ask(1)[0]
                    opt.tell_failed(t.id)
        nb = len(buckets)
        # one compile per bucket a program is dispatched at; prescale_C
        # depends only on mc_samples; absorb never runs (every trial is
        # told failed before the next ask)
        rep.expected = {
            "bank_factors": nb, "bank_prescale_X": nb,
            "bank_prescale_C": 1, "bank_absorb": 0, "bank_dist": nb,
            "bank_exp": nb, "bank_pick": nb, "bank_cluster_pick": nb,
            "fit_hypers_bank": len(fit_buckets),
            "fused_tpe_propose_bank": nb,
        }
    retraces = rep.violations
    detail = rep.detail() or "all=expected"
    _emit("single_study_retrace", float(retraces),
          f"retraces={retraces},boundaries={nb},strategies=3,{detail}")
    return retraces


def run(batch_sizes=(1, 4, 16), n_obs_grid=(16, 64, 256, 512),
        n_cand=2000, dim=4, fit_steps=40, reps=3, seed=0):
    from repro.core.strategies import (FusedHallucinationStrategy,
                                       HallucinationStrategy)

    rng = np.random.default_rng(seed)
    rows = []
    for n in n_obs_grid:
        X = rng.uniform(size=(n, dim)).astype(np.float32)
        y = np.sum(-(X - 0.5) ** 2, axis=-1).astype(np.float32)
        C = rng.uniform(size=(n_cand, dim)).astype(np.float32)
        # refit-boundary cost: cold (from-scratch Adam) vs warm-started
        warm_probe = FusedHallucinationStrategy(dim, 1e6,
                                                fit_steps=fit_steps,
                                                refit_every=10 ** 9)
        warm_probe.gp.fit(X, y)            # warm the jit caches (both step
        warm_probe.gp.fit(X, y)            # counts compile out-of-band)
        t_cold = _time_full_fit(warm_probe, X, y, reps=reps)
        t_warm = _time_warm_refit(warm_probe, X, y, reps=reps)
        _emit(f"refit_cold_n{n}", t_cold * 1e6, "speedup=1.0x")
        _emit(f"refit_warm_n{n}", t_warm * 1e6,
              f"speedup={t_cold / max(t_warm, 1e-12):.1f}x")
        for bs in batch_sizes:
            ref = HallucinationStrategy(dim, 1e6, fit_steps=fit_steps)
            # huge refit_every so the timed steady-state window never
            # crosses a refit boundary (with the default 8, appending
            # bs >= 8 rows would pull the full refit into the window)
            fused = FusedHallucinationStrategy(dim, 1e6,
                                               fit_steps=fit_steps,
                                               refit_every=10 ** 9)
            # warm the jit caches out-of-band
            ref.propose(X, y, C, bs)
            fused.propose(X, y, C, bs)

            # per-rep setups reset strategy state untimed; the fused path
            # pre-observes n - bs rows (synced) so the timed call pays one
            # steady-state tuner iteration, not the first-call full fit
            import jax

            def setup_ref():
                ref.gp.state = None
                ref.gp.n_fit = 0

            def setup_fused():
                fused.gp.state = None
                fused.gp.n_fit = 0
                pfx = max(1, n - bs)
                st = fused.gp.observe(X[:pfx], y[:pfx])
                jax.block_until_ready((st.L, st.ls, st.var, st.noise))

            t_ref, t_fused = _interleaved_medians(
                [lambda: ref.propose(X, y, C, bs),
                 lambda: fused.propose(X, y, C, bs)],
                reps=reps, setups=[setup_ref, setup_fused])
            # amortized whole-loop cost under the default schedule: each
            # iteration appends bs rows, so a refit runs every
            # ceil(refit_every / bs) iterations -> min(1, bs/refit_every)
            # refits per iteration — and steady-state refits are *warm*
            t_amort = t_fused + t_warm * min(1.0, bs / DEFAULT_REFIT_EVERY)
            speedup = t_ref / max(t_fused, 1e-12)
            rows.append((bs, n, t_ref, t_fused, speedup))
            _emit(f"proposal_seed_bs{bs}_n{n}", t_ref * 1e6, "speedup=1.0x")
            _emit(f"proposal_fused_bs{bs}_n{n}", t_fused * 1e6,
                  f"amortized={t_amort * 1e6:.0f}us,speedup={speedup:.1f}x")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="small grid for smoke runs")
    ap.add_argument("--json", type=str, default=None, metavar="PATH",
                    help="also write every emitted row as JSON (the CI "
                         "tier-2 job uploads this as BENCH_*.json)")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.quick:
        rows = run(batch_sizes=(4,), n_obs_grid=(64, 256), reps=args.reps)
        run_pallas_pending(n_obs_grid=(64,), reps=args.reps)
        run_perslot_rescore(n_grid=(64, 256), reps=args.reps)
        run_clustering(n_obs_grid=(64,), reps=args.reps)
        kinv_rows = run_kinv_hardening(n_grid=(256,), reps=args.reps)
        tpe_rows = run_tpe(n_cand_grid=(2048,), n_obs_grid=(64, 256),
                           reps=args.reps)
        run_bank_of_one(reps=args.reps)
        retraces = run_single_study_retrace(max_obs=256)
    else:
        rows = run(reps=args.reps)
        run_pallas_pending(reps=args.reps)
        run_perslot_rescore(reps=args.reps)
        run_clustering(reps=args.reps)
        kinv_rows = run_kinv_hardening(reps=args.reps)
        tpe_rows = run_tpe(reps=args.reps)
        run_bank_of_one(reps=args.reps)
        retraces = run_single_study_retrace(max_obs=1024)
    target = [r for r in rows if r[0] == 4 and r[1] == 256]
    if target:
        bs, n, t_ref, t_fused, speedup = target[0]
        print(f"# CLAIM issue1 'fused >= 3x at batch_size=4, n_obs=256': "
              f"{speedup:.1f}x -> {'PASS' if speedup >= 3.0 else 'FAIL'}")
    tpe_target = [s for n, S, s in tpe_rows if S >= 512]
    if tpe_target:
        worst = min(tpe_target)
        print(f"# CLAIM issue4 'tpe fused >= 2x over host at "
              f"n_candidates >= 512': worst {worst:.1f}x -> "
              f"{'PASS' if worst >= 2.0 else 'FAIL'}")
    kinv_target = [o for nn, o in kinv_rows if nn == 1024]
    if kinv_target:
        print(f"# CLAIM issue5 'conditioning hardening <10% over the f32 "
              f"Schur rescore path at n=1024': {kinv_target[0]:+.1f}% -> "
              f"{'PASS' if kinv_target[0] < 10.0 else 'FAIL'}")
    print(f"# CLAIM issue10 'zero steady-state retraces across "
          f"single-study growth (gp/tpe/clustering)': {retraces} -> "
          f"{'PASS' if retraces == 0 else 'FAIL'}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"benchmark": "proposal_latency", "rows": ROWS}, f,
                      indent=2)
        print(f"# wrote {len(ROWS)} rows to {args.json}")
    if retraces:
        sys.exit(1)


if __name__ == "__main__":
    main()
