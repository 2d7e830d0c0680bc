"""StudyBank: optimizer state as a pytree of arrays (multi-tenant asks).

Mango frames HPO as a production service (paper §1/§2.4); Tune and
Auptimizer make the same point — a tuning platform hosts *many* concurrent
studies, not one notebook loop.  This module gives the engine that shape:

  * ``StudyLedger`` — a registered pytree of fixed-capacity numpy arrays
    holding every study's trial ledger (encoded X rows, raw y, status,
    completion order), counters, per-study RNG state, GP hyperparameter /
    fit-schedule state.
    ``AskTellOptimizer`` is a *view* into one row of a ledger (a bank of
    one by default), so the single-study API is unchanged while the state
    itself is array-shaped.
  * ``StudyBank`` — N studies over one ledger.  ``ask_all`` gathers the
    bank into shape-bucketed device buffers (power-of-2 trial capacity, so
    a growing study re-enters a cached compiled program instead of
    retracing) and serves every study through the ONE staged proposal
    pipeline: ``gp.bank_*`` stages feeding ``bank_pick`` (GP-BUCB),
    ``bank_cluster_pick`` (clustering) or ``tpe.fused_tpe_propose_bank``.
    Strategies are per-study data (a bank may mix GP, TPE and clustering
    studies — ``ask_all`` sub-batches the dispatch per strategy family
    within one columnar candidate draw).  Observation-dependent device
    state (gather, factors, standardization) is cached on the ledger's
    ``obs_stamp``, so ask/tell_failed churn never recomputes a Cholesky.
  * Bank-of-one: a standalone ``AskTellOptimizer.ask`` routes through
    ``ask_view`` on this same bucketed pipeline (``StudyBank._wrap_view``),
    so the single-study hot path compiles once per power-of-2 bucket and
    never retraces across observation growth.
  * One-write fleet checkpoints — ``save`` serializes the whole ledger
    pytree (plus a JSON meta block for params dicts / RNG streams) as a
    single ``.npz`` write; ``load`` restores every study mid-flight.

Bucketing contract: device buffers are padded to ``pow2(max(16, ...))``
rows with ``n_obs``/``n_pending`` carried as masked ranks, so within a
bucket the compiled program is reused ask after ask (the
``steady_state_retrace`` bench row asserts zero retraces across a
64→1024-observation growth sweep, compiles at bucket edges aside).  The
hyperparameter fit runs only the studies due a refit, padded to a
power-of-2 row bucket; the first fit at a bucket compiles the fit at
every row bucket, so later fits of any due count compile nothing.
"""
from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import strategies
from repro.tracing import Counters, span

# trial-status codes (ledger ``status`` array; 0 = empty slot)
S_EMPTY, S_PENDING, S_OBSERVED, S_FAILED = 0, 1, 2, 3

_U64 = np.uint64
_MASK64 = (1 << 64) - 1


# what the bank counts for operators (``TuningService.stats``): the obs
# stage's calls and cache hits, the fits with the rows due and the rows
# run, the fits that only compile a row bucket, and the factor program
# with the rows it factored, their observations and pending rows, the
# slots of their bucket and the rows past the condition limit
BANK_COUNTERS = ("obs_stage.calls", "obs_stage.hits", "obs_stage.ns",
                 "fit.calls", "fit.rows_due", "fit.rows_run", "fit.ns",
                 "fit.warm_calls", "factors.calls", "factors.ns",
                 "factors.rows", "factors.obs", "factors.slots",
                 "factors.ill_conditioned")


def _pow2(n: int) -> int:
    p = 16
    while p < n:
        p *= 2
    return p


def row_bucket(k: int, R: int) -> int:
    """Rows the fit program runs for ``k`` due rows of a sub-batch of
    ``R``: the power of 2 at or above ``k``, capped at ``R``."""
    return min(1 << (k - 1).bit_length(), R)


def row_buckets(R: int) -> List[int]:
    """Every row bucket of a sub-batch of ``R`` rows: 1, 2, 4, ... below
    ``R``, then ``R``."""
    return sorted({row_bucket(k, R) for k in range(1, R + 1)})


def _y_standardization(v: np.ndarray):
    """Frozen-standardization scalars over a signed f32 history: f32 numpy
    mean (exact f32 round-trip) and ``float(v.std()) + 1e-6`` (f64 add,
    rounded to f32 at the consuming op).  Used by the bank fit schedule
    AND v1-checkpoint restore so a resumed run standardizes
    bit-identically."""
    v = np.asarray(v, np.float32)
    if not len(v):
        return np.float32(0.0), np.float32(1.0)
    return np.float32(v.mean()), np.float32(float(v.std()) + 1e-6)


# the one bit-generator the 6-word packed layout below encodes; checkpoints
# carry it as a meta tag so a future second generator type fails loudly at
# load instead of silently unpacking garbage words into a PCG64
RNG_KIND = "PCG64"


def pack_rng_state(rng: np.random.Generator) -> np.ndarray:
    """Pack a PCG64 Generator's full state into 6 uint64 words
    (state lo/hi, inc lo/hi, has_uint32, uinteger) for array storage."""
    st = rng.bit_generator.state
    kind = st.get("bit_generator")
    if kind != RNG_KIND:
        raise ValueError(
            f"pack_rng_state only encodes {RNG_KIND} streams; this "
            f"generator is {kind!r} — its state does not fit the 6-word "
            "packed layout (add a new rng_kind to the checkpoint format)")
    s, inc = st["state"]["state"], st["state"]["inc"]
    return np.array([s & _MASK64, (s >> 64) & _MASK64,
                     inc & _MASK64, (inc >> 64) & _MASK64,
                     st["has_uint32"], st["uinteger"]], dtype=_U64)


def rng_from_state(state: Dict[str, Any]) -> np.random.Generator:
    """Generator rebuilt from a serialized bit-generator state.  The
    explicit seed is a placeholder (the state overwrite replaces it) so
    restoring a stream never draws OS entropy."""
    rng = np.random.default_rng(0)
    rng.bit_generator.state = state
    return rng


def unpack_rng_state(words: np.ndarray) -> np.random.Generator:
    w = [int(x) for x in words]
    return rng_from_state({
        "bit_generator": "PCG64",
        "state": {"state": w[0] | (w[1] << 64), "inc": w[2] | (w[3] << 64)},
        "has_uint32": w[4], "uinteger": w[5]})


class StudyLedger:
    """Pytree-of-arrays state for ``n_studies`` concurrent studies.

    Everything array-shaped lives here; params *dicts* (needed to call the
    user's objective) stay on the owning optimizer views.  Trial slot index
    == trial id (ids are dense), so gathers are plain fancy indexing.
    Capacities grow by doubling from 16 — bank-wide, so every study in the
    bank always shares one bucket shape.
    """

    # leaf order is the pytree/checkpoint contract
    ARRAY_FIELDS = (
        "X", "y", "status", "obs_seq",
        "n_trials", "ask_count", "obs_count", "n_failed",
        "log_ls", "log_var", "log_noise", "have_fit", "n_fit",
        "y_mean", "y_std", "rng_state",
    )

    # Monotone observation stamp: bumped by every mutation that can change
    # the *observed* system (tells, value/order writes, hyper refits, study
    # resets, checkpoint loads) — but NOT by pending-only traffic
    # (ask/tell_failed), which is regathered fresh each ask.  The bank's
    # staged GP dispatch keys its device cache (prescaled observations,
    # Cholesky factors, standardized y, hypers) on this stamp, so the
    # no-new-observations steady state skips the Cholesky entirely.  A
    # class attribute (not an ``__init__`` field, not a pytree leaf, never
    # serialized) so unflattened/restored ledgers start valid at 0.
    obs_stamp = 0

    def __init__(self, n_studies: int, dim: int, capacity: int = 16):
        if n_studies < 1:
            raise ValueError("n_studies must be >= 1")
        B, d = int(n_studies), int(dim)
        cap = _pow2(max(16, capacity))
        self.n_studies, self.dim = B, d
        # ---- trial ledger -------------------------------------------------
        self.X = np.zeros((B, cap, d), np.float32)   # encoded rows by id
        self.y = np.zeros((B, cap), np.float64)      # raw objective values
        self.status = np.zeros((B, cap), np.int8)
        self.obs_seq = np.full((B, cap), -1, np.int32)
        self.n_trials = np.zeros((B,), np.int64)     # == next trial id
        self.ask_count = np.zeros((B,), np.int64)
        self.obs_count = np.zeros((B,), np.int64)
        self.n_failed = np.zeros((B,), np.int64)
        # ---- GP hypers + fit schedule (cold rows carry the cold-fit init
        # values, so a bank fit can always warm-start from these arrays) ----
        self.log_ls = np.full((B, d), np.log(0.5), np.float32)
        self.log_var = np.zeros((B,), np.float32)
        self.log_noise = np.full((B,), np.log(1e-2), np.float32)
        self.have_fit = np.zeros((B,), np.int8)
        self.n_fit = np.zeros((B,), np.int64)
        self.y_mean = np.zeros((B,), np.float32)
        self.y_std = np.ones((B,), np.float32)
        # ---- per-study RNG streams (synced from the views at save time) ---
        self.rng_state = np.zeros((B, 6), _U64)

    # ------------------------------------------------------------ capacity
    @property
    def capacity(self) -> int:
        return self.X.shape[1]

    def ensure_capacity(self, n: int) -> None:
        cap = self.capacity
        if n <= cap:
            return
        new = _pow2(n)
        B, d = self.n_studies, self.dim
        X = np.zeros((B, new, d), np.float32)
        X[:, :cap] = self.X
        y = np.zeros((B, new), np.float64)
        y[:, :cap] = self.y
        status = np.zeros((B, new), np.int8)
        status[:, :cap] = self.status
        obs_seq = np.full((B, new), -1, np.int32)
        obs_seq[:, :cap] = self.obs_seq
        self.X, self.y, self.status, self.obs_seq = X, y, status, obs_seq

    # ----------------------------------------------------------- per-study
    def reset_study(self, b: int) -> None:
        """Clear one study's row back to the cold state (load target)."""
        self.obs_stamp += 1
        self.X[b] = 0.0
        self.y[b] = 0.0
        self.status[b] = S_EMPTY
        self.obs_seq[b] = -1
        self.n_trials[b] = self.ask_count[b] = 0
        self.obs_count[b] = self.n_failed[b] = 0
        self.log_ls[b] = np.log(0.5)
        self.log_var[b] = 0.0
        self.log_noise[b] = np.log(1e-2)
        self.have_fit[b] = 0
        self.n_fit[b] = 0
        self.y_mean[b], self.y_std[b] = 0.0, 1.0
        self.rng_state[b] = 0

    def n_observed(self) -> np.ndarray:
        return (self.status == S_OBSERVED).sum(axis=1)

    def n_pending(self) -> np.ndarray:
        return (self.status == S_PENDING).sum(axis=1)

    def obs_ids(self, b: int) -> np.ndarray:
        """Observed trial ids of study ``b`` in completion (tell) order."""
        ids = np.nonzero(self.status[b] == S_OBSERVED)[0]
        return ids[np.argsort(self.obs_seq[b, ids], kind="stable")]

    def pending_ids(self, b: int) -> np.ndarray:
        return np.nonzero(self.status[b] == S_PENDING)[0]


def _ledger_flatten(led: StudyLedger):
    return (tuple(getattr(led, f) for f in StudyLedger.ARRAY_FIELDS),
            (led.n_studies, led.dim))


def _ledger_unflatten(aux, leaves) -> StudyLedger:
    led = object.__new__(StudyLedger)
    led.n_studies, led.dim = aux
    for f, v in zip(StudyLedger.ARRAY_FIELDS, leaves):
        setattr(led, f, v)
    return led


jax.tree_util.register_pytree_node(
    StudyLedger, _ledger_flatten, _ledger_unflatten)


class StudyBank:
    """N independent studies over one ``StudyLedger``; one sub-batched
    device dispatch per strategy family per ``ask_all``.

    Every study shares the parameter space but owns its strategy, RNG
    stream, sign, counters and GP state, so per-study results are
    reproducible independent of its bankmates' *values* (bucket shapes are
    shared, proposals are not).  ``optimizer`` may be one strategy name
    (homogeneous fleet) or a per-study list — a mixed GP + TPE +
    clustering fleet is served from one process with one columnar
    candidate draw, the dispatch sub-batched per family.
    """

    def __init__(self, param_space, n_studies: int, *,
                 optimizer=None, seed: int = 0,
                 sign: float = 1.0, domain_size: Optional[float] = None,
                 mc_samples: Optional[int] = None, fit_steps: int = 40,
                 refit_every: int = 8,
                 strategy_kwargs: Optional[Dict[str, Any]] = None):
        from repro.core.optimizer import AskTellOptimizer
        from repro.core.spaces import ParamSpace
        self.space = (param_space if isinstance(param_space, ParamSpace)
                      else ParamSpace(param_space))
        if optimizer is None:
            optimizer = "bayesian"
        names = (list(optimizer)
                 if isinstance(optimizer, (list, tuple))
                 else [optimizer] * int(n_studies))
        if len(names) != int(n_studies):
            raise ValueError(
                f"optimizer list has {len(names)} entries for "
                f"{n_studies} studies")
        self.strategy_names: List[str] = names
        self.optimizer = (names[0] if len(set(names)) == 1 else "mixed")
        self.mc_samples = mc_samples
        self.fit_steps = fit_steps
        self.refit_every = refit_every
        self.strategy_kwargs = dict(strategy_kwargs or {})
        self.seed = seed
        self.ledger = StudyLedger(n_studies, self.space.dim)
        self._gp_cache = None   # obs_stamp-keyed device state (staged ask)
        self._fit_warmed = set()  # (na, R) whose fit row buckets compiled
        self.counters = Counters(BANK_COUNTERS)
        # monotonic operation sequence for journaled (WAL) deployments: the
        # last op applied through ``apply_op``; snapshots carry it so crash
        # recovery can skip journal records the snapshot already contains
        self.op_seq = 0
        self.extra = None       # side-channel meta restored by ``load``
        # bank-wide candidate stream: one flat draw of B*n_mc candidates per
        # ask_all, independent of the per-study streams
        self._rng = np.random.default_rng(seed)
        self.studies: List[AskTellOptimizer] = [
            AskTellOptimizer(self.space, optimizer=names[i],
                             seed=seed + 1 + i, sign=sign,
                             domain_size=domain_size, mc_samples=mc_samples,
                             fit_steps=fit_steps, refit_every=refit_every,
                             strategy_kwargs=strategy_kwargs,
                             ledger=self.ledger, study_index=i)
            for i in range(n_studies)]
        for v in self.studies:
            v._bank = self
        self._members = {i: v for i, v in enumerate(self.studies)}
        self._rebuild_groups()

    @classmethod
    def _wrap_view(cls, view) -> "StudyBank":
        """Bank-of-one engine over an existing view's ledger (what a
        standalone ``AskTellOptimizer.ask`` routes through).  Shares the
        view's ledger row and settings; the bank candidate stream is unused
        (``ask_view`` draws through the view's own RNG, preserving the
        pre-refactor per-study stream bit-for-bit)."""
        bank = object.__new__(cls)
        bank.space = view.space
        bank.optimizer = view.optimizer
        bank.mc_samples = view.mc_samples
        bank.fit_steps = view.fit_steps
        bank.refit_every = view.refit_every
        bank.strategy_kwargs = dict(view.strategy_kwargs)
        bank.seed = None
        bank.ledger = view._led
        bank._gp_cache = None
        bank._fit_warmed = set()
        bank.counters = Counters(BANK_COUNTERS)
        bank.op_seq = 0
        bank.extra = None
        bank._rng = None
        bank.studies = [view]
        bank.strategy_names = [view.optimizer]
        bank._members = {view._b: view}
        bank._rebuild_groups()
        return bank

    def _rebuild_groups(self) -> None:
        """Recompute the strategy-family routing tables (and drop the
        device cache, whose row layout depends on them)."""
        fams = {b: strategies.STRATEGIES[v.optimizer]
                for b, v in self._members.items()}
        self._fams = fams
        gpr = sorted(b for b, f in fams.items() if f in ("gp", "cluster"))
        self._gp_fam_rows = np.array(gpr, np.int64)
        self._gp_pos = {int(r): i for i, r in enumerate(gpr)}
        bankable = np.zeros(self.ledger.n_studies, bool)
        for b, f in fams.items():
            bankable[b] = f != "random"
        self._bankable = bankable
        self._gp_cache = None

    def set_strategy(self, b: int, name: str) -> None:
        """Switch study ``b``'s strategy (per-study data, not bank code
        paths).  Counters/observations are untouched; the next ask routes
        through the new family's pick head."""
        strategies.check_name(name)
        b = int(b)
        v = self.studies[b]
        if v.optimizer != name:
            v.optimizer = name
            self.strategy_names[b] = name
        self.optimizer = (self.strategy_names[0]
                          if len(set(self.strategy_names)) == 1
                          else "mixed")
        self._rebuild_groups()

    # -------------------------------------------------------------- basics
    @property
    def n_studies(self) -> int:
        return self.ledger.n_studies

    def study(self, i: int):
        return self.studies[i]

    def tell(self, study: int, trial_id: int, value: float):
        return self.studies[study].tell(trial_id, value)

    def tell_failed(self, study: int, trial_id: int):
        return self.studies[study].tell_failed(trial_id)

    # ------------------------------------------------------ journal replay
    def next_op_seq(self) -> int:
        """Sequence number the *next* journaled operation must carry."""
        return self.op_seq + 1

    def validate_op(self, op: Dict[str, Any]) -> None:
        """Reject a malformed op *before* it is journaled.  Pure check, no
        state mutated.  The WAL contract is journal-then-apply, so anything
        appended must be guaranteed to apply — a record that journals and
        then raises would poison every future replay of the log.  Raises
        ``ValueError``/``KeyError``/``TypeError`` on a bad op."""
        kind = op["op"]
        b = int(op["study"])
        if not 0 <= b < self.n_studies:
            raise ValueError(f"op targets study row {b}, bank holds "
                             f"{self.n_studies}")
        view = self.studies[b]
        if kind == "create":
            float(op.get("sign", 1.0))
            nm = op.get("optimizer")
            strategies.check_kwargs(view.optimizer if nm is None else nm,
                                    view.strategy_kwargs, view.space.dim,
                                    view.domain_size)
        elif kind == "ask":
            if int(op["n"]) < 1:
                raise ValueError("ask(n) requires n >= 1")
        elif kind in ("tell", "tell_failed"):
            tid = int(op["trial_id"])
            if tid not in view._trials:
                raise KeyError(f"unknown trial id {tid!r} "
                               "(tell before ask?)")
            if kind == "tell":
                float(op["value"])
        elif kind == "observe":
            # encode raises KeyError on a param name missing from the
            # space and TypeError/ValueError on un-encodable values
            self.space.encode([dict(op["params"])])
            float(op["value"])
        elif kind == "trace":
            pass
        else:
            raise ValueError(f"unknown journal op kind {kind!r}")

    def apply_op(self, op: Dict[str, Any]):
        """Apply one journaled operation to the bank (the WAL replay entry
        point).  Ops are dicts ``{"seq", "op", "study", ...}``; ``seq``
        must extend the bank's monotonic op sequence by exactly one — a
        gap or reorder means the journal does not match this snapshot and
        replay would diverge, so it raises instead of guessing.

        Because every proposal is a pure function of the bank state and
        each study's RNG stream, re-applying the op sequence from any
        snapshot reconstructs bit-identical optimizer state: an ``ask``
        record replays to the *same* trial ids and configurations the
        original call served.  Tells replay through the idempotent
        ``tell_once`` path, so an at-least-once journal (duplicate tell
        records) cannot double-apply an observation.
        """
        seq = int(op["seq"])
        if seq <= self.op_seq:
            return None     # already contained in the snapshot: skip
        if seq != self.op_seq + 1:
            raise ValueError(
                f"journal op seq {seq} does not extend bank op_seq "
                f"{self.op_seq} (missing or reordered WAL records)")
        kind = op["op"]
        b = int(op["study"])
        if not 0 <= b < self.n_studies:
            raise ValueError(f"journal op targets study row {b}, bank "
                             f"holds {self.n_studies}")
        view = self.studies[b]
        # the seq is consumed even if the apply raises: a journaled record
        # must never be half-committed — op_seq advancing past it means the
        # next op gets a fresh seq (no duplicate-seq frames) and replay
        # re-raises at the same point with the same state, so recovery can
        # skip the record deterministically instead of wedging the service
        try:
            if kind == "create":
                view.sign = float(op.get("sign", 1.0))
                nm = op.get("optimizer")
                if nm is not None:
                    self.set_strategy(b, nm)
                result = view
            elif kind == "ask":
                result = view.ask(int(op["n"]))
            elif kind == "tell":
                result = view.tell_once(int(op["trial_id"]),
                                        float(op["value"]))
            elif kind == "tell_failed":
                result = view.tell_failed_once(int(op["trial_id"]))
            elif kind == "observe":
                result = view.observe_params(dict(op["params"]),
                                             float(op["value"]))
            elif kind == "trace":
                view.snapshot_trace()
                result = None
            else:
                raise ValueError(f"unknown journal op kind {kind!r}")
        finally:
            self.op_seq = seq
        return result

    # ------------------------------------------------------------- ask_all
    def ask_all(self, n: int = 1) -> List[list]:
        """Propose ``n`` new trials for every study.

        Studies still in the random phase (< 2 observations) or running
        the ``random`` strategy ask through their own view; every other
        study is gathered into one
        shape-bucketed device batch and served by the staged pipeline,
        sub-batched per strategy family.  Returns
        ``[trials_of_study_0, ...]``.
        """
        if n < 1:
            raise ValueError("ask_all(n) requires n >= 1")
        for v in self.studies:
            v._check_kwargs()
        led = self.ledger
        B = led.n_studies
        n_obs = led.n_observed()
        device = (n_obs >= 2) & self._bankable
        out: List[Optional[list]] = [None] * B
        for b in np.nonzero(~device)[0]:
            out[b] = self.studies[int(b)].ask(n)
        if not device.any():
            return out
        picks = self._ask_device(n, n_obs, device)
        # bulk registration: one fancy-indexed ledger write per field for
        # every device-phase study (the per-view ``_register_asked`` loop
        # was the last O(B) Python/ledger hot spot in the steady state);
        # ids stay dense (slot == trial id), statuses/obs_seq identical to
        # the per-view path.
        from repro.core.optimizer import Trial
        dev = np.array(sorted(picks))
        tids0 = led.n_trials[dev].astype(np.int64)
        led.ensure_capacity(int((tids0 + n).max()))
        rows = dev[:, None]
        slot = tids0[:, None] + np.arange(n)[None, :]
        led.X[rows, slot] = np.stack([picks[int(b)][1] for b in dev])
        led.status[rows, slot] = S_PENDING
        led.obs_seq[rows, slot] = -1
        led.n_trials[dev] = tids0 + n
        led.ask_count[dev] += 1
        for i, b in enumerate(dev):
            b = int(b)
            v = self.studies[b]
            trials = []
            for j, p in enumerate(picks[b][0]):
                t = Trial(int(tids0[i]) + j, dict(p), _ledger=led,
                          _study=b)
                v._trials[t.id] = t
                trials.append(t)
            out[b] = trials
        return out

    def _ask_device(self, n: int, n_obs: np.ndarray, device: np.ndarray):
        """Per-family sub-batched dispatch over ONE columnar candidate
        draw; returns ``{study: (configs, encoded_rows)}`` for every
        device-phase study.  GP and clustering rows share the obs-stage
        cache (gather, standardization, factors); each family pays one
        pick program and one exit sync."""
        led, space = self.ledger, self.space
        B, d = led.n_studies, led.dim
        k_obs = n_obs.astype(np.int32)
        k_pend = led.n_pending().astype(np.int32)
        pend_cap = max(4, -(-int(k_pend.max()) // 4) * 4)
        na = _pow2(max(16, int(k_obs.max()) + pend_cap + n))
        n_mc = self.mc_samples or self.space.mc_samples(n)
        # one columnar draw for the whole bank (no per-candidate dicts)
        cols = space.sample_columns(B * n_mc, self._rng)
        Cflat = np.asarray(space.encode_columns(cols, B * n_mc), np.float32)
        C = Cflat.reshape(B, n_mc, d)
        dev = np.nonzero(device)[0]
        groups: Dict[str, np.ndarray] = {}
        for f in ("gp", "cluster", "tpe"):
            rows = np.array([int(b) for b in dev
                             if self._fams[int(b)] == f], np.int64)
            if len(rows):
                groups[f] = rows
        cache = None
        if "gp" in groups or "cluster" in groups:
            cache = self._obs_stage(k_obs, na)
        picks: Dict[int, tuple] = {}
        for f, rows in groups.items():
            if f == "tpe":
                Xd, yraw, _ = self._gather_obs(k_obs[rows], na, rows)
                Pd = self._gather_pend(k_pend[rows], pend_cap, rows)
                idx = self._pick_tpe(Xd, yraw, Pd, C[rows], k_obs[rows],
                                     k_pend[rows], n, na)
            else:
                idx = self._pick_gp(cache, rows, f, C[rows], k_obs[rows],
                                    k_pend[rows], n, na, pend_cap)
            flat = (rows[:, None] * n_mc + idx).astype(np.int64)  # (R, n)
            cfgs = space.configs_at(cols, flat.ravel())
            enc = Cflat[flat.ravel()].reshape(len(rows), -1, Cflat.shape[1])
            for i, b in enumerate(rows):
                picks[int(b)] = (cfgs[i * n:(i + 1) * n], enc[i])
        return picks

    def ask_view(self, view, n: int, cols, n_mc: int):
        """Bank-of-one ask: one view's proposal served by the bucketed
        pipeline.  Candidates arrive columnar, drawn by the *view's* own
        RNG stream (so the pre-refactor per-study stream is preserved
        bit-for-bit); bucket shapes stay bank-wide so a view inside a
        fleet re-enters the same compiled programs as ``ask_all``.
        Returns ``(configs, encoded_rows)`` for ``n`` picks."""
        led, space = self.ledger, self.space
        b = view._b
        n = min(n, n_mc)
        fam = self._fams[b]
        k_obs = led.n_observed().astype(np.int32)
        k_pend = led.n_pending().astype(np.int32)
        pend_cap = max(4, -(-int(k_pend.max()) // 4) * 4)
        na = _pow2(max(16, int(k_obs.max()) + pend_cap + n))
        Cflat = np.asarray(space.encode_columns(cols, n_mc), np.float32)
        C = Cflat.reshape(1, n_mc, led.dim)
        rows = np.array([b], np.int64)
        if fam == "tpe":
            Xd, yraw, _ = self._gather_obs(k_obs[rows], na, rows)
            Pd = self._gather_pend(k_pend[rows], pend_cap, rows)
            idx = self._pick_tpe(Xd, yraw, Pd, C, k_obs[rows],
                                 k_pend[rows], n, na)
        else:
            cache = self._obs_stage(k_obs, na)
            idx = self._pick_gp(cache, rows, fam, C, k_obs[rows],
                                k_pend[rows], n, na, pend_cap)
        idx = idx[0].astype(np.int64)
        return space.configs_at(cols, idx), Cflat[idx]

    def _gather_obs(self, k_obs: np.ndarray, na: int, rows: np.ndarray):
        """Masked-rank observation gather at the bucket shape for the
        ``rows`` sub-batch: one stable argsort of the completion order
        (empty / pending / failed slots pushed past the horizon by a
        sentinel) replaces the per-study ``obs_ids`` fancy-indexing loop.
        Returns ``(Xd (R, na, d), yraw signed (R, na), mask (R, na))``."""
        led = self.ledger
        d, cap = led.dim, led.capacity
        R = len(rows)
        m = min(cap, na)
        status = led.status[rows]
        seq = np.where(status == S_OBSERVED, led.obs_seq[rows],
                       np.iinfo(np.int32).max)
        order = np.argsort(seq, axis=1, kind="stable")[:, :m]
        rr = np.arange(R)[:, None]
        valid = np.arange(m)[None, :] < k_obs[:, None]
        sign = np.array([self._members[int(b)].sign
                         for b in rows])[:, None]
        Xsub, ysub = led.X[rows], led.y[rows]
        Xd = np.zeros((R, na, d), np.float32)
        yraw = np.zeros((R, na), np.float32)     # signed, unstandardized
        mask = np.zeros((R, na), np.float32)
        Xd[:, :m] = np.where(valid[..., None], Xsub[rr, order], 0.0)
        yraw[:, :m] = np.where(valid, sign * ysub[rr, order],
                               0.0).astype(np.float32)
        mask[:, :m] = valid
        return Xd, yraw, mask

    def _gather_pend(self, k_pend: np.ndarray, pend_cap: int,
                     rows: np.ndarray) -> np.ndarray:
        """In-flight rows at the ``pend_cap`` shape (ascending trial id,
        like ``pending_ids``) for the ``rows`` sub-batch.  Never cached —
        pending churn happens every ask/tell_failed."""
        led = self.ledger
        d, cap = led.dim, led.capacity
        R = len(rows)
        Pd = np.zeros((R, pend_cap, d), np.float32)
        if int(k_pend.max()):
            status = led.status[rows]
            ids = np.where(status == S_PENDING,
                           np.arange(cap)[None, :], np.iinfo(np.int32).max)
            order = np.argsort(ids, axis=1, kind="stable")[:, :pend_cap]
            rr = np.arange(R)[:, None]
            valid = np.arange(pend_cap)[None, :] < k_pend[:, None]
            Pd[:] = np.where(valid[..., None], led.X[rows][rr, order], 0.0)
        return Pd

    def _fit_if_due(self, Xd, yraw, mask, ko, rows) -> bool:
        """Count-based fit schedule over the gp-family sub-batch: (re)fit
        hypers for every study whose observation count advanced
        ``refit_every`` past its last fit (or that never fit).  The fit
        program runs over the due rows only, gathered on the host and
        padded to a power-of-2 row bucket (``row_bucket``) with copies of
        the first due row, whose results are dropped; write-back touches
        the due rows alone, so non-due studies' frozen hypers (and frozen
        y standardization) stay bit-stable.  Standardization scalars are
        computed on the host (``_y_standardization``, which a checkpoint
        restore also runs), so a resumed study standardizes
        bit-identically.
        Returns True when anything refit (obs stamp was bumped)."""
        led = self.ledger
        ko64 = ko.astype(np.int64)
        due = ((led.have_fit[rows] == 0) |
               (ko64 - led.n_fit[rows] >= self.refit_every))
        # frozen-standardization sanity: a degenerate fit (y_std ~ 1e-6
        # from constant initial observations) would blow incoming values
        # up to ~1e6 standardized and wreck the acquisition surface for up
        # to refit_every asks —
        # re-tune immediately instead.  Checked over everything observed
        # since the last fit so replay reaches the same decision.
        for i, r in enumerate(rows):
            if due[i] or not led.have_fit[r]:
                continue
            nf, k = int(led.n_fit[r]), int(ko64[i])
            if k > nf:
                zt = (np.abs(yraw[i, nf:k] - led.y_mean[r])
                      / led.y_std[r])
                if zt.size and float(zt.max()) > 1e3:
                    due[i] = True
        due &= ko64 >= 2
        if not due.any():
            return False
        from repro.core import gp as gp_lib
        sel = np.nonzero(due)[0]
        k, rb = len(sel), row_bucket(len(sel), len(rows))
        # the due rows, then copies of the first due row up to the bucket
        take = np.concatenate([sel, np.repeat(sel[:1], rb - k)])
        g = np.asarray(rows)[take]
        ym = led.y_mean[g].copy()
        ys = led.y_std[g].copy()
        for j, i in enumerate(sel):
            ym[j], ys[j] = _y_standardization(yraw[i, :int(ko64[i])])
        ym[k:], ys[k:] = ym[0], ys[0]
        args = (Xd[take], yraw[take], mask[take], led.log_ls[g],
                led.log_var[g], led.log_noise[g], ym, ys)
        c = self.counters
        c.add("fit.calls")
        c.add("fit.rows_due", k)
        c.add("fit.rows_run", rb)
        with span("mango.fit", rows_due=k, rows_run=rb, na=Xd.shape[1]), \
                c.timed("fit.ns"):
            lls, lv, ln = gp_lib.fit_hypers_bank(*args, steps=self.fit_steps)
            # one explicit exit transfer for the three hyper arrays
            lls, lv, ln = jax.device_get((lls, lv, ln))
        self._warm_fit_buckets(args, rb, len(rows))
        g = g[:k]
        led.log_ls[g] = lls[:k]
        led.log_var[g] = lv[:k]
        led.log_noise[g] = ln[:k]
        led.y_mean[g] = ym[:k]
        led.y_std[g] = ys[:k]
        led.n_fit[g] = ko64[sel]
        led.have_fit[g] = 1
        led.obs_stamp += 1    # new hypers/standardization: factors stale
        return True

    def _warm_fit_buckets(self, args, rb: int, R: int) -> None:
        """Compile the fit at every other row bucket of this ``(na, R)``
        the first time it fits there, so a later fit at any due count
        finds its program compiled.  Each dispatch runs on copies of the
        first row of ``args`` and its result is dropped: the ledger, the
        RNG, the obs stamp and the journal are untouched, so a replayed
        bank makes the same calls and reaches the same state.  The
        dispatches go back to back and are waited for together, so the
        device runs one while the host loads the next."""
        key = (int(args[0].shape[1]), R)
        if key in self._fit_warmed:
            return
        self._fit_warmed.add(key)
        from repro.core import gp as gp_lib
        outs = []
        for b in row_buckets(R):
            if b == rb:
                continue
            self.counters.add("fit.warm_calls")
            outs.append(gp_lib.fit_hypers_bank(
                *(np.repeat(a[:1], b, axis=0) for a in args),
                steps=self.fit_steps))
        jax.block_until_ready(outs)

    def _obs_stage(self, k_obs: np.ndarray, na: int):
        """Observation-dependent stages for every gp-family row (GP and
        clustering share them): masked gather, fit schedule, frozen
        standardization, prescale, Cholesky factors + condition estimate.
        Cached on the ledger's ``obs_stamp`` + bucket shape, so the
        ask/tell_failed steady state pays only the candidate-dependent
        pick stages."""
        led = self.ledger
        signs = tuple(self._members[int(b)].sign for b in self._gp_fam_rows)
        key = (led.obs_stamp, na, signs)
        cache = self._gp_cache
        hit = cache is not None and cache["key"] == key
        self.counters.add("obs_stage.calls")
        self.counters.add("obs_stage.hits", int(hit))
        with span("mango.obs_stage", hit=int(hit)), \
                self.counters.timed("obs_stage.ns"):
            return cache if hit else self._factor_obs(k_obs, na, signs)

    def _factor_obs(self, k_obs: np.ndarray, na: int, signs: tuple):
        """The obs stage's cache miss: gather, fit if due, factor.  The
        factors stay on the device, in the cache the pick reads; only
        the per-row condition estimates come to the host."""
        led = self.ledger
        gpr = self._gp_fam_rows
        ko = k_obs[gpr]
        from repro.core import gp as gp_lib
        with span("mango.gather"):
            Xd, yraw, mask = self._gather_obs(ko, na, gpr)
        self._fit_if_due(Xd, yraw, mask, ko, gpr)   # a refit bumps the stamp
        # frozen standardization
        z = (yraw - led.y_mean[gpr][:, None]) / led.y_std[gpr][:, None]
        z = (z * mask).astype(np.float32)
        ls = np.exp(led.log_ls[gpr]).astype(np.float32)
        var = np.exp(led.log_var[gpr]).astype(np.float32)
        noise = (np.exp(led.log_noise[gpr]) + 1e-5).astype(np.float32)
        c = self.counters
        rows = len(gpr)
        obs = int(ko.sum()) + int(led.n_pending()[gpr].sum())
        c.add("factors.calls")
        c.add("factors.rows", rows)
        c.add("factors.obs", obs)
        c.add("factors.slots", rows * na)
        with span("mango.factors", na=na, rows=rows, obs=obs):
            with c.timed("factors.ns"):
                L, Linv, cond = gp_lib.bank_factors(Xd, mask, ls, var,
                                                    noise)
                # the condition estimates' readback waits for the factors
                # anyway: waiting here keeps the program's time in this
                # counter
                jax.block_until_ready((L, Linv, cond))
            Xs = gp_lib.bank_prescale_X(Xd, ls)
        cache = self._gp_cache = {
            "key": (led.obs_stamp, na, signs), "Xs": Xs, "z": jnp.asarray(z),
            "mask": jnp.asarray(mask), "L": L, "Linv": Linv,
            "ls": jnp.asarray(ls), "var": jnp.asarray(var),
            "noise": jnp.asarray(noise),
            "cond": np.asarray(jax.device_get(cond), np.float64)}
        self._count_ill_conditioned(cache["cond"], gpr)
        return cache

    def _count_ill_conditioned(self, cond: np.ndarray,
                               gpr: np.ndarray) -> None:
        """Count the factored rows whose condition estimate passes
        ``scoring.COND_PROXY_WARN``, or is NaN where the factorization
        broke down, at every factoring; warn at the first such row of the
        bank's life."""
        import warnings
        from repro.core import scoring
        bad = np.nonzero(~(cond <= scoring.COND_PROXY_WARN))[0]
        self.counters.add("factors.ill_conditioned", len(bad))
        if len(bad) and not getattr(self, "_cond_warned", False):
            self._cond_warned = True
            b = int(gpr[bad[0]])
            warnings.warn(
                f"study {b}: GP kernel condition estimate "
                f"{cond[bad[0]]:.2e} exceeds {scoring.COND_PROXY_WARN:.0e};"
                " posterior scores may be unreliable (consider more noise"
                " or fewer near-duplicate observations)", RuntimeWarning)

    def _pick_gp(self, cache, rows, fam, C, ko, kp, n, na, pend_cap):
        """One GP-family sub-batch's picks ``(R, n)``, on the host (one
        exit sync per family).  The span carries what the pick works on:
        ``S`` candidates of ``d`` dims, the batch ``n``, and each study's
        rows in the system (observations and pending), not the bucket's."""
        with span("mango.pick_gp",
                  rows=lambda: ",".join(str(int(a) + int(b))
                                        for a, b in zip(ko, kp)),
                  S=int(C.shape[1]), d=int(C.shape[2]), n=int(n)):
            idx = self._dispatch_gp(cache, rows, fam, C, ko, kp, n, na,
                                    pend_cap)
            return np.asarray(jax.device_get(idx))

    def _dispatch_gp(self, cache, rows, fam, C, ko, kp, n, na, pend_cap):
        """Candidate-dependent stages for one family sub-batch, sliced
        out of the shared obs-stage cache: prescale-C, pending absorb,
        distances, exp, and the family's pick head (GP-BUCB downdate loop
        or clustered-batch top-k/k-means/argmax)."""
        from repro.core import gp as gp_lib
        led = self.ledger
        pos = np.array([self._gp_pos[int(r)] for r in rows])
        full = (len(pos) == len(self._gp_fam_rows)
                and np.array_equal(pos, np.arange(len(pos))))
        take = (lambda a: a) if full else (lambda a: a[pos])
        ls, var, noise = take(cache["ls"]), take(cache["var"]), \
            take(cache["noise"])
        Xs, z, maskd = take(cache["Xs"]), take(cache["z"]), \
            take(cache["mask"])
        L, Linv = take(cache["L"]), take(cache["Linv"])
        Cs = gp_lib.bank_prescale_C(C, ls)
        if int(kp.max()):
            Pd = self._gather_pend(kp, pend_cap, rows)
            Xs, z, maskd, L, Linv = gp_lib.bank_absorb(
                Xs, z, maskd, L, Linv, Pd, kp.astype(np.float32),
                ko.astype(np.float32), ls, var, noise, pend_cap=pend_cap)
        d2, s = gp_lib.bank_dist(Cs, Xs)
        e = gp_lib.bank_exp(s)
        n_eff = (ko + kp).astype(np.float32)
        dom = np.float32(self._members[int(rows[0])].domain_size)
        if fam == "cluster":
            top_frac = self.strategy_kwargs.get("top_frac", 0.2)
            n_top = strategies.n_top_candidates(C.shape[1], n, top_frac)
            # one vmap'd seeding dispatch for the sub-batch (J101/J102:
            # a per-study PRNGKey loop is R device calls + R host reads)
            keys = jax.vmap(jax.random.PRNGKey)(
                jnp.asarray(led.ask_count[rows], jnp.uint32))
            return gp_lib.bank_cluster_pick(
                d2, s, e, jnp.asarray(C), z, maskd, Linv, var, noise,
                n_eff, dom, keys, batch_size=n, n_top=n_top, S=C.shape[1])
        return gp_lib.bank_pick(
            d2, s, e, Cs, z, maskd, L, Linv, var, noise, n_eff,
            dom, batch_size=n, S=C.shape[1])

    def _pick_tpe(self, Xd, yraw, Pd, C, k_obs, k_pend, n, na):
        """One TPE sub-batch's picks ``(R, n)``, on the host."""
        with span("mango.pick_tpe"):
            idx = self._dispatch_tpe(Xd, yraw, Pd, C, k_obs, k_pend, n, na)
            return np.asarray(jax.device_get(idx))

    def _dispatch_tpe(self, Xd, yraw, Pd, C, k_obs, k_pend, n, na):
        from repro.core import tpe as tpe_lib
        d = self.ledger.dim
        R = Xd.shape[0]
        dp = tpe_lib.pad_dims(d)
        # TPE layout: observed rows, then pending rows, then zeros
        Xt = np.zeros((R, na, dp), np.float32)
        yt = np.zeros((R, na), np.float32)
        for i in range(R):
            ko, kp = int(k_obs[i]), int(k_pend[i])
            Xt[i, :ko, :d] = Xd[i, :ko]
            yt[i, :ko] = yraw[i, :ko]
            if kp:
                Xt[i, ko:ko + kp, :d] = Pd[i, :kp]
        Sp = C.shape[1]
        Ct = np.zeros((R, Sp, dp), np.float32)
        Ct[:, :, :d] = C
        gamma = self.strategy_kwargs.get("gamma", 0.25)
        pending_penalty = self.strategy_kwargs.get("pending_penalty", False)
        kp_eff = (k_pend if pending_penalty
                  else np.zeros_like(k_pend))
        meta = np.stack([k_obs.astype(np.float32),
                         kp_eff.astype(np.float32),
                         np.full((R,), Sp, np.float32),
                         np.full((R,), gamma, np.float32)], axis=1)
        return tpe_lib.fused_tpe_propose_bank(
            Xt, yt, Ct, meta, batch_size=n, d_true=d)

    # ---------------------------------------------------------- checkpoint
    def state_dict(self) -> Dict[str, Any]:
        """JSON-able fleet snapshot: the bank candidate stream plus every
        study's v1 single-study snapshot (so one study's entry is exactly
        what its view's own ``state_dict`` returns)."""
        led = self.ledger
        return {
            "version": 1,
            "kind": "study_bank",
            "n_studies": self.n_studies,
            "rng_state": self._rng.bit_generator.state,
            "strategies": list(self.strategy_names),
            "studies": [v.state_dict() for v in self.studies],
            # the bank fit schedule lives in the ledger — carried
            # bank-level so the per-study entries stay exactly the v1
            # single-study format
            "gp_bank": [{
                "log_ls": [float(x) for x in led.log_ls[b]],
                "log_var": float(led.log_var[b]),
                "log_noise": float(led.log_noise[b]),
                "have_fit": int(led.have_fit[b]),
                "n_fit": int(led.n_fit[b]),
                "y_mean": float(led.y_mean[b]),
                "y_std": float(led.y_std[b]),
            } for b in range(led.n_studies)],
        }

    def load_state_dict(self, sd: Dict[str, Any]) -> None:
        if sd.get("kind") != "study_bank":
            raise ValueError("not a study_bank state dict")
        if sd["n_studies"] != self.n_studies:
            raise ValueError(f"bank holds {self.n_studies} studies, "
                             f"snapshot has {sd['n_studies']}")
        self._rng = rng_from_state(sd["rng_state"])
        # restore per-study strategies before the view loads (pre-mixed
        # snapshots carry no "strategies" key: names stay as constructed)
        for b, nm in enumerate(sd.get("strategies", [])):
            self.set_strategy(b, nm)
        for v, s in zip(self.studies, sd["studies"]):
            v.load_state_dict(s)      # resets the ledger row first
        led = self.ledger
        for b, g in enumerate(sd.get("gp_bank", [])):
            led.log_ls[b] = np.asarray(g["log_ls"], np.float32)
            led.log_var[b] = g["log_var"]
            led.log_noise[b] = g["log_noise"]
            led.have_fit[b] = g["have_fit"]
            led.n_fit[b] = g["n_fit"]
            led.y_mean[b] = g["y_mean"]
            led.y_std[b] = g["y_std"]

    def save(self, path, iteration: int = 0, extra=None) -> None:
        """One-write fleet checkpoint: every ledger array (the pytree
        leaves) plus a JSON meta block (params dicts, best traces, RNG
        streams) in a single atomically-replaced ``.npz`` file.

        ``extra`` is an optional JSON-serializable side channel stored
        verbatim in the meta block — the durable service keeps its study
        name table and ask-dedup cache there so one snapshot write covers
        the whole recovery state.  ``load`` hands it back via
        ``self.extra``; when omitted, the bank's current ``self.extra``
        is persisted so callers that set the attribute directly still
        round-trip.
        """
        from repro.core.optimizer import _to_jsonable
        led = self.ledger
        for b, v in enumerate(self.studies):
            led.rng_state[b] = pack_rng_state(v._rng)
        leaves, _ = jax.tree_util.tree_flatten(led)
        arrays = {f"led_{name}": np.asarray(leaf) for name, leaf
                  in zip(StudyLedger.ARRAY_FIELDS, leaves)}
        meta = {
            # v2: per-study "strategy" column (mixed banks); v1 checkpoints
            # (no strategy key) load unchanged — names stay as constructed
            "version": 2,
            "kind": "study_bank",
            "rng_kind": RNG_KIND,
            "iteration": iteration,
            "op_seq": self.op_seq,
            "extra": self.extra if extra is None else extra,
            "n_studies": self.n_studies,
            "dim": led.dim,
            "bank_rng_state": self._rng.bit_generator.state,
            "studies": [{
                "sign": v.sign,
                "strategy": self.strategy_names[b],
                "best_trace": list(v._best_trace),
                "gp": v._gp_export(),
                "params": [_to_jsonable(v._trials[i].params)
                           for i in range(int(led.n_trials[b]))],
            } for b, v in enumerate(self.studies)],
        }
        p = Path(path)
        tmp = p.with_suffix(".tmp")
        with open(tmp, "wb") as fh:
            np.savez(fh, meta=np.frombuffer(
                json.dumps(meta).encode(), dtype=np.uint8), **arrays)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, p)  # atomic: a crash never corrupts the checkpoint

    def load(self, path) -> int:
        """Restore a ``save`` checkpoint in place; returns the stored
        iteration.  Arrays are restored directly (no re-encode), params
        dicts and RNG streams come from the meta block."""
        from repro.core.optimizer import Trial
        with np.load(path) as z:
            meta = json.loads(bytes(z["meta"]).decode())
            if meta.get("kind") != "study_bank":
                raise ValueError("not a study_bank checkpoint")
            # checkpoints written before the tag existed are all PCG64
            rng_kind = meta.get("rng_kind", RNG_KIND)
            if rng_kind != RNG_KIND:
                raise ValueError(
                    f"checkpoint packs {rng_kind!r} RNG streams but this "
                    f"build only decodes {RNG_KIND}; the 6-word rng_state "
                    "rows would unpack into a different generator's state")
            if meta["n_studies"] != self.n_studies:
                raise ValueError(
                    f"bank holds {self.n_studies} studies, checkpoint has "
                    f"{meta['n_studies']}")
            # older snapshots also carry the factors (``led_L``,
            # ``led_Linv``): the obs stage recomputes them, so they are
            # left unread
            arrays = {name: z[f"led_{name}"]
                      for name in StudyLedger.ARRAY_FIELDS}
        led = self.ledger
        for name in StudyLedger.ARRAY_FIELDS:
            setattr(led, name, arrays[name])
        led.obs_stamp += 1   # wholesale array swap: device cache is stale
        self._rng = rng_from_state(meta["bank_rng_state"])
        for b, v in enumerate(self.studies):
            ms = meta["studies"][b]
            nm = ms.get("strategy")
            if nm is not None:     # v2 meta; v1 keeps constructed names
                self.set_strategy(b, nm)
            v.sign = ms["sign"]
            v._best_trace = list(ms["best_trace"])
            v._gp_snapshot = ms["gp"]
            v._rng = unpack_rng_state(led.rng_state[b])
            v._trials = {
                tid: Trial(tid, dict(params), _ledger=led, _study=b)
                for tid, params in enumerate(ms["params"])}
        self.op_seq = int(meta.get("op_seq", 0))
        self.extra = meta.get("extra")
        return meta["iteration"]
