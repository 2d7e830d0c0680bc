"""Plain float64 references for what the served path answers.

Nothing here imports the program.  The references take the data of one
ask as the service's durable state holds it (the encoded observations in
the order they were told, the pending rows, the candidate block the ask
drew) and the journal's order of operations, and redo the rest:

  * the hyperparameters (``fit_hypers_bank``): every fit the service's
    schedule makes for a study, from the cold start through each refit
    (``refit_every`` observations after the last, warm-started from it),
    by Adam on the negative log marginal likelihood of a Matern-5/2 ARD GP
    with analytic gradients, with the study's values standardized at each
    fit and frozen until the next;
  * GP-BUCB (``bank_factors``, ``bank_pick``): the posterior under those
    hyperparameters, the adaptive-beta UCB, and the batch loop that
    conditions the variance on each earlier pick;
  * clustering (``bank_cluster_pick``): the same posterior and UCB; the
    best pick must be the UCB's best, and every pick must lie in the
    UCB's top ``n_top`` set;
  * TPE (``fused_tpe_propose_bank``): the l(x)/g(x) product-Parzen score;
    the picks must be its top ``n``.

The pick gaps are the amounts by which the picks lie below what the
reference would have picked, in units of ``1 + |reference best|``.  The
fit gap is the amount by which the service's hyperparameters fall short
of the reference's in log marginal likelihood per observation, both read
on the reference's data of the last fit.  The WAL reader is a second,
independent reader of the journal's frame format.
"""
from __future__ import annotations

import json
import math
import struct
import zlib
from typing import Dict, List, Tuple

import numpy as np
from scipy.linalg import lapack, solve_triangular

WAL_MAGIC = 0x57414C31
_HEADER = struct.Struct("<III")
_BLOCK = 4096                      # candidate rows per float64 block


def read_wal(path) -> List[dict]:
    """Every intact frame of a journal file (magic, length, CRC32, JSON)."""
    with open(path, "rb") as fh:
        buf = fh.read()
    out, off = [], 0
    while off + _HEADER.size <= len(buf):
        magic, length, crc = _HEADER.unpack_from(buf, off)
        start, end = off + _HEADER.size, off + _HEADER.size + length
        if magic != WAL_MAGIC or end > len(buf) \
                or zlib.crc32(buf[start:end]) & 0xFFFFFFFF != crc:
            break
        out.append(json.loads(buf[start:end]))
        off = end
    return out


# ---------------------------------------------------------------- the GP
def _sqdist(A: np.ndarray, B: np.ndarray) -> np.ndarray:
    d2 = ((A * A).sum(1)[:, None] + (B * B).sum(1)[None, :]
          - 2.0 * A @ B.T)
    return np.maximum(d2, 0.0)


def matern52(A: np.ndarray, B: np.ndarray, var: float) -> np.ndarray:
    """Matern-5/2 between rows already divided by the lengthscales."""
    d2 = _sqdist(A, B)
    s = math.sqrt(5.0) * np.sqrt(d2)
    return var * (1.0 + s + (5.0 / 3.0) * d2) * np.exp(-s)


def jitter(var: float) -> float:
    return 1e-6 * max(var, 1.0)


def beta(t: float, domain_size: float) -> float:
    t = max(float(t), 1.0)
    b = 2.0 * math.log(max(domain_size, 2.0) * t * t * math.pi ** 2 / 0.6)
    return min(max(b, 1.0), 100.0)


# ------------------------------------------------------ the hyperparameters
# the service's cold start, Adam settings and lengthscale box
COLD_LOG_LS, COLD_LOG_VAR, COLD_LOG_NOISE = math.log(0.5), 0.0, math.log(1e-2)
ADAM_LR, ADAM_B1, ADAM_B2, ADAM_EPS = 0.08, 0.9, 0.999, 1e-8
LOG_LS_BOX = (math.log(0.01), math.log(10.0))
NOISE_FLOOR = 1e-5
GUARD_Z = 1e3          # a told value this far out refits at once


def hypers(theta: np.ndarray) -> Tuple[np.ndarray, float, float]:
    """``(lengthscales, signal variance, noise)`` from the log-parameters
    ``[log_ls..., log_var, log_noise]``."""
    d = len(theta) - 2
    return (np.exp(theta[:d]), float(np.exp(theta[d])),
            float(np.exp(theta[d + 1])) + NOISE_FLOOR)


def nll_grad(theta: np.ndarray, D2: np.ndarray,
             z: np.ndarray) -> Tuple[float, np.ndarray]:
    """The negative log marginal likelihood per observation and its
    gradient in the log-parameters.  ``D2`` (d, n, n) holds the squared
    differences of the raw encodings per dimension."""
    d, n = D2.shape[0], D2.shape[1]
    ls, var, noise = hypers(theta)
    r2 = np.tensordot(1.0 / (ls * ls), D2, axes=1)
    s = math.sqrt(5.0) * np.sqrt(r2)
    es = np.exp(-s)
    K = var * (1.0 + s + r2 * (5.0 / 3.0)) * es
    K[np.diag_indices(n)] = var + noise + jitter(var)
    L, info = lapack.dpotrf(K, lower=1, clean=1)
    if info:
        raise np.linalg.LinAlgError(f"kernel matrix not positive ({info})")
    Ki, info = lapack.dpotri(L, lower=1)
    Ki = np.tril(Ki)
    Ki = Ki + np.tril(Ki, -1).T                     # K^-1
    alpha = Ki @ z
    W = Ki - np.outer(alpha, alpha)
    nll = (0.5 * float(z @ alpha) + float(np.log(np.diag(L)).sum())
           + 0.5 * n * math.log(2.0 * math.pi)) / n
    G = (5.0 / 3.0) * var * (1.0 + s) * es
    G[np.diag_indices(n)] = 0.0
    g = np.empty(d + 2)
    g[:d] = np.tensordot(D2, W * G, axes=([1, 2], [0, 1])) / (ls * ls)
    Kv = K
    Kv[np.diag_indices(n)] = var + (jitter(var) if var > 1.0 else 0.0)
    g[d] = float((W * Kv).sum())
    g[d + 1] = float(np.trace(W)) * (noise - NOISE_FLOOR)
    return nll, 0.5 * g / n


def sq_diffs(X: np.ndarray) -> np.ndarray:
    X = np.asarray(X, np.float64)
    return (X.T[:, :, None] - X.T[:, None, :]) ** 2


def adam_fit(theta: np.ndarray, X: np.ndarray, z: np.ndarray,
             steps: int) -> np.ndarray:
    """``steps`` steps of Adam from ``theta`` with fresh moments, the
    lengthscales kept in their box after each step."""
    D2 = sq_diffs(X)
    z = np.asarray(z, np.float64)
    d = X.shape[1]
    theta = np.array(theta, np.float64)
    m = np.zeros_like(theta)
    v = np.zeros_like(theta)
    for i in range(steps):
        _, g = nll_grad(theta, D2, z)
        m = ADAM_B1 * m + (1.0 - ADAM_B1) * g
        v = ADAM_B2 * v + (1.0 - ADAM_B2) * g * g
        t = i + 1
        theta = theta - ADAM_LR * (m / (1.0 - ADAM_B1 ** t)) / (
            np.sqrt(v / (1.0 - ADAM_B2 ** t)) + ADAM_EPS)
        theta[:d] = np.clip(theta[:d], *LOG_LS_BOX)
    return theta


class HyperChain:
    """One study's fits as the service schedules them: at each ask of the
    GP family (any study's), the study refits when it never fit, when
    ``refit_every`` observations came since its last fit, or when one of
    them lies ``GUARD_Z`` standard deviations out; never under two
    observations.  ``X``, ``y`` are the study's observations in the order
    they were told (signed values)."""

    def __init__(self, X, y, refit_every: int, steps: int):
        self.X = np.asarray(X, np.float64)
        self.y = np.asarray(y, np.float64)
        d = self.X.shape[1]
        self.theta = np.array([COLD_LOG_LS] * d + [COLD_LOG_VAR,
                                                  COLD_LOG_NOISE])
        self.refit_every, self.steps = int(refit_every), int(steps)
        self.n_fit, self.fitted = 0, False
        self.ym, self.ys = 0.0, 1.0
        self.fits = 0

    def at_ask(self, k: int) -> None:
        due = not self.fitted or k - self.n_fit >= self.refit_every
        if not due and k > self.n_fit:
            zt = np.abs(self.y[self.n_fit:k] - self.ym) / self.ys
            due = float(zt.max()) > GUARD_Z
        if not due or k < 2:
            return
        yk = self.y[:k]
        self.ym, self.ys = float(yk.mean()), float(yk.std()) + 1e-6
        self.theta = adam_fit(self.theta, self.X[:k],
                              (yk - self.ym) / self.ys, self.steps)
        self.n_fit, self.fitted = k, True
        self.fits += 1

    def state(self) -> Dict:
        return {"theta": self.theta.copy(), "n_fit": self.n_fit,
                "ym": self.ym, "ys": self.ys}


def replay_fits(ops: List[dict], wanted: Dict[int, int],
                obs: Dict[int, Tuple[np.ndarray, np.ndarray]], gp_family,
                refit_every: int, steps: int) -> Dict[int, Dict]:
    """Redo the fits of the studies of the sampled asks ``wanted`` (journal
    sequence number -> study), following the journal ``ops`` from the
    snapshot of the seeded history on.  ``obs[study]`` holds all of the
    study's observations in the order they were told, ``(X, y)``; its
    count at the snapshot is theirs less the tells in the journal, and
    grows by one at each tell.  ``gp_family(study)`` says whether a
    study's asks run the fit schedule.  Returns, per sampled ask, its
    study's fitted state as that ask saw it, with the count ``k`` of
    observations it saw; ``fits`` is the number of fits made."""
    told: Dict[int, int] = {}
    for op in ops:
        if op["op"] in ("tell", "observe"):
            told[int(op["study"])] = told.get(int(op["study"]), 0) + 1
    chains = {b: HyperChain(X, y, refit_every, steps)
              for b, (X, y) in obs.items()}
    count = {b: len(obs[b][1]) - told.get(b, 0) for b in obs}
    out: Dict[int, Dict] = {}
    stop = max(wanted, default=-1)
    for op in sorted(ops, key=lambda o: int(o["seq"])):
        seq, b = int(op["seq"]), int(op["study"])
        if seq > stop:
            break
        if op["op"] in ("tell", "observe") and b in count:
            count[b] += 1
        elif op["op"] == "ask" and gp_family(b):
            for r, ch in chains.items():
                ch.at_ask(count[r])
            if seq in wanted:
                out[seq] = dict(chains[b].state(), k=count[b])
    out["fits"] = sum(ch.fits for ch in chains.values())
    return out


def fit_gap(ask: Dict) -> float:
    """How far the service's hyperparameters at this ask fall short of the
    reference's in log marginal likelihood per observation, on the data
    and standardization of the reference's last fit (non-finite
    hyperparameters count as infinitely short)."""
    ref = ask["ref"]
    k = int(ref["n_fit"])
    prog = np.asarray(ask["theta"], np.float64)
    if k < 2:
        return 0.0
    if not np.all(np.isfinite(prog)):
        return math.inf
    D2 = sq_diffs(ask["X"][:k])
    z = (np.asarray(ask["y"][:k], np.float64) - ref["ym"]) / ref["ys"]
    mine, _ = nll_grad(ref["theta"], D2, z)
    try:
        theirs, _ = nll_grad(prog, D2, z)
    except np.linalg.LinAlgError:
        return math.inf
    return max(0.0, theirs - mine) if math.isfinite(theirs) else math.inf


class Posterior:
    """GP posterior over the candidates ``C`` from observations ``X``
    (standardized values ``z``), with the variance also conditioned on the
    in-flight rows ``P`` (GP-BUCB: hallucinated at the posterior mean, so
    the mean is unchanged).  Rows are raw encodings; ``ls`` scales them."""

    def __init__(self, X, z, P, C, ls, var, noise):
        ls = np.asarray(ls, np.float64)
        self.var, self.noise = float(var), float(noise)
        self.C = np.asarray(C, np.float64) / ls
        X = np.asarray(X, np.float64) / ls
        P = np.asarray(P, np.float64).reshape(-1, X.shape[1]) / ls
        self.A = np.vstack([X, P])
        k = len(X)
        diag = self.var + self.noise + jitter(self.var)
        K = matern52(self.A, self.A, self.var)
        K[np.diag_indices_from(K)] = diag
        self.L = np.linalg.cholesky(K)
        alpha = solve_triangular(
            self.L[:k, :k].T,
            solve_triangular(self.L[:k, :k], np.asarray(z, np.float64),
                             lower=True), lower=False)
        self.mu = np.empty(len(self.C))
        self.V = np.empty((len(self.A), len(self.C)))
        for s0 in range(0, len(self.C), _BLOCK):
            blk = slice(s0, s0 + _BLOCK)
            Kac = matern52(self.A, self.C[blk], self.var)
            self.mu[blk] = Kac[:k].T @ alpha
            self.V[:, blk] = solve_triangular(self.L, Kac, lower=True)
        self.sig2 = np.maximum(self.var + self.noise
                               - (self.V * self.V).sum(0), 1e-10)

    def condition_on(self, i: int) -> None:
        """Add candidate ``i`` to the conditioning set (variance only)."""
        c = self.C[i:i + 1]
        l_row = solve_triangular(self.L, matern52(self.A, c, self.var)[:, 0],
                                 lower=True)
        l_nn = math.sqrt(max(self.var + self.noise + jitter(self.var)
                             - float(l_row @ l_row), 1e-12))
        v = (matern52(c, self.C, self.var)[0] - l_row @ self.V) / l_nn
        n = len(self.L)
        L = np.zeros((n + 1, n + 1))
        L[:n, :n] = self.L
        L[n, :n], L[n, n] = l_row, l_nn
        self.L, self.A = L, np.vstack([self.A, c])
        self.V = np.vstack([self.V, v])
        self.sig2 = np.maximum(self.sig2 - v * v, 1e-10)

    def ucb(self, t: float, domain_size: float) -> np.ndarray:
        return self.mu + math.sqrt(beta(t, domain_size)) * np.sqrt(self.sig2)


def posterior(ask: Dict) -> Posterior:
    """The posterior an ask should have scored with: the reference's own
    hyperparameters and frozen standardization (``replay_fits``)."""
    ref = ask["ref"]
    ls, var, noise = hypers(ref["theta"])
    z = (np.asarray(ask["y"], np.float64) - ref["ym"]) / ref["ys"]
    return Posterior(ask["X"], z, ask["P"], ask["C"], ls, var, noise)


def gp_bucb_gaps(ask: Dict) -> List[float]:
    """The gap of each pick of a GP-BUCB batch: at each slot the pick's
    UCB against the best UCB among the candidates not yet picked, the
    variance conditioned on the observations, the pending rows and earlier
    picks."""
    post = posterior(ask)
    gaps, taken = [], []
    for j, p in enumerate(ask["picks"]):
        acq = post.ucb(ask["n_obs_eff"] + j, ask["domain_size"])
        acq[taken] = -np.inf
        best = float(acq.max())
        gaps.append((best - float(acq[p])) / (1.0 + abs(best)))
        taken.append(int(p))
        post.condition_on(int(p))
    return gaps


def cluster_gaps(ask: Dict) -> List[float]:
    """The gaps of a clustered batch: first, that of its best pick below
    the UCB's best (the cluster that holds the best candidate picks it),
    then that of each pick below the UCB's ``n_top``-th value.  A pick
    repeated within the batch counts as the whole UCB range."""
    post = posterior(ask)
    acq = post.ucb(ask["n_obs_eff"], ask["domain_size"])
    order = np.sort(acq)[::-1]
    best, thr = float(order[0]), float(order[int(ask["n_top"]) - 1])
    scale = 1.0 + abs(best)
    picks = [int(p) for p in ask["picks"]]
    if len(set(picks)) < len(picks):
        return [(best - float(order[-1])) / scale] * (len(picks) + 1)
    lead = (best - max(float(acq[p]) for p in picks)) / scale
    return [lead] + [max(0.0, thr - float(acq[p])) / scale for p in picks]


# ------------------------------------------------------------------- TPE
def _scott(n: int, d: int) -> float:
    return max(max(n, 1) ** (-1.0 / (d + 4)), 1e-2) * 0.5 + 1e-3


def tpe_scores(X, y, C, gamma: float, dtype=np.float64) -> np.ndarray:
    """log l(x) - log g(x) for every candidate: observations split at the
    ``gamma`` quantile of the signed values (best first), each split a
    product of 1-D Gaussian Parzen windows with the Scott bandwidth scaled
    per dimension by the split's spread.  ``dtype`` is the precision of
    the densities (bandwidths, kernels, sums and logs)."""
    X = np.asarray(X, np.float64)
    C = np.asarray(C, np.float64)
    y = np.asarray(y, np.float64)
    n, d = X.shape
    n_good = max(1, int(math.ceil(np.float32(gamma) * np.float32(n))))
    order = np.argsort(-y, kind="stable")
    good, bad = X[order[:n_good]], X[order[n_good:]]
    if not len(bad):
        bad = good

    def bw(pts):
        return _scott(len(pts), d) * np.clip(2.0 * pts.std(0), 0.1, 1.0)

    def logdens(pts, h):
        out = np.empty(len(C))
        a = (0.5 / (h * h)).astype(dtype)
        pts = pts.astype(dtype)
        for s0 in range(0, len(C), 256):
            cb = C[s0:s0 + 256].astype(dtype)
            e = np.exp(-(cb[:, None, :] - pts[None, :, :]) ** 2 * a)
            dens = e.sum(1) / dtype(len(pts)) + dtype(1e-12)
            out[s0:s0 + 256] = np.log(dens).sum(1)
        return out

    return logdens(good, bw(good)) - logdens(bad, bw(bad))


def tpe_control_picks(ask: Dict) -> List[int]:
    """The control for TPE: the top ``n`` candidates of the reference
    computed in bfloat16, the precision below the program's float32."""
    import ml_dtypes
    score = tpe_scores(ask["X"], ask["y"], ask["C"], ask["gamma"],
                       dtype=ml_dtypes.bfloat16)
    return np.argsort(-score, kind="stable")[:len(ask["picks"])].tolist()


def tpe_gaps(ask: Dict) -> List[float]:
    """The gap of each pick of a TPE batch below the reference's score of
    the same rank in its top ``n``."""
    score = tpe_scores(ask["X"], ask["y"], ask["C"], ask["gamma"])
    top = np.sort(score)[::-1]
    scale = 1.0 + abs(float(top[0]))
    picks = [int(p) for p in ask["picks"]]
    if len(set(picks)) < len(picks):
        return [(float(top[0]) - float(top[-1])) / scale] * len(picks)
    return [max(0.0, float(top[j]) - float(score[p])) / scale
            for j, p in enumerate(picks)]


GAPS = {"gp": gp_bucb_gaps, "cluster": cluster_gaps, "tpe": tpe_gaps}


def judge(asks: List[Dict]) -> Tuple[Dict[str, float], List[str]]:
    """The readings over the sampled asks: per family the widest pick gap
    (``<family>_pick_gap``) and the mean of all its gaps
    (``<family>_pick_gap_mean``); over the GP family (GP-BUCB and
    clustering) the widest fit gap (``fit_gap``) and the number of asks
    that saw another number of observations than the journal says, or
    whose study the service fit last at another count than the reference
    did (``fit_count_mismatch``); and one line per ask for the run's
    log.  Each GP-family ask carries ``ref`` from ``replay_fits``."""
    gaps: Dict[str, List[float]] = {}
    fits: List[float] = []
    mismatch = 0
    lines = []
    for a in asks:
        g = GAPS[a["family"]](a)
        gaps.setdefault(a["family"], []).extend(g)
        extra = ""
        if a["family"] != "tpe":
            fits.append(fit_gap(a))
            mismatch += (int(a["n_fit"]) != int(a["ref"]["n_fit"])
                         or len(a["X"]) != int(a["ref"]["k"]))
            extra = (f", last fit at {a['n_fit']} obs (reference "
                     f"{a['ref']['n_fit']}), fit gap {fits[-1]!r}")
        lines.append(f"{a['family']} ask seq {a['seq']} study {a['study']}: "
                     f"{len(a['X'])} obs, {len(a['P'])} pending, "
                     f"{len(a['C'])} candidates, gaps {g!r}{extra}")
    out: Dict[str, float] = {}
    for fam, g in gaps.items():
        out[f"{fam}_pick_gap"] = max(g)
        out[f"{fam}_pick_gap_mean"] = sum(g) / len(g)
    if fits:
        out["fit_gap"] = max(fits)
        out["fit_count_mismatch"] = mismatch
    return out, lines
