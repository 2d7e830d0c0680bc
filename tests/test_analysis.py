"""repro-lint engine: per-rule good/bad fixtures, noqa suppression,
baseline round-trip, CLI exit codes, and the repo-sweep-clean gate.

Deliberately jax/numpy-free: the engine is stdlib-only so the CI lint
job runs without installing the stack, and these tests keep it that way.
"""
import json
import textwrap
from pathlib import Path

import pytest

from repro.analysis import Baseline, lint_paths
from repro.analysis.__main__ import main as cli_main
from repro.analysis.rules import all_rules, rule_ids

REPO = Path(__file__).resolve().parents[1]


def _write(tmp_path: Path, rel: str, src: str) -> Path:
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    return p


def _findings(tmp_path, rel, src, rule_id=None):
    p = _write(tmp_path, rel, src)
    res = lint_paths([str(p)])
    if rule_id is None:
        return res.findings
    return [f for f in res.findings if f.rule == rule_id]


# --------------------------------------------------------------------------- #
# fixtures: for every rule, a firing bad case and a clean good case.
# paths mimic the real tree so rule *scoping* is exercised too.
# --------------------------------------------------------------------------- #
BAD_FIXTURES = {
    "REPRO-D001": ("core/tuner.py", """
        import time

        def deadline():
            return time.time() + 5.0
        """),
    "REPRO-D002": ("core/optimizer.py", """
        import numpy as np

        def propose():
            rng = np.random.default_rng()
            return np.random.uniform(0.0, 1.0)
        """),
    "REPRO-D003": ("service/server.py", """
        import time

        def apply_op(op):
            op["at"] = time.time()
            return op
        """),
    "REPRO-J101": ("core/gp.py", """
        import jax.numpy as jnp
        import numpy as np

        def score(c):
            v = jnp.exp(c)
            return np.asarray(v)
        """),
    "REPRO-J102": ("core/studybank.py", """
        import jax.numpy as jnp

        def per_study(xs):
            return [jnp.exp(x) for x in xs]
        """),
    "REPRO-J103": ("core/scoring.py", """
        import jax

        def make(scale):
            @jax.jit
            def inner(x):
                return x * scale
            return inner
        """),
    "REPRO-C201": ("scheduler/pool.py", """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0

            def bump(self):
                with self._lock:
                    self._n += 1

            def reset(self):
                self._n = 0
        """),
    "REPRO-C202": ("scheduler/workers.py", """
        import threading

        def start(fn):
            threading.Thread(target=fn).start()
        """),
    "REPRO-C203": ("scheduler/drops.py", """
        def run(fn):
            try:
                return fn()
            except Exception:
                pass
        """),
    "REPRO-W301": ("service/commit.py", """
        class Svc:
            def commit(self, op):
                return self.bank.apply_op(op)
        """),
    "REPRO-W302": ("service/snapshot.py", """
        import json

        def publish(path, obj):
            with open(path, "w") as fh:
                json.dump(obj, fh)
        """),
}

GOOD_FIXTURES = {
    "REPRO-D001": ("core/tuner.py", """
        import time

        def deadline():
            return time.monotonic() + 5.0
        """),
    "REPRO-D002": ("core/optimizer.py", """
        import numpy as np

        def propose(seed):
            rng = np.random.default_rng(seed)
            return rng.uniform(0.0, 1.0)
        """),
    "REPRO-D003": ("service/server.py", """
        import time

        def report():
            return time.monotonic()

        def apply_op(op):
            return dict(op)
        """),
    "REPRO-J101": ("core/gp.py", """
        import jax
        import jax.numpy as jnp

        def score(c):
            v = jnp.exp(c)
            return jax.device_get(v)
        """),
    "REPRO-J102": ("core/studybank.py", """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def traced(xs):
            return [jnp.exp(x) for x in xs]

        def tpe_kde_kernel(x_ref, o_ref):
            for j in range(4):
                o_ref[j] = jnp.exp(x_ref[j])
        """),
    "REPRO-J103": ("core/scoring.py", """
        import functools

        import jax

        def make(scale):
            @functools.partial(jax.jit, static_argnums=1)
            def inner(x, s):
                return x * s
            return lambda x: inner(x, scale)
        """),
    "REPRO-C201": ("scheduler/pool.py", """
        import threading

        from repro.analysis.sanitizers import assert_holds

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0

            def bump(self):
                with self._lock:
                    self._n += 1

            def reset(self):
                with self._lock:
                    self._n = 0

            def _reset_locked(self):
                assert_holds(self._lock)
                self._n = 0
        """),
    "REPRO-C202": ("scheduler/workers.py", """
        import threading

        def start(fn):
            threading.Thread(target=fn, daemon=True).start()
        """),
    "REPRO-C203": ("scheduler/drops.py", """
        import logging

        _log = logging.getLogger(__name__)

        def run(fn):
            try:
                return fn()
            except Exception as e:
                _log.debug("dropped: %r", e)
        """),
    "REPRO-W301": ("service/commit.py", """
        class Svc:
            def commit(self, op):
                self.wal.append(op)
                return self.bank.apply_op(op)
        """),
    "REPRO-W302": ("service/snapshot.py", """
        import json
        import os

        def publish(path, obj):
            tmp = path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(obj, fh)
                fh.flush()
                os.fsync(fh.fileno())
            os.replace(tmp, path)
        """),
}


@pytest.mark.parametrize("rule_id", sorted(BAD_FIXTURES))
def test_bad_fixture_fires(tmp_path, rule_id):
    rel, src = BAD_FIXTURES[rule_id]
    assert _findings(tmp_path, rel, src, rule_id), \
        f"{rule_id} bad fixture produced no finding"


@pytest.mark.parametrize("rule_id", sorted(GOOD_FIXTURES))
def test_good_fixture_is_clean(tmp_path, rule_id):
    rel, src = GOOD_FIXTURES[rule_id]
    found = _findings(tmp_path, rel, src, rule_id)
    assert not found, f"{rule_id} good fixture fired: {found}"


def test_every_registered_rule_has_a_firing_bad_fixture():
    """Meta-test: adding a rule without fixtures fails here, so the
    'every rule demonstrably fires' invariant survives new rules."""
    ids = set(rule_ids())
    assert ids == set(BAD_FIXTURES), \
        "every rule needs a BAD_FIXTURES entry (and vice versa)"
    assert ids == set(GOOD_FIXTURES)
    assert len(ids) >= 8


def test_rules_scope_to_their_directories(tmp_path):
    """The same offending source outside a rule's scope is not flagged."""
    _, src = BAD_FIXTURES["REPRO-D001"]
    assert not _findings(tmp_path, "viz/plots.py", src, "REPRO-D001")
    _, src = BAD_FIXTURES["REPRO-J101"]
    assert not _findings(tmp_path, "core/plots.py", src, "REPRO-J101")


# --------------------------------------------------------------------------- #
# noqa suppression
# --------------------------------------------------------------------------- #
def test_noqa_with_rule_id_suppresses(tmp_path):
    src = """
        import time

        def deadline():
            return time.time() + 5.0  # repro: noqa REPRO-D001
        """
    assert not _findings(tmp_path, "core/a.py", src, "REPRO-D001")


def test_bare_noqa_suppresses_everything_on_the_line(tmp_path):
    src = """
        import time

        def deadline():
            return time.time() + 5.0  # repro: noqa
        """
    assert not _findings(tmp_path, "core/a.py", src)


def test_noqa_for_other_rule_does_not_suppress(tmp_path):
    src = """
        import time

        def deadline():
            return time.time() + 5.0  # repro: noqa REPRO-J101
        """
    assert _findings(tmp_path, "core/a.py", src, "REPRO-D001")


# --------------------------------------------------------------------------- #
# baseline round-trip
# --------------------------------------------------------------------------- #
def test_baseline_roundtrip_add_suppress_stale(tmp_path):
    rel, src = BAD_FIXTURES["REPRO-D001"]
    p = _write(tmp_path, rel, src)
    res = lint_paths([str(p)])
    assert res.findings and not res.ok

    bl_path = tmp_path / "baseline.json"
    Baseline.from_findings(res.findings, note="known wall clock") \
        .save(str(bl_path))
    bl = Baseline.load(str(bl_path))
    res2 = lint_paths([str(p)], baseline=bl)
    assert res2.ok
    assert len(res2.baselined) == len(res.findings)
    assert not res2.stale

    # the match key is line *content*, so pure line-number churn
    # (a comment above) keeps the entry matching ...
    p.write_text("# moved\n" + p.read_text())
    res3 = lint_paths([str(p)], baseline=bl)
    assert res3.ok and not res3.stale

    # ... and removing the offending line makes the entry stale
    fixed = src.replace("time.time()", "time.monotonic()")
    p.write_text(textwrap.dedent(fixed))
    res4 = lint_paths([str(p)], baseline=bl)
    assert res4.ok
    assert len(res4.stale) == len(res.findings)


def test_unparsable_file_is_an_error(tmp_path):
    p = _write(tmp_path, "core/broken.py", "def f(:\n")
    res = lint_paths([str(p)])
    assert res.errors and not res.ok


# --------------------------------------------------------------------------- #
# CLI exit contract
# --------------------------------------------------------------------------- #
def test_cli_exit_codes(tmp_path, capsys):
    rel, src = BAD_FIXTURES["REPRO-D001"]
    bad = _write(tmp_path, rel, src)
    assert cli_main([str(bad)]) == 1
    out = capsys.readouterr().out
    assert "REPRO-D001" in out

    bl = tmp_path / "bl.json"
    assert cli_main([str(bad), "--write-baseline", str(bl)]) == 0
    assert cli_main([str(bad), "--baseline", str(bl)]) == 0
    assert cli_main([str(bad), "--baseline", str(tmp_path / "nope")]) == 2

    good = _write(tmp_path, "core/clean.py", "X = 1\n")
    assert cli_main([str(good)]) == 0
    assert cli_main(["--list-rules"]) == 0


def test_cli_json_format(tmp_path, capsys):
    rel, src = BAD_FIXTURES["REPRO-C203"]
    bad = _write(tmp_path, rel, src)
    assert cli_main([str(bad), "--format", "json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["unbaselined"]
    assert payload["unbaselined"][0]["rule"] == "REPRO-C203"


# --------------------------------------------------------------------------- #
# the repo itself stays clean (the CI lint gate, as a test)
# --------------------------------------------------------------------------- #
def test_repo_sweep_clean_under_committed_baseline():
    bl = Baseline.load(str(REPO / ".repro-lint-baseline"))
    res = lint_paths([str(REPO / "src")], baseline=bl)
    assert res.ok, [f.format() for f in res.unbaselined] + res.errors
    assert not res.stale, res.stale


def test_rule_metadata_complete():
    for rule in all_rules():
        assert rule.id.startswith("REPRO-")
        assert rule.family and rule.description and rule.rationale
        assert rule.scopes  # every current rule is repo-scoped
