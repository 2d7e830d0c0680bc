"""Diff two ``BENCH_*.json`` perf-trajectory files row by row.

The CI ``bench`` job restores the previous push's JSON from the actions
cache, runs the quick grid, and pipes this tool's markdown table into
``$GITHUB_STEP_SUMMARY`` — a per-row regression view on every consecutive
push to a branch.

    python benchmarks/bench_delta.py OLD.json NEW.json \
        [--threshold 1.15] [--gate 'studies_per_sec_256:1.25' ...]

Rows are matched by ``name``.  Two de-noising mechanisms make the deltas
meaningful on shared CI runners:

  * the benchmark itself times paired paths with *interleaved* median-of-N
    reps (``multi_study._interleaved_medians``), so CPU-share throttling
    bursts hit both paths of a pair equally within one run;
  * rows with a same-run baseline partner (``studies_per_sec_*`` ->
    ``multi_study_loop_*``, ``autotune_ask_gp`` -> ``autotune_ask_random``)
    are compared as *ratios to that baseline* rather than absolute
    microseconds — a run
    that is globally 2x slower (noisy neighbor) moves numerator and
    denominator together and produces no false flag.  Such rows are marked
    ``rel`` in the table; rows without a partner fall back to the raw
    comparison.

A row is flagged as a regression when its (normalized) new/old ratio
exceeds ``--threshold`` (default +15%) and as an improvement below the
inverse.  Added/removed rows are listed, not flagged.

``--gate GLOB:RATIO`` (repeatable) promotes matching rows to *blocking*:
if any gated row regresses beyond its own ratio, the table is still
printed but the exit code is 2.  Rows serving as someone's normalization
denominator are exempt from gating (their comparison is raw microseconds
— the very noise the normalization cancels), so in practice the CI
``studies_per_sec_256:1.25`` gate blocks on the *bank-vs-loop ratio*
regressing >25%; a uniform slowdown of both arms stays advisory.
"""
from __future__ import annotations

import argparse
import fnmatch
import json
import sys

# derived row prefix -> same-run baseline row prefix (first match wins)
BASELINES = [
    ("studies_per_sec", "multi_study_loop"),
    ("autotune_ask_gp", "autotune_ask_random"),
]


def baseline_name(name):
    """The same-run row this row normalizes against, or None."""
    for derived, base in BASELINES:
        if name.startswith(derived):
            return base + name[len(derived):]
    return None


def load_rows(path):
    with open(path) as f:
        doc = json.load(f)
    return {r["name"]: float(r["us_per_call"]) for r in doc.get("rows", [])}


def _ratio(old, new, name):
    """(new/old ratio, normalized?) — relative to the same-run baseline
    row when both runs carry it."""
    base = baseline_name(name)
    if base and base in old and base in new and old[base] > 0 \
            and new[base] > 0:
        o = old[name] / old[base]
        n = new[name] / new[base]
        if o > 0:
            return n / o, True
    o, n = old[name], new[name]
    if o <= 0:
        # 0-valued counter rows (e.g. steady_state_retrace) are equal-or-
        # better when the new run is also 0 — not an infinite regression.
        return (1.0 if n <= 0 else float("inf")), False
    return n / o, False


def delta_table(old, new, threshold=1.15, gates=()):
    """(markdown lines, gated-regression row names)."""
    lines = ["| row | old (us) | new (us) | delta | |",
             "|---|---:|---:|---:|---|"]
    n_reg = 0
    gated = []
    # rows serving as someone's normalization denominator are never gated:
    # their comparison is raw microseconds, which is exactly the shared-
    # runner noise the normalization exists to cancel (they stay visible
    # with the advisory flag)
    denominators = {baseline_name(n) for n in new} - {None}
    for name in new:
        if name not in old:
            continue
        ratio, normalized = _ratio(old, new, name)
        flag = ""
        if ratio > threshold:
            flag = "REGRESSION"
            n_reg += 1
        elif ratio < 1.0 / threshold:
            flag = "improved"
        if name not in denominators:
            for pat, gate_ratio in gates:
                if fnmatch.fnmatch(name, pat) and ratio > gate_ratio:
                    flag = "REGRESSION (blocking)"
                    gated.append(name)
                    break
        rel = " rel" if normalized else ""
        lines.append(f"| `{name}` | {old[name]:.1f} | {new[name]:.1f} | "
                     f"{(ratio - 1.0) * 100:+.1f}%{rel} | {flag} |")
    added = sorted(set(new) - set(old))
    removed = sorted(set(old) - set(new))
    if added:
        lines.append("")
        lines.append("New rows: " + ", ".join(f"`{a}`" for a in added))
    if removed:
        lines.append("")
        lines.append("Removed rows: " + ", ".join(f"`{r}`"
                                                  for r in removed))
    header = (f"### Bench delta vs previous push — "
              f"{n_reg} row(s) over the +{(threshold - 1) * 100:.0f}% "
              f"threshold"
              + (f", {len(gated)} BLOCKING" if gated else ""))
    return [header, ""] + lines, gated


def parse_gate(spec):
    pat, _, ratio = spec.rpartition(":")
    if not pat:
        raise argparse.ArgumentTypeError(
            f"--gate wants GLOB:RATIO, got {spec!r}")
    return pat, float(ratio)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("old")
    ap.add_argument("new")
    ap.add_argument("--threshold", type=float, default=1.15,
                    help="advisory regression flag at (normalized) "
                         "new/old above this ratio")
    ap.add_argument("--gate", type=parse_gate, action="append", default=[],
                    metavar="GLOB:RATIO",
                    help="blocking gate: exit 2 if a row matching GLOB "
                         "regresses beyond RATIO (repeatable)")
    args = ap.parse_args()
    try:
        old = load_rows(args.old)
        new = load_rows(args.new)
    except (OSError, ValueError, KeyError) as e:
        print(f"bench_delta: unreadable input: {e}", file=sys.stderr)
        return 1
    lines, gated = delta_table(old, new, args.threshold, args.gate)
    print("\n".join(lines))
    if gated:
        print(f"bench_delta: blocking regression on {', '.join(gated)}",
              file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
