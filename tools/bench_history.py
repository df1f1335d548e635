#!/usr/bin/env python3
"""Bench-trajectory report: the BENCH_r01→rNN history as one table.

The repo pins one ``BENCH_rNN.json`` snapshot per bench round (the
driver envelope: ``{"n", "cmd", "rc", "tail", "parsed"}`` where
``tail`` carries the run's stdout — metrics-sidecar and bench-row JSON
lines included), but nothing read them ACROSS rounds: the performance
story lived in prose.  This tool parses every snapshot, extracts the
trajectory columns — raw sort throughput, end-to-end (ingest-included)
throughput and its ratio, the scale-out row's cap saving, the serve
row's SLO numbers — and renders a markdown table with one row per
round plus per-metric regression flags: a value below ``threshold``
(default 0.9) of the best earlier round is marked ``⚠ (0.83x)``.

Usage::

    python tools/bench_history.py [--dir .] [--threshold 0.9]
    make bench-history

Exit code 0 always — the trajectory is a report, not a gate (the
per-PR gates live in report.py ``--baseline`` and the selftests);
``--strict`` exits 2 when any flag fires, for CI jobs that want one.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

#: (column key, pretty header, unit, higher-is-better) — the trajectory
#: columns.  ``None`` cells render as ``-`` (a round predating the
#: metric is not a regression).
COLUMNS: tuple[tuple[str, str, str, bool], ...] = (
    ("sort_mkeys_per_s", "sort", "Mkeys/s", True),
    ("sort_incl_ingest_mkeys_per_s", "incl-ingest", "Mkeys/s", True),
    ("ingest_ratio", "ingest ratio", "x", True),
    ("encode_gb_per_s", "encode", "GB/s", True),
    ("cap_saving_pct", "cap saving", "%", True),
    ("serve_mkeys_per_s", "serve", "Mkeys/s", True),
    ("serve_p99_ms", "serve p99", "ms", False),
    # plan provenance (ISSUE 12): rows record the run's decision regret
    # beside its throughput, so the trajectory captures DECISIONS —
    # rising regret is a planner/negotiation regression even when the
    # throughput column still looks fine
    ("plan_regret", "plan regret", "x", False),
    # out-of-core external sort (ISSUE 15): spill+merge throughput
    # under a forced memory budget; pre-r06 rounds render "-"
    ("external_mkeys_per_s", "external", "Mkeys/s", True),
)

#: String-valued trajectory columns (ISSUE 13): rendered verbatim, no
#: regression math — the engine column exists so `exchange_engine=
#: {lax,pallas}` rows land comparable from r06 onward (a throughput
#: jump that coincides with an engine flip is attribution, not noise).
LABEL_COLUMNS: tuple[tuple[str, str], ...] = (
    ("exchange_engine", "engine"),
    # ISSUE 17: the local-sort engine the row measured under (lax /
    # bitonic family) — pinned on measured rows
    # via setdefault; pre-r06 rounds render "-".
    ("local_engine", "local"),
    # ISSUE 14: the planner mode the row measured under — pinned "off"
    # on measured rows via setdefault; pre-r06 rounds render "-".
    ("planner", "planner"),
    # ISSUE 16: the timeline fold's per-pass straggler factor
    # (max/median rank bytes, the 8dev row's value when present) —
    # rendered as a ratio string, no regression math, pre-r06 "-".
    ("straggler", "straggler"),
    # ISSUE 20: the external row's spill compression ratio (logical /
    # spilled bytes) and measured final-merge disk/compute overlap —
    # rendered verbatim (pre-r06 rounds, and rounds predating the
    # fields, render "-"; no regression math).
    ("spill_ratio", "spill ratio"),
    ("disk_overlap", "disk ov"),
)

_RUN_RE = re.compile(r"BENCH_r(\d+)\.json$")

#: Absolute floor for LOWER-is-better columns when comparing against
#: the best earlier round: a best of 0 (common for plan_regret — every
#: prediction matched) would otherwise make ANY later nonzero value an
#: infinite-ratio regression, failing the strict CI gate on meaningless
#: near-zero jitter.  Values must exceed best-or-floor / threshold to
#: flag.
LOWER_BEST_FLOOR = 0.25


def _json_lines(text: str) -> list[dict]:
    out = []
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(obj, dict):
            out.append(obj)
    return out


def load_run(path: Path) -> dict[str, object]:
    """Extract the trajectory metrics from one BENCH_rNN.json envelope.
    Both record shapes in the tail are folded: metrics sidecars
    (``{"config", "metrics": {name: {"value": ...}}}``) and bench rows
    (``{"metric", "value", ...}`` — including the ``_8dev`` scale-out
    and serve rows with their extra fields).  Numeric trajectory values
    keyed by metric name, plus a ``"_labels"`` dict of string columns
    (the ISSUE 13 engine column)."""
    env = json.loads(path.read_text())
    vals: dict[str, float] = {}
    labels: dict[str, str] = {}

    def put(name: str, v: object) -> None:
        try:
            vals[name] = float(v)  # type: ignore[arg-type]
        except (TypeError, ValueError):
            pass

    for obj in _json_lines(str(env.get("tail", ""))):
        if "metrics" in obj and "config" in obj:
            for mname, m in obj["metrics"].items():
                if isinstance(m, dict) and "value" in m:
                    put(mname, m["value"])
        elif "metric" in obj and "value" in obj:
            name = str(obj["metric"])
            if name.startswith("serve_"):
                put("serve_mkeys_per_s", obj["value"])
                put("serve_p99_ms", obj.get("p99_ms"))
            elif name.endswith("_8dev"):
                put("cap_saving_pct", obj.get("cap_saving_pct"))
                put("plan_regret", obj.get("plan_regret"))
                # ISSUE 16: the scale-out row is the one with a real
                # exchange, so its straggler wins over the primary's
                sf = obj.get("straggler_factor")
                if isinstance(sf, (int, float)):
                    labels["straggler"] = f"{sf:g}x"
            elif name.startswith("external_sort_"):
                # ISSUE 15: the out-of-core row — never folded into
                # the in-memory sort column
                put("external_mkeys_per_s", obj["value"])
                # ISSUE 20: compression + IO-overlap labels (rows
                # predating the fields render "-")
                sr = obj.get("spill_ratio")
                if isinstance(sr, (int, float)):
                    labels["spill_ratio"] = f"{sr:g}x"
                do = obj.get("disk_overlap")
                if isinstance(do, (int, float)):
                    labels["disk_overlap"] = f"{100 * do:.0f}%"
            else:
                put("sort_row_mkeys_per_s", obj["value"])
                if "plan_regret" not in vals:
                    put("plan_regret", obj.get("plan_regret"))
                # ISSUE 13: the primary row's exchange engine (pre-r06
                # rounds predate the field and render "-")
                if isinstance(obj.get("exchange_engine"), str):
                    labels["exchange_engine"] = obj["exchange_engine"]
                # ISSUE 14: ditto the planner column
                if isinstance(obj.get("planner"), str):
                    labels["planner"] = obj["planner"]
                # ISSUE 17: ditto the local-sort engine column
                if isinstance(obj.get("local_engine"), str):
                    labels["local_engine"] = obj["local_engine"]
                # ISSUE 16: primary-row straggler only when no 8dev
                # row carried one (single-device runs usually don't)
                sf = obj.get("straggler_factor")
                if isinstance(sf, (int, float)):
                    labels.setdefault("straggler", f"{sf:g}x")
    vals["_labels"] = labels  # type: ignore[assignment]
    # derived: end-to-end ratio when a round recorded both throughputs
    # but not the ratio itself (pre-ISSUE-6 rounds)
    if "ingest_ratio" not in vals and \
            vals.get("sort_mkeys_per_s") and \
            vals.get("sort_incl_ingest_mkeys_per_s"):
        vals["ingest_ratio"] = round(
            vals["sort_incl_ingest_mkeys_per_s"] / vals["sort_mkeys_per_s"],
            3)
    # the sidecar's sort_mkeys_per_s and the bench row agree by
    # construction; fall back to the row when only it parsed
    if "sort_mkeys_per_s" not in vals and "sort_row_mkeys_per_s" in vals:
        vals["sort_mkeys_per_s"] = vals["sort_row_mkeys_per_s"]
    return vals


def find_runs(directory: Path) -> list[tuple[int, Path]]:
    runs = []
    for p in sorted(directory.glob("BENCH_r*.json")):
        m = _RUN_RE.search(p.name)
        if m:
            runs.append((int(m.group(1)), p))
    return sorted(runs)


def build_table(runs: list[tuple[int, Path]],
                threshold: float = 0.9) -> tuple[str, list[str]]:
    """(markdown table, regression flag descriptions).  A cell is
    flagged when it is worse than ``threshold`` x the best earlier
    round (direction per column); earlier-missing metrics never flag."""
    rows = [(rid, load_run(p)) for rid, p in runs]
    flags: list[str] = []
    header = "| run | " + " | ".join(
        f"{title} ({unit})" for _k, title, unit, _hib in COLUMNS)
    header += " | " + " | ".join(t for _k, t in LABEL_COLUMNS) + " |"
    sep = "|---" * (len(COLUMNS) + len(LABEL_COLUMNS) + 1) + "|"
    lines = [header, sep]
    best: dict[str, float] = {}
    for rid, vals in rows:
        labels = vals.get("_labels") or {}
        cells = [f"r{rid:02d}"]
        for key, title, _unit, hib in COLUMNS:
            v = vals.get(key)
            if not isinstance(v, (int, float)):
                cells.append("-")
                continue
            cell = f"{v:g}"
            prev = best.get(key)
            if prev is not None:
                floor = max(prev, LOWER_BEST_FLOOR)
                regressed = (v < threshold * prev) if hib else \
                    (v > floor / threshold)
                if regressed:
                    ratio = (v / prev) if hib else (floor / v)
                    cell += f" ⚠ ({ratio:.2f}x)"
                    flags.append(
                        f"r{rid:02d} {title}: {v:g} vs best {prev:g} "
                        f"({ratio:.2f}x, threshold {threshold:g})")
            best[key] = max(prev, v) if (prev is not None and hib) else \
                min(prev, v) if prev is not None else v
            cells.append(cell)
        for key, _title in LABEL_COLUMNS:
            lv = labels.get(key) if isinstance(labels, dict) else None
            cells.append(lv if isinstance(lv, str) else "-")
        lines.append("| " + " | ".join(cells) + " |")
    return "\n".join(lines), flags


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=".",
                    help="directory holding BENCH_rNN.json (default .)")
    ap.add_argument("--threshold", type=float, default=0.9,
                    help="flag when worse than THRESHOLD x the best "
                         "earlier round (default 0.9)")
    ap.add_argument("--strict", action="store_true",
                    help="exit 2 when any regression flag fires")
    args = ap.parse_args(argv)
    runs = find_runs(Path(args.dir))
    if not runs:
        print(f"[ERROR] no BENCH_rNN.json under {args.dir!r}",
              file=sys.stderr)
        return 1
    table, flags = build_table(runs, args.threshold)
    print(f"bench trajectory ({len(runs)} round(s), regression "
          f"threshold {args.threshold:g}):\n")
    print(table)
    if flags:
        print("\nregression flags:")
        for f in flags:
            print(f"  ⚠ {f}")
    else:
        print("\nno regression flags")
    return 2 if (flags and args.strict) else 0


if __name__ == "__main__":
    sys.exit(main())
