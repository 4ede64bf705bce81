"""Bench regression sentinel: the BENCH_r*/MULTICHIP_r* trajectory gate.

The repo's standing discipline puts every device-sensitive claim into a
captured record with an ``*_ok`` guard — but until ISSUE 9 nothing read
the records AS A SERIES: a capture could quietly regress a headline
number (or flip a guard that a previous round held green) and the only
defense was a reviewer's memory.  This tool is the missing comparator:

* loads every ``BENCH_r*.json`` (the ``parsed`` block) and every
  ``MULTICHIP_r*.json`` (the ``dryrun_multichip PARITY {...}`` JSON in
  the captured tail, when a round carries one — the same extraction
  tools/perf_report.py uses);
* builds the per-field trajectory and judges the NEWEST record:
  - any watched ms/throughput/quality field more than its tolerance
    (default 10%) WORSE than the best prior record -> regression;
  - any boolean ``*_ok`` / ``*parity*`` guard that is False in the
    newest record -> flagged (a ``guard_flip`` when the latest prior
    record carrying the field had it True, ``guard_false`` otherwise);
* exits non-zero when anything is flagged, so a driver capture can be
  gated on it (tools/ci_gate.py wires it next to the tier-1 budget
  guard), and renders the trend rows tools/perf_report.py turns into
  its report's "Trend" section.

Watched fields are a CURATED list, not a regex sweep: several recorded
ms fields are methodology-coupled (e.g. ``hist_ms_per_iter`` re-prices
the replayed schedule each round; the roofline fraction divides by a
same-session measured peak that itself drifts), and a sentinel that cries wolf
on those gets disabled within two rounds.  Each entry names its
direction and tolerance; quality fields get tight tolerances, clocked
fields get the 10% bar the acceptance criteria name.

Usage:

    python tools/bench_trend.py                 # repo records, exit 0/1
    python tools/bench_trend.py --dir /tmp/recs # any record directory
    python tools/bench_trend.py --json          # machine-readable report
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (field, direction, relative tolerance).  direction "up": bigger is
# better (throughput/quality); "down": smaller is better (clocks).
WATCHED: Tuple[Tuple[str, str, float], ...] = (
    ("value", "up", 0.10),
    ("vs_baseline", "up", 0.10),
    ("vs_ref_same_host", "up", 0.10),
    ("vs_ref_500iter", "up", 0.10),
    ("auc", "up", 0.005),
    ("tpu_500iter_auc", "up", 0.005),
    ("tpu_500iter_wall_s", "down", 0.10),
    ("hist_ms_per_pass", "down", 0.10),
    ("hist_ms_per_pass_deep", "down", 0.10),
    ("levelwise_M_row_trees_per_s", "up", 0.10),
    ("dart_M_row_trees_per_s", "up", 0.10),
    ("multiclass_M_row_trees_per_s", "up", 0.10),
    ("rank_M_row_trees_per_s", "up", 0.10),
    ("multiclass_logloss", "down", 0.02),
    ("rank_ndcg10", "up", 0.005),
    ("predict_M_rows_per_s", "up", 0.10),
    ("predict_device_compute_M_rows_per_s", "up", 0.10),
    # serving megakernel (ISSUE 19): the fused walk+accumulate rate and
    # the 4-bit packed serving-code transport — analytic ceil(F/2)
    # bytes/row, so ANY upward move means packing stopped engaging at
    # the bench twin; predict_fused_ok is the boolean guard beside them
    ("predict_fused_M_rows_per_s", "up", 0.10),
    ("predict_h2d_bytes_per_row_packed", "down", 0.10),
    ("serve_qps", "up", 0.10),
    ("serve_p99_ms", "down", 0.10),
    # multi-tenant serving (ISSUE 20): the shared-jit-cache hit rate —
    # ANY downward move means tenants stopped adopting each other's
    # executables — and the noisy-neighbor p99 tax on the cold tenant
    # under the hot-tenant overload probe (CPU-thread-scheduling noisy,
    # so the bar is loose); tenant_ok is the boolean guard beside them
    ("tenant_compile_share_frac", "up", 0.10),
    ("tenant_isolation_p99_delta_ms", "down", 0.50),
    ("stream_ms_per_iter", "down", 0.10),
    ("pipeline_ms_per_iter", "down", 0.10),
    ("obs_overhead_frac", "down", 0.10),
    # forensics & SLO (ISSUE 10): the availability SLI is a quality
    # field (tight bar); slo_ok / forensics_ok / obs_agg_ok /
    # chaos_forensics_ok are booleans — the guard sweep below flags any
    # False automatically
    ("slo_availability", "up", 0.005),
    # fault-tolerant fleet (ISSUE 11): the elastic re-bootstrap clock is
    # lease-timeout-dominated, so the bar is loose; fleet_ok /
    # chaos_fleet_ok / the *_ok sub-guards are booleans the guard sweep
    # flags automatically
    ("fleet_recovery_s", "down", 0.50),
    # device truth (ISSUE 12): compile time is noisy (cache state, load,
    # whole-process cumulative) — generous bar, watched so a retrace
    # storm or a compile-time explosion is still a flagged number; the
    # HBM footprint gets the standard 10% bar so the Pallas-megakernel
    # work of ROADMAP item 2 lands against a baseline
    ("compile_ms_total", "down", 0.50),
    ("hbm_peak_bytes", "down", 0.10),
    # sub-byte bin residency (ISSUE 18): the per-round packed binned
    # read in bytes — analytic ceil(F/2) * N, so ANY upward move means
    # the packed layout stopped engaging at the bench config; packed_ok
    # / packed_parity_ok are booleans the guard sweep flags
    # automatically
    ("packed_binned_bytes", "down", 0.10),
    # model-quality & drift (ISSUE 14): the skew-injection probe's
    # detection magnitude is deterministic (same shift, same shape) —
    # a capture where the injected PSI collapses means the detector
    # lost power.  drift_overhead_frac is deliberately NOT watched
    # (sub-noise-floor fraction; the drift_ok guard already enforces
    # the <= 2% contract), like the other methodology-coupled fields.
    ("drift_injected_psi", "up", 0.25),
    # pod-scale two-level collective (ISSUE 16): the DCN (slow inter-
    # host link) histogram wire bytes per round, flat-scalar mirror of
    # hier_comm_bytes_per_round["data"]["dcn"]["hist_bytes"], at the
    # standard 10% bar — a regression here means the slow link started
    # carrying more than the 1/C chip slice; hier_comm_ok is the
    # boolean guard the sweep flags automatically
    ("hier_dcn_hist_bytes", "down", 0.10),
)

_PARITY_RE = re.compile(r"dryrun_multichip PARITY (\{.*\})")


def _is_guard_field(name: str, value) -> bool:
    return isinstance(value, bool) and (name.endswith("_ok")
                                        or "parity" in name)


def load_bench_records(root: str) -> List[Tuple[str, Dict]]:
    """``[(name, parsed record)]`` sorted by round."""
    out = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        try:
            with open(path) as fh:
                rec = json.load(fh)
        except ValueError:
            continue
        parsed = rec.get("parsed", rec)
        if isinstance(parsed, dict) and parsed:
            out.append((os.path.basename(path), parsed))
    return out


def load_multichip_records(root: str) -> List[Tuple[str, Dict]]:
    """``[(name, PARITY record)]`` for captures whose tail carries one
    (older rounds were liveness-only and contribute nothing)."""
    out = []
    for path in sorted(glob.glob(os.path.join(root, "MULTICHIP_r*.json"))):
        try:
            with open(path) as fh:
                rec = json.load(fh)
        except ValueError:
            continue
        m = _PARITY_RE.search(rec.get("tail", "") or "")
        if not m:
            continue
        try:
            out.append((os.path.basename(path), json.loads(m.group(1))))
        except ValueError:
            continue
    return out


def _best_prior(records: List[Tuple[str, Dict]], field: str,
                direction: str) -> Optional[Tuple[str, float]]:
    best = None
    for name, rec in records[:-1]:
        v = rec.get(field)
        if not isinstance(v, (int, float)) or isinstance(v, bool):
            continue
        if best is None or (direction == "up" and v > best[1]) \
                or (direction == "down" and v < best[1]):
            best = (name, float(v))
    return best


def check_series(records: List[Tuple[str, Dict]],
                 watched=WATCHED) -> Tuple[List[Dict], List[Dict]]:
    """Judge the newest record of one series; returns
    ``(flags, trend_rows)``.  ``trend_rows`` carries every watched field
    present in the newest record (for the PERF.md "Trend" table);
    ``flags`` the regressions/guard failures."""
    flags: List[Dict] = []
    rows: List[Dict] = []
    if not records:
        return flags, rows
    newest_name, newest = records[-1]
    for field, direction, tol in watched:
        cur = newest.get(field)
        if not isinstance(cur, (int, float)) or isinstance(cur, bool):
            continue
        best = _best_prior(records, field, direction)
        row = {"field": field, "direction": direction, "tol": tol,
               "current": float(cur), "record": newest_name,
               "best_prior": best[1] if best else None,
               "best_prior_record": best[0] if best else None,
               "regressed": False}
        if best is not None and best[1] > 0:
            if direction == "up":
                regressed = cur < best[1] * (1.0 - tol)
            else:
                regressed = cur > best[1] * (1.0 + tol)
            if regressed:
                row["regressed"] = True
                flags.append({
                    "kind": "regression", "field": field,
                    "record": newest_name, "current": float(cur),
                    "best_prior": best[1], "best_prior_record": best[0],
                    "direction": direction, "tol": tol,
                })
        rows.append(row)
    # guard flips: every boolean *_ok / *parity* field of the newest
    # record that reads False fails the gate; "flip" when the latest
    # prior record carrying the field held it True
    for field, val in sorted(newest.items()):
        if not _is_guard_field(field, val) or val:
            continue
        prior = None
        for name, rec in reversed(records[:-1]):
            if field in rec and isinstance(rec[field], bool):
                prior = (name, rec[field])
                break
        flags.append({
            "kind": ("guard_flip" if prior and prior[1] else "guard_false"),
            "field": field, "record": newest_name,
            "prior_record": prior[0] if prior else None,
        })
    return flags, rows


def run(root: str = ROOT, watched=WATCHED) -> Dict:
    """The full sentinel pass over a record directory."""
    bench = load_bench_records(root)
    multichip = load_multichip_records(root)
    b_flags, b_rows = check_series(bench, watched)
    m_flags, m_rows = check_series(multichip, watched)
    return {
        "bench_records": [n for n, _ in bench],
        "multichip_records": [n for n, _ in multichip],
        "flags": b_flags + m_flags,
        "trend_rows": b_rows + m_rows,
        "ok": not (b_flags + m_flags),
    }


def render_report(result: Dict, out=print) -> None:
    names = result["bench_records"]
    out(f"bench_trend: {len(names)} BENCH record(s) "
        f"({names[0] if names else '—'} .. {names[-1] if names else '—'}), "
        f"{len(result['multichip_records'])} MULTICHIP PARITY record(s)")
    for row in result["trend_rows"]:
        if row["best_prior"] is None:
            note = "first capture of this field"
        else:
            arrow = {"up": ">=", "down": "<="}[row["direction"]]
            note = (f"best prior {row['best_prior']:g} "
                    f"({row['best_prior_record']}), bar: {arrow} "
                    f"{(1 - row['tol']) if row['direction'] == 'up' else (1 + row['tol']):g}x")
        mark = "REGRESSED" if row["regressed"] else "ok"
        out(f"  [{mark:>9}] {row['field']} = {row['current']:g} — {note}")
    for f in result["flags"]:
        if f["kind"] == "regression":
            out(f"  FLAG regression: {f['field']} {f['current']:g} vs best "
                f"prior {f['best_prior']:g} ({f['best_prior_record']}) "
                f"beyond {f['tol']:.0%}")
        else:
            out(f"  FLAG {f['kind']}: {f['field']} is False in "
                f"{f['record']}"
                + (f" (was True in {f['prior_record']})"
                   if f.get("prior_record") else ""))
    out(f"bench_trend: {'OK' if result['ok'] else 'REGRESSIONS FLAGGED'}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=ROOT,
                    help="directory holding BENCH_r*/MULTICHIP_r* records")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable report")
    args = ap.parse_args(argv)
    result = run(args.dir)
    if args.json:
        print(json.dumps(result, indent=1))
    else:
        render_report(result)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
