"""One CI gate: the bench regression sentinel + the tier-1 wall budget.

Two guards existed as separate tools with separate exit codes
(tools/bench_trend.py, tools/tier1_budget.py); driver/CI wiring wants
ONE entry with ONE exit code, so a capture or a suite run is gated by a
single command:

    python tools/ci_gate.py [--records DIR] [--t1-log PATH]
                            [--skip-trend] [--skip-t1]

* **trend** — ``bench_trend.run()`` over the record directory: the
  newest BENCH/MULTICHIP record must not regress a watched field >10%
  vs the best prior capture nor read False on any ``*_ok`` guard.
* **tier1** — ``tier1_budget`` over the per-test durations JSONL (or the
  tee'd pytest log): the projected tier-1 wall must fit 95% of the
  870 s driver budget.  A MISSING log fails the gate (a guard that
  silently skips is not a guard) unless ``--skip-t1`` says the caller
  genuinely has no suite run to judge (e.g. a records-only capture box).
* **required guards** — ``--require-guards obs_ok,slo_ok,forensics_ok``
  (ISSUE 10): the NEWEST BENCH record must CONTAIN each named guard and
  hold it True.  The trend sentinel only flags a guard that is present
  and False; this check additionally fails a capture that silently
  dropped the field (a guard that vanishes is a guard that failed).
  Off by default so records predating a guard still gate cleanly;
  driver captures after ISSUE 11 pass ``--require-guards`` with the
  full set in :data:`REQUIRED_GUARDS` (obs/slo/forensics/chaos plus the
  fleet guards ``fleet_ok`` and ``chaos_fleet_ok``) — or simply
  ``--require-guards default``, which expands to it.

Exit code 0 only when every enabled guard passes; each guard's own
report is printed so the failing one is obvious.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_trend  # noqa: E402
import tier1_budget  # noqa: E402

# the full post-ISSUE-14 driver guard set: ``--require-guards default``
# expands to this, so the driver command line stops rotting as guards
# are added (a new *_ok lands here in the same PR that records it);
# obs_device_ok is the device-truth telemetry guard (compile counters,
# serving zero-retrace, HBM/ledger reconciliation — bench.py
# measure_obs); drift_ok is the model-quality guard (skew-injection probe detected + zero clean
# false alarms + streamed-vs-resident reference byte parity + armed
# sampling within the <= 2% serving contract — bench.py measure_drift);
# hier_comm_ok is the pod-scale two-level collective guard (ISSUE 16:
# DCN histogram bytes <= flat reduce-scatter wire / num_hosts, and the
# voting learner's DCN payload <= its top-2k analytic bound —
# parallel/cluster.py hier_comm_table_per_round);
# predict_fused_ok is the serving-megakernel guard (ISSUE 19: fused
# walk+accumulate node/bit parity with the host oracle, zero retraces
# within a bucket, and on device >= 1.5x the scan walk's compute rate
# with cost_analysis bytes confirming the single-read contract —
# bench.py measure_predict); tenant_ok is the multi-tenant serving
# guard (ISSUE 20: cross-tenant compile-bucket sharing proven by
# per-label counters — the second tenant's warm adds zero compiles,
# zero retraces under mixed traffic — plus fair-share isolation under
# a 2x hot-tenant overload, per-tenant publish/rollback parity and the
# SLO-driven placement-move drill — bench.py measure_tenants)
REQUIRED_GUARDS = ("obs_ok", "slo_ok", "forensics_ok", "chaos_ok",
                   "fleet_ok", "chaos_fleet_ok", "obs_device_ok",
                   "drift_ok", "hier_comm_ok", "packed_ok",
                   "predict_fused_ok", "tenant_ok")


def check_required_guards(records_dir: str, guards, out=print) -> bool:
    """The newest BENCH record must carry every named guard as True —
    present-and-True, not merely not-False (a capture that dropped the
    field fails)."""
    records = bench_trend.load_bench_records(records_dir)
    if not records:
        out("ci_gate: --require-guards with NO bench records — FAIL")
        return False
    name, newest = records[-1]
    ok = True
    for g in guards:
        v = newest.get(g)
        if v is True:
            out(f"ci_gate: required guard {g} = True ({name})")
        else:
            out(f"ci_gate: required guard {g} "
                f"{'MISSING from' if g not in newest else f'= {v} in'} "
                f"{name} — FAIL")
            ok = False
    return ok


def run_gate(records_dir: str, t1_log: str, skip_trend: bool = False,
             skip_t1: bool = False, budget: float = None,
             frac: float = None, require_guards=(), out=print) -> dict:
    """Run the guards; returns ``{"trend_ok", "t1_ok", "guards_ok",
    "ok"}`` (skipped guards report True and are marked in the dict)."""
    results = {"trend_ok": True, "t1_ok": True, "guards_ok": True,
               "trend_skipped": bool(skip_trend),
               "t1_skipped": bool(skip_t1)}
    if not skip_trend:
        trend = bench_trend.run(records_dir)
        bench_trend.render_report(trend, out=out)
        results["trend_ok"] = bool(trend["ok"])
    else:
        out("ci_gate: trend guard SKIPPED")
    if require_guards:
        results["guards_ok"] = check_required_guards(
            records_dir, require_guards, out=out)
    if not skip_t1:
        if not os.path.exists(t1_log):
            out(f"ci_gate: tier-1 log {t1_log!r} not found — the budget "
                "guard cannot run, FAILING the gate (pass --skip-t1 for "
                "a records-only check)")
            results["t1_ok"] = False
        else:
            per_test, wall = tier1_budget.load(t1_log)
            kw = {}
            if budget is not None:
                kw["budget"] = budget
            if frac is not None:
                kw["frac"] = frac
            results["t1_ok"] = bool(
                tier1_budget.report(per_test, wall, out=out, **kw))
    else:
        out("ci_gate: tier-1 budget guard SKIPPED")
    results["ok"] = (results["trend_ok"] and results["t1_ok"]
                     and results["guards_ok"])
    out(f"ci_gate: {'PASS' if results['ok'] else 'FAIL'} "
        f"(trend_ok={results['trend_ok']}, t1_ok={results['t1_ok']}, "
        f"guards_ok={results['guards_ok']})")
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--records", default=bench_trend.ROOT,
                    help="BENCH_r*/MULTICHIP_r* record directory")
    ap.add_argument("--t1-log", default="/tmp/_t1.log",
                    help="tier-1 durations JSONL or tee'd pytest log")
    ap.add_argument("--skip-trend", action="store_true")
    ap.add_argument("--skip-t1", action="store_true")
    ap.add_argument("--budget", type=float, default=None)
    ap.add_argument("--frac", type=float, default=None)
    ap.add_argument("--require-guards", default="",
                    help="comma-separated guard fields the NEWEST bench "
                         "record must carry as True; 'default' expands "
                         "to " + ",".join(REQUIRED_GUARDS))
    args = ap.parse_args(argv)
    guards = tuple(g for g in args.require_guards.split(",") if g)
    if "default" in guards:
        guards = tuple(g for g in guards if g != "default") \
            + REQUIRED_GUARDS
    results = run_gate(args.records, args.t1_log,
                       skip_trend=args.skip_trend, skip_t1=args.skip_t1,
                       budget=args.budget, frac=args.frac,
                       require_guards=guards)
    return 0 if results["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
