"""Benchmark harness — one suite per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only SUITE] [--fast] \
      [--json PATH]

Prints ``name,us_per_call,derived`` CSV rows (scaffold contract).

``--json PATH`` additionally writes a machine-readable report: one entry
per suite with its rows and (when the suite attaches one) a
``MetricsRegistry`` snapshot — the live counters/gauges/latency
histograms behind the derived strings.  The file is MERGED on re-runs,
so ``make bench-smoke``'s per-suite invocations accumulate into a single
``BENCH_smoke.json`` artifact.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

SUITES = [
    ("attention_variants", "Table 1: GQA-8 vs MLA/Muon-Split/MLA-256"),
    ("mtp_accept", "Table 2: MTP accept length (shared vs single)"),
    ("dsa_longcontext", "Table 3/6 + Fig 6: DSA retrofit recipe"),
    ("attn_ablation", "Table 5: SWA/GDN/SimpleGDN ablation"),
    ("context_mgmt", "Figure 8: context management strategies"),
    ("rl_async", "S3.6/S4.1: async RL infra"),
    ("pd_disagg", "S3.6.2: PD disaggregation tail latency"),
    ("serving_throughput", "S3.6: continuous vs static batching tok/s"),
    ("prefix_cache", "S3.6: radix prefix cache on agentic workloads"),
    ("tiered_kv", "S3.6: host-RAM KV spill tier on a long-tail "
                  "multi-tenant trace"),
    ("paged_decode", "S3.6: in-place paged decode vs full-view gather"),
    ("paged_prefill", "S3.6: in-place paged prefill vs padded-view gather"),
    ("speculative_decode", "S2.1/S3.6: MTP spec decode through the engine"),
    ("async_frontend", "S3.6/S4.1: async front-end vs blocking serve "
                       "under weight pushes"),
    ("fault_tolerance", "S3.6.3: deadlines/cancel/shed/supervision under "
                        "an injected fault trace"),
    ("roofline_report", "SRoofline: dry-run derived terms"),
]

JSON_SCHEMA = "repro-bench-v1"


def _write_json(path: str, suites: dict, fast: bool) -> None:
    """Merge ``suites`` into the report at ``path`` (create if absent).

    Merging keeps the ``--only SUITE`` workflow cumulative: six separate
    invocations against one path build one report."""
    data = {"schema": JSON_SCHEMA, "suites": {}}
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = json.load(f)
            if isinstance(prev, dict) and prev.get("schema") == JSON_SCHEMA:
                data = prev
        except (OSError, ValueError):
            pass                       # corrupt/foreign file: start over
    data.setdefault("suites", {}).update(suites)
    data["fast"] = fast
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true",
                    help="fewer steps/episodes (CI mode)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write/merge a machine-readable report (rows + "
                         "per-suite registry snapshot) at PATH")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = []
    report = {}
    for mod_name, desc in SUITES:
        if args.only and args.only != mod_name:
            continue
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            kw = {}
            if args.fast:
                import inspect
                sig = inspect.signature(mod.run)
                if "steps" in sig.parameters:
                    kw["steps"] = 16
                if "episodes" in sig.parameters:
                    kw["episodes"] = 8
                if "fast" in sig.parameters:
                    kw["fast"] = True
            rows = mod.run(**kw)
            registry = None
            out_rows = []
            for r in rows:
                # a suite attaches its engine's registry snapshot to any
                # row; the report carries it per-suite (last one wins)
                registry = r.pop("registry", None) or registry
                derived = str(r["derived"]).replace(",", ";")
                print(f"{r['name']},{r['us_per_call']:.1f},{derived}")
                out_rows.append({"name": r["name"],
                                 "us_per_call": float(r["us_per_call"]),
                                 "derived": str(r["derived"])})
            sys.stdout.flush()
            report[mod_name] = {"description": desc, "rows": out_rows,
                                "registry": registry}
        except Exception as e:  # noqa: BLE001
            failures.append((mod_name, repr(e)))
            traceback.print_exc()
    if args.json and report:
        _write_json(args.json, report, args.fast)
    if failures:
        print(f"# {len(failures)} suite failures: {failures}",
              file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
