"""Benchmark driver: one module per paper table/figure.

``python -m benchmarks.run``          — quick CPU settings (CI-sized)
``python -m benchmarks.run --full``   — the paper-scale sweeps

Emits ``name,value,unit,detail`` CSV rows (captured into
bench_output.txt by the top-level runs) AND, per suite, a
machine-readable ``results/BENCH_<suite>.json`` with one record per row
(value + mean/p50 stats for timed rows, fused vs. unfused megastep
measurements included) — the perf trajectory tracked across PRs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

from benchmarks import (bench_ablation, bench_dist, bench_fixed_lstm,
                        bench_graph_construction, bench_memory,
                        bench_roofline, bench_serving, bench_tree_fc,
                        bench_tree_lstm, bench_var_lstm)
from benchmarks.common import add_stage_rows, emit_pipeline_stages
from repro.compile_cache import enable_compile_cache
from repro.obs import trace
from repro.obs.registry import fresh_registry

SUITES = [
    ("fixed_lstm (Fig 8a/e)", bench_fixed_lstm),
    ("var_lstm (Fig 8b/f)", bench_var_lstm),
    ("tree_fc (Fig 8c/g, Tab 1)", bench_tree_fc),
    ("tree_lstm (Fig 8d/h, Tab 1-2)", bench_tree_lstm),
    ("graph_construction (Fig 9)", bench_graph_construction),
    ("memory (Tab 2)", bench_memory),
    ("ablation (Fig 10)", bench_ablation),
    ("roofline (beyond-paper)", bench_roofline),
    ("serving (beyond-paper)", bench_serving),
    ("dist (beyond-paper)", bench_dist),
]


def _suite_slug(title: str) -> str:
    head = title.split()[0]
    return "".join(ch for ch in head if ch.isalnum() or ch == "_")


def _dump_json(title: str, col, out_dir: str, elapsed_s: float) -> None:
    records = getattr(col, "records", None)
    if not records:
        return
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{_suite_slug(title)}.json")
    with open(path, "w") as f:
        json.dump({"suite": title, "elapsed_s": round(elapsed_s, 2),
                   "rows": records}, f, indent=1)
    print(f"# wrote {path}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="substring filter on suite names")
    ap.add_argument("--out-dir", default="results",
                    help="directory for BENCH_<suite>.json records")
    args = ap.parse_args()
    enable_compile_cache()

    print("suite,name,value,unit,detail")
    failures = 0
    for title, mod in SUITES:
        if args.only and args.only not in title:
            continue
        print(f"# === {title} ===", flush=True)
        t0 = time.time()
        try:
            # Per-suite tracer + registry: any instrumented path the
            # suite exercises (pipeline, serving, kernels) feeds the
            # registry's span.* histograms; emit_pipeline_stages then
            # guarantees the core compose→pack→fwd→bwd stages exist
            # even for suites that bypass SchedulePipeline, and the
            # aggregate becomes stage/<name> rows in BENCH_<suite>.json.
            with fresh_registry() as reg, \
                    trace.install_tracer(trace.Tracer(registry=reg)):
                col = mod.main(["--full"] if args.full else [])
                emit_pipeline_stages()
                add_stage_rows(col, reg)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"# SUITE FAILED: {title}", flush=True)
            traceback.print_exc()
        else:
            _dump_json(title, col, args.out_dir, time.time() - t0)
        print(f"# --- {title} done in {time.time() - t0:.1f}s", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
