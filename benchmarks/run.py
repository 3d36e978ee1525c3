"""Benchmark harness -- one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  overlap/*        fused vs unfused streaming exchanges, n_chunks sweep
                   (the paper's Fig. 3 chunk-size axis, as a runtime knob)
  fig45_strong/*   FFT strong scaling per strategy + reference (Figs. 4-5)
  fft_measure/*    measured planner vs alpha-beta model per backend
  pencil_sweep/*   slab vs pencil decomposition per grid shape
  real_sweep/*     c2c vs r2c (Hermitian payload) per backend per P
  serve_sweep/*    spectral serving: p50/p99 latency + transforms/sec vs
                   offered load, coalescing on vs off, warm plan pool
  moe_dispatch/*   paper technique on the LM stack (MoE a2a strategies)
  local_fft/*      local FFT impls (XLA vs MXU-matmul vs Pallas)

Run: PYTHONPATH=src python -m benchmarks.run
         [--only overlap,fig45,moe,kernel,fft,pencil,real,serve]
     [--json BENCH_fft.json] [--force] [--explain]

``--explain`` first prints each representative plan's stage schedule
(``Plan.describe()``: the declarative pipeline IR with per-stage model
microseconds and wire bytes) followed by its decision provenance
(``Plan.why_text()``: which channel picked the backend, over which
timing table, under which calibration constants); ``--explain --only
''`` prints only the schedules and times nothing.

``--json PATH`` additionally writes the fft_measure + pencil_sweep +
real_sweep + overlap rows (measured + model-predicted per backend / per
grid shape / per transform kind / per pipeline variant) as
machine-readable JSON -- the perf trajectory artifact CI uploads.
Sections that did not run in this invocation keep their rows from an
existing file at PATH (a partial run merges instead of clobbering the
committed baseline); a top-level ``meta`` section (e.g. the planner
accuracy score written by ``benchmarks/planner_score.py --write-meta``)
survives merges the same way. ``--force`` overwrites the file with only
this run's sections. ``fig3`` is accepted as a legacy alias for
``overlap``.

``--trace PATH`` records a Chrome-trace (chrome://tracing / Perfetto)
timeline of the harness: one span per benchmark section, plus -- for the
fft section -- per-stage spans of each subprocess's winning plan
(``Plan.profile`` timelines, one trace process row per device count).
"""

import argparse
import contextlib
import json
import os
import sys

BENCH_SCHEMA = 2


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="overlap,fig45,moe,kernel,fft,pencil,real,serve")
    ap.add_argument(
        "--json",
        default=None,
        metavar="PATH",
        help="write fft_measure rows (+ pencil_sweep/real_sweep rows when "
        "those sections are selected) as JSON, merging into an existing "
        "file; implies the fft section",
    )
    ap.add_argument(
        "--force",
        action="store_true",
        help="with --json: overwrite PATH instead of merging this run's "
        "sections into its existing rows",
    )
    ap.add_argument(
        "--history",
        default=None,
        metavar="PATH",
        help="with --json: the benchmark history ledger to append this "
        "run's snapshot to (default: BENCH_history.jsonl next to the "
        "--json file); see benchmarks/regress.py",
    )
    ap.add_argument(
        "--no-history",
        action="store_true",
        help="with --json: do not append a snapshot to the history ledger "
        "(CI's slow job appends AFTER re-scoring stamps fresh meta)",
    )
    ap.add_argument(
        "--trace",
        default=None,
        metavar="PATH",
        help="write a Chrome-trace JSON timeline of this run (section "
        "spans + per-stage plan profiles from the fft section); load at "
        "ui.perfetto.dev or chrome://tracing",
    )
    ap.add_argument(
        "--explain",
        action="store_true",
        help="before timing anything, print each representative plan's "
        "stage schedule (per-stage model microseconds + wire bytes); "
        "alone (with --only ''), just the schedules",
    )
    args = ap.parse_args()
    from repro.core.compat import use_compile_cache

    use_compile_cache()
    rec = None
    if args.trace:
        from repro.obs import TraceRecorder

        rec = TraceRecorder()
        rec.set_process_name(0, "benchmarks.run")
    if args.explain:
        from benchmarks import explain

        print(explain.run(), end="")
        sys.stdout.flush()
    wanted = set(args.only.split(","))
    print("name,us_per_call,derived")
    rows = []
    if "kernel" in wanted:
        from benchmarks import kernel_bench

        with _section(rec, "kernel"):
            rows += kernel_bench.run()
        _flush(rows)
    if "fig45" in wanted:
        from benchmarks import strong_scaling

        with _section(rec, "fig45"):
            rows += strong_scaling.run()
        _flush(rows)
    jrows = []
    if "overlap" in wanted or "fig3" in wanted:
        from benchmarks import chunk_scaling

        with _section(rec, "overlap"):
            orows = chunk_scaling.run_json()
        jrows += orows
        rows += chunk_scaling.to_csv(orows)
        _flush(rows)
    if "fft" in wanted or args.json:
        from benchmarks import fft_measure

        with _section(rec, "fft"):
            frows = fft_measure.run_json(trace=rec)
        jrows += frows
        rows += fft_measure.to_csv(frows)
        _flush(rows)
    if "pencil" in wanted:
        from benchmarks import pencil_sweep

        with _section(rec, "pencil"):
            prows = pencil_sweep.run_json()
        jrows += prows
        rows += pencil_sweep.to_csv(prows)
        _flush(rows)
    if "real" in wanted:
        from benchmarks import real_sweep

        with _section(rec, "real"):
            rrows = real_sweep.run_json()
        jrows += rrows
        rows += real_sweep.to_csv(rrows)
        _flush(rows)
    if "serve" in wanted:
        from benchmarks import serve_sweep

        with _section(rec, "serve"):
            srows = serve_sweep.run_json()
        jrows += srows
        rows += serve_sweep.to_csv(srows)
        _flush(rows)
    if args.json:
        merged, meta = _merge_json(args.json, jrows, force=args.force)
        meta = _stamp_meta(meta, merged)
        doc = {"schema": BENCH_SCHEMA, "meta": meta, "rows": merged}
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
        print(
            f"# wrote {len(merged)} rows to {args.json} "
            f"({len(jrows)} from this run)",
            file=sys.stderr,
        )
        if not args.no_history:
            from repro.obs import history as obs_history

            hpath = args.history or os.path.join(
                os.path.dirname(os.path.abspath(args.json)), "BENCH_history.jsonl"
            )
            snap = obs_history.snapshot_from_bench(doc)
            obs_history.append_snapshot(hpath, snap)
            print(
                f"# appended snapshot ({len(snap['metrics'])} metrics, "
                f"commit {snap['commit']}) -> {hpath}",
                file=sys.stderr,
            )
    if "moe" in wanted:
        from benchmarks import moe_dispatch

        with _section(rec, "moe"):
            rows += moe_dispatch.run()
        _flush(rows)
    if rec is not None:
        rec.write_chrome_trace(args.trace)
        n_ev = len(rec.to_chrome_trace()["traceEvents"])
        print(f"# wrote {n_ev} trace events to {args.trace}", file=sys.stderr)


def _section(rec, name: str):
    """Span context for one benchmark section (no-op when untraced)."""
    if rec is None:
        return contextlib.nullcontext()
    return rec.span(f"section:{name}", cat="section")


def _stamp_meta(meta: dict, rows, *, commit=None, now=None) -> dict:
    """Inject run provenance into the baseline's meta section: the git
    ``commit`` this tree is at, the rows' ``device_kind``, and an ISO
    UTC ``timestamp``. Injected at the harness level -- never read
    inside jitted code -- and re-stamped on every ``--json`` write, so a
    merge carries the freshest run's identity while older meta fields
    (planner scores etc.) survive untouched. ``commit``/``now`` are
    injectable for tests."""
    import datetime
    import subprocess

    if commit is None:
        try:
            out = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                capture_output=True,
                text=True,
                cwd=os.path.dirname(os.path.abspath(__file__)),
                timeout=10,
            )
            commit = out.stdout.strip() if out.returncode == 0 else ""
        except (OSError, subprocess.SubprocessError):
            commit = ""
    devs = sorted(
        {
            r["device_kind"]
            for r in rows
            if isinstance(r, dict) and isinstance(r.get("device_kind"), str)
        }
    )
    meta = dict(meta)
    meta["commit"] = commit or "unknown"
    meta["device_kind"] = "+".join(devs) if devs else meta.get("device_kind", "unknown")
    if now is None:
        now = datetime.datetime.now(datetime.timezone.utc).isoformat(timespec="seconds")
    meta["timestamp"] = now
    return meta


def _merge_json(path: str, new_rows, *, force: bool = False):
    """Merge this run's rows into an existing BENCH json: sections (the
    ``bench`` key) produced now replace their old rows; sections that did
    not run survive -- so a partial ``--only`` run cannot clobber the
    committed multi-section baseline. The file's top-level ``meta`` dict
    (planner-accuracy score etc.) is carried over untouched. ``force``
    skips the read. Returns ``(rows, meta)``."""
    if force or not os.path.exists(path):
        return list(new_rows), {}
    try:
        with open(path) as f:
            old = json.load(f)
        old_rows = old.get("rows", []) if isinstance(old, dict) else []
        meta = old.get("meta", {}) if isinstance(old, dict) else {}
        if not isinstance(meta, dict):
            meta = {}
    except (OSError, json.JSONDecodeError) as e:
        print(f"# --json: could not merge existing {path} ({e}); overwriting", file=sys.stderr)
        return list(new_rows), {}
    ran = {r.get("bench") for r in new_rows}
    kept = [r for r in old_rows if isinstance(r, dict) and r.get("bench") not in ran]
    return kept + list(new_rows), meta


_printed = 0


def _flush(rows):
    global _printed
    for r in rows[_printed:]:
        print(r)
        sys.stdout.flush()
    _printed = len(rows)


if __name__ == "__main__":
    main()
