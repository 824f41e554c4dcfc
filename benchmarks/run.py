# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows, then the §Roofline aggregation from the dry-run artifacts.
#
#   --json PATH   also emit a machine-readable BENCH_executor.json-style
#                 trajectory (name, us_per_call, derived, arena_bytes,
#                 dtypes) so future PRs have a perf baseline to diff
#                 against (see benchmarks/compare.py for the CI gate)
#   --only a,b    run only the named benchmarks (e.g. figure1,executor)
#   --smoke       small-graph subset inside each benchmark (CI)
#   --update-baseline [PATH]
#                 envelope-merge this run into the committed baseline
#                 (default benchmarks/BENCH_baseline.json) instead of
#                 hand-editing it: us_per_call takes the max of old and
#                 new (first-call timings vary run to run — the baseline
#                 is an envelope), arena_bytes are exact and may only
#                 shrink; growth aborts the merge unless
#                 --allow-bytes-growth is passed (a deliberate memory
#                 regression must be visible in the diff, not slipped in)
#
# Benchmarks call ``report(name, us_per_call, derived, **meta)``; the
# recognised meta keys are ``arena_bytes`` (peak/arena BYTES — the unit is
# part of the trajectory contract since the byte-granular dtype refactor),
# ``dtypes`` ("float32" / "int8" / "mixed"), ``pareto`` (the joint
# solver's memory/latency front as sorted [extra_macs, peak_bytes] pairs
# — gated point-by-point by compare.py), and ``nodes`` (solver search
# nodes, informational).  ``--pareto-json PATH`` additionally collects
# every reported front into one artifact for the CI upload / README link.
import argparse
import json
import os
import sys
import traceback

DEFAULT_BASELINE = os.path.join(os.path.dirname(__file__),
                                "BENCH_baseline.json")


def merge_baseline(baseline: dict, fresh_rows: list,
                   allow_bytes_growth: bool = False) -> list:
    """Envelope-merge ``fresh_rows`` into ``baseline`` in place: max-us,
    exact bytes (growth refused), new rows appended, rows not re-run kept.
    Returns a list of human-readable change notes; raises ``SystemExit``
    on a bytes regression without ``allow_bytes_growth``."""
    by_name = {r["name"]: r for r in baseline["rows"]}
    notes = []
    for row in fresh_rows:
        old = by_name.get(row["name"])
        if old is None:
            by_name[row["name"]] = dict(row)
            baseline["rows"].append(by_name[row["name"]])
            notes.append(f"new row {row['name']}")
            continue
        of, nf = old.get("pareto"), row.get("pareto")
        if of:
            if not nf:
                # same reasoning as the arena_bytes guard below: a merge
                # must not silently disarm the compare.py Pareto gate
                raise SystemExit(
                    f"refusing to merge: {row['name']} lost its pareto "
                    f"front (baseline has {len(of)} points); fix the "
                    f"benchmark row before refreshing the baseline")
            from .compare import front_covers
            uncovered = front_covers(of, nf)
            if uncovered and not allow_bytes_growth:
                raise SystemExit(
                    f"refusing to loosen baseline: {row['name']} pareto "
                    f"points {uncovered} no longer matched or dominated; "
                    f"pass --allow-bytes-growth if this regression is "
                    f"deliberate")
            if [list(p) for p in of] != [list(p) for p in nf]:
                notes.append(f"{row['name']}: pareto front "
                             f"{len(of)} -> {len(nf)} points")
        ob, nb = old.get("arena_bytes"), row.get("arena_bytes")
        if ob is not None and nb is None:
            # a fresh row without bytes (e.g. the -1 budget-exhausted
            # sentinel) must not wipe the committed exact figure — that
            # would silently disarm the compare.py growth gate for it
            raise SystemExit(
                f"refusing to merge: {row['name']} lost its arena_bytes "
                f"(baseline has {ob}); fix the benchmark row before "
                f"refreshing the baseline")
        if ob is not None and nb is not None and nb > ob:
            if not allow_bytes_growth:
                raise SystemExit(
                    f"refusing to loosen baseline: {row['name']} "
                    f"arena_bytes grew {ob} -> {nb} (+{nb - ob} B); "
                    f"pass --allow-bytes-growth if this regression is "
                    f"deliberate")
            notes.append(f"{row['name']}: bytes grew {ob} -> {nb} "
                         f"(--allow-bytes-growth)")
        elif ob != nb:
            notes.append(f"{row['name']}: bytes {ob} -> {nb}")
        orps, nrps = old.get("requests_per_s"), row.get("requests_per_s")
        if orps is not None and nrps is None:
            # same reasoning as arena_bytes: a merge must not silently
            # disarm the compare.py requests/s floor gate
            raise SystemExit(
                f"refusing to merge: {row['name']} lost its requests_per_s "
                f"(baseline has {orps}); fix the benchmark row before "
                f"refreshing the baseline")
        ou, nu = old.get("us_per_call"), row.get("us_per_call")
        if ou is not None and nu is not None and nu > ou:
            notes.append(f"{row['name']}: us envelope {ou:.0f} -> {nu:.0f}")
        old.update({k: v for k, v in row.items() if k != "us_per_call"})
        old["us_per_call"] = (max(ou, nu) if ou is not None
                              and nu is not None else nu or ou)
        if orps is not None and nrps is not None:
            # floor envelope: the committed figure is the weakest observed
            # run, so the CI floor gate holds on any reference-class host
            if nrps < orps:
                notes.append(f"{row['name']}: requests/s floor "
                             f"{orps:.1f} -> {nrps:.1f}")
            old["requests_per_s"] = min(orps, nrps)
    return notes


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write rows as JSON to PATH as well as CSV stdout")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names to run "
                         "(figure1,table1,scheduler,jaxpr,pex,executor,"
                         "kernels,roofline,serving)")
    ap.add_argument("--smoke", action="store_true",
                    help="restrict benchmarks to their small-graph subsets")
    ap.add_argument("--pareto-json", metavar="PATH", default=None,
                    help="collect every reported Pareto front (joint "
                         "solver memory/latency trade-offs) into one "
                         "JSON artifact at PATH")
    ap.add_argument("--update-baseline", metavar="PATH", nargs="?",
                    const=DEFAULT_BASELINE, default=None,
                    help="envelope-merge this run into the committed "
                         "baseline (max-us, exact bytes; see header)")
    ap.add_argument("--allow-bytes-growth", action="store_true",
                    help="permit --update-baseline to record larger "
                         "arena_bytes (deliberate memory regression)")
    args = ap.parse_args(argv)
    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    from repro.compile_cache import enable_compile_cache
    # children (bench_serving's host mesh) find the same cache through
    # the variable JAX reads itself
    os.environ["JAX_COMPILATION_CACHE_DIR"] = enable_compile_cache()

    from . import (bench_figure1, bench_table1, bench_scheduler,
                   bench_jaxpr, bench_kernels, bench_pex, bench_roofline,
                   bench_executor, bench_serving)

    by_name = {
        "figure1": bench_figure1,
        "table1": bench_table1,
        "scheduler": bench_scheduler,
        "jaxpr": bench_jaxpr,
        "pex": bench_pex,
        "executor": bench_executor,
        "kernels": bench_kernels,
        "roofline": bench_roofline,
        "serving": bench_serving,
    }
    if args.only:
        unknown = [n for n in args.only.split(",") if n not in by_name]
        if unknown:
            ap.error(f"unknown benchmarks {unknown}; "
                     f"choose from {sorted(by_name)}")
        mods = [by_name[n] for n in args.only.split(",")]
    else:
        mods = list(by_name.values())

    rows = []

    def report(name, us_per_call, derived, **meta):
        rows.append((name, us_per_call, derived, meta))
        print(f"{name},{us_per_call:.1f},{derived}")

    failed = []
    for mod in mods:
        print(f"# --- {mod.__name__} ---", flush=True)
        try:
            mod.run(report)
        except Exception:
            traceback.print_exc()
            failed.append(mod.__name__)

    json_rows = []
    for name, us, derived, meta in rows:
        jr = {
            "name": name,
            "us_per_call": us,
            "derived": derived if isinstance(derived, (int, float, str,
                                                       bool)) else
            repr(derived),
            # fallback: an int `derived` is a byte figure on legacy
            # rows — but only when non-negative (benchmarks use -1 as
            # a "budget exhausted" sentinel, which must not enter the
            # strict bytes gate)
            "arena_bytes": meta.get(
                "arena_bytes",
                derived if isinstance(derived, int)
                and not isinstance(derived, bool)
                and derived >= 0 else None),
            "dtypes": meta.get("dtypes"),
        }
        # solver metadata, only on rows that carry it (keeps the committed
        # baseline free of null noise)
        if meta.get("pareto") is not None:
            jr["pareto"] = [list(p) for p in meta["pareto"]]
        if meta.get("nodes") is not None:
            jr["nodes"] = meta["nodes"]
        # serving throughput metadata: requests_per_s enters the
        # compare.py floor gate; the latency percentiles ride along.
        # tile_rows/tile_cols tag 2-D tiled-cascade rows with the steady
        # working-tile shape (rows per chunk x columns per W-strip)
        # expired/shed are the chaos-gate counters: compare.py requires
        # them to be exactly zero on no-fault serving rows
        for k in ("requests_per_s", "p50_ms", "p99_ms", "replicas",
                  "tile_rows", "tile_cols", "expired", "shed"):
            if meta.get(k) is not None:
                jr[k] = meta[k]
        json_rows.append(jr)

    if args.json:
        payload = {
            "rows": json_rows,
            "failed": failed,
            "smoke": args.smoke,
            "units": {"us_per_call": "microseconds",
                      "arena_bytes": "bytes"},
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {len(rows)} rows to {args.json}")

    if args.pareto_json:
        fronts = {r["name"]: r["pareto"] for r in json_rows
                  if r.get("pareto")}
        with open(args.pareto_json, "w") as f:
            json.dump({"fronts": fronts,
                       "units": {"point": "[extra_macs, peak_bytes]"},
                       "smoke": args.smoke}, f, indent=2)
            f.write("\n")
        print(f"# wrote {len(fronts)} Pareto fronts to {args.pareto_json}")

    if args.update_baseline:
        if failed:
            print(f"# NOT updating baseline: failed benchmarks {failed}")
        else:
            try:
                with open(args.update_baseline) as f:
                    baseline = json.load(f)
            except FileNotFoundError:
                baseline = {"rows": [],
                            "units": {"us_per_call": "microseconds",
                                      "arena_bytes": "bytes"}}
            notes = merge_baseline(baseline, json_rows,
                                   args.allow_bytes_growth)
            baseline["rows"].sort(key=lambda r: r["name"])
            baseline["note"] = ("envelope baseline: us_per_call is the max "
                                "over merged runs on the reference machine; "
                                "arena_bytes are exact (refreshed via "
                                "run.py --update-baseline)")
            with open(args.update_baseline, "w") as f:
                json.dump(baseline, f, indent=2)
                f.write("\n")
            for n in notes:
                print(f"# baseline: {n}")
            print(f"# merged {len(json_rows)} rows into "
                  f"{args.update_baseline}")

    if failed:
        print(f"# FAILED: {failed}")
        sys.exit(1)
    print(f"# {len(rows)} benchmark rows OK")


if __name__ == "__main__":
    main()
