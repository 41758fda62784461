# One function per paper table. Print ``name,us_per_call,derived`` CSV.
#
# ``--json`` additionally writes a machine-readable BENCH_<suite>.json
# snapshot per suite into the repo root (name/us_per_call/derived rows
# plus the jax version and backend that produced them) — the recorded
# perf trajectory ROADMAP item 5 asks for, committed alongside the code
# change that moved the numbers.
import argparse
import json
import os
import sys

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)


def _snapshot_meta() -> dict:
    """Provenance block for BENCH_*.json: which commit produced these
    numbers, when, and on what host — without it a committed snapshot is
    just a table of context-free floats."""
    import datetime
    import socket
    import subprocess
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=_ROOT, capture_output=True,
            text=True, timeout=10, check=True).stdout.strip()
    except Exception:  # noqa: BLE001 - not a checkout / no git binary
        sha = "unknown"
    return {"git_sha": sha,
            "timestamp_utc": datetime.datetime.now(
                datetime.timezone.utc).isoformat(timespec="seconds"),
            "hostname": socket.gethostname()}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="long versions (more epochs, bigger shapes)")
    ap.add_argument("--only", default="",
                    help="comma list: tables,fig2,kernels,attn,roofline,"
                         "serve,prefix,kvcache,spec")
    ap.add_argument("--json", action="store_true",
                    help="also write BENCH_<suite>.json per suite (repo "
                         "root) with rows + jax version + backend")
    args = ap.parse_args()
    quick = not args.full
    only = set(args.only.split(",")) if args.only else None

    from benchmarks import attn_bench, beanna_tables, fig2_training, \
        kernel_bench, kvcache_bench, roofline, serve_bench, spec_bench

    suites = [
        ("tables", beanna_tables.run),
        ("kernels", kernel_bench.run),
        ("attn", attn_bench.run),
        ("fig2", fig2_training.run),
        ("roofline", roofline.run),
        ("serve", serve_bench.run),
        ("prefix", serve_bench.run_prefix),
        ("kvcache", kvcache_bench.run),
        ("spec", spec_bench.run),
    ]
    meta = _snapshot_meta() if args.json else None
    failed = []
    print("name,us_per_call,derived")
    for name, fn in suites:
        if only and name not in only:
            continue
        kw = {}
        if name == "serve" and args.json:
            # the serve suite also dumps its measured run's request-
            # lifecycle trace: the Perfetto artifact CI uploads next to
            # BENCH_serve.json
            kw["trace_out"] = os.path.join(_ROOT, "BENCH_serve_trace.json")
        rows = []
        try:
            for row in fn(quick=quick, **kw):
                n, us, derived = row
                print(f"{n},{us:.2f},{derived}")
                rows.append({"name": n, "us_per_call": round(us, 2),
                             "derived": str(derived)})
        except Exception as e:  # noqa: BLE001 - report, run the rest, fail
            print(f"{name}/ERROR,0,{e!r}", file=sys.stdout)
            failed.append(name)
            rows = None
        if args.json and rows is not None:
            import jax
            snap = {"suite": name, "jax": jax.__version__,
                    "backend": jax.default_backend(), "meta": meta,
                    "rows": rows}
            path = os.path.join(_ROOT, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump(snap, f, indent=2)
                f.write("\n")
            print(f"# wrote {os.path.normpath(path)}", file=sys.stderr)
    sys.stdout.flush()
    if failed:
        sys.exit(f"suites raised: {','.join(failed)}")


if __name__ == '__main__':
    main()
