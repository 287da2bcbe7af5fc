"""Benchmark harness: one module per paper table/figure (+ beyond-paper).

Discovers every ``benchmarks/*.py`` module exposing a ``run(report)``
callable (no hand-maintained registry — a new benchmark file is picked up
automatically), prints ``name,us_per_call,derived`` CSV rows while running,
and finishes with one summary table of every ``BENCH_*.json`` artifact in
the working directory so the whole perf trajectory is visible in one
place.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run ccmlb      # filter by substring
  PYTHONPATH=src python -m benchmarks.run --summary  # just the table
  PYTHONPATH=src python -m benchmarks.run --summary --records  # + records
"""
from __future__ import annotations

import glob
import importlib
import json
import pkgutil
import sys
import traceback

import benchmarks

# preferred display names (and run order) for the paper-figure modules;
# discovered modules not listed here run afterwards in alphabetical order
DISPLAY = {
    "milp_vs_ccmlb": "fig4a_milp_vs_ccmlb",
    "delta_sweep": "fig4b_delta_sweep",
    "assembly_scaling": "fig5_assembly_scaling",
    "costmodel_eval": "costmodel",
}
ORDER = ["milp_vs_ccmlb", "delta_sweep", "assembly_scaling", "costmodel_eval",
         "ccmlb_scaling", "ccmlb_spec", "ccmlb_fleet", "ccmlb_pipeline",
         "ccmlb_async", "ccmlb_fault", "ccmlb_memory", "ccmlb_quiesce",
         "scorer_paths",
         "expert_placement"]


def discover():
    """``(modules, failed)``: (display_name, module) for every benchmarks
    submodule with run(), and the names of those that failed to import."""
    names = [m.name for m in pkgutil.iter_modules(benchmarks.__path__)
             if m.name != "run"]
    names.sort(key=lambda n: (ORDER.index(n) if n in ORDER else len(ORDER), n))
    out, failed = [], []
    for name in names:
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
        except Exception:
            traceback.print_exc()
            failed.append(DISPLAY.get(name, name))
            continue
        if callable(getattr(mod, "run", None)):
            out.append((DISPLAY.get(name, name), mod))
    return out, failed


def _fmt(v) -> str:
    if isinstance(v, bool):
        return str(v)
    if isinstance(v, float):
        return f"{v:.3f}"
    return str(v)


def _records_table(records, out):
    """Render a list of per-config record dicts as one aligned table.

    Different configs legitimately carry different fields (a spec record
    has window/rollback counters a scalar record doesn't; the fanout sweep
    has no backend column), so the columns are the UNION of keys in
    first-seen order and a record missing a field shows ``-`` instead of
    raising.  List/dict-valued fields are skipped — they don't fit a cell.
    """
    cols = []
    for rec in records:
        if not isinstance(rec, dict):
            return
        for k, v in rec.items():
            if k not in cols and not isinstance(v, (list, dict)):
                cols.append(k)
    if not cols:
        return
    table = [cols] + [[_fmt(rec[k]) if k in rec
                       and not isinstance(rec[k], (list, dict)) else "-"
                       for k in cols] for rec in records]
    widths = [max(len(row[i]) for row in table) for i in range(len(cols))]
    for row in table:
        out("    " + "  ".join(c.ljust(w) for c, w in zip(row, widths))
            .rstrip())


def summarize_bench_json(out=print, records: bool = False):
    """One table over every BENCH_*.json: headline scalar fields per file,
    plus (with ``records=True``) the per-record table of each artifact."""
    paths = sorted(glob.glob("BENCH_*.json"))
    if not paths:
        out("(no BENCH_*.json artifacts found)")
        return
    rows = []
    for path in paths:
        try:
            with open(path) as f:
                payload = json.load(f)
        except Exception as exc:  # unreadable artifact: surface, don't die
            rows.append((path, [f"UNREADABLE: {exc}"], None))
            continue
        fields = [f"{k}={_fmt(v)}" for k, v in payload.items()
                  if isinstance(v, (int, float, bool))
                  and not isinstance(v, str)]
        recs = payload.get("results", [])
        n = len(recs) if isinstance(recs, list) else 0
        if n:
            fields.insert(0, f"records={n}")
        rows.append((path, fields, recs if n else None))
    width = max(len(p) for p, _, _ in rows)
    out("")
    out("=" * 72)
    out("BENCH_*.json summary")
    out("=" * 72)
    for path, fields, recs in rows:
        out(f"{path:<{width}}  {'; '.join(fields) if fields else '-'}")
        if records and recs:
            _records_table(recs, out)
    out("=" * 72)


def main() -> int:
    """Runs the benchmarks; returns 1 when any of them failed to import or
    to run, 0 otherwise."""
    args = [a for a in sys.argv[1:]]
    if "--summary" in args:
        summarize_bench_json(records="--records" in args)
        return 0
    args = [a for a in args if not a.startswith("--")]
    filt = args[0] if args else ""
    print("name,us_per_call,derived")

    def report(name: str, us: float, derived: str = ""):
        print(f"{name},{us:.1f},{derived}", flush=True)

    mods, failed = discover()
    failed = [name for name in failed if not filt or filt in name]
    for name in failed:
        report(f"{name}_FAILED", 0.0, "import failed, see stderr")
    for name, mod in mods:
        if filt and filt not in name:
            continue
        try:
            mod.run(report)
        except Exception:
            traceback.print_exc()
            report(f"{name}_FAILED", 0.0, "see stderr")
            failed.append(name)
    summarize_bench_json()
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
