"""CLI entry point: ``python -m repro.campaign [run|validate|report|list]``.

A spec file is either one campaign — the JSON form of
:class:`~repro.campaign.spec.CampaignSpec` (see ``docs/campaign.md`` for
the full field reference) — or a *suite* that sequences several::

    {"name": "paper", "suite": ["fig7_resnet.json", "fig10_gemm.json"]}

Suite entries are paths relative to the suite file (or inline campaign
dicts); sub-campaigns run sequentially, sharing one persistent (H, C, R)
cache and writing results under ``<out>/<campaign-name>/``.  This is what
makes ``python -m repro.campaign run specs/paper_full.json`` a
single-command full-paper reproduction.

``validate`` checks every spec (grid axes, zip groups, workload sources,
mesh shapes) and prints the expanded grid size without running anything —
CI runs it on the checked-in ``specs/*.json``.

``list`` prints the live extension vocabularies — registered estimator
kinds, topology kinds, and the system catalog with each entry's source
file — so the open vocabularies stay discoverable; ``--check``
additionally validates every catalog record against the schema (CI runs
``list --check`` over the shipped ``specs/systems/`` in the docs job).
``--systems PATH`` (file or directory of system JSON records, repeatable,
all subcommands) overlays user catalogs; campaign specs can do the same
with a ``system_catalog`` field.

``report`` turns campaign results into the paper's evaluation artifacts
(MAPE vs recorded references, Kendall-τ/Spearman rank preservation,
fidelity tables — ``repro.campaign.report``), emitted as JSON + markdown.
``--check`` additionally gates the predictions against the checked-in
golden snapshots (``specs/golden/``), failing on drift beyond tolerance
or any rank inversion; ``--update-golden`` regenerates the snapshots and
reference rows after an intentional change.  CI runs ``report --check``
on every checked-in spec grid.

Arch workloads with a ``mesh`` need that many XLA devices; the CLI counts
the devices the specs need and presets
``--xla_force_host_platform_device_count`` *before* jax initializes.

Minimal single-campaign example::

    {
      "name": "gpu-sweep",
      "workloads": [{"name": "llama3-100m", "arch": "llama3-100m",
                     "mode": "train", "mesh": [4, 1],
                     "seq": 256, "batch": 4}],
      "systems": ["a100", "h100", "b200"],
      "estimators": [{"kind": "roofline"},
                     {"kind": "roofline", "fidelity": "raw",
                      "options": {"mode": "per-op",
                                  "include_overheads": true}}],
      "slicers": ["linear", "dep"]
    }
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# only spec.py + the api facade (pure stdlib) at module load: `validate`
# and `list` must work in an environment without jax/numpy installed
# (the CI docs job); the runner and its estimator imports load lazily in
# the `run` branch
from .spec import CampaignSpec


def load_specs(path: str,
               session=None) -> list[tuple[str, CampaignSpec]]:
    """Load a spec file into ``[(campaign_name, CampaignSpec), ...]``.

    A plain campaign yields one entry; a suite file yields one per
    sub-campaign (path entries resolved relative to the suite file).
    ``session`` scopes spec validation to its registries/catalogs.
    """
    with open(path) as f:
        raw = json.load(f)
    if "suite" not in raw:
        spec = CampaignSpec.from_file_dict(raw, path, session=session)
        return [(spec.name, spec)]
    base = os.path.dirname(os.path.abspath(path))
    out: list[tuple[str, CampaignSpec]] = []
    for entry in raw["suite"]:
        if isinstance(entry, str):
            sub = os.path.join(base, entry)
            spec = CampaignSpec.from_json(sub, session=session)
        else:
            spec = CampaignSpec.from_dict(entry, session=session)
        if any(spec.name == n for n, _ in out):
            # names key per-campaign output dirs — a duplicate would
            # silently clobber the earlier campaign's results
            raise ValueError(
                f"suite {path!r}: duplicate campaign name {spec.name!r}")
        out.append((spec.name, spec))
    return out


def _devices_needed(specs: list[tuple[str, CampaignSpec]]) -> int:
    need = 1
    for _, spec in specs:
        for w in spec.workloads:
            if w.mesh:
                n = 1
                for s in w.mesh:
                    n *= s
                need = max(need, n)
    return need


def _preset_device_count(specs: list[tuple[str, CampaignSpec]]) -> None:
    """Give the host XLA platform enough devices for every spec mesh.

    Only effective before jax initializes, and only when the user hasn't
    set XLA_FLAGS themselves."""
    need = _devices_needed(specs)
    if need <= 1:
        return
    if "jax" in sys.modules:
        return  # too late to change the platform; builders will verify
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={need}")


def _print_grid(name: str, spec: CampaignSpec) -> None:
    jobs = spec.expand()
    zipped = {a: tuple(g) for g in spec.zip_axes for a in g}
    shown, bits = set(), []
    for axis in ("workloads", "systems", "estimators", "slicers",
                 "topologies"):
        if axis in shown:
            continue
        group = zipped.get(axis)
        if group is None:
            bits.append(f"{len(getattr(spec, axis))} {axis}")
        else:
            shown.update(group)
            bits.append(f"{len(getattr(spec, axis))} zipped "
                        + "⊗".join(group))
    print(f"campaign {name!r}: {len(jobs)} grid points "
          f"({' × '.join(bits)})", flush=True)


def _load_results_jsonl(path: str) -> list[dict]:
    """Read back a streamed results file (stdlib twin of
    ``runner.load_jsonl`` — reporting on existing results must not pull
    in the estimator stack)."""
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                rows.append(json.loads(line))
    return rows


def _report_command(args, session=None) -> int:
    """The ``report`` subcommand: build evaluation reports (and golden
    checks/updates) for every campaign named by the spec arguments."""
    from .report import (DEFAULT_TOLERANCE, build_report, check_rows,
                         golden_path, load_json, make_golden,
                         make_reference, reference_path, render_markdown,
                         write_json)

    entries = []  # (spec_file_path, campaign_name, CampaignSpec)
    for path in args.spec:
        for name, spec in load_specs(path, session=session):
            if any(name == n for _, n, _ in entries):
                raise ValueError(
                    f"report: duplicate campaign name {name!r} across "
                    "spec arguments")
            entries.append((path, name, spec))
    if args.results and len(entries) != 1:
        print("report: --results requires exactly one campaign")
        return 2

    failures: list[str] = []
    num_failed = 0
    if not args.results:
        _preset_device_count([(n, s) for _, n, s in entries])
        from ..launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    for path, name, spec in entries:
        out_dir = os.path.join(args.out, name)
        if args.results:
            rows = _load_results_jsonl(args.results)
        else:
            from .runner import run_campaign

            _print_grid(name, spec)
            result = run_campaign(
                spec, out_dir=out_dir, executor=args.executor,
                max_workers=args.jobs, cache_path=args.cache,
                progress=not args.quiet, session=session)
            rows = result.rows

        reference = load_json(reference_path(path, name))
        if args.update_golden:
            tol = (args.tolerance if args.tolerance is not None
                   else DEFAULT_TOLERANCE)
            gpath = write_json(
                golden_path(path, name),
                make_golden(name, rows, tolerance=tol,
                            meta={"spec": os.path.basename(path)}))
            # references are recorded evaluation *baselines*, not
            # regression snapshots: only seed a missing file (delete it
            # first to deliberately re-record).  Seeding happens before
            # build_report so the very first --update-golden run already
            # reports MAPE against the freshly recorded rows.
            rpath = reference_path(path, name)
            if reference is None:
                reference = make_reference(name, rows)
                write_json(rpath, reference)
                print(f"  wrote {gpath}, {rpath}")
            else:
                print(f"  wrote {gpath} (kept existing {rpath})")

        report = build_report(name, rows, reference=reference)
        num_failed += report["num_failed"]
        if args.check:
            golden = load_json(golden_path(path, name))
            if golden is None:
                check = {"failures": [
                    f"{name}: no golden snapshot at "
                    f"{golden_path(path, name)} — generate one with "
                    "--update-golden"], "rows_checked": 0,
                    "tolerance": (args.tolerance
                                  if args.tolerance is not None
                                  else DEFAULT_TOLERANCE)}
            else:
                check = check_rows(golden, rows,
                                   tolerance=args.tolerance)
            report["golden_check"] = check
            failures.extend(check["failures"])

        jpath = write_json(os.path.join(out_dir, "report.json"), report)
        mpath = os.path.join(out_dir, "report.md")
        with open(mpath, "w") as f:
            f.write(render_markdown(report))
        rp = report["rank_preservation"]
        trend = ("n/a" if rp["min_kendall_tau"] is None else
                 f"min τ {rp['min_kendall_tau']}")
        check_tag = ""
        if "golden_check" in report:
            gc = report["golden_check"]
            n_fail = len(gc["failures"])
            drift = gc.get("max_drift")
            drift_tag = ("" if drift is None
                         else f", max drift {drift:.1e}")
            check_tag = (f" · golden OK{drift_tag}" if not n_fail
                         else f" · golden FAILED ({n_fail})")
        print(f"report {name!r}: {report['num_ok']}/{report['num_rows']} "
              f"rows · {trend}{check_tag}")
        print(f"  wrote {jpath}, {mpath}")

    for f in failures:
        print(f"GOLDEN-CHECK FAILURE: {f}")
    if num_failed:
        # mirror `run`: a half-failed campaign must not exit 0 just
        # because its surviving rows produced a report
        print(f"report: {num_failed} grid points failed")
    return 1 if failures or num_failed else 0


def _list_command(args) -> int:
    """The ``list`` subcommand: print (and with ``--check`` validate)
    the live extension vocabularies."""
    from .. import api
    from ..core.catalog import validate_system_dict

    failures: list[str] = []
    try:
        session = api.Session(systems=args.systems or ())
    except (OSError, ValueError, TypeError) as e:
        print(f"INVALID catalog: {e}")
        return 1
    info = session.describe()
    print("estimator kinds: " + ", ".join(info["estimators"]))
    print("topology kinds:  " + ", ".join(info["topologies"]))
    print(f"systems ({len(info['systems'])} catalog entries + 'host'):")
    width = max((len(s["id"]) for s in info["systems"]), default=0)
    for s in info["systems"]:
        print(f"  {s['id']:<{width}}  {s['name']:<18} {s['source']}")
    if args.check:
        # re-validate every catalog *file* against the schema, with
        # per-file errors: the shipped specs/systems/ dir plus any
        # --systems paths (CI's docs job runs this)
        from ..core.catalog import _DEFAULT_DIR
        files: list[str] = []
        for p in [_DEFAULT_DIR, *(args.systems or [])]:
            if os.path.isdir(p):
                files += [os.path.join(p, n) for n in sorted(os.listdir(p))
                          if n.endswith(".json")]
            elif os.path.exists(p):
                files.append(p)
        for path in files:
            try:
                with open(path) as f:
                    validate_system_dict(json.load(f), source=path)
            except (ValueError, json.JSONDecodeError) as e:
                failures.append(str(e))
        for f in failures:
            print(f"INVALID {f}")
        print(f"catalog check: {len(files)} file(s), "
              f"{len(failures)} failure(s)")
    return 1 if failures else 0


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    command = "run"
    if argv and argv[0] in ("run", "validate", "report", "list"):
        command = argv.pop(0)

    ap = argparse.ArgumentParser(
        prog="python -m repro.campaign",
        description="Run, validate, report on a prediction campaign "
                    "from a JSON grid spec (single campaign or suite), "
                    "or list the registered backends/system catalog.")
    if command != "list":
        ap.add_argument("spec", nargs="+" if command != "run" else None,
                        help="path to the campaign/suite spec (JSON)")
    ap.add_argument("--systems", action="append", default=[],
                    metavar="PATH",
                    help="extra system-catalog file or directory of JSON "
                         "records (repeatable); ids become usable on the "
                         "spec 'systems' axis")
    if command == "list":
        ap.add_argument("--check", action="store_true",
                        help="validate every catalog record against the "
                             "schema; exit nonzero on failures")
    if command in ("run", "report"):
        ap.add_argument("--executor", default="thread",
                        choices=("serial", "thread", "process"),
                        help="job executor (default: thread)")
        ap.add_argument("--jobs", type=int, default=None,
                        help="max parallel workers (default: executor's "
                             "choice)")
        ap.add_argument("--cache", default=None, metavar="PATH",
                        help="persistent (H,C,R) cache file shared across "
                             "runs and live workers")
        ap.add_argument("--quiet", action="store_true",
                        help="suppress per-job progress lines")
    if command == "run":
        ap.add_argument("--out", default="artifacts/campaign",
                        help="output directory for results.jsonl/csv + "
                             "summary.json (default: artifacts/campaign)")
        ap.add_argument("--schedule", default="locality",
                        choices=("locality", "grid"),
                        help="job ordering: 'locality' groups jobs by "
                             "shared plan/cache keyset (leader first, "
                             "fingerprint-heavy plans warm the cache "
                             "early); 'grid' is pure grid order "
                             "(default: locality)")
        ap.add_argument("--dry-run", action="store_true",
                        help="print the expanded grid and exit")
        ap.add_argument("--resume", action="store_true",
                        help="crash-safe restart: replay <out>/"
                             "results.jsonl from a previous (possibly "
                             "killed) run — completed rows land in the "
                             "artifacts as-is (tagged 'resumed'), while "
                             "error, missing, and stale rows re-run")
        ap.add_argument("--retries", type=int, default=0, metavar="N",
                        help="re-run a job whose evaluate phase raised, "
                             "up to N extra attempts (default: 0; plan "
                             "and transport failures are not retried)")
        ap.add_argument("--fault-plan", default=None, metavar="PATH",
                        help="test-only: install a deterministic fault-"
                             "injection plan (JSON; see repro.serve."
                             "faults) via the environment so every "
                             "worker process inherits it")
        ap.add_argument("--server", default=None, metavar="URL",
                        help="run on a warm repro.serve daemon (e.g. "
                             "http://127.0.0.1:8733) instead of "
                             "in-process: rows stream back over HTTP and "
                             "the same artifacts are written locally; "
                             "--cache is ignored (the daemon owns the "
                             "store)")
    if command == "report":
        ap.add_argument("--out", default="artifacts/report",
                        help="output directory: campaign artifacts + "
                             "report.json/report.md per campaign "
                             "(default: artifacts/report)")
        ap.add_argument("--results", default=None, metavar="PATH",
                        help="report on an existing results.jsonl instead "
                             "of running the campaign (single campaign "
                             "only)")
        ap.add_argument("--check", action="store_true",
                        help="gate predictions against the checked-in "
                             "golden snapshots (specs/golden/): fail on "
                             "drift beyond tolerance, grid changes, or "
                             "rank inversions")
        ap.add_argument("--update-golden", action="store_true",
                        help="(re)write the golden snapshot and recorded "
                             "reference rows for each campaign from this "
                             "run")
        ap.add_argument("--tolerance", type=float, default=None,
                        help="relative drift tolerance; overrides the "
                             "per-snapshot value (and sets it with "
                             "--update-golden)")
    args = ap.parse_args(argv)

    if command == "list":
        return _list_command(args)

    # every other subcommand resolves kinds/systems through one session
    # (the stable repro.api facade) so user catalogs apply uniformly
    from .. import api
    try:
        session = api.Session(systems=args.systems or ())
    except (OSError, ValueError, TypeError) as e:
        print(f"INVALID catalog: {type(e).__name__}: {e}")
        return 1

    if command == "report":
        return _report_command(args, session=session)

    if command == "validate":
        bad = 0
        for path in args.spec:
            try:
                with open(path) as f:
                    raw = json.load(f)
                if "ladder" in raw or "objectives" in raw:
                    # search specs live beside the campaign grids, so
                    # `validate specs/*.json` must cover both kinds
                    from ..search.spec import SearchSpec
                    sspec = SearchSpec.from_file_dict(raw, path,
                                                      session=session)
                    n = len(sspec.campaign_for_rung(0).expand())
                    print(f"search {sspec.name!r}: {n} candidates, "
                          f"{len(sspec.ladder)}-rung ladder, objectives "
                          f"{list(sspec.objectives)}")
                    print(f"ok {path}")
                    continue
                specs = load_specs(path, session=session)
                for name, spec in specs:
                    spec.validate(session=session)
                    _print_grid(name, spec)
            except (OSError, ValueError, KeyError, TypeError,
                    json.JSONDecodeError) as e:
                print(f"INVALID {path}: {type(e).__name__}: {e}")
                bad += 1
                continue
            print(f"ok {path}")
        return 1 if bad else 0

    from .summary import format_table

    if args.fault_plan:
        # through the environment on purpose: spawned campaign workers
        # (and any daemon this process boots) inherit the plan
        from ..serve import faults
        os.environ[faults.ENV_PLAN] = args.fault_plan

    specs = load_specs(args.spec, session=session)
    if not args.server:
        _preset_device_count(specs)
        from ..launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    multi = len(specs) > 1
    failed = 0
    for name, spec in specs:
        _print_grid(name, spec)
        if args.dry_run:
            for j in spec.expand():
                r = j.to_row()
                print("  " + " × ".join(str(r[k]) for k in
                                        ("workload", "fidelity", "system",
                                         "estimator", "slicer", "topology")))
            continue
        out_dir = os.path.join(args.out, name) if multi else args.out
        resume_rows = None
        if args.resume:
            prev = os.path.join(out_dir, "results.jsonl")
            resume_rows = []
            if os.path.exists(prev):
                resume_rows = _load_results_jsonl(prev)
                print(f"  resuming from {prev} "
                      f"({len(resume_rows)} prior rows)")
            else:
                print(f"  --resume: no {prev} yet, running from scratch")
        if args.server:
            summary = _run_on_server(args, spec, name, multi, out_dir,
                                     resume_rows=resume_rows)
        else:
            from .runner import run_campaign

            result = run_campaign(
                spec, out_dir=out_dir, executor=args.executor,
                max_workers=args.jobs, cache_path=args.cache,
                schedule=args.schedule, progress=not args.quiet,
                session=session, resume_rows=resume_rows,
                retries=args.retries)
            summary = result.summary
            if result.csv_path:
                print(f"  wrote {result.jsonl_path}, {result.csv_path}, "
                      f"{result.summary_path}")
        print(format_table(summary))
        failed += summary["num_failed"]
    return 1 if failed else 0


def _run_on_server(args, spec: CampaignSpec, name: str, multi: bool,
                   out_dir: str, resume_rows: list | None = None) -> dict:
    """Run one campaign on a warm ``repro.serve`` daemon: stream the
    rows back and materialize the standard artifact set locally, so
    downstream tooling (``report --results``, the CI golden diff) sees
    exactly what an in-process run would have written.  A single spec
    file ships as its path (daemon and CLI are localhost peers, and the
    path preserves ``base_dir`` for backend-relative files); suite
    sub-campaigns ship as inline dicts."""
    from ..serve.client import ServeClient, write_campaign_artifacts

    client = ServeClient(args.server)
    kwargs: dict = {"executor": args.executor, "schedule": args.schedule,
                    "max_workers": args.jobs}
    if getattr(args, "retries", 0):
        kwargs["retries"] = args.retries
    if resume_rows is not None:
        kwargs["resume_rows"] = resume_rows
    if multi:
        kwargs["spec"] = spec.to_dict()
    else:
        kwargs["spec_path"] = os.path.abspath(args.spec)
    stream = client.campaign(**kwargs)
    fresh = []
    for row in stream:
        fresh.append(row)
        if not args.quiet:
            tag = (f"{row['step_time_s'] * 1e3:9.3f} ms"
                   if "step_time_s" in row else f"ERROR {row.get('error')}")
            print(f"  [{row['job_id']:4d}] {row['workload']} × "
                  f"{row['system']} × {row['estimator']} × "
                  f"{row['slicer']}: {tag}", flush=True)
    summary = stream.summary or {}
    rows = fresh
    if resume_rows:
        # the daemon replays trusted rows without re-streaming them
        # (this client already has them) — fold them back in, letting
        # freshly streamed rows win and dropping rows outside the grid
        seen = {r.get("job_id") for r in fresh}
        grid = summary.get("num_jobs", len(resume_rows) + len(fresh))
        kept = [dict(r, resumed=True) for r in resume_rows
                if r.get("job_id") not in seen and "error" not in r
                and r.get("job_id", grid) < grid]
        rows = sorted(kept + fresh, key=lambda r: r.get("job_id", 0))
    paths = write_campaign_artifacts(rows, summary, out_dir)
    print(f"  wrote {paths['jsonl']}, {paths['csv']}, {paths['summary']} "
          f"(served by {args.server})")
    return summary


if __name__ == "__main__":
    sys.exit(main())
