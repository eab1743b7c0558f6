#!/usr/bin/env python
"""Golden-metric regression gate (SURVEY.md §4.5).

Compare a result line (stdin or file: ``{"metric", "value", "extra":
{"device": ...}}``, the shape ``serve_bench.py`` prints) against
benchmarks/golden.json for the device it ran on; exit 1 if any matched metric
regressed more than ``--tolerance`` (default 10%). Metrics or devices without
a golden entry are reported but never fail. The training rows of golden.json
come from a machine that is gone and no program prints them any more: the
chip's numbers are ``chipbench/run.py``'s, judged by the driver against
``PERF_LEDGER.jsonl``, not here.

``--aot-bytes`` gates a per-region byte report (the ``aot_regions`` section
of golden.json; ``serve_bench.py --aot`` writes the serving ones). Bytes
regress UPWARD (more traffic = worse), it needs no chip (the numbers are
facts of the lowered program), and ``--record`` writes the first golden.
``--lint``, ``--ttfs``, ``--slo``, ``--goodput`` and ``--metrics-jsonl`` gate
the other record files (see each flag's help).

Usage:
    python benchmarks/serve_bench.py | python benchmarks/check_regression.py
    python benchmarks/check_regression.py RESULT.json
    python benchmarks/check_regression.py --lint
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

GOLDEN_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "golden.json")


def load_golden(path: str = GOLDEN_PATH) -> dict:
    with open(path) as fh:
        return {k: v for k, v in json.load(fh).items()
                if not k.startswith("_")}


def iter_rows(result: dict):
    """A bench result line carries the headline row plus optional extras.lm."""
    yield result["metric"], float(result["value"]), result.get("extra", {})
    lm = result.get("extra", {}).get("lm")
    if lm:
        yield lm["metric"], float(lm["value"]), result.get("extra", {})


def check_health(jsonl_path: str):
    """Scan a run's metrics.jsonl for non-finite training-health scalars.

    A golden run whose health pack went NaN/inf mid-run produced its
    throughput number while training garbage — flag it even if the
    images/sec headline looks fine. (``json.loads`` accepts the bare
    ``NaN``/``Infinity`` tokens Python's json.dump emits, so the scan sees
    them as real floats.)
    """
    import math

    failures, report = [], []
    with open(jsonl_path) as fh:
        for ln, line in enumerate(fh, 1):
            line = line.strip()
            if not line:
                continue
            row = json.loads(line)
            if row.get("kind") not in (None, "train", "health"):
                continue
            bad = [k for k, v in row.items()
                   if not isinstance(v, bool) and isinstance(v, (int, float))
                   and not math.isfinite(v)]
            if bad:
                msg = (f"{jsonl_path}:{ln}: non-finite health scalar(s) "
                       f"{bad} at step {row.get('step', '?')}")
                failures.append(msg)
                report.append("NON-FINITE " + msg)
    if not failures:
        report.append(f"HEALTH-OK {jsonl_path}: all scalars finite")
    return failures, report


def check(result: dict, golden: dict, tolerance: float = 0.10):
    """Returns (failures, report_lines); a failure is a >tolerance drop."""
    device = result.get("extra", {}).get("device", "")
    table = golden.get(device, {})
    failures, report = [], []
    for metric, value, _ in iter_rows(result):
        ref = table.get(metric)
        if not ref:
            report.append(f"NO-GOLDEN {metric} ({device}): measured {value}")
            continue
        ratio = value / ref["value"]
        line = (f"{metric} ({device}): {value:.1f} vs golden "
                f"{ref['value']:.1f} ({ratio:.2%})")
        if ratio < 1.0 - tolerance:
            failures.append(line)
            report.append("REGRESSION " + line)
        else:
            report.append("OK " + line)
    return failures, report


def check_goodput(path: str, min_coverage: float = 0.95,
                  cluster: bool = False):
    """Gate a run's ``goodput.json`` on instrumentation coverage.

    Accepts both single-attempt files and the merged multi-attempt files an
    elastic/supervisor run writes (``attempts`` > 1, with the inter-attempt
    gap folded into the ``restart`` badput bucket). The gate is on cumulative
    ``coverage`` — spans must explain at least ``min_coverage`` of the total
    wall clock across every attempt, so a restart tax that the telemetry
    failed to attribute shows up as a failure rather than vanishing.

    With ``cluster=True`` the input is a fleet launcher's
    ``cluster_goodput.json`` (fleetobs.aggregate_cluster_goodput): an
    aggregate over independent jobs, where distinct run_ids are the expected
    shape — the mixed-run refusal below is the *single-run* staleness check
    and does not apply. The coverage floor is then cluster-wide
    (wall-weighted across jobs).
    """
    failures, report = [], []
    try:
        with open(path) as fh:
            data = json.load(fh)
        coverage = float(data["coverage"])
        wall = float(data["wall_s"])
    except (OSError, ValueError, KeyError, TypeError) as e:
        msg = f"goodput {path}: unreadable or malformed ({e})"
        failures.append(msg)
        report.append("MALFORMED " + msg)
        return failures, report
    attempts = int(data.get("attempts", 1))
    restart_s = float(data.get("categories_s", {}).get("restart", 0.0))
    run_ids = [r for r in (data.get("run_ids") or []) if r]
    if cluster:
        jobs = data.get("jobs") or []
        if not data.get("cluster"):
            msg = (f"goodput {path}: --cluster expects a fleet "
                   "cluster_goodput.json (aggregate_cluster_goodput), got a "
                   "single-run summary")
            failures.append(msg)
            report.append("MALFORMED " + msg)
            return failures, report
        line = (f"cluster goodput {path}: coverage {coverage:.3f} over "
                f"{wall:.1f}s device-wall, {len(jobs)} job(s) "
                f"{sorted(jobs)}, {len(set(run_ids))} run id(s), "
                f"{attempts} attempt(s), restart tax {restart_s:.1f}s")
        if coverage < min_coverage:
            failures.append(line + f" — below floor {min_coverage}")
            report.append("REGRESSION " + line + f" (floor {min_coverage})")
        else:
            report.append("OK " + line)
        return failures, report
    # Mixed-run refusal: a cumulative/fleet summary stamped with more than
    # one run id silently sums UNRELATED attempts (stale artifacts in a
    # reused checkpoint dir) — its coverage and goodput are meaningless, so
    # fail loudly instead of gating on fiction.
    if len(set(run_ids)) > 1:
        msg = (f"goodput {path}: merged across {len(set(run_ids))} different "
               f"runs {sorted(set(run_ids))} — refusing to gate a mixed-run "
               f"summary (stale artifacts? clear the dir or re-merge)")
        failures.append(msg)
        report.append("MIXED-RUN " + msg)
        return failures, report
    line = (f"goodput {path}: coverage {coverage:.3f} over {wall:.1f}s wall, "
            f"{attempts} attempt(s), restart tax {restart_s:.1f}s")
    if coverage < min_coverage:
        failures.append(line + f" — below floor {min_coverage}")
        report.append("REGRESSION " + line + f" (floor {min_coverage})")
    else:
        report.append("OK " + line)
    return failures, report


def check_ttfs(path: str, max_ratio: float = 0.8):
    """Gate warm-restart time-to-first-step against cold (goodput.json).

    The executable cache (core/xcache.py) exists to make restarts fast; this
    gate keeps that property from silently rotting. ``ttfs_history`` (one
    entry per attempt, carried across supervisor restarts by the telemetry
    merge) is split by mode: every ``warm`` attempt must beat the SLOWEST
    ``cold`` attempt by at least ``max_ratio`` (warm < max_ratio * cold).

    Neutral by design when there is nothing to compare: a run whose cache
    was missing, corrupted (quarantined -> cold recompile) or never
    populated has no warm entries — that is the cache layer behaving
    correctly, not a regression, so the gate reports OK and moves on. An
    unreadable goodput.json still fails loudly, same as --goodput.
    """
    failures, report = [], []
    try:
        with open(path) as fh:
            data = json.load(fh)
        history = list(data.get("ttfs_history") or [])
    except (OSError, ValueError, AttributeError, TypeError) as e:
        msg = f"ttfs {path}: unreadable or malformed ({e})"
        failures.append(msg)
        report.append("MALFORMED " + msg)
        return failures, report
    try:
        cold = [float(h["ttfs_s"]) for h in history if h.get("mode") == "cold"]
        warm = [float(h["ttfs_s"]) for h in history if h.get("mode") == "warm"]
    except (ValueError, KeyError, TypeError) as e:
        msg = f"ttfs {path}: malformed ttfs_history entry ({e})"
        failures.append(msg)
        report.append("MALFORMED " + msg)
        return failures, report
    if not warm or not cold:
        report.append(
            f"OK ttfs {path}: no warm/cold pair to compare "
            f"({len(cold)} cold, {len(warm)} warm attempt(s)) — neutral")
        return failures, report
    worst_warm, worst_cold = max(warm), min(cold)
    line = (f"ttfs {path}: warm {worst_warm:.3f}s vs cold {worst_cold:.3f}s "
            f"(x{worst_warm / worst_cold:.2f}, floor x{max_ratio}) over "
            f"{len(cold)} cold / {len(warm)} warm attempt(s)")
    if worst_warm >= max_ratio * worst_cold:
        failures.append(line + " — executable cache is not paying for itself")
        report.append("REGRESSION " + line)
    else:
        report.append("OK " + line)
    return failures, report


def check_slo(path: str):
    """Gate a serving run's ``slo.jsonl`` (serve/slo.py SLOTracker.flush).

    Well-formedness contract: every line parses; exactly one ``slo_header``
    (first row) naming the window size; at least one ``slo_window`` row,
    each with finite quantiles, sample counts within [1, window] (the
    window-coverage check — a count of 0 means a phantom row, above the
    window means the deque invariant broke), and attainment in [0, 1];
    exactly one ``slo_summary`` with finite attainment; and a single
    run_id across all rows (stale-artifact refusal, same spirit as the
    goodput mixed-run gate).
    """
    failures, report = [], []
    rows = []
    try:
        with open(path) as fh:
            for i, line in enumerate(fh, 1):
                if line.strip():
                    rows.append(json.loads(line))
    except (OSError, ValueError) as e:
        msg = f"slo {path}: unreadable or malformed line {len(rows) + 1} ({e})"
        failures.append(msg)
        report.append("MALFORMED " + msg)
        return failures, report

    def fail(msg):
        failures.append(f"slo {path}: {msg}")
        report.append(f"MALFORMED slo {path}: {msg}")

    headers = [r for r in rows if r.get("kind") == "slo_header"]
    windows = [r for r in rows if r.get("kind") == "slo_window"]
    summaries = [r for r in rows if r.get("kind") == "slo_summary"]
    if len(headers) != 1 or rows[0] is not headers[0]:
        fail(f"expected exactly one leading slo_header, got {len(headers)}")
        return failures, report
    run_ids = sorted({str(r.get("run_id")) for r in rows})
    if len(run_ids) > 1:
        fail(f"rows span {len(run_ids)} run ids {run_ids} — stale "
             f"artifacts? clear the dir or re-flush")
        return failures, report
    window = headers[0].get("window")
    if not isinstance(window, int) or window < 1:
        fail(f"header window must be a positive int, got {window!r}")
        return failures, report
    if not windows:
        fail("no slo_window rows (no samples observed?)")
    for r in windows:
        key = f"{r.get('replica')}/{r.get('role')}"
        n_t, n_i = r.get("ttft_count", 0), r.get("itl_count", 0)
        if not (isinstance(n_t, int) and isinstance(n_i, int)) \
                or n_t + n_i < 1 or n_t > window or n_i > window:
            fail(f"window {key}: counts ttft={n_t} itl={n_i} outside "
                 f"[1, {window}] coverage")
            continue
        for metric in ("ttft_p50_ms", "ttft_p99_ms", "itl_p50_ms",
                       "itl_p99_ms", "attainment"):
            v = r.get(metric)
            if v is not None and not (isinstance(v, (int, float))
                                      and math.isfinite(v)):
                fail(f"window {key}: non-finite {metric}={v!r}")
        att = r.get("attainment")
        if isinstance(att, (int, float)) and not 0.0 <= att <= 1.0:
            fail(f"window {key}: attainment {att} outside [0, 1]")
    if len(summaries) != 1:
        fail(f"expected exactly one slo_summary, got {len(summaries)}")
    else:
        att = summaries[0].get("attainment")
        if not (isinstance(att, (int, float)) and math.isfinite(att)
                and 0.0 <= att <= 1.0):
            fail(f"summary attainment {att!r} not a finite [0, 1] value")
    if not failures:
        s = summaries[0]
        report.append(
            f"OK slo {path}: run {run_ids[0]}, {len(windows)} window "
            f"row(s), attainment {s['attainment']}, "
            f"{s.get('breaches', 0)} breach(es), "
            f"{s.get('dropped_spans', 0)} dropped span(s)")
    return failures, report


def aot_key(result: dict) -> str:
    """Golden key for a per-region byte report: model + shape + dispatch formulation.
    EP rows (lowered at an expert mesh) extend the key with the degree and
    transport so replicated/a2a/a2a_overlap goldens coexist per shape;
    composed-topology rows (r22) append dp/pp/seq tokens when those axes
    are in the mesh, so every golden row is one (dp, ep, pp, seq) tuple.
    Single-axis rows keep their historical keys unchanged."""
    key = (f"{result['model']} b{result['per_chip_batch']} "
           f"s{result['seq_len']} {result.get('moe_dispatch_impl', '-')}")
    if int(result.get("ep_degree", 1) or 1) > 1:
        key += (f" ep{result['ep_degree']} "
                f"{result.get('moe_ep_dispatch', 'replicated')}")
    if int(result.get("dp_degree", 0) or 0) > 1:
        key += f" dp{result['dp_degree']}"
    if int(result.get("pp_degree", 1) or 1) > 1:
        key += f" pp{result['pp_degree']}"
    if int(result.get("seq_degree", 1) or 1) > 1:
        key += f" seq{result['seq_degree']}"
    return key


def check_aot_bytes(result: dict, golden: dict, tolerance: float = 0.10):
    """Gate per-region AOT modeled bytes against golden.json ``aot_regions``.

    Unlike throughput (lower = regression), modeled bytes regress UPWARD:
    a region fails when its gbytes_modeled exceeds the golden by more than
    ``tolerance``. Shrinking is always fine — improvements re-record.
    Goldens are specific to the lowering backend (XLA:CPU fuses differently
    from TPU) and to the fusion-attribution model, so a mismatch on either
    field skips the comparison rather than failing on incomparable numbers.
    """
    failures, report = [], []
    key = aot_key(result)
    entry = golden.get("aot_regions", {}).get(key)
    if not entry:
        report.append(f"NO-GOLDEN aot_regions[{key}]: record with --record")
        return failures, report
    for field in ("backend_lowering", "attribution"):
        if entry.get(field) != result.get(field):
            report.append(
                f"SKIP aot_regions[{key}]: {field} mismatch "
                f"(golden {entry.get(field)!r}, result {result.get(field)!r})")
            return failures, report
    for region, ref in sorted(entry["regions"].items()):
        row = result.get("regions", {}).get(region)
        if row is None:
            report.append(f"NO-REGION {region} ({key}): absent from result")
            continue
        val = float(row["gbytes_modeled"])
        ratio = val / ref if ref else (float("inf") if val else 1.0)
        line = (f"aot_bytes {region} ({key}): {val:.3f} GB vs golden "
                f"{ref:.3f} GB ({ratio:.2%})")
        if ratio > 1.0 + tolerance:
            failures.append(line)
            report.append("REGRESSION " + line)
        else:
            report.append("OK " + line)
    # Memory census (r22): the abstract lowering's per-device high-water
    # regresses UPWARD like traffic. Only temps + resident are gated —
    # argument bytes are a function of the param count and sharding, which
    # the regions gate already pins transitively.
    mem = result.get("memory")
    ref_mem = entry.get("memory")
    if mem and ref_mem:
        for field in ("temp_bytes", "resident_bytes"):
            if ref_mem.get(field) is None or mem.get(field) is None:
                continue
            val, ref = float(mem[field]), float(ref_mem[field])
            ratio = val / ref if ref else (float("inf") if val else 1.0)
            line = (f"aot_memory {field} ({key}): {val / 1e6:.1f} MB vs "
                    f"golden {ref / 1e6:.1f} MB ({ratio:.2%})")
            if ratio > 1.0 + tolerance:
                failures.append(line)
                report.append("REGRESSION " + line)
            else:
                report.append("OK " + line)
    # Sequence-parallel shrink gate (r22): the point of the context axis is
    # that per-device activation temps scale ~1/seq (ring attention never
    # materializes the full [S, S] score block and every residual tensor is
    # [B, S/seq, d]). A seq row must undercut its seq=1 sibling golden by at
    # least half the ideal scaling — val * seq <= ref * 2.0 — or the sharded
    # lowering has stopped paying for its collectives.
    seq = int(result.get("seq_degree", 1) or 1)
    if mem and seq > 1:
        sib_key = aot_key({**result, "seq_degree": 1})
        sib = golden.get("aot_regions", {}).get(sib_key, {}).get("memory")
        if sib is None or sib.get("temp_bytes") is None:
            report.append(f"NO-GOLDEN aot_regions[{sib_key}]: record the "
                          "seq=1 sibling to arm the seq-shrink gate")
        else:
            val, ref = float(mem["temp_bytes"]), float(sib["temp_bytes"])
            line = (f"aot_seq_shrink ({key}): temp bytes {val / 1e6:.1f} MB "
                    f"x seq{seq} vs seq1 golden {ref / 1e6:.1f} MB")
            if val * seq > ref * 2.0:
                failures.append(line + " — per-device activation temps no "
                                "longer shrink ~1/seq")
                report.append("REGRESSION " + line)
            else:
                report.append("OK " + line)
    # EP comms model (r17): collective moe bytes regress upward like any
    # traffic number, and an a2a row must also UNDERCUT its replicated
    # sibling golden at the same shape/degree — the whole point of sharding
    # the dropless path is that token shards cost less than weight gathers,
    # so losing that inequality is a regression even inside tolerance.
    coll = result.get("collectives")
    ref_coll = entry.get("collectives")
    if coll and ref_coll and ref_coll.get("moe_bytes") is not None:
        val, ref = float(coll["moe_bytes"]), float(ref_coll["moe_bytes"])
        ratio = val / ref if ref else (float("inf") if val else 1.0)
        line = (f"aot_collective_moe_bytes ({key}): {val / 1e6:.3f} MB vs "
                f"golden {ref / 1e6:.3f} MB ({ratio:.2%})")
        if ratio > 1.0 + tolerance:
            failures.append(line)
            report.append("REGRESSION " + line)
        else:
            report.append("OK " + line)
    ep_dispatch = result.get("moe_ep_dispatch", "replicated")
    if (coll and int(result.get("ep_degree", 1) or 1) > 1
            and ep_dispatch != "replicated"):
        rep_key = aot_key({**result, "moe_ep_dispatch": "replicated"})
        rep = golden.get("aot_regions", {}).get(rep_key, {}).get("collectives")
        if rep is None:
            report.append(f"NO-GOLDEN aot_regions[{rep_key}]: record the "
                          "replicated sibling to arm the a2a<replicated gate")
        else:
            val, ref = float(coll["moe_bytes"]), float(rep["moe_bytes"])
            line = (f"aot_ep_comms ({key}): moe collective bytes "
                    f"{val / 1e6:.3f} MB vs replicated golden "
                    f"{ref / 1e6:.3f} MB")
            if val >= ref:
                failures.append(line + " — a2a no longer undercuts "
                                "replicated weight gathers")
                report.append("REGRESSION " + line)
            else:
                report.append("OK " + line)
    return failures, report


def record_aot_golden(result: dict, path: str = GOLDEN_PATH) -> str:
    """Write a report's per-region bytes as the golden entry (full-file
    rewrite: golden.json is small and hand-tended)."""
    with open(path) as fh:
        golden = json.load(fh)  # keep "_"-prefixed comment keys
    entry = {
        "backend_lowering": result.get("backend_lowering"),
        "attribution": result.get("attribution"),
        "regions": {tag: row["gbytes_modeled"]
                    for tag, row in result.get("regions", {}).items()},
    }
    if result.get("xla_flops_per_step") is not None:
        entry["xla_flops_per_step"] = result["xla_flops_per_step"]
    if result.get("memory"):
        entry["memory"] = dict(result["memory"])
    coll = result.get("collectives")
    if coll:
        entry["collectives"] = {
            "total_bytes": coll["total_bytes"],
            "moe_bytes": coll["moe_bytes"],
            "by_opcode": {op: row["bytes"]
                          for op, row in coll.get("by_opcode", {}).items()},
        }
    golden.setdefault("aot_regions", {})[aot_key(result)] = entry
    with open(path, "w") as fh:
        json.dump(golden, fh, indent=2)
        fh.write("\n")
    return aot_key(result)


def check_lint(root=None, baseline=None, ir_model=None):
    """Run graftlint (AST layer; optionally one IR lowering) as a gate.

    Fails on any unbaselined error-severity finding; stale suppressions are
    reported but do not fail (the code they covered moved — refresh with
    ``--record``).
    """
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import graftlint

    findings = graftlint.run_ast(root or graftlint.REPO_ROOT)
    if ir_model:
        findings += graftlint.run_ir(ir_model)
    doc = graftlint.load_baseline(baseline or graftlint.DEFAULT_BASELINE)
    unbaselined, baselined, stale = graftlint.split_findings(findings, doc)
    failures, report = [], []
    for f in findings:
        if f in baselined:
            report.append(f"LINT-BASELINED {f.render()}")
        elif f.severity == graftlint.ERROR:
            failures.append(f.render())
            report.append(f"LINT-FAIL {f.render()}")
        else:
            report.append(f"LINT-INFO {f.render()}")
    for s in stale:
        report.append(f"LINT-STALE suppression no longer matches: "
                      f"{s.get('rule')} {s.get('path')} {s.get('scope')}")
    report.append(f"LINT {len(findings)} finding(s), {len(baselined)} "
                  f"baselined, {len(failures)} unbaselined error(s), "
                  f"{len(stale)} stale")
    return failures, report, findings


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("result", nargs="?", help="bench JSON file (default: stdin)")
    p.add_argument("--tolerance", type=float, default=0.10)
    p.add_argument("--metrics-jsonl", default=None,
                   help="also scan this run's metrics.jsonl for non-finite "
                        "training-health scalars (telemetry rows); any hit "
                        "fails the gate")
    p.add_argument("--goodput", default=None, metavar="GOODPUT_JSON",
                   help="also gate this run's goodput.json on span coverage "
                        "(cumulative across supervisor attempts for elastic "
                        "runs); fails below --goodput-min-coverage")
    p.add_argument("--goodput-min-coverage", type=float, default=0.95)
    p.add_argument("--ttfs", default=None, metavar="GOODPUT_JSON",
                   help="also gate warm-restart time-to-first-step from "
                        "this goodput.json's ttfs_history: every warm "
                        "(executable-cache hit) attempt must come in under "
                        "--ttfs-max-ratio of the slowest cold compile; "
                        "neutral when the run has no warm/cold pair "
                        "(missing or quarantined cache = cold-only = OK)")
    p.add_argument("--ttfs-max-ratio", type=float, default=0.8)
    p.add_argument("--slo", default=None, metavar="SLO_JSONL",
                   help="also gate this serving run's slo.jsonl "
                        "(serve/slo.py): well-formed rows, single run_id, "
                        "window coverage, finite quantiles")
    p.add_argument("--cluster", action="store_true",
                   help="with --goodput: the file is a fleet "
                        "cluster_goodput.json (launch.py --fleet) — gate "
                        "wall-weighted coverage across jobs and accept the "
                        "distinct per-job run_ids a multi-tenant aggregate "
                        "carries by construction")
    p.add_argument("--aot-bytes", action="store_true",
                   help="input is a per-region byte report: gate "
                        "per-region modeled bytes (UP is the regression "
                        "direction) against golden.json aot_regions; runs "
                        "without a chip")
    p.add_argument("--record", action="store_true",
                   help="with --aot-bytes: write the report's regions as "
                        "the golden entry instead of comparing; with "
                        "--lint: refresh the suppression baseline (new "
                        "entries land as UNREVIEWED)")
    p.add_argument("--lint", action="store_true",
                   help="run graftlint (AST layer) as a gate: fail on any "
                        "unbaselined error finding; chip-free and jax-free")
    p.add_argument("--lint-ir", default=None, metavar="MODEL",
                   help="with --lint: also IR-lint MODEL's abstract "
                        "lowering (donation/precision/host-transfer/"
                        "sharding rules; needs jax)")
    p.add_argument("--lint-root", default=None,
                   help="with --lint: lint this tree instead of the repo "
                        "(fixture testing)")
    p.add_argument("--lint-baseline", default=None,
                   help="with --lint: suppression file (default "
                        "benchmarks/lint_baseline.json)")
    args = p.parse_args(argv)
    failures, report = [], []
    if args.lint:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        l_failures, l_report, findings = check_lint(
            args.lint_root, args.lint_baseline, args.lint_ir)
        if args.record:
            import graftlint

            graftlint.record_baseline(
                findings, args.lint_baseline or graftlint.DEFAULT_BASELINE)
            print("RECORDED lint baseline "
                  f"({sum(1 for f in findings if f.severity == graftlint.ERROR)} "
                  "suppression(s); review any UNREVIEWED entries)")
            return 0
        for line in l_report:
            print(line)
        return 1 if l_failures else 0
    if args.aot_bytes:
        raw = open(args.result).read() if args.result else sys.stdin.read()
        try:
            data = json.loads(raw)
        except json.JSONDecodeError:
            data = json.loads(raw.strip().splitlines()[-1])
        result = data.get("parsed", data)
        if args.record:
            key = record_aot_golden(result)
            print(f"RECORDED aot_regions[{key}]")
            return 0
        failures, report = check_aot_bytes(result, load_golden(),
                                           args.tolerance)
        for line in report:
            print(line)
        return 1 if failures else 0
    # --metrics-jsonl / --goodput / --slo alone are standalone scans (no
    # bench row expected on stdin); a positional result file, or plain piped
    # usage, still runs the golden comparison.
    if args.result or not (args.metrics_jsonl or args.goodput or args.slo
                           or args.ttfs):
        raw = open(args.result).read() if args.result else sys.stdin.read()
        # Accept a driver BENCH_r{N}.json wrapper (pretty-printed, result
        # under "parsed") or piped benchmark output (last stdout line is the
        # JSON).
        try:
            data = json.loads(raw)
        except json.JSONDecodeError:
            data = json.loads(raw.strip().splitlines()[-1])
        result = data.get("parsed", data)
        failures, report = check(result, load_golden(), args.tolerance)
    if args.metrics_jsonl:
        h_failures, h_report = check_health(args.metrics_jsonl)
        failures += h_failures
        report += h_report
    if args.goodput:
        g_failures, g_report = check_goodput(args.goodput,
                                             args.goodput_min_coverage,
                                             cluster=args.cluster)
        failures += g_failures
        report += g_report
    if args.ttfs:
        t_failures, t_report = check_ttfs(args.ttfs, args.ttfs_max_ratio)
        failures += t_failures
        report += t_report
    if args.slo:
        s_failures, s_report = check_slo(args.slo)
        failures += s_failures
        report += s_report
    for line in report:
        print(line)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
