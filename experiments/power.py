#!/usr/bin/env python
"""Merged power-analysis deliverable — the fork's notebooks 1-3 power
outputs as one CLI over the merged discrete artifact.

The reference fork's distinguishing deliverable is its power comparison:
`1 - Parse results.ipynb` builds per-seed power / usage-efficiency /
failed-pod curves on a cumulative-workload axis and averages them per
(trace, policy); `2 - Generate plots.ipynb` turns them into the
power-savings-vs-FGD figure (plot_energy_savings -> pwrsaving_<level>.pdf),
the GRAR comparison figure (plot_comparison_metric -> gpuocc_<level>.pdf)
and the failed-relative plot (plot_failed_relative); `3 - Generate
tables.ipynb` emits LaTeX GRAR tables per trace family. This tool produces
all of those from experiments/merge.py's *_discrete CSVs alone:

  power_savings_<workload>.png   % cluster power savings vs the reference
                                 policy at each arrived-load %
                                 (plot_energy_savings, notebook 2 cell 4)
  usage_efficiency_<workload>.png  GRAR curves (plot_comparison_metric on
                                 usage_efficiency, notebook 2 cells 2/9)
  failed_relative_<workload>.png cumulative failed pods minus the
                                 reference policy's (plot_failed_relative,
                                 notebook 2 cell 3)
  power_tables.md / .tex         GRAR at 100% load per trace family
                                 (notebook 3 cells 5-6) + mean cluster
                                 watts at 100% load with savings vs the
                                 reference policy

Curves are seed-means, like the notebooks (sum(dfs)/len(dfs)); the load
axis is the integer arrived-load percent of the *_discrete schema (the
notebooks' cumulative_workload 0..1 maps to 0..100 here).

A sweep's lanes do not feed this tool: `SweepLane.power_cpu_w` /
`power_gpu_w` are the watts of a lane's FINAL cluster (at full depth, the
tables' "watts at 100% load" of one seed), not the curve over arrived load
that the discrete CSVs hold; `report_per_event` lanes carry the series.

    python experiments/power.py --merged experiments/analysis_results \
        --out experiments/analysis_results/power
"""

from __future__ import annotations

import argparse
import csv
import re
import sys
from collections import defaultdict
from pathlib import Path
from statistics import mean

import matplotlib

matplotlib.use("Agg")
import matplotlib.pyplot as plt

sys.path.insert(0, str(Path(__file__).parent / "plot"))
from plot_openb import LOAD_COLS, PALETTE, SURFACE, _style  # noqa: E402

REFERENCE_POLICY = "06-FGD"  # notebook 2 cell 9: reference_competitor = 'FGD'


def load_curves(path: Path, series: str = None):
    """merged *_discrete CSV -> {(workload, policy): {load%: seed-mean}}.

    `series` filters analysis_pwr_discrete.csv rows (cluster/cpu/gpu);
    None for the single-series files. Refuses mixed tuning ratios, like
    compare.py — averaging across tunes is meaningless."""
    acc = defaultdict(lambda: defaultdict(list))
    tunes = set()
    with open(path, newline="") as f:
        for r in csv.DictReader(f):
            if series is not None and r.get("series") != series:
                continue
            tunes.add(r.get("tune"))
            key = (r["workload"], r["sc_policy"])
            for col in LOAD_COLS:
                v = r.get(col)
                if v not in (None, ""):
                    acc[key][int(col)].append(float(v))
    if len(tunes) > 1:
        raise SystemExit(
            f"{path} mixes tuning ratios {sorted(tunes)}; run power.py on a "
            "single-tune artifact (averaging across tunes is meaningless)"
        )
    return {
        key: {x: mean(vs) for x, vs in per_load.items()}
        for key, per_load in acc.items()
    }


def _policy_color(policy):
    return PALETTE.get(policy, PALETTE["08-Custom"])


def _plot_policies(curves, workload, value_fn, ylabel, title, out_png,
                   xlim=(0, 100)):
    """One line per policy (skipping any value_fn returns None for)."""
    fig, ax = plt.subplots(figsize=(6.4, 4.2), dpi=150)
    fig.patch.set_facecolor(SURFACE)
    drew = False
    for (wl, policy) in sorted(curves):
        if wl != workload:
            continue
        pts = value_fn(policy, curves[(wl, policy)])
        if not pts:
            continue
        xs, ys = zip(*pts)
        ax.plot(xs, ys, color=_policy_color(policy), linewidth=1.6,
                label=policy, zorder=3)
        drew = True
    if not drew:
        plt.close(fig)
        return False
    _style(ax, "arrived GPU load (% of cluster capacity)", ylabel, title)
    ax.set_xlim(xlim)
    ax.legend(fontsize=7, ncol=2, framealpha=0.9)
    fig.tight_layout()
    fig.savefig(out_png)
    plt.close(fig)
    return True


def plot_power_savings(pwr, workload, out_png):
    """plot_energy_savings (notebook 2 cell 4): per policy,
    (ref_power - policy_power) / ref_power * 100 at each load."""
    ref = pwr.get((workload, REFERENCE_POLICY))
    if not ref:
        return False

    def value_fn(policy, curve):
        if policy == REFERENCE_POLICY:
            return None
        return [
            (x, 100.0 * (ref[x] - y) / ref[x])
            for x, y in sorted(curve.items())
            if x in ref and ref[x] > 0 and x <= 100
        ]

    return _plot_policies(
        pwr, workload, value_fn,
        f"% cluster power savings vs {REFERENCE_POLICY}",
        f"Power savings vs {REFERENCE_POLICY} — {workload}", out_png,
    )


def plot_usage_efficiency(usage, workload, out_png):
    """plot_comparison_metric on usage_efficiency (notebook 2 cells 2/9);
    the fork plots x in [0.8, 1.0] -> loads 80..100 here."""

    def value_fn(policy, curve):
        return [(x, y) for x, y in sorted(curve.items()) if 80 <= x <= 100]

    return _plot_policies(
        usage, workload, value_fn,
        "GPU allocated vs requested ratio (GRAR)",
        f"GPU usage efficiency — {workload}", out_png, xlim=(80, 100),
    )


def plot_failed_relative(failed, workload, out_png):
    """plot_failed_relative (notebook 2 cell 3): cumulative failed pods
    minus the reference policy's, per load."""
    ref = failed.get((workload, REFERENCE_POLICY))
    if not ref:
        return False

    def value_fn(policy, curve):
        if policy == REFERENCE_POLICY:
            return None
        return [
            (x, y - ref[x]) for x, y in sorted(curve.items())
            if x in ref and x <= 100
        ]

    return _plot_policies(
        failed, workload, value_fn,
        f"cumulative failed pods vs {REFERENCE_POLICY}",
        f"Failed pods relative to {REFERENCE_POLICY} — {workload}", out_png,
    )


def _split_family(workload):
    """openb_pod_list_cpu050 -> ('openb_pod_list_cpu', '050')
    (notebook 3 cell 4 split_string)."""
    m = re.match(r"([a-zA-Z_]+)(\d+)$", workload)
    return m.groups() if m else (workload, "")


def _at_load(curve, load=100):
    """Value at the target load; nearest sampled load below if the exact
    sample is missing (short traces may stop a hair under 100%)."""
    if not curve:
        return None
    if load in curve:
        return curve[load]
    below = [x for x in curve if x <= load]
    return curve[max(below)] if below else None


def build_tables(usage, pwr):
    """GRAR per trace family (notebook 3 cell 5: value at full load, one
    column per trace percentage) + cluster power at 100% with savings."""
    grar = {}  # family -> {policy: {perc: value}}
    for (workload, policy), curve in usage.items():
        fam, perc = _split_family(workload)
        v = _at_load(curve)
        if v is not None:
            grar.setdefault(fam, {}).setdefault(policy, {})[perc] = v
    power = {}  # workload -> {policy: watts@100}
    for (workload, policy), curve in pwr.items():
        v = _at_load(curve)
        if v is not None:
            power.setdefault(workload, {})[policy] = v
    return grar, power


def emit_tables(grar, power, out_dir: Path):
    md, tex = [], []
    for fam in sorted(grar):
        percs = sorted({p for pol in grar[fam].values() for p in pol})
        headers = ["Scheduling Policy"] + [
            f"GRAR ({p}%)" if p else "GRAR" for p in percs
        ]
        md.append(f"## GRAR — {fam}\n")
        md.append("| " + " | ".join(headers) + " |")
        md.append("|" + "---|" * len(headers))
        tex.append(f"% GRAR — {fam}")
        tex.append("\\begin{tabular}{" + "c" * len(headers) + "}")
        tex.append(
            " & ".join(
                "\\textbf{%s}" % h.replace("%", "\\%") for h in headers
            )
            + " \\\\ \\hline"
        )
        for policy in sorted(grar[fam]):
            vals = [grar[fam][policy].get(p) for p in percs]
            cells = ["" if v is None else f"{v:.3f}" for v in vals]
            md.append("| " + " | ".join([policy] + cells) + " |")
            tex.append(
                " & ".join([f"\\textbf{{{policy}}}".replace("_", "\\_")] + cells)
                + " \\\\"
            )
        tex.append("\\end{tabular}\n")
        md.append("")
    md.append("## Cluster power at 100% arrived load\n")
    md.append(f"| Workload | Policy | Watts | Savings vs {REFERENCE_POLICY} |")
    md.append("|---|---|---|---|")
    tex.append("% Cluster power at 100% arrived load")
    tex.append("\\begin{tabular}{llrr}")
    tex.append(
        "\\textbf{Workload} & \\textbf{Policy} & \\textbf{Watts} & "
        f"\\textbf{{Savings vs {REFERENCE_POLICY}}} \\\\ \\hline"
    )
    for workload in sorted(power):
        ref = power[workload].get(REFERENCE_POLICY)
        for policy in sorted(power[workload]):
            w = power[workload][policy]
            sav = (
                f"{100.0 * (ref - w) / ref:+.2f}%"
                if ref and policy != REFERENCE_POLICY
                else "—"
            )
            md.append(f"| {workload} | {policy} | {w:,.0f} | {sav} |")
            tex.append(
                f"{workload} & {policy} & {w:,.0f} & {sav} \\\\".replace(
                    "_", "\\_"
                ).replace("%", "\\%").replace("—", "--")
            )
    tex.append("\\end{tabular}")
    (out_dir / "power_tables.md").write_text("\n".join(md) + "\n")
    (out_dir / "power_tables.tex").write_text("\n".join(tex) + "\n")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--merged", default="experiments/analysis_results")
    ap.add_argument("--out", default=None,
                    help="output dir (default: <merged>/power)")
    args = ap.parse_args()
    merged = Path(args.merged)
    out_dir = Path(args.out) if args.out else merged / "power"
    out_dir.mkdir(parents=True, exist_ok=True)

    pwr_csv = merged / "analysis_pwr_discrete.csv"
    if not pwr_csv.is_file():
        raise SystemExit(
            f"{pwr_csv} not found — regenerate the artifact with "
            "experiments/merge.py (adds the power/usage/failed merges)"
        )
    pwr = load_curves(pwr_csv, series="cluster")
    usage = load_curves(merged / "analysis_usage_discrete.csv")
    failed_csv = merged / "analysis_failed_discrete.csv"
    failed = load_curves(failed_csv) if failed_csv.is_file() else {}

    workloads = sorted({wl for wl, _ in pwr})
    n_figs = 0
    for wl in workloads:
        n_figs += bool(
            plot_power_savings(pwr, wl, out_dir / f"power_savings_{wl}.png")
        )
        n_figs += bool(
            plot_usage_efficiency(
                usage, wl, out_dir / f"usage_efficiency_{wl}.png"
            )
        )
        if failed:
            n_figs += bool(
                plot_failed_relative(
                    failed, wl, out_dir / f"failed_relative_{wl}.png"
                )
            )
    grar, power = build_tables(usage, pwr)
    emit_tables(grar, power, out_dir)
    print(
        f"[power] {n_figs} figures + power_tables.{{md,tex}} "
        f"({len(workloads)} workloads) → {out_dir}"
    )


if __name__ == "__main__":
    main()
