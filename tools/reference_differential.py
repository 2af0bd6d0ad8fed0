"""Capture golden outputs by EXECUTING the reference's analysis scripts.

VERDICT r1 #2: the strongest parity evidence available in a zero-egress
environment is to actually run the reference's CPU-runnable analysis code on
the committed data CSVs and diff our artifacts against its outputs. This
tool does that:

  1. Builds a sandbox under /tmp, copies four reference scripts into it and
     applies ONLY mechanical environment patches (the patched copies stay in
     /tmp — nothing from the reference tree enters this repo):
       - hard-coded personal paths ("G:/My Drive/...") -> "."
         (SURVEY.md §5 config: the reference has no path flags)
       - pd.read_excel -> pd.read_csv + the .xlsx filename -> .csv
         (this image has no openpyxl; values are unaffected)
  2. Stages identical inputs for both sides:
       - the committed D2/D3 CSVs from /root/reference/data
       - a deterministic synthetic D6 (lir_tpu.data.synthetic — the real D6
         is a generated artifact the upstream repo never committed)
       - D7 (survey_analysis_detailed.json) regenerated from D3 by OUR
         loader — both the reference bootstrap script and our D9 writer
         consume this same file
  3. Runs each script (subprocess, cwd=sandbox, Agg backend), collects every
     numeric artifact they write plus full-precision values from direct
     function calls, and writes tests/golden/reference_executed.json.

tests/test_reference_differential.py then diffs lir_tpu's own outputs
against that JSON under the ≤1% gate (BASELINE.json north star).

Run:  python tools/reference_differential.py
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
REF = Path("/root/reference")
SANDBOX = Path("/tmp/lir_ref_differential")
GOLDEN = REPO / "tests" / "golden" / "reference_executed.json"

SCRIPTS = {
    "model_comparison_graph.py": REF / "analysis/model_comparison_graph.py",
    "calculate_cohens_kappa.py": REF / "analysis/calculate_cohens_kappa.py",
    "survey_analysis_consolidated.py":
        REF / "survey_analysis/survey_analysis_consolidated.py",
    "analyze_llm_agreement_simple_bootstrap.py":
        REF / "survey_analysis/analyze_llm_agreement_simple_bootstrap.py",
    "analyze_perturbation_results.py":
        REF / "analysis/analyze_perturbation_results.py",
    "analyze_results_base_versus_instruct.py":
        REF / "analysis/analyze_results_base_versus_instruct.py",
    "analyze_llm_human_agreement.py":
        REF / "survey_analysis/analyze_llm_human_agreement.py",
    "analyze_model_family_differences.py":
        REF / "survey_analysis/analyze_model_family_differences.py",
    "calculate_correlation_pvalues.py":
        REF / "survey_analysis/calculate_correlation_pvalues.py",
    "analyze_base_vs_instruct_vs_human.py":
        REF / "survey_analysis/analyze_base_vs_instruct_vs_human.py",
    "bootstrap_confidence_intervals.py":
        REF / "survey_analysis/bootstrap_confidence_intervals.py",
}

_GDRIVE_DIR = re.compile(r"G:/My Drive/Computational/llm_interpretation/")
_GDRIVE = re.compile(r"G:/My Drive/Computational/llm_interpretation")


def _patch(text: str) -> str:
    text = _GDRIVE_DIR.sub("./", text)
    text = _GDRIVE.sub(".", text)
    text = text.replace("pd.read_excel", "pd.read_csv")
    text = text.replace(".to_excel(", ".to_csv(")
    text = text.replace("combined_results.xlsx", "combined_results.csv")
    text = text.replace("results_30_multi_model.xlsx", "combined_results.csv")
    return text


def stage_sandbox() -> None:
    if SANDBOX.exists():
        shutil.rmtree(SANDBOX)
    SANDBOX.mkdir(parents=True)
    for name, src in SCRIPTS.items():
        (SANDBOX / name).write_text(_patch(src.read_text()))
    for csv in ("instruct_model_comparison_results.csv",
                "model_comparison_results.csv",
                "word_meaning_survey_results.csv"):
        shutil.copy(REF / "data" / csv, SANDBOX / csv)

    from lir_tpu.data import synthetic
    synthetic.write_synthetic_d6(SANDBOX / "combined_results.csv")

    # D7 from OUR loader — the same file our D9 pipeline consumes.
    from lir_tpu.survey import loader
    survey_df, qcols = loader.load_survey(SANDBOX / "word_meaning_survey_results.csv")
    clean_df, _ = loader.apply_exclusions(survey_df, qcols)
    loader.write_survey_detailed(
        clean_df, qcols, SANDBOX / "survey_analysis_detailed.json")


def _run(script: str, timeout: int = 3600) -> str:
    env = dict(os.environ, MPLBACKEND="Agg", PYTHONHASHSEED="0")
    proc = subprocess.run(
        [sys.executable, script], cwd=SANDBOX, env=env, timeout=timeout,
        capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"{script} failed rc={proc.returncode}\n--- stdout\n"
            f"{proc.stdout[-4000:]}\n--- stderr\n{proc.stderr[-4000:]}")
    return proc.stdout


_GRAPH_DRIVER = """
import json, sys
import numpy as np, pandas as pd
sys.path.insert(0, ".")
import model_comparison_graph as g

df = pd.read_csv("instruct_model_comparison_results.csv")
df = df[~df["model"].str.contains("opt-iml-1.3b")]
df = df[~df["model"].str.contains("mistral", case=False)]

out = {}
for corr_type in ("pearson", "spearman"):
    s = g.calculate_model_correlations(df, correlation_type=corr_type,
                                       n_bootstrap=1000)
    out[corr_type] = {
        "mean_correlation": s["mean_correlation"],
        "median_correlation": s["median_correlation"],
        "std_correlation": s["std_correlation"],
        "min_correlation": s["min_correlation"],
        "max_correlation": s["max_correlation"],
        "mean_ci": list(s["mean_ci"]),
        "median_ci": list(s["median_ci"]),
        "std_ci": list(s["std_ci"]),
        "correlation_matrix": s["correlation_matrix"].values.tolist(),
        "models": list(s["correlation_matrix"].columns),
    }
k = g.calculate_aggregate_cohens_kappa(df)
out["aggregate_kappa"] = {key: (float(val) if np.isscalar(val) else val)
                          for key, val in k.items()
                          if isinstance(val, (int, float, np.floating, np.integer))}
json.dump(out, open("graph_golden.json", "w"), indent=1)
print("graph driver ok")
"""


def capture() -> dict:
    golden: dict = {"_provenance": {
        "generated_by": "tools/reference_differential.py",
        "reference_snapshot": "/root/reference @ 2025-09-12",
        "inputs": {
            "instruct_csv": "reference data/instruct_model_comparison_results.csv",
            "base_csv": "reference data/model_comparison_results.csv",
            "survey_csv": "reference data/word_meaning_survey_results.csv",
            "perturbation_d6": "lir_tpu.data.synthetic (seed 20260730)",
            "survey_detailed_d7": "lir_tpu.survey.loader.write_survey_detailed",
        },
        "patches": "paths G:/->. ; read_excel->read_csv (no openpyxl)",
    }}

    (SANDBOX / "graph_driver.py").write_text(_GRAPH_DRIVER)
    _run("graph_driver.py")
    golden["model_comparison_graph"] = json.loads(
        (SANDBOX / "graph_golden.json").read_text())

    _run("calculate_cohens_kappa.py")
    kdir = SANDBOX / "output/kappa_analysis"
    import pandas as pd
    golden["calculate_cohens_kappa"] = {
        stem: pd.read_csv(kdir / f"{stem}.csv").to_dict(orient="list")
        for stem in ("model_kappa_metrics", "perturbation_kappa_metrics",
                     "model_legal_kappas", "perturbation_legal_kappas",
                     "combined_kappa_results")
    }

    _run("survey_analysis_consolidated.py")
    golden["survey_consolidated"] = json.loads(
        (SANDBOX / "consolidated_analysis_results.json").read_text())

    _run("analyze_llm_agreement_simple_bootstrap.py")
    golden["llm_human_agreement_bootstrap"] = json.loads(
        (SANDBOX / "llm_human_agreement_bootstrap.json").read_text())

    # The 2,025-line perturbation analyzer (C20-C27 in one script): per-model
    # summary stats, KS/AD normality, the zero/one-inflated truncated-normal
    # MC fit, within-prompt kappa, and both compliance checkers — run on the
    # synthetic D6 whose edge model exercises every hairy branch.
    from lir_tpu.data.synthetic import SYNTH_EDGE_MODEL, SYNTH_MODEL
    _run("analyze_perturbation_results.py")
    pert = {}
    for model in (SYNTH_MODEL, SYNTH_EDGE_MODEL):
        safe = model.replace(".", "_").replace("-", "_")
        mdir = SANDBOX / "output" / safe
        pert[model] = {
            stem: pd.read_csv(mdir / f"{stem}.csv").to_dict(orient="list")
            for stem in ("summary_statistics", "normality_test_results",
                         "truncated_normal_test_results",
                         "cohens_kappa_results",
                         "output_compliance_results",
                         "confidence_compliance_results")
        }
    golden["analyze_perturbation_results"] = pert

    # C28: base-vs-instruct family deltas on the committed D2.
    _run("analyze_results_base_versus_instruct.py")
    adir = SANDBOX / "analysis_results"
    golden["base_versus_instruct"] = {
        stem: pd.read_csv(adir / f"{stem}.csv").to_dict(orient="list")
        for stem in ("model_rel_prob_statistics",
                     "prompt_rel_prob_differences",
                     "prompt_rel_prob_heatmap_data")
    }

    # C39: per-model human-LLM agreement (MAE/MSE/correlation suite).
    _run("analyze_llm_human_agreement.py")
    golden["llm_human_agreement"] = json.loads(
        (SANDBOX / "llm_human_agreement_analysis.json").read_text())

    # C42: family differences — a print-only script; its stdout IS the
    # artifact, so the numeric report is parsed into structure.
    out = _run("analyze_model_family_differences.py")
    golden["family_differences"] = _parse_family_differences(out)

    # C43: correlation p-value suite. The full human pairwise list is tens
    # of thousands of rows; keep the distribution-level comparison (every
    # statistic the report prints) plus the complete LLM pair list.
    _run("calculate_correlation_pvalues.py")
    pv = json.loads(
        (SANDBOX / "correlation_pvalues_analysis.json").read_text())
    golden["correlation_pvalues"] = {
        "comparison": pv["comparison"],
        "llm_correlations": pv["llm_correlations"],
        "n_human_correlations": len(pv["human_correlations"]),
    }

    # Base vs instruct vs human correlations (survey-side C28 companion).
    _run("analyze_base_vs_instruct_vs_human.py")
    golden["base_vs_instruct_vs_human"] = pd.read_csv(
        SANDBOX / "model_human_correlations.csv").to_dict(orient="list")

    # C38: the simulated-individual bootstrap (10,000 iterations of a
    # pure-Python resampling loop — by far the slowest capture; hours).
    if os.environ.get("LIR_SKIP_SLOW_BOOTSTRAP") != "1":
        _run("bootstrap_confidence_intervals.py", timeout=6 * 3600)
        golden["bootstrap_confidence_intervals"] = json.loads(
            (SANDBOX / "bootstrap_confidence_intervals.json").read_text())

    return golden


_FAMILY_ROW = re.compile(
    r"^(\w+)\s+(MAE|MSE|MAPE)\s+([+\-\d.]+)%?\s+([+\-\d.]+)%?\s+"
    r"([+\-\d.]+)%?\s+\[([+\-\d.]+)%?, ([+\-\d.]+)%?\]\s+(Yes|No)\s*$",
    re.MULTILINE)
_MC_FAMILY = re.compile(r"^([A-Z]+)\n-{60}", re.MULTILINE)
_MC_ROW = re.compile(
    r"^(MAE|MSE|MAPE): ([+\-\d.]+)%? \[([+\-\d.]+)%?, ([+\-\d.]+)%?\], "
    r"p = ([\d.]+)\s*$", re.MULTILINE)


def _parse_family_differences(stdout: str) -> dict:
    """Structure analyze_model_family_differences.py's printed report:
    the CI-combination summary table and the seed-42 Monte-Carlo section
    (its only outputs — the script writes no files)."""
    table = {}
    for m in _FAMILY_ROW.finditer(stdout):
        fam, metric = m.group(1), m.group(2)
        table.setdefault(fam, {})[metric] = {
            "base": float(m.group(3)), "instruct": float(m.group(4)),
            "diff": float(m.group(5)),
            "ci": [float(m.group(6)), float(m.group(7))],
            "significant": m.group(8) == "Yes",
        }
    mc_section = stdout.split("BOOTSTRAP-BASED DIFFERENCE ANALYSIS", 1)[-1]
    mc: dict = {}
    fams = list(_MC_FAMILY.finditer(mc_section))
    for i, fm in enumerate(fams):
        seg = mc_section[fm.end():
                         fams[i + 1].start() if i + 1 < len(fams) else None]
        mc[fm.group(1)] = {
            r.group(1): {"diff": float(r.group(2)),
                         "ci": [float(r.group(3)), float(r.group(4))],
                         "p": float(r.group(5))}
            for r in _MC_ROW.finditer(seg)
        }
    return {"summary_table": table, "mc_differences": mc}


def main() -> None:
    # Statistics-only work: keep jax (used by lir_tpu.survey.loader) off
    # the chip, which belongs to one process at a time.
    from lir_tpu.utils.profiling import ensure_cpu_backend
    ensure_cpu_backend()
    stage_sandbox()
    golden = capture()
    GOLDEN.parent.mkdir(parents=True, exist_ok=True)
    GOLDEN.write_text(json.dumps(golden, indent=1, sort_keys=True))
    print(f"golden written: {GOLDEN} ({GOLDEN.stat().st_size} bytes)")


if __name__ == "__main__":
    main()
