"""Regenerate the data-driven section of EXPERIMENTS.md from
experiments/benchmarks/*.json. Idempotent: replaces the placeholder /
previously generated blocks between the <!-- X --> markers.
"""
from __future__ import annotations

import glob
import json
import os

ROOT = os.path.join(os.path.dirname(__file__), "..")
MD = os.path.join(ROOT, "EXPERIMENTS.md")


def bench_section() -> str:
    lines = []
    res = {os.path.basename(f)[:-5]: json.load(open(f))
           for f in glob.glob(os.path.join(ROOT, "experiments/benchmarks/*.json"))}

    if "table1_accuracy" in res:
        lines += ["### Table 1 — accuracy vs baselines (synthetic stand-ins)",
                  "", "| method | label-skew | domain-shift |", "|---|---|---|"]
        rows = res["table1_accuracy"]
        methods = []
        for r in rows:
            if r["method"] not in methods:
                methods.append(r["method"])
        for m in methods:
            def cell(d):
                r = next((x for x in rows if x["method"] == m
                          and x["distribution"] == d), None)
                return f"{r['acc_mean']:.3f}±{r['acc_std']:.3f}" if r else "—"
            lines.append(f"| {m} | {cell('label-skew')} | {cell('domain-shift')} |")
        lines += ["", "Claim check: FedELMY tops both columns; SFL methods "
                  "(MetaFed/FedSeq/FedELMY) ≫ one-shot PFL methods — same "
                  "ordering as the paper's Table 1.", ""]

    def simple_table(key, title, cols, claim=""):
        if key not in res:
            return []
        rows = res[key]
        if isinstance(rows, dict):
            rows = [rows]
        out = [f"### {title}", "", "| " + " | ".join(cols) + " |",
               "|" + "---|" * len(cols)]
        for r in rows:
            out.append("| " + " | ".join(
                f"{r.get(c):.3f}" if isinstance(r.get(c), float)
                else str(r.get(c)) for c in cols) + " |")
        if claim:
            out += ["", claim]
        out.append("")
        return out

    lines += simple_table("table2_fewshot", "Table 2 — few-shot scaling",
                          ["shots", "fedelmy", "fedseq"],
                          "Claim check: FedELMY ≥ FedSeq at every shot count.")
    lines += simple_table("table3_ablation", "Table 3 — pool / d1 / d2 ablation",
                          ["variant", "acc_mean", "acc_std"],
                          "Claim check: pool M alone beats FedSeq (+0.24); "
                          "d1 and d2 each add over M-only; M+d2 and M+d1+d2 "
                          "are within noise of each other at this task's "
                          "ceiling (paper Table 3 direction).")
    lines += simple_table("table4_order", "Table 4 — client order robustness",
                          ["order", "fedelmy", "fedseq"],
                          "Claim check: FedELMY beats FedSeq for every "
                          "domain order.")
    lines += simple_table("fig5_comm_cost", "Fig. 5 — communication cost "
                          "(N=10, measured serialized checkpoints)",
                          ["arch", "method", "model_mb", "total_mb"],
                          "Claim check: FedELMY/FedSeq = (N−1)·M is the "
                          "minimum; mesh-gossip PFL is ~N× worse.")
    lines += simple_table("fig6_compute_matched", "Fig. 6 — compute-matched",
                          ["method", "local_steps_per_client", "acc"],
                          "Claim check (partial): both saturate at the "
                          "ceiling under equal S·E_local compute; at the "
                          "paper-default budget (last row) FedSeq is "
                          "clearly behind.")
    lines += simple_table("fig9_distance_measures", "Fig. 9 — distance "
                          "measures", ["measure", "acc"],
                          "Claim check (partial): every measure reaches the "
                          "task ceiling here, so the paper's L2-beats-others "
                          "ranking is not resolvable at this scale; L1 is "
                          "marginally worse, consistent with the paper.")
    if "fig10_pool_heatmap" in res:
        r = res["fig10_pool_heatmap"]
        lines += ["### Fig. 10 — final-client pool pairwise L2 distances", "",
                  f"pool size {r['pool_size']}, off-diagonal mean "
                  f"{r['offdiag_mean']:.3f}, std {r['offdiag_std']:.3f} "
                  f"(coefficient of variation {r['offdiag_cv']:.2f}) — "
                  "non-degenerate diversity, no monotone trend "
                  f"(full matrix in experiments/benchmarks/fig10_pool_heatmap.json).", ""]
    lines += simple_table("table9_pfl", "Table 9 — decentralized-PFL "
                          "adaptation", ["method", "acc"],
                          "Claim check (partial): all PFL variants land far "
                          "below the SFL variant (reproduces the paper's "
                          "main point); FedELMY(PFL) *trails* the PFL "
                          "baselines at this step budget, whereas the paper "
                          "shows it winning 3 of 4 datasets — independent "
                          "per-client inits + short training favor the "
                          "momentum/SAM baselines here.")
    return "\n".join(lines)


def splice(md: str, marker: str, content: str) -> str:
    start = md.index(f"<!-- {marker} -->")
    end_tag = f"<!-- END {marker} -->"
    if end_tag in md:
        end = md.index(end_tag) + len(end_tag)
    else:
        nxt = md.find("\n## ", start)
        end = nxt if nxt != -1 else len(md)
    return (md[:start] + f"<!-- {marker} -->\n" + content +
            f"\n{end_tag}\n\n" + md[end:].lstrip("\n"))


def main():
    with open(MD) as f:
        md = f.read()
    md = splice(md, "BENCH_RESULTS", bench_section())
    with open(MD, "w") as f:
        f.write(md)
    print("EXPERIMENTS.md updated")


if __name__ == "__main__":
    main()
