"""Fig. 3 ablations: placer attention and superposition on/off."""
from __future__ import annotations

import dataclasses
from typing import Dict

from benchmarks import common as C


def run(iterations: int = 60, tasks=None) -> Dict:
    """GDP-one with attention/superposition toggled off (Fig. 3)."""
    tasks = tasks or C.paper_tasks()[:3]
    rows: Dict[str, Dict] = {}
    for flag in ("full", "no_attention", "no_superposition"):
        pcfg = C.POLICY
        if flag == "no_attention":
            pcfg = dataclasses.replace(pcfg, use_attention=False)
        if flag == "no_superposition":
            pcfg = dataclasses.replace(pcfg, use_superposition=False)
        for t in tasks:
            r = C.run_gdp_one(t, iterations, pcfg=pcfg)
            rows.setdefault(t.name, {})[flag] = r["best"]
        print(f"[ablation] {flag}: " + " ".join(
            f"{t.name}={rows[t.name][flag]:.4f}" for t in tasks), flush=True)
    return rows


def main(quick: bool = True):
    """Run the ablation campaign; full-budget runs only are cached."""
    rows = run(iterations=40 if quick else 300)
    C.cache_section("ablation", rows, campaign_grade=not quick)
    return rows


if __name__ == "__main__":
    from repro.obs.jaxprof import enable_compile_cache
    enable_compile_cache()
    main(quick=False)
